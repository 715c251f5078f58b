"""Host microseconds the RT cache spends indexing requests' clips
(``rt.index`` spans: dedup of the token rows, lookups and encode passes
of unseen rows) per clip of the service's healthy flushes."""

from harness import spans


def read(r):
    return spans.us_per_served_clip(r, "rt.index")
