"""Host microseconds the batcher spends deduplicating context tokens for
the fused step (``predict.dedup`` spans) per clip of the service's
healthy flushes."""

from harness import spans


def read(r):
    return spans.us_per_served_clip(r, "predict.dedup")
