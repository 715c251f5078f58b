"""Host microseconds the engine spends assembling peer-channel contexts
(``engine.peer_context`` spans: every core's register blocks, tagged and
joined, and the clips' rows taken) per clip fed with that layout
(``capsim_peer_context_clips_total``).  A program without the span or
the counter reads ``None``."""

from harness import spans


def read(r):
    s = spans.seconds(r, "engine.peer_context")
    clips = r.counter("capsim_peer_context_clips_total")
    if s is None or not clips:
        return None
    return 1e6 * s / clips
