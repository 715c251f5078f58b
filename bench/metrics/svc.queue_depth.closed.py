"""Mean number of requests waiting in the service's admission queue over
the window: the seconds requests spent queued (``svc.queue`` spans, from
arrival to dequeue) over the window's seconds, by Little's law."""

from harness import spans


def read(r):
    s = spans.seconds(r, "svc.queue")
    return None if s is None else s / r.window_s
