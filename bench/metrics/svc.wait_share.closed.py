"""Share of the window the service's worker spends waiting on an empty
queue (``svc.wait`` spans), in percent."""

from harness import spans


def read(r):
    return spans.share(r, "svc.wait")
