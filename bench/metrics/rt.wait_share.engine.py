"""Share of the window the RT cache spends blocked on the device after an
encode pass (``rt.wait`` spans, inside ``rt.build``: the pass itself and
any predict batches queued ahead of it), in percent."""

from harness import spans


def read(r):
    return spans.share(r, "rt.wait")
