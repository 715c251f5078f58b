"""Share of the traced window in which no operation ran on the chip
(1 - union of device op intervals / window), in percent."""


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
