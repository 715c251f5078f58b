"""Share of the traced window's device-idle time at which no program span
(``engine.*``, ``predict.*``, ``rt.*``, ``svc.*``) is open on any host
thread, in percent: idle time the program's spans do not explain."""

from harness import spans


def read(r):
    return spans.idle_unattributed(r.trace)
