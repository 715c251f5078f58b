"""Clips served per healthy flush of the service (continuous-batching
window size)."""

EVENTS = "capsim_service_tier_events_total"


def read(r):
    flushes = r.counter(EVENTS, event="flushes")
    if not flushes:
        return None
    return r.counter(EVENTS, event="clips") / flushes
