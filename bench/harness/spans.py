"""Readings of the program's own spans: their window seconds from the
registry, and the device's idle time that no program span covers in the
profiler trace.

The program's spans are those of its layers, named ``engine.*``,
``predict.*``, ``rt.*`` and ``svc.*``; the harness's own annotations
(``bench.*``) and the runtime's events are not.  A program that does not
record a span reads ``None`` here, never a false 0."""
from __future__ import annotations

from typing import List, Optional, Tuple

from harness.reading import SPAN_SECONDS
from harness.trace import SpanIndex, clip, union

PROGRAM_PREFIXES = ("engine.", "predict.", "rt.", "svc.")
TIER_EVENTS = "capsim_service_tier_events_total"


def seconds(r, span: str) -> Optional[float]:
    """Seconds of ``span`` over the window, summed over instances;
    ``None`` when the registry has never seen the span."""
    cells = [v for labels, v in r.cells(SPAN_SECONDS)
             if labels.get("span") == span]
    return sum(cells) if cells else None


def share(r, span: str) -> Optional[float]:
    """``span`` seconds over the window, in percent."""
    s = seconds(r, span)
    return None if s is None else 100.0 * s / r.window_s


def us_per_served_clip(r, span: str) -> Optional[float]:
    """``span`` microseconds per clip of the service's healthy flushes."""
    s = seconds(r, span)
    clips = r.counter(TIER_EVENTS, event="clips")
    if s is None or not clips:
        return None
    return 1e6 * s / clips


def idle_gaps(busy: List[Tuple[float, float]], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi) that the sorted, disjoint ``busy``
    intervals leave free."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_unattributed(summary) -> Optional[float]:
    """Share of the window's device-idle time at which no program span
    is open on any host thread, each gap judged at its midpoint, in
    percent; ``None`` without device events or without program spans."""
    if summary is None or not summary.devices:
        return None
    lo, hi = summary.lo, summary.hi
    program = [e for e in summary.host
               if e.name.startswith(PROGRAM_PREFIXES)
               and e.end_ns > lo and e.start_ns < hi]
    if not program:
        return None
    ops = [e for evs in summary.devices.values() for e in evs]
    busy = clip(union((e.start_ns, e.end_ns) for e in ops), lo, hi)
    index = SpanIndex(program)
    idle = bare = 0.0
    for s, e in idle_gaps(busy, lo, hi):
        idle += e - s
        if index.innermost((s + e) / 2) is None:
            bare += e - s
    return 100.0 * bare / idle if idle else 0.0
