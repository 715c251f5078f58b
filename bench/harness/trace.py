"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Two layers:

``extract``   reads the file with ``jax.profiler.ProfileData`` into plain
              ``Event`` tuples: the device operations of each chip
              (planes ``/device:TPU:<n>``, line ``XLA Ops``) and the host
              spans (TraceMe and ``TraceAnnotation`` events of host
              threads).
``reduce``    pure functions over those tuples: the union of busy
              intervals inside the measured window, the time of each
              named kernel, the operations that took most time, and the
              idle gaps labelled by the innermost host span open at the
              gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    start_ns: float
    end_ns: float
    name: str
    stats: Tuple[Tuple[str, object], ...] = ()

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


@dataclasses.dataclass
class Extracted:
    devices: Dict[str, List[Event]]          # plane name -> op events
    host: List[Event]                        # host spans, any thread


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line, with_stats: bool) -> Iterable[Event]:
    for e in line.events:
        st = tuple((k, v) for k, v in e.stats) if with_stats else ()
        yield Event(float(e.start_ns), float(e.start_ns + e.duration_ns),
                    e.name, st)


def extract(path: str, device_prefix: str = "/device:TPU:") -> Extracted:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith(device_prefix):
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            evs = [ev for ln in ops for ev in _events(ln, True)]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in lines:
                host.extend(ev for ev in _events(ln, False)
                            if ev.end_ns > ev.start_ns)
    return Extracted(devices=devices, host=host)


# ----------------------------- reduction ----------------------------- #

def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(ex: Extracted) -> Tuple[float, float]:
    """The measured window: the harness's ``bench.window`` span."""
    spans = [e for e in ex.host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w = max(spans, key=lambda e: e.end_ns - e.start_ns)
    return w.start_ns, w.end_ns


def busy_seconds(events: Sequence[Event], lo: float, hi: float) -> float:
    busy = clip(union((e.start_ns, e.end_ns) for e in events), lo, hi)
    return sum(e - s for s, e in busy) * 1e-9


def op_kind(name: str) -> str:
    """``%flash_attention_bhsd.12 = f32[...] custom-call(...)`` ->
    ``flash_attention_bhsd``: the HLO instruction's name without its
    number, so one kind of operation sums over programs and layers."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    while base and (num.isdigit() or num == "clone"):
        head = base
        base, _, num = head.rpartition(".")
    return head


def top_ops(devices: Dict[str, List[Event]], lo: float, hi: float,
            n: int = 10) -> List[List]:
    """Device seconds by kind of operation inside the window, summed
    over chips, largest first."""
    tot: Dict[str, float] = defaultdict(float)
    for evs in devices.values():
        for e in evs:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                tot[op_kind(e.name)] += (t - s) * 1e-9
    return [[k, v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


class SpanIndex:
    """Host spans bucketed by time, to find the spans open at an
    instant without scanning them all."""

    def __init__(self, host: Sequence[Event], bin_ns: float = 1e6):
        self.bin_ns = bin_ns
        self.bins: Dict[int, List[Event]] = defaultdict(list)
        for e in host:
            for b in range(int(e.start_ns // bin_ns),
                           int(e.end_ns // bin_ns) + 1):
                self.bins[b].append(e)

    def innermost(self, t: float, skip: Callable[[str], bool]
                  = lambda n: False) -> Optional[str]:
        best: Optional[Event] = None
        for e in self.bins.get(int(t // self.bin_ns), ()):
            if e.start_ns <= t < e.end_ns and not skip(e.name):
                if best is None or (e.end_ns - e.start_ns
                                    < best.end_ns - best.start_ns):
                    best = e
        return best.name if best is not None else None


def idle_gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
              hi: float, n: int = 10) -> List[List]:
    """Idle device time inside the window grouped by the innermost host
    span open at each gap's midpoint, largest total first."""
    busy = clip(union((e.start_ns, e.end_ns) for e in events), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    index = SpanIndex([e for e in host if e.end_ns > lo and e.start_ns < hi
                       and e.name != WINDOW_SPAN])
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        label = index.innermost((s + e) / 2) or "no host span"
        tot[label] += (e - s) * 1e-9
    return [[k, v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def matching(events: Sequence[Event], substrings: Sequence[str]
             ) -> List[Event]:
    """Events whose name or HLO op/long name holds one of
    ``substrings``."""
    out = []
    for e in events:
        text = " ".join(str(x) for x in (e.name, e.stat("hlo_op", ""),
                                         e.stat("long_name", ""),
                                         e.stat("tf_op", "")))
        if any(s in text for s in substrings):
            out.append(e)
    return out


@dataclasses.dataclass
class Summary:
    """What a traced run hands the per-layer readers."""
    lo: float
    hi: float
    devices: Dict[str, List[Event]]
    host: List[Event]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips that ran anything."""
        if not self.devices:
            return 0.0
        return sum(busy_seconds(evs, self.lo, self.hi)
                   for evs in self.devices.values()) / len(self.devices)

    def kernel_events(self, substrings: Sequence[str]) -> List[Event]:
        return [e for evs in self.devices.values()
                for e in matching(evs, substrings)
                if e.start_ns >= self.lo and e.end_ns <= self.hi]

    def breakdown(self) -> Dict[str, List[List]]:
        all_ops = [e for evs in self.devices.values() for e in evs]
        return {"device_ops": top_ops(self.devices, self.lo, self.hi),
                "idle_gaps": idle_gaps(all_ops, self.host, self.lo,
                                       self.hi)}


def summarize(path: str) -> Summary:
    ex = extract(path)
    lo, hi = window_of(ex)
    return Summary(lo=lo, hi=hi, devices=ex.devices, host=ex.host)
