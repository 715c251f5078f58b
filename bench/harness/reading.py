"""What a per-layer reader gets: the window's length, deltas of the
program's span seconds and counters over the window, the traffic driver's own
counts, the reduced device trace and the chip's peaks."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

SPAN_SECONDS = "capsim_span_seconds_total"

Snap = Dict[str, Dict[Tuple[Tuple[str, str], ...], float]]


def snapshot() -> Snap:
    """Counter and gauge values (histogram sums) of the program's
    metrics registry, by family and label set."""
    from repro.obs import REGISTRY
    out: Snap = {}
    for name, fam in REGISTRY.snapshot().items():
        cells = {}
        for v in fam["values"]:
            key = tuple(sorted(v["labels"].items()))
            cells[key] = float(v.get("value", v.get("sum", 0.0)))
        out[name] = cells
    return out


@dataclasses.dataclass
class Reading:
    window_s: float
    before: Snap
    after: Snap
    extra: Dict
    model: Dict
    peak: Dict[str, float]
    trace: Optional[object] = None         # harness.trace.Summary

    def counter(self, name: str, **match: str) -> float:
        """Delta over the window of a registry family, summed over every
        label set that holds ``match``."""
        total = 0.0
        want = {k: str(v) for k, v in match.items()}
        for key, v in self.after.get(name, {}).items():
            labels = dict(key)
            if all(labels.get(k) == val for k, val in want.items()):
                total += v - self.before.get(name, {}).get(key, 0.0)
        return total

    def cells(self, name: str):
        """(labels, delta) of every label set of a registry family."""
        before = self.before.get(name, {})
        return [(dict(key), v - before.get(key, 0.0))
                for key, v in self.after.get(name, {}).items()]

    def span_s(self, span: str) -> float:
        return self.counter(SPAN_SECONDS, span=span)
