"""Span ring with Chrome-trace-event / Perfetto export, and the bridge to
the JAX profiler.

:class:`Tracer` keeps finished spans in a bounded ring buffer
(``deque(maxlen=ring_size)``) so a long-running service never grows
without bound.  Spans are timed by ``Observability.span``, which hands
each finished one to :meth:`Tracer.record` with its id, its parent's id
and its nesting depth; a disabled tracer drops them.

``export_chrome()`` emits the Chrome trace-event JSON format (complete
``"ph": "X"`` events, microsecond timestamps); open the file at
https://ui.perfetto.dev to get a zoomable per-thread timeline.

:func:`profiler_active` and :func:`annotation` are the one place that
touches the JAX profiler: while a profiler session records, every span
also opens a ``jax.profiler.TraceAnnotation`` under its own name, so the
span lands in the ``.xplane.pb`` trace on the profiler's clock next to
the device operations.  JAX is imported on first use; without it no
session can be active and spans stay on the host clock alone.
"""
from __future__ import annotations

import json
import numbers
import threading
import time
from collections import deque
from typing import Dict, List, Optional

_TRACE_ANNOTATION = None       # jax.profiler.TraceAnnotation, or False


def _trace_annotation():
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION


def profiler_active() -> bool:
    """True while a JAX profiler session records host events (jaxlib's
    ``TraceMe.is_enabled()``, ~0.1 us)."""
    ann = _trace_annotation()
    return bool(ann) and ann.is_enabled()


def annotation(name: str, span_id: int, parent_id: int,
               args: Optional[Dict[str, object]] = None):
    """An un-entered profiler annotation for one span.  Its metadata are
    the span's ``id``, its ``parent`` and the integer-valued ``args``:
    the profiler cuts a string value at its first comma, so anything
    but an integer is left out."""
    meta = {"id": span_id, "parent": parent_id}
    if args:
        for k, v in args.items():
            if isinstance(v, numbers.Integral):
                meta[k] = int(v)
    return _trace_annotation()(name, **meta)


class SpanRecord:
    """One finished span (or instant event when ``dur_ns`` is None).

    ``span_id`` is unique in the process; ``parent_id`` is the id of the
    span that caused it (0 for none), on this thread or, for a span
    opened with an explicit parent, on another."""

    __slots__ = ("name", "cat", "start_ns", "dur_ns", "tid", "depth",
                 "span_id", "parent_id", "args")

    def __init__(self, name: str, cat: str, start_ns: int,
                 dur_ns: Optional[int], tid: int, depth: int,
                 args: Optional[Dict[str, object]], span_id: int = 0,
                 parent_id: int = 0):
        self.name = name
        self.cat = cat
        self.start_ns = start_ns
        self.dur_ns = dur_ns
        self.tid = tid
        self.depth = depth
        self.span_id = span_id
        self.parent_id = parent_id
        self.args = args


class Tracer:
    """Ring buffer of finished spans and instant events."""

    def __init__(self, ring_size: int = 4096, enabled: bool = False):
        self.enabled = bool(enabled)
        self._ring: deque = deque(maxlen=int(ring_size))
        self._lock = threading.Lock()
        self._t0_ns = time.perf_counter_ns()

    def _append(self, rec: SpanRecord) -> None:
        with self._lock:
            self._ring.append(rec)

    # -- recording ----------------------------------------------------------
    def record(self, name: str, start_ns: int, dur_ns: int,
               cat: str = "span",
               args: Optional[Dict[str, object]] = None, *,
               span_id: int = 0, parent_id: int = 0,
               depth: int = 0) -> None:
        """Append an already-timed span."""
        if not self.enabled:
            return
        self._append(SpanRecord(name, cat, start_ns, dur_ns,
                                threading.get_ident(), depth, args,
                                span_id, parent_id))

    def instant(self, name: str, cat: str = "event",
                args: Optional[Dict[str, object]] = None, *,
                parent_id: int = 0, depth: int = 0) -> None:
        """Record a zero-duration instant event (tier trips, faults)."""
        if not self.enabled:
            return
        self._append(SpanRecord(name, cat, time.perf_counter_ns(), None,
                                threading.get_ident(), depth, args, 0,
                                parent_id))

    # -- control / export ---------------------------------------------------
    def set_enabled(self, enabled: bool) -> bool:
        prev, self.enabled = self.enabled, bool(enabled)
        return prev

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def spans(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._ring)

    def export_chrome(self) -> dict:
        """Chrome trace-event JSON (open at ui.perfetto.dev)."""
        events = []
        for rec in self.spans():
            ev = {"name": rec.name, "cat": rec.cat,
                  "ts": (rec.start_ns - self._t0_ns) / 1e3,
                  "pid": 0, "tid": rec.tid}
            if rec.dur_ns is None:
                ev["ph"] = "i"
                ev["s"] = "t"
            else:
                ev["ph"] = "X"
                ev["dur"] = rec.dur_ns / 1e3
            args = dict(rec.args) if rec.args else {}
            args["depth"] = rec.depth
            args["id"] = rec.span_id
            args["parent"] = rec.parent_id
            ev["args"] = args
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.export_chrome(), f)
        return path


#: Process-global default tracer (disabled until someone enables it).
TRACER = Tracer()
