"""Unified observability: span tracing, metrics, flight recorder.

:class:`Observability` is the bundle components hold.  Its
:meth:`~Observability.span` primitive always times (the registry is the
system of record — the Stats view classes read it back), feeds the
tracer ring when tracing is enabled, and, while a JAX profiler session
records, opens a ``jax.profiler.TraceAnnotation`` under the span's name,
so one ``with obs.span(...)`` stanza is the registry's seconds, the
ring's timeline and the profiler trace's host event at once.

Construction::

    obs = Observability.from_config(config.observability)  # None -> defaults
    with obs.span("engine.tokenize", instance=self._inst) as sp:
        ...
    elapsed = sp.seconds          # same clock the registry recorded

Every span gets a process-unique ``id`` and its parent's id: the span
open on the same thread when it starts, or the ``parent=`` span given
explicitly for work handed to another thread.  The ids ride along into
the ring, the Chrome export, postmortems and the profiler's metadata.

``from_config(None)`` shares the process-global registry and the
disabled global tracer; ``ObservabilityConfig(trace=True)`` gets a
private enabled :class:`Tracer` the owner can dump with
``obs.tracer.dump(path)``.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

from .flight import POSTMORTEM_SCHEMA_VERSION, FlightRecorder
from .metrics import (COUNTER, DEFAULT_TIME_BUCKETS, GAUGE, HISTOGRAM,
                      REGISTRY, MetricsRegistry, exp_buckets)
from . import trace as _trace
from .trace import TRACER, SpanRecord, Tracer

__all__ = [
    "COUNTER", "GAUGE", "HISTOGRAM", "DEFAULT_TIME_BUCKETS", "REGISTRY",
    "TRACER", "POSTMORTEM_SCHEMA_VERSION", "MetricsRegistry",
    "Tracer", "SpanRecord", "FlightRecorder", "Observability",
    "exp_buckets", "SPAN_SECONDS_TOTAL", "SPAN_SECONDS_HIST",
]

SPAN_SECONDS_TOTAL = "capsim_span_seconds_total"
SPAN_SECONDS_HIST = "capsim_span_seconds"

_SPAN_IDS = itertools.count(1)           # 0 means "no parent"
_OPEN = threading.local()                # .stack: this thread's open spans


def _open_spans() -> List["_ObsSpan"]:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


class _ObsSpan:
    """Times one span; writes the registry always, the tracer if on, the
    profiler while a session records."""

    __slots__ = ("_obs", "_name", "_instance", "_args", "_parent", "_start",
                 "_stack", "_annot", "id", "parent_id", "depth", "seconds")

    def __init__(self, obs: "Observability", name: str, instance: str,
                 args: Optional[Dict[str, object]],
                 parent: Optional["_ObsSpan"]):
        self._obs = obs
        self._name = name
        self._instance = instance
        self._args = args
        self._parent = parent
        self._annot = None
        self.id = 0
        self.parent_id = 0
        self.depth = 0
        self.seconds = 0.0

    def __enter__(self):
        stack = _open_spans()
        parent = self._parent if self._parent is not None else (
            stack[-1] if stack else None)
        self.id = next(_SPAN_IDS)
        if parent is not None:
            self.parent_id = parent.id
            self.depth = parent.depth + 1
        stack.append(self)
        self._stack = stack
        if _trace.profiler_active():
            self._annot = _trace.annotation(self._name, self.id,
                                            self.parent_id, self._args)
            self._annot.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_ns = time.perf_counter_ns() - self._start
        if self._annot is not None:
            self._annot.__exit__(None, None, None)
            self._annot = None
        self._stack.remove(self)
        self.seconds = dur_ns * 1e-9
        self._obs._record(self._name, self._instance, self._start, dur_ns,
                          self._args, self.id, self.parent_id, self.depth)
        return False


class Observability:
    """Bundle of tracer + metrics registry + optional flight recorder."""

    def __init__(self, *, metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 flight: Optional[FlightRecorder] = None):
        self.metrics = REGISTRY if metrics is None else metrics
        self.tracer = TRACER if tracer is None else tracer
        self.flight = flight
        self._span_total = self.metrics.counter(
            SPAN_SECONDS_TOTAL, "Cumulative seconds per span.",
            ("span", "instance"))
        self._span_hist = self.metrics.histogram(
            SPAN_SECONDS_HIST, "Span latency distribution.",
            ("span", "instance"))
        self._handles: Dict[Tuple[str, str], tuple] = {}

    @classmethod
    def from_config(cls, config=None) -> "Observability":
        """Build from an ``ObservabilityConfig`` (or None -> defaults)."""
        if config is None:
            return cls()
        tracer = (Tracer(ring_size=config.trace_ring, enabled=True)
                  if config.trace else None)
        flight = (FlightRecorder(config.flight_dir,
                                 max_spans=config.flight_spans,
                                 max_events=config.flight_events)
                  if config.flight_dir is not None else None)
        return cls(tracer=tracer, flight=flight)

    # -- span primitive -----------------------------------------------------
    def span(self, name: str, instance: str = "",
             args: Optional[Dict[str, object]] = None, *,
             parent: Optional[_ObsSpan] = None) -> _ObsSpan:
        """Context manager timing one span.  ``parent`` names the span
        that caused this one when it runs on another thread; by default
        the parent is the span open on this thread."""
        return _ObsSpan(self, name, instance, args, parent)

    def record_span(self, name: str, start_ns: int, dur_ns: int, *,
                    instance: str = "",
                    args: Optional[Dict[str, object]] = None) -> None:
        """Record a span timed elsewhere (``start_ns`` on the
        ``perf_counter_ns`` clock), with no parent, into the registry and
        the ring.  The profiler cannot take an event that began in the
        past, so it does not see this one."""
        self._record(name, instance, start_ns, dur_ns, args,
                     next(_SPAN_IDS), 0, 0)

    def _record(self, name: str, instance: str, start_ns: int,
                dur_ns: int, args: Optional[Dict[str, object]],
                span_id: int, parent_id: int, depth: int) -> None:
        key = (name, instance)
        handles = self._handles.get(key)
        if handles is None:
            handles = (self._span_total.labels(span=name, instance=instance),
                       self._span_hist.labels(span=name, instance=instance))
            self._handles[key] = handles
        secs = dur_ns * 1e-9
        handles[0].inc(secs)
        handles[1].observe(secs)
        if self.tracer.enabled:
            targs = dict(args) if args else {}
            if instance:
                targs["instance"] = instance
            self.tracer.record(name, start_ns, dur_ns, args=targs or None,
                               span_id=span_id, parent_id=parent_id,
                               depth=depth)

    # -- events -------------------------------------------------------------
    def event(self, kind: str, **data: object) -> None:
        """Record a structured event to flight ring + trace (if on)."""
        if self.flight is not None:
            self.flight.record(kind, **data)
        if self.tracer.enabled:
            stack = _open_spans()
            self.tracer.instant(
                kind, args=dict(data) or None,
                parent_id=stack[-1].id if stack else 0,
                depth=stack[-1].depth + 1 if stack else 0)

    def postmortem(self, reason: str,
                   state: Optional[dict] = None) -> Optional[str]:
        """Dump a postmortem if a flight recorder is configured."""
        if self.flight is None:
            return None
        return self.flight.postmortem(reason, state=state,
                                      tracer=(self.tracer
                                              if self.tracer.enabled
                                              else None),
                                      metrics=self.metrics)
