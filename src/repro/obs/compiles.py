"""Compile accounting from ``jax.monitoring`` events.

JAX reports the start of each trace, lowering and backend compile as a
scalar event and its end as a duration event, both on the thread doing
the work.  :class:`CompileMonitor` turns those into

  counts     backend compiles (a program loaded from the persistent
             cache counts as one), and persistent-cache hits and misses
             (``chip_smoke.py`` prints them per phase);
  a clock    per-thread compile seconds, including a compile still in
             progress (the serving watchdog leaves them out of a flush's
             budget, so a cold compile never reads as a stuck device).

One process-wide monitor registers its listeners on first use
(:func:`compile_monitor`); nothing happens at import.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional

# event names emitted by jax._src.dispatch / jax._src.compiler
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_SPAN_EVENTS = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT)


class CompileAccount:
    """Compile seconds of one thread while it is attached
    (``CompileMonitor.attach``); nested trace/lower/compile spans count
    once, from the outermost start to its end."""

    def __init__(self):
        self._closed = 0.0
        self._depth = 0
        self._start = 0.0

    def _open(self) -> None:
        if self._depth == 0:
            self._start = time.monotonic()
        self._depth += 1

    def _close(self) -> None:
        if self._depth == 0:           # attached mid-span: nothing to end
            return
        self._depth -= 1
        if self._depth == 0:
            self._closed += time.monotonic() - self._start

    def seconds(self) -> float:
        """Compile seconds so far, the one in progress included."""
        start, depth = self._start, self._depth
        live = time.monotonic() - start if depth else 0.0
        return self._closed + live


class CompileMonitor:
    """Process-wide compile counters plus per-thread compile clocks."""

    def __init__(self):
        import jax.monitoring as mon
        self._lock = threading.Lock()
        self._accounts: Dict[int, List[CompileAccount]] = {}
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_scalar_listener(self._on_start)
        mon.register_event_duration_secs_listener(self._on_end)
        mon.register_event_listener(self._on_event)

    def _mine(self) -> List[CompileAccount]:
        return self._accounts.get(threading.get_ident(), [])

    def _on_start(self, event: str, value, **kw) -> None:
        if event in _SPAN_EVENTS:
            with self._lock:
                for acct in self._mine():
                    acct._open()

    def _on_end(self, event: str, duration: float, **kw) -> None:
        if event in _SPAN_EVENTS:
            with self._lock:
                if event == COMPILE_EVENT:
                    self.compiles += 1
                for acct in self._mine():
                    acct._close()

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1
        elif event == CACHE_MISS_EVENT:
            with self._lock:
                self.cache_misses += 1

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return {"compiles": self.compiles,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}

    @contextmanager
    def attach(self, acct: Optional[CompileAccount] = None
               ) -> Iterator[CompileAccount]:
        """Charge the calling thread's compile time to ``acct`` (a fresh
        account if None) until the block exits."""
        acct = acct if acct is not None else CompileAccount()
        ident = threading.get_ident()
        with self._lock:
            self._accounts.setdefault(ident, []).append(acct)
        try:
            yield acct
        finally:
            with self._lock:
                mine = self._accounts[ident]
                mine.remove(acct)
                if not mine:
                    del self._accounts[ident]


_MONITOR: Optional[CompileMonitor] = None
_MONITOR_LOCK = threading.Lock()


def compile_monitor() -> CompileMonitor:
    """The process-wide monitor; registers its listeners on first call."""
    global _MONITOR
    with _MONITOR_LOCK:
        if _MONITOR is None:
            _MONITOR = CompileMonitor()
        return _MONITOR
