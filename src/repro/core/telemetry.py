"""Telemetry for the sync stack and the train path (paper §3.1: "telemetry
for monitoring").

One thread-safe :class:`Telemetry` instance rides a whole run: every sync
phase appends a timestamped :class:`Event` (dataset, target, phase, wall
time), named counters accumulate the work done — request/byte counts
from the instrumented storage layer, per-subsystem occurrences from the
daemon (checkpoint saves, breaker trips, catalog publishes/errors), JAX
lowerings — and named spans total the time spent in each part of a hot
loop (count and seconds per name, so a run of any length keeps a bounded
state).  The daemon, fleet, executor, trainer, data loader and benchmarks
all report through it, so a single object answers "what happened, in
order" (the event timeline), "how much did it cost" (the counters) and
"where did the time go" (the spans).

While JAX is loaded, each span is also a profiler ``TraceAnnotation``:
under a running trace it lands on the host plane, on the clock of the
device's ops, and costs next to nothing otherwise.  This module never
imports JAX itself, so ``repro.core`` imports without it.
"""

from __future__ import annotations

import sys
import threading
import time
import weakref
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field

# JAX records this event once per lowering of a jaxpr to an MLIR module,
# whether the executable then comes from a compile or the persistent cache.
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILES = "jax.compiles"

_compile_watchers: weakref.WeakSet = weakref.WeakSet()
_watch_lock = threading.Lock()
_listening = False


def _on_jax_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        with _watch_lock:
            watchers = list(_compile_watchers)
        for tel in watchers:
            tel.bump(COMPILES)


def _annotation(name: str):
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    return nullcontext() if profiler is None else \
        profiler.TraceAnnotation(name)


@dataclass
class Event:
    ts_ms: int
    dataset: str
    target: str
    phase: str          # plan | full | incremental | skip | error
    detail: str = ""
    elapsed_s: float = 0.0


@dataclass(eq=False)
class Telemetry:
    """Thread-safe: sync units report from executor worker threads, the
    loader's prefetch from its producer thread."""

    events: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    spans: dict = field(default_factory=dict)     # name -> [count, seconds]
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    def bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n

    def record(self, dataset: str, target: str, phase: str, detail: str = "",
               elapsed_s: float = 0.0) -> None:
        with self._lock:
            self.events.append(Event(time.time_ns() // 1_000_000, dataset,
                                     target, phase, detail, elapsed_s))

    @contextmanager
    def span(self, name: str):
        """Add the block's duration to the total of ``name``; under a
        running profiler trace the block is also an annotation ``name``."""
        with _annotation(name):
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                dt = (time.perf_counter_ns() - t0) * 1e-9
                with self._lock:
                    tot = self.spans.get(name)
                    if tot is None:
                        self.spans[name] = [1, dt]
                    else:
                        tot[0] += 1
                        tot[1] += dt

    @contextmanager
    def timed(self, dataset: str, target: str, phase: str, detail: str = ""):
        """A span named ``phase`` that also records its :class:`Event`."""
        t0 = time.perf_counter()
        try:
            with self.span(phase):
                yield
        finally:
            self.record(dataset, target, phase, detail,
                        time.perf_counter() - t0)

    def watch_compiles(self) -> None:
        """Count every JAX lowering from now on under ``jax.compiles``.

        One listener serves the whole process; it bumps every watching
        instance that is still alive, so watchers can come and go freely.
        """
        global _listening
        import jax.monitoring
        with _watch_lock:
            if not _listening:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_jax_duration)
                _listening = True
            _compile_watchers.add(self)
        with self._lock:
            self.counters.setdefault(COMPILES, 0)

    def timeline(self) -> list[str]:
        return [f"[{e.ts_ms}] {e.dataset} -> {e.target}: {e.phase} "
                f"{e.detail} ({e.elapsed_s * 1e3:.2f} ms)" for e in self.events]

    def summary(self) -> dict:
        """``{"counters": {name: n}, "spans": {name: [count, seconds]}}``."""
        with self._lock:
            return {"counters": dict(self.counters),
                    "spans": {k: list(v) for k, v in self.spans.items()}}
