"""What the two kinds of cell share: the outcome of a run, the traced
stretch, and the readings handed to per-layer metric readers."""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from chipbench import trace as tr

TRACE_STRETCH_S = 8.0     # traced part of a --trace 1 window, at its start


@dataclass
class Readings:
    """What a per-layer metric reader may read."""
    config: dict
    traffic: dict
    peaks: dict
    chips: int
    window: dict                    # counts and seconds of the traced stretch
    setup: dict = field(default_factory=dict)    # spans and events of set-up
    trace: tr.TraceSummary | None = None


@dataclass
class Outcome:
    end_to_end: dict                # metric name -> value
    numbers: dict                   # name compared -> value
    attempted: int
    failed: int
    memory_peak_bytes: int | None
    readings: Readings | None = None


class Stretch:
    """The traced stretch: starts the profiler and opens ``bench.window``
    and, inside it, the span ``phase`` for what the host does between the
    harness's own calls into the program; :meth:`tick` closes them once
    ``limit`` seconds have passed."""

    def __init__(self, log_dir: str | None, phase: str,
                 limit: float = TRACE_STRETCH_S):
        self.log_dir = log_dir
        self.phase = phase
        self.limit = limit
        self.t0 = self.t1 = None
        self._anns = []

    @property
    def open(self) -> bool:
        return bool(self._anns)

    def begin(self) -> None:
        import jax
        if self.log_dir is None:
            return
        tr.start(self.log_dir)
        self._anns = [jax.profiler.TraceAnnotation(tr.WINDOW),
                      jax.profiler.TraceAnnotation(self.phase)]
        for a in self._anns:
            a.__enter__()
        self.t0 = time.perf_counter()

    def tick(self, force: bool = False) -> None:
        if not self.open:
            return
        now = time.perf_counter()
        if force or now - self.t0 >= self.limit:
            self.t1 = now
            for a in reversed(self._anns):
                a.__exit__(None, None, None)
            self._anns = []
            tr.stop()

    def summary(self) -> tr.TraceSummary | None:
        return tr.reduce(self.log_dir) if self.log_dir else None
