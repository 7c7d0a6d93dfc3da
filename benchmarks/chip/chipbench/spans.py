"""Host spans the harness opens around its calls into the program.

Each span is kept in memory on the host clock and is also written into the
profiler's trace as a ``TraceAnnotation`` (a no-op when no trace is
running), so that idle gaps on the device can be put down to what the host
was doing at the time.
"""

from __future__ import annotations

import time
from contextlib import contextmanager


class Spans:
    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> tuple[int, float]:
        """(count, seconds) of the spans named ``name`` that lie in [lo, hi]."""
        sel = [e - s for n, s, e in self.records
               if n == name and s >= lo and e <= hi]
        return len(sel), sum(sel)
