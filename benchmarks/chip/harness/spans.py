"""Host spans of the benchmark's own, kept in memory.

A span is (name, start, end) on `time.perf_counter`. While a profiler trace
is being taken the same span is also written into the trace as a
`jax.profiler.TraceAnnotation`, so idle gaps of the device can be attributed
to what the host was doing. With tracing off a span costs two clock reads.
"""

from __future__ import annotations

import time
from contextlib import contextmanager


class Spans:
    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []
        self.annotate = False       # set while a profiler trace is running

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                try:
                    yield
                finally:
                    self.rows.append((name, t0, time.perf_counter()))
        else:
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))

    def total(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> float:
        """Seconds of `name` spans that started inside [t0, t1)."""
        return sum(e - s for n, s, e in self.rows if n == name and t0 <= s < t1)

    def durations(self, name: str, t0: float = float("-inf"),
                  t1: float = float("inf")) -> list[float]:
        return [e - s for n, s, e in self.rows if n == name and t0 <= s < t1]
