"""The harness's own spans and the counters it reads from the program.

A span is recorded around each call into a layer, from the benchmark's
side: name, start and end on ``time.perf_counter``. While a trace is
being taken the same span is also a ``jax.profiler.TraceAnnotation``
named ``bench:<name>``, so it lies on the device trace's clock and an
idle gap can be named after it. Kept in memory, read when the run ends.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.records = []      # (name, start, end)
        self.annotate = False  # True while the profiler runs

    @contextlib.contextmanager
    def span(self, name: str):
        annotation = contextlib.nullcontext()
        if self.annotate:
            import jax

            annotation = jax.profiler.TraceAnnotation("bench:" + name)
        with annotation:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, since: float = 0.0, until: float = 1e30):
        """(seconds, count) of the spans of that name inside [since, until]."""
        picked = [(s, e) for n, s, e in self.records
                  if n == name and s >= since and e <= until]
        return sum(e - s for s, e in picked), len(picked)


def counter_value(metric, tags=None) -> float:
    """A program counter's value now: the series with exactly ``tags``
    (a tuple of label values), or all series summed."""
    series = metric.series()
    if tags is not None:
        return float(series.get(tuple(tags), 0))
    return float(sum(series.values()))
