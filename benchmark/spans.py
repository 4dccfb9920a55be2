"""The benchmark's own spans: kept in memory, written nowhere
until the run ends. With the profiler on, each span is also a
``TraceAnnotation``, so the device trace carries it on the same clock and
idle gaps can be named after what the host was doing."""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float, dict]] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1, attrs))

    def now(self) -> float:
        """An instant on the spans' clock (sweep ends)."""
        return time.perf_counter()

    def durations(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1, _ in self.records if n == name]
