"""Host spans recorded from the benchmark's own files.

``Recorder.span(name)`` times a block on the host clock and keeps it in
memory; with tracing on it also enters ``jax.profiler.TraceAnnotation``
under ``bench.<name>``, so the span lands in the profiler's trace on the
same clock as the device's operations.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Iterator, List, Tuple

SPAN_PREFIX = "bench."


@dataclass
class Recorder:
    traced: bool = False
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.monotonic()
        if self.traced:
            import jax

            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.monotonic()))

    def durations(self, name: str, t_from: float = float("-inf"),
                  t_to: float = float("inf")) -> List[float]:
        """Seconds of each ``name`` span that started in [t_from, t_to)."""
        return [t1 - t0 for n, t0, t1 in self.spans
                if n == name and t_from <= t0 < t_to]
