"""The benchmark's host spans: wall-clock intervals, and with tracing on
also ``TraceAnnotation`` events in the profiler's trace."""

from __future__ import annotations

import time
from contextlib import contextmanager

__all__ = ["Spans"]


class Spans:
    """Spans as (name, start, end) in seconds after ``t0``."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.t0 = time.perf_counter()
        self.items: list[tuple[str, float, float]] = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    @contextmanager
    def span(self, name: str):
        if self.trace:
            import jax

            ann = jax.profiler.TraceAnnotation("bench." + name)
            ann.__enter__()
        s = self.now()
        try:
            yield
        finally:
            self.items.append((name, s, self.now()))
            if self.trace:
                ann.__exit__(None, None, None)

    def durations(self, name: str) -> list[float]:
        return [e - s for n, s, e in self.items if n == name]

    def wrap(self, name: str, fn, *, sync: bool = False):
        """``fn`` inside a span; with ``sync``, the span ends when its
        results are ready on the device."""
        import jax

        def call(*args):
            with self.span(name):
                out = fn(*args)
                if sync:
                    out = jax.block_until_ready(out)
            return out
        return call
