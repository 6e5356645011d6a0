"""The benchmark's own spans around its calls into each layer.

Recorded only in a ``--trace 1`` run: each span is kept in memory on the
host clock (for the span-based per-layer metrics) and written into the
profiler's trace as a ``bench:<name>`` annotation, so that an idle gap of
the device can be laid to what the host was doing in it.
"""
from __future__ import annotations

import contextlib
import time

PREFIX = "bench:"


class Spans:
    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.records: list[tuple[str, float, float]] = []   # name, t0, t1

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.enabled:
            yield
            return
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def wrap(self, name: str, fn):
        """``fn`` with a span of this name around every call."""
        def call(*args, **kw):
            with self.span(name):
                return fn(*args, **kw)
        return call

    def total(self, name: str, t_from: float = 0.0,
              t_to: float = float("inf")) -> float:
        """Seconds inside spans of this name that ended in [from, to]."""
        return sum(t1 - t0 for n, t0, t1 in self.records
                   if n == name and t_from <= t1 <= t_to)
