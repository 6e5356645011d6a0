"""Seconds spent in compile-or-load-from-cache and what the persistent
cache did, from JAX's own monitoring events (a copy of chip_smoke.py's
CompileClock, PR 22: the benchmark keeps its own yardstick)."""
from __future__ import annotations


class CompileClock:
    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1     # compiled here and written

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "programs": self.programs}
