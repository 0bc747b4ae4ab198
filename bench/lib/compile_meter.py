"""Backend compile seconds and compile events, from JAX's monitoring events.

A persistent-cache hit is reported inside the compile span it replaces, so
`compiles` counts every executable built or loaded, from the cache or not,
and `cache_hits` those loaded from the cache. The harness reads a snapshot
before and after the measured window: any compile in between fails the run.
"""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.cache_hits}
