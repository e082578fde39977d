"""Compilations and persistent-cache hits, counted from JAX's monitoring
events (``/jax/core/compile/backend_compile_duration`` and
``/jax/compilation_cache/cache_hits``)."""

from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self, jax):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == CACHE_HITS:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}
