"""Counts XLA compiles and persistent-cache loads through ``jax.monitoring``.

Copied from ``chip_smoke.py``'s ``CompileClock`` and extended with the
cache's own events.  JAX records ``backend_compile_duration`` around every
program it builds, whether the persistent cache served it or not, so a
program compiled afresh is a backend event that is not a cache hit.
"""
from __future__ import annotations

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileClock:
    def __init__(self):
        self.backend_n = 0
        self.backend_s = 0.0
        self.hits = 0
        self.misses = 0
        self.read_s = 0.0
        self.programs = []  # (seconds, name) of every backend event
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="", **_):
        if event == BACKEND_COMPILE:
            self.backend_n += 1
            self.backend_s += duration
            self.programs.append((duration, fun_name))
        elif event == CACHE_READ:
            self.read_s += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    @property
    def compiled_n(self) -> int:
        """Programs built by the compiler, not loaded from the cache."""
        return self.backend_n - self.hits

    @property
    def compile_s(self) -> float:
        """Seconds in the compiler, cache loads excluded."""
        return max(self.backend_s - self.read_s, 0.0)

    def snapshot(self) -> dict:
        return {"backend_n": self.backend_n, "compiled_n": self.compiled_n,
                "cache_hits": self.hits, "compile_s": self.compile_s,
                "cache_load_s": self.read_s}

    def slowest(self, n=5):
        return sorted(self.programs, reverse=True)[:n]
