"""Process-wide XLA compile accounting from jax.monitoring (the
benchmark's copy of chip_smoke.py's CompileWatch): backend compiles with
their seconds, and the persistent cache's hits and misses."""
from __future__ import annotations


class CompileWatch:
    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    def _dur(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _ev(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
