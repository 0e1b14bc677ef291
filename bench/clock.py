"""Lowerings, XLA compiles and persistent-cache hits, from JAX's monitoring
events, so that set-up can be told from compilation inside the window.

A copy of the bring-up smoke's ``Clock`` (``chip_smoke.py``), with counts
beside the seconds.  JAX reports a cache miss only when it writes the new
entry.
"""
from __future__ import annotations

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


class Clock:
    def __init__(self):
        import jax

        self.lowerings = 0
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == LOWER:
            self.lowerings += 1
            self.compile_s += duration
        elif event == COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self) -> dict:
        return {"lowerings": self.lowerings, "compiles": self.compiles,
                "compile_s": self.compile_s, "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}
