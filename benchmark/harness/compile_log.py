"""What JAX itself says it compiled (copied from ``chip_smoke.CompileLog``,
the original is listed in PERF.md for a later PR to fold).

Every XLA compile request with its seconds (a persistent-cache hit is
still a request: the jit saw a new shape) and the cache's hits and
misses.  ``trainer.step_compile_s`` only sees the rebuilds the trainer
asks for; a silent retrace shows here.
"""

from __future__ import annotations

import time
from typing import List, Tuple

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileLog:
    def __init__(self):
        import jax
        self.compiles: List[Tuple[float, float]] = []   # (when, seconds)
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kw):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def _on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.compiles.append((time.perf_counter(), duration))

    def between(self, t0: float, t1: float) -> List[float]:
        """Seconds of each compile request that ended in [t0, t1]."""
        return [d for when, d in list(self.compiles) if t0 <= when <= t1]

    def summary(self) -> dict:
        return {"requests": len(self.compiles),
                "seconds": sum(d for _, d in self.compiles),
                "cache_hits": self.hits, "cache_misses": self.misses}
