"""Where XLA's persistent compile cache lives.

A cold TPU compile of the packed step costs tens of seconds; the cache
turns the second process on the same checkout into a disk read.  The
directory is part of every entry's key, so it must be a path that does
not move between runs — never a temp dir, a pid or a timestamp.

One rule, one place: an operator who sets ``JAX_COMPILATION_CACHE_DIR``
owns the placement (jax reads the variable itself, so nothing is set in
code); otherwise the cache sits at ``<checkout>/.jax_cache``, derived
from this package's own location.  Entry points call :func:`enable`
before their first jit.

What was compiled is JAX's to say, not the caller's to guess:
:func:`watch_compiles` (``fleet.init`` calls it) counts JAX's own compile
events into the stat registry, so a silent retrace (a new shape reaching
a jitted function) counts like a rebuild the program asked for.
"""

from __future__ import annotations

import os

from paddlebox_tpu.utils.monitor import stat_add, stat_observe

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Place the compile cache; returns the directory in effect."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


# JAX's monitoring events (jax._src.dispatch / compilation_cache)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

# XLA compile requests this process has made since watch_compiles().  A
# caller that times a dispatch reads it before and after: a dispatch during
# which it moved traced and compiled, and is not a steady-state sample.
compile_requests = 0
_watching = False


def _on_duration(event: str, duration: float, **kw) -> None:
    global compile_requests
    if event == BACKEND_COMPILE:
        compile_requests += 1
        stat_observe("jit.compile_s", duration)


def _on_event(event: str, **kw) -> None:
    if event == CACHE_HIT:
        stat_add("jit.cache_hits")
    elif event == CACHE_MISS:
        stat_add("jit.cache_misses")


def watch_compiles() -> None:
    """Count every XLA compile request of this process into
    ``jit.compile_s`` (a persistent-cache hit is still a request: the jit
    saw a new shape) and the persistent cache's answers into
    ``jit.cache_hits`` / ``jit.cache_misses``.  JAX's listeners are
    process-wide and cannot be taken back, so this registers once."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
