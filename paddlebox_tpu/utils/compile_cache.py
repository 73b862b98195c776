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
"""

from __future__ import annotations

import os

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
