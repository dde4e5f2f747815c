"""JAX's persistent compilation cache for the entry points.

A cold train step of a full-width config compiles for minutes; the cache
makes the second run of the same program start at once.  The directory is
placed from outside: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads the variable itself, so nothing is set in code), else a fixed
directory inside the checkout — the path is part of the cache's key, so
it never depends on a temp name, a pid or the time.  Tests leave the
cache off; only the ``main``s of the entry points call this.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
