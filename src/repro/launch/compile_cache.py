"""JAX's persistent compilation cache, placed from outside the program.

A cold 40-layer serving tick compiles for a minute; the cache lets the
processes of one machine share that work.  The cache key includes its
directory, so the directory never moves: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads it itself, and nothing here overrides
it), otherwise ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
