"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles (the chip smoke check, the benchmarks, the
test suite) calls :func:`enable_compile_cache` before its first compile, so
all of them share one cache directory per checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (gitignored); fixed, so reruns from the same checkout
# find what earlier runs compiled
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
    directory is the cache: nothing here sets another. Otherwise the cache
    lives at ``<checkout>/.jax_cache``.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
