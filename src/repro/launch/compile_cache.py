"""JAX persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call :func:`enable` once before they compile
anything; library code never does.  The cache key includes the cache
path, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is
set (JAX reads it itself and no other directory is set here), else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those that took over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
