"""JAX's persistent compilation cache for the entry points.

Called once by each entry point (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) before its first compile; nothing turns the cache
on at import time or in tests. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and that directory stands. Otherwise the cache
lives at a fixed path inside the checkout: the path is part of what a
later run must match to find an entry, so it never derives from a temp
name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # kernels compile in about a second each: cache them all, not only
    # the programs past JAX's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
