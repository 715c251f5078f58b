"""JAX's persistent compilation cache, placed once per entry point.

Every entry point (``chip_smoke.py``, ``launch/serve``, ``launch/train``,
the bench mains) calls :func:`enable_compile_cache` before its first
compile; importing this module changes nothing.

Where the cache lives:

  ``JAX_COMPILATION_CACHE_DIR`` set   JAX reads the variable itself; the
                                      helper leaves the directory to it.
  unset                               ``<checkout>/.jax_cache`` — a fixed
                                      path, because the path is part of the
                                      cache key and a directory that moves
                                      never hits.

The step programs compile in well under JAX's default one-second floor
for caching on the TPU, so the helper drops the minimum compile time to
zero: every program the run compiles is written, and a second run of the
same code finds it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    cache_dir = os.environ.get(CACHE_ENV)
    if not cache_dir:
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
