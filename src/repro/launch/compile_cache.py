"""JAX's persistent compilation cache, at a path that stays put.

The cache key includes the directory, so a cache that moves between runs
never hits.  :func:`enable_compile_cache` is called by every entry point
(serve and train launchers, the benchmark harness, ``chip_smoke.py``):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
  here overrides it;
- otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
