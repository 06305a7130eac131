"""JAX's persistent compilation cache, placed from outside.

Entry points call :func:`enable_compile_cache` before their first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and nothing else is set; otherwise the cache lives at the fixed
``<repo>/.jax_cache``. The directory is part of the cache's key, so it
never depends on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory it uses."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
