"""JAX persistent compilation cache for the entry points.

Called once by a program's ``main`` (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) before its first compile, never when a library module
is imported.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and this sets nothing.  Otherwise the cache lives at a fixed path inside
the checkout (``<repo>/.jax_cache``, gitignored): the directory is part of
the cache's key, so it is never built from a temporary name, a pid or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
