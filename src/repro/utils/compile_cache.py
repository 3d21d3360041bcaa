"""Placement of JAX's persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, at start-up (never at
import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing else is set here; otherwise the cache goes to one fixed
directory inside the checkout (``.jax_cache/``, git-ignored).  A fixed
path matters: the directory is part of what makes a later run find the
entries, so a temporary or per-process name would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every compiled program is kept (no minimum compile time), so a warm
    run skips the many sub-second sampler and gather compiles too."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
