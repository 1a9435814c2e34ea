"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``)
call ``enable_compile_cache()`` first thing; nothing calls it on import.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
helper changes nothing.  Otherwise the cache lives in ``.jax_cache/`` at
the root of the checkout (ignored by git): the directory is part of the
cache's key, so it is never built from a temporary name, a process id or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
