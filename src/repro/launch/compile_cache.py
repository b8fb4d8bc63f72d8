"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, the examples, ``benchmarks/run.py``) call
``enable_compile_cache()`` once at start-up; library modules and tests never
do.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache goes to a fixed ``.jax_cache/`` at the repo
root: the directory is part of the cache key, so it must not move between
runs (never a temporary name, a process id or a time stamp).
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
