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
    """Turn the persistent cache on; return the directory it uses.

    JAX leaves debug info out of the cache key by default, so a program
    that differs only in its stage scopes (``repro.obs.spans``) would load
    an executable compiled without them, and a profile of it would name no
    stage.  The key keeps the op names here; locations carry no source file
    or line, so the key still holds when a checkout moves or a line shifts.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
