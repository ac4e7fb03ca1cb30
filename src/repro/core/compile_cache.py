"""JAX's persistent compilation cache, kept at one fixed place.

A cache directory is part of every cache key, so a path that moves (a
temporary name, a pid, a time) never hits.  Entry points call
``enable_compile_cache()`` once, before their first compile.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: src/repro/core/compile_cache.py -> parents[3].
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself; nothing is changed), else this checkout's ``.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
