"""JAX's persistent compilation cache, as the entry points use it."""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: a fixed directory inside the checkout: the cache's key includes the
#: path, so a directory that moved between runs would never hit
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
    is set here; otherwise the cache is ``CACHE_DIR``.  Entry points call
    it first thing in ``main``, so that every compile of the run is
    cached; importing a module never turns the cache on.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
