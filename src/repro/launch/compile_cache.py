"""Where JAX keeps its persistent compilation cache.

Call :func:`place_compile_cache` once at the start of an entry point,
before anything compiles; library code never calls it.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing is
  set here.
* Otherwise the cache goes to ``<checkout>/.jax_cache``.  The path is
  part of the cache key, so it is fixed: never a temporary, pid- or
  time-derived name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (this file is ``<checkout>/src/repro/launch/...``)
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def place_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
