"""Where JAX keeps its persistent compilation cache.

One serving run compiles many programs (the decode step per KV bound, one
prefill per prompt bucket, warm builds on recomposition).  A cache kept at
a stable path lets the next run of the same checkout skip them; the path is
part of the cache's key, so it is never made from a temporary name, a pid
or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/common/jax_cache.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the place: JAX reads it
    itself and nothing here overrides it.  Otherwise the cache goes to the
    checkout's git-ignored ``.jax_cache/``.  Call before the first
    compilation."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
