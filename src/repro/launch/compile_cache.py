"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the cache directory, so a path that moves (a temporary
name, a pid, a time) never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set,
places the cache from outside: JAX reads that variable itself.  Otherwise the
cache lives in ``.jax_cache/`` at the root of this checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Call before the first compilation: JAX settles on its cache then.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
