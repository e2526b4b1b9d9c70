"""JAX's persistent compilation cache, at one fixed place.

Every entry point calls :func:`enable_compile_cache` before its first
compile, so processes of one run (and later runs on the same disk) reuse
each other's compiled programs instead of paying the compile again.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache — fixed, never derived from a temp name, pid or
# time: a cache directory that moves between runs never hits
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to :data:`CACHE_DIR`
    inside the checkout (listed in ``.gitignore``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
