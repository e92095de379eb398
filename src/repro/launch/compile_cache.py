"""JAX's persistent compilation cache, kept at one fixed path per checkout.

A chip run compiles every kernel and jitted step it meets; with the cache
on, a second run of the same programs loads them instead.  A run finds
only what earlier runs left at the same path, so the path must not move
between runs: no temporary name, process id or time goes into it.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and no other directory is set.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Either way every compiled program is
    cached, not only those that took more than JAX's default second: a
    Pallas kernel compiles in well under one.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
