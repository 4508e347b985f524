"""JAX's persistent compilation cache at one fixed place.

Every entry point (``launch/serve``, ``launch/train``, ``benchmarks/run``,
``chip_smoke.py``) calls :func:`enable_compile_cache` before it compiles
anything, so separate processes of one checkout share compiled programs.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

__all__ = ["enable_compile_cache", "CACHE_DIR"]

# <checkout>/.jax_cache (listed in .gitignore).  The path is part of the
# cache key, so it is never built from a temporary name, a pid or the time.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory.  Otherwise the cache lives in
    :data:`CACHE_DIR`.  Programs that compile in under a second are not
    written (JAX's ``jax_persistent_cache_min_compile_time_secs``).

    The key covers each program's named scopes: by default JAX strips
    debug information, scopes included, before it hashes a program, so an
    executable compiled before a scope was added or moved would be loaded
    in its place and a profile could not attribute the ops to it.  Source
    frames stay out of the locations the key hashes, so the key does not
    depend on where the checkout lies."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
