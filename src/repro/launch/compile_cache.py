"""JAX's persistent compilation cache, turned on by entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache`` before their first compile; importing
``repro`` never does. ``JAX_COMPILATION_CACHE_DIR``, where set, is the
cache directory: JAX reads it itself and no other directory is set in
code. Otherwise the cache lives at the caller's fixed default, a
directory in the checkout that ``.gitignore`` lists. The path is part
of what a cache hit matches, so it never comes from a temp name, a pid
or the clock.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR_ENV", "enable_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(default_dir: str) -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env_dir = os.environ.get(CACHE_DIR_ENV)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(default_dir))
    return str(default_dir)
