"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.serve``) call
:func:`enable_compile_cache` once, before their first compile; library code
and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: the repository root's ``.jax_cache/`` (listed in ``.gitignore``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    the variable itself and no other directory is set here.  Otherwise the
    cache lives in the fixed :data:`DEFAULT_CACHE_DIR` — never a temporary,
    per-process or timestamped path, since a later process finds its
    entries only at the same place.  Every compile is cached however short
    it was: the stencil executor's many small eager programs are a large
    part of a cold start.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = Path(env)
    else:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
