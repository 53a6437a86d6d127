"""Where JAX keeps its persistent compilation cache.

A cold PixHomology run on a TPU spends most of its first minutes
compiling (tens of seconds per image shape and capacity tier), so the
command-line entry points and ``chip_smoke.py`` share one on-disk cache.
Call :func:`setup_compile_cache` once at program start-up; importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The repository checkout: src/repro/launch/compile_cache.py -> root.
CHECKOUT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (git-ignored): the directory is part of a
    cache entry's identity, so it never derives from a temporary name, a
    process id or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
