"""Where JAX's persistent compilation cache lives for this checkout."""

import os
from pathlib import Path

import jax


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed place and return
    it.  Entry points call this first thing, before anything compiles.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing
    is set here.  Otherwise the cache goes to `<checkout>/.jax_cache`,
    derived from this file's location: the directory is part of every
    entry's key, so one that moved between runs would never hit."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
