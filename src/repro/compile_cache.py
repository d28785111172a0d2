"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

# Fixed, so that every run from this checkout finds the entries an earlier
# run wrote: a directory named after a temp file, a pid or the time would
# never hit.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
