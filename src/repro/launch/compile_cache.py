"""JAX's persistent compilation cache for the repository's entry points.

A cold process on a TPU recompiles every kernel and dispatch it runs; the
persistent cache lets later processes read those programs back.  The cache
directory is part of what makes an entry findable, so it never moves: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX reads
it itself), else ``<repo>/.jax_cache`` (git-ignored).

Entry points call :func:`enable_compile_cache` from their ``main``; nothing
turns the cache on at import, so tests run without it.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
