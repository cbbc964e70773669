"""JAX's persistent compilation cache at a fixed place.

Entry points call :func:`enable_compile_cache` first thing in ``main()``
(never at import, never in tests). Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and nothing here overrides it. Otherwise
the cache lives in ``<checkout>/.jax_cache``, resolved from this file's
own path: the directory is part of the cache key, so it must not move
between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
