"""Where compiled programs are kept between runs."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# One fixed, git-ignored directory inside the checkout. The path is part of
# the cache key, so it is never built from a temporary name, a pid or the
# time: a cache that moves never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable, before
    the first compile; returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache can be placed from
    outside: JAX reads the variable itself and this sets nothing.
    Otherwise the cache lives in :data:`DEFAULT_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
