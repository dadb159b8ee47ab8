"""JAX's persistent compilation cache for the launchers.

The cache key includes the cache path, so the default lives at a fixed
place: ``<repo>/.jax_cache`` (git-ignored). A ``JAX_COMPILATION_CACHE_DIR``
set in the environment wins — JAX reads it itself, and this helper then
sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
