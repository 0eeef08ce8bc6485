"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
directory stands.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed
path, because the path is part of what a cache hit matches.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
