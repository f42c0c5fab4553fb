"""JAX's persistent compilation cache, set up in one place.

A compiled step of a model at published widths takes minutes to build;
the cache lets the next process on the same machine skip that. The
directory is part of the cache key, so it is a fixed path: never a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache for a source checkout run with PYTHONPATH=src
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory: $JAX_COMPILATION_CACHE_DIR
    where it is set (JAX reads it itself; nothing else is set), otherwise
    `CHECKOUT_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
