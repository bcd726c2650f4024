"""JAX's persistent compilation cache for the programs that run on the chip.

Call :func:`enable_compile_cache` once, before the first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there and
this sets no other directory. Otherwise the cache goes to ``.jax_cache/`` at
the root of the checkout: a fixed path, because the directory is part of
what makes a later process find an entry again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Give the persistent cache its directory; returns that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
