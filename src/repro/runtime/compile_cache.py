"""Persistent XLA compilation cache for the entry points.

Entry points call `enable_compile_cache()` at the top of `main()`, never at
import.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
this sets nothing.  Otherwise the cache goes to `.jax_cache/` at the root of
the checkout: a fixed path, because the path is part of what a cache entry is
found by.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
