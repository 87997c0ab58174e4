"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move between
runs: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
otherwise the fixed, gitignored ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.
    Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
