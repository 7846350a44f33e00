"""Where JAX keeps its persistent compilation cache.

A program's entry point calls ``use_compile_cache()`` before its first
compile, so that a later run finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, inside the checkout: a directory whose name changed from run to run
# (a temp dir, a pid, a time) would never be found again.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Path:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache is ``.jax_cache/`` at the root
    of the checkout.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return Path(placed)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return CHECKOUT_CACHE
