"""The persistent compilation cache goes where the environment says, and
otherwise to a fixed directory inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE, use_compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("placed", [None, "/elsewhere/cache"])
def test_use_compile_cache(monkeypatch, placed):
    before = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if placed:
        assert got == Path(placed)
        assert after == before          # JAX reads the variable; code sets nothing
    else:
        assert got == CHECKOUT_CACHE == ROOT / ".jax_cache"
        assert after == str(CHECKOUT_CACHE)
