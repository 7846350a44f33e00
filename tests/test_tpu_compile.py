"""Compiles for a TPU v5e that is described, not attached.

The main path's Pallas kernels and the full-width paged decode step are
lowered with ``interpret=False`` and compiled by the TPU compiler: what the
chip would refuse (tiling, fast-memory use, device memory) fails here.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never while a module is
imported: one process at a time may load the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flush_score import flush_scores
from repro.kernels.paged_attention import paged_attention
from repro.models.transformer import init_params
from repro.serving.paged_model import init_pools, make_paged_decode_step

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:       # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "kernel fell out of the program"
    return compiled


@pytest.mark.parametrize("b,h,kvh,hd,page,max_pages,pool", [
    (8, 16, 8, 64, 16, 16, 4097),        # granite-moe-1b-a400m
    (8, 32, 8, 128, 16, 64, 2049),       # qwen3-8b
])
def test_paged_attention_compiles(one_chip, b, h, kvh, hd, page, max_pages,
                                  pool):
    kv = _spec(one_chip, (pool, page, kvh, hd), jnp.bfloat16)
    _compile(functools.partial(paged_attention, interpret=False),
             _spec(one_chip, (b, h, hd), jnp.bfloat16), kv, kv,
             _spec(one_chip, (b, max_pages), jnp.int32),
             _spec(one_chip, (b,), jnp.int32))


def test_flash_attention_compiles(one_chip):
    q = _spec(one_chip, (1, 2048, 32, 128), jnp.bfloat16)      # qwen3-8b
    kv = _spec(one_chip, (1, 2048, 8, 128), jnp.bfloat16)
    _compile(functools.partial(flash_attention, causal=True, interpret=False),
             q, kv, kv)


def test_flush_scores_compiles(one_chip):
    ns, ss = 4096, 12                    # sets of the paper's 12 ways
    _compile(functools.partial(flush_scores, interpret=False),
             _spec(one_chip, (ns, ss), jnp.int32),
             _spec(one_chip, (ns,), jnp.int32),
             _spec(one_chip, (ns, ss), jnp.bool_))


def test_granite_paged_decode_step_compiles(one_chip):
    """All 24 layers at published widths, 4,097 pages of 16 tokens."""
    cfg = get_config("granite-moe-1b-a400m")
    batch, page, max_pages = 8, 16, 64
    on_chip = lambda tree: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(functools.partial(init_params, cfg=cfg),
                                    jax.random.PRNGKey(0)))
    pools = on_chip(jax.eval_shape(functools.partial(
        init_pools, cfg, num_pages=4097, page_size=page, max_batch=batch)))
    step = make_paged_decode_step(cfg, page_size=page, use_kernel=True)
    compiled = step.lower(params, pools,
                          _spec(one_chip, (batch, 1), jnp.int32),
                          _spec(one_chip, (batch,), jnp.int32),
                          _spec(one_chip, (batch, max_pages), jnp.int32),
                          _spec(one_chip, (batch,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"
