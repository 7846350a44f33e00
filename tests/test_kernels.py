"""Pallas kernel allclose sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flush_score import flush_scores
from repro.kernels.paged_attention import paged_attention
from repro.kernels import ref

RNG = np.random.default_rng(42)


def _sweep(cases, keep=1):
    """Full allclose sweep runs nightly; the first ``keep`` cases stay in the
    fast tier as smoke coverage."""
    return [c if i < keep else pytest.param(c, marks=pytest.mark.slow)
            for i, c in enumerate(cases)]


def _rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


FLASH_CASES = [
    # b, sq, skv, h, kv, hd, causal, window, softcap
    (2, 128, 128, 4, 2, 64, True, 0, 0.0),
    (1, 256, 256, 8, 8, 64, True, 64, 50.0),       # SWA + softcap (gemma2)
    (2, 64, 192, 4, 1, 128, False, 0, 0.0),        # MQA cross-shape
    (1, 100, 100, 2, 2, 32, True, 0, 0.0),         # non-multiple-of-block
    (1, 16, 144, 6, 6, 64, True, 0, 0.0),          # MHA (whisper-like)
    (3, 128, 128, 8, 4, 16, True, 32, 0.0),
]


@pytest.mark.parametrize("case", _sweep(FLASH_CASES, keep=2))
@pytest.mark.parametrize("dtype", _sweep([jnp.float32, jnp.bfloat16]))
def test_flash_attention_matches_ref(case, dtype):
    b, sq, skv, h, kv, hd, causal, window, cap = case
    q = _rand((b, sq, h, hd), dtype)
    k = _rand((b, skv, kv, hd), dtype)
    v = _rand((b, skv, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_kv=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("blocks", _sweep([(32, 32), (64, 128), (128, 64)]))
def test_flash_attention_block_shape_invariance(blocks):
    bq, bkv = blocks
    q = _rand((1, 192, 4, 64), jnp.float32)
    k = _rand((1, 192, 2, 64), jnp.float32)
    v = _rand((1, 192, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bkv,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_q_offset_decode_tail():
    """Chunked decode: q is a tail slice at offset into the kv history."""
    q_full = _rand((1, 64, 4, 32), jnp.float32)
    k = _rand((1, 64, 4, 32), jnp.float32)
    v = _rand((1, 64, 4, 32), jnp.float32)
    full = ref.flash_attention_ref(q_full, k, v, causal=True)
    tail = flash_attention(q_full[:, 48:], k, v, causal=True, q_offset=48,
                           block_q=16, block_kv=16, interpret=True)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, 48:]),
                               atol=2e-5, rtol=2e-5)


PAGED_CASES = [
    # b, h, kv, hd, page, max_pages, pool
    (4, 8, 2, 64, 16, 8, 64),
    (2, 4, 4, 128, 32, 4, 16),
    (3, 6, 6, 32, 8, 16, 128),
    (1, 16, 8, 64, 64, 4, 8),
]


@pytest.mark.parametrize("case", _sweep(PAGED_CASES, keep=2))
@pytest.mark.parametrize("dtype", _sweep([jnp.float32, jnp.bfloat16]))
def test_paged_attention_matches_ref(case, dtype):
    b, h, kv, hd, page, maxp, pool = case
    q = _rand((b, h, hd), dtype)
    kp = _rand((pool, page, kv, hd), dtype)
    vp = _rand((pool, page, kv, hd), dtype)
    table = jnp.asarray(RNG.integers(0, pool, size=(b, maxp)), jnp.int32)
    lengths = jnp.asarray(RNG.integers(1, maxp * page, size=(b,)), jnp.int32)
    out = paged_attention(q, kp, vp, table, lengths, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, table, lengths)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_paged_attention_softcap():
    b, h, kv, hd, page, maxp, pool = 2, 4, 2, 32, 8, 4, 16
    q = _rand((b, h, hd), jnp.float32)
    kp = _rand((pool, page, kv, hd), jnp.float32)
    vp = _rand((pool, page, kv, hd), jnp.float32)
    table = jnp.asarray(RNG.integers(0, pool, size=(b, maxp)), jnp.int32)
    lengths = jnp.asarray([5, 30], jnp.int32)
    out = paged_attention(q, kp, vp, table, lengths, softcap=30.0,
                          interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, table, lengths, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("kernel", ["flash", "paged", "flush"])
def test_kernel_off_tpu_needs_explicit_interpret(kernel):
    """No silent fallback: off the TPU a kernel runs only when the caller
    asks for interpret mode."""
    if jax.default_backend() == "tpu":
        pytest.skip("the kernels compile on the TPU")
    q = _rand((1, 16, 2, 32), jnp.float32)
    call = {
        "flash": lambda: flash_attention(q, q, q),
        "paged": lambda: paged_attention(
            q[0], q.reshape(2, 8, 2, 32), q.reshape(2, 8, 2, 32),
            jnp.zeros((16, 2), jnp.int32), jnp.ones((16,), jnp.int32)),
        "flush": lambda: flush_scores(jnp.zeros((8, 4), jnp.int32),
                                      jnp.zeros((8,), jnp.int32),
                                      jnp.ones((8, 4), bool)),
    }[kernel]
    with pytest.raises(ValueError, match="interpret"):
        call()


@pytest.mark.parametrize("ns,ss", [(100, 12), (1000, 12), (64, 7), (513, 16),
                                   (1, 12), (256, 2)])
def test_flush_scores_matches_ref(ns, ss):
    hits = jnp.asarray(RNG.integers(0, 15, size=(ns, ss)), jnp.int32)
    clock = jnp.asarray(RNG.integers(0, ss, size=(ns,)), jnp.int32)
    valid = jnp.asarray(RNG.random((ns, ss)) > 0.3)
    out = flush_scores(hits, clock, valid, block_sets=128, interpret=True)
    want = ref.flush_scores_ref(hits, clock, valid)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_flush_scores_matches_host_policies():
    """Kernel == core/policies.py (the paper's exact formulation)."""
    from repro.core import policies
    hits = RNG.integers(0, 15, size=(50, 12)).astype(np.int64)
    clock = RNG.integers(0, 12, size=(50,))
    valid = RNG.random((50, 12)) > 0.2
    out = np.asarray(flush_scores(jnp.asarray(hits, jnp.int32),
                                  jnp.asarray(clock, jnp.int32),
                                  jnp.asarray(valid), interpret=True))
    for i in range(50):
        want = policies.flush_scores(hits[i], int(clock[i]), valid=valid[i])
        np.testing.assert_array_equal(out[i], want)
