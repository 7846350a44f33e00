"""DeepSeek-V2-Lite's mechanisms at a tiny size on the CPU: YaRN and the
softmax scale against their closed forms, absorbed decode against expanded
latent attention, the unnormalised top-k gate, and the serving engine's
logits (prefill, paged decode on the reference path and through the Pallas
kernel in interpret mode, preemption with offload and fetch of latent
pages) against the plain reference ``chipbench/reference/deepseek_v2.py``."""
import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import bench, weights as W
from chipbench.model import model_config
from chipbench.reference import deepseek_v2 as ref
from repro.configs import get_config
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.moe import init_moe, moe_local
from repro.serving import ServeEngine

PUBLISHED = get_config("deepseek-v2-lite")
CELL = bench.load_json(bench.PKG / "configs" / "deepseek-v2-lite-pp6.json")
# every mechanism of the cell's model at a size the CPU runs in seconds;
# YaRN's original context cut to 64 so that decode passes it
TINY = dict(CELL["model"], n_layers=3, d_model=64, n_heads=4, d_ff=96,
            vocab=128, moe_experts=8, moe_topk=3, moe_d_ff=32,
            moe_shared_d_ff=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, yarn_original_max_pos=64,
            moe_capacity_factor=4.0, dtype="float32")


def test_yarn_blend_range_and_softmax_scale():
    rope = PUBLISHED.qk_rope_head_dim
    assert L.yarn_blend_range(rope, PUBLISHED) == (10, 23)
    want = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert L.mla_softmax_scale(PUBLISHED) == pytest.approx(want, rel=1e-12)
    assert round(want, 5) == 0.11472
    inv = L.rope_inv_freq(rope, PUBLISHED)
    plain = 10000.0 ** -(np.arange(0, rope, 2) / rope)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)     # kept
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)  # scaled
    assert np.all((inv[11:23] < plain[11:23]) & (inv[11:23] > plain[11:23] / 40))
    model = dataclasses.asdict(PUBLISHED)
    np.testing.assert_allclose(ref.rotary(model)[0], inv, rtol=1e-6)
    assert ref.rotary(model)[1] == 1.0            # mscale == mscale_all_dim
    assert ref.softmax_scale(model) == pytest.approx(want, rel=1e-12)


def test_absorbed_decode_equals_expanded_attention():
    cfg = model_config(TINY)
    p = L.init_mla(jax.random.PRNGKey(1), cfg)
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2), p["kv_norm"].shape)
    b, s = 2, 70                                  # past YaRN's 64 positions
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    with jax.default_matmul_precision("highest"):
        want = L.mla_attention(x, p, cfg, positions=pos)[:, -1]
        lat = L.mla_latent(x, p, cfg, pos)                     # (B, S, W)
        q = L.mla_absorbed_query(x[:, -1:], p, cfg, pos[:, -1:])
        o = L.decode_attention(q[:, None], lat[:, :, None], lat[:, :, None],
                               lengths=jnp.full((b,), s),
                               sm_scale=L.mla_softmax_scale(cfg))
        got = L.mla_absorbed_output(o.reshape(q.shape), p, cfg)[:, 0]
    assert lat.shape[-1] == cfg.mla_cache_width == 128
    np.testing.assert_allclose(np.asarray(lat[..., 40:]), 0.0)  # padding lanes
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arch,normalised", [
    ("deepseek-v2-lite", False), ("olmoe-1b-7b", True),
    ("granite-moe-1b-a400m", True)])
def test_top_k_weights_normalised_only_where_configured(arch, normalised):
    """The routed experts' gate: the top-k softmax weights, renormalised
    only where the config says so."""
    cfg = dataclasses.replace(get_config(arch), d_model=32, moe_experts=8,
                              moe_topk=3, moe_d_ff=16, moe_shared_d_ff=0,
                              moe_capacity_factor=8 / 3, dtype="float32")
    assert cfg.moe_norm_topk is normalised
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, _ = moe_local(x, p, cfg)
    xs = np.asarray(x[0], np.float64)
    probs = np.exp(xs @ np.asarray(p["router"], np.float64))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(xs)
    for t in range(xs.shape[0]):
        top = np.argsort(-probs[t])[:3]
        w = probs[t, top] / (probs[t, top].sum() if normalised else 1.0)
        for e, we in zip(top, w):
            g = xs[t] @ np.asarray(p["w_gate"][e], np.float64)
            u = xs[t] @ np.asarray(p["w_up"][e], np.float64)
            want[t] += we * ((g / (1 + np.exp(-g)) * u)
                             @ np.asarray(p["w_down"][e], np.float64))
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=1e-5, rtol=1e-4)


def _serve(cfg, params, prompts, max_new, **engine):
    """Serves the prompts; returns each request's tokens and the logits the
    engine computed for each of its positions (prefill's last position and
    every decode step's)."""
    eng = ServeEngine(cfg, params, max_batch=3, page_size=8, **engine)
    seen: dict = {}
    prefill, step = eng._prefill, eng.step_fn

    def record_prefill(params, toks, max_seq):
        logits, cache = prefill(params, toks, max_seq=max_seq)
        seen[tuple(np.asarray(toks[0]))] = np.asarray(logits[0, -1])
        return logits, cache

    def record_step(params, pools, tokens, lengths, table, active):
        logits, new_pools = step(params, pools, tokens, lengths, table, active)
        for row, rid in enumerate(eng._rows):
            if rid is not None and bool(active[row]):
                seen[(rid, int(lengths[row]))] = np.asarray(logits[row, -1])
        return logits, new_pools

    eng._prefill, eng.step_fn = record_prefill, record_step
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    eng.run(600)
    outs = [eng.result(r).out for r in rids]
    stats = eng.stats()
    eng.close()
    return rids, outs, seen, stats


@pytest.mark.parametrize("case", ["reference", "kernel", "pressure"])
def test_engine_logits_match_the_reference(case):
    """Prefill's logits, then paged decode's at every position, equal the
    reference's full forward pass over the prompt and the served tokens.
    Under pressure (a pool of 12 pages) rows are preempted: their latent
    pages go to the host tier and are fetched back."""
    cfg = model_config(TINY)
    params, leaves = W.program_params(7, cfg, T.init_params)
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab, n)] for n in (5, 19, 11)]
    engine = {"num_sets": 16, "set_size": 4}
    if case == "kernel":
        engine["interpret"] = True
    if case == "pressure":
        engine = {"num_sets": 4, "set_size": 3}
    rids, outs, seen, stats = _serve(cfg, params, prompts, 48, **engine)
    if case == "pressure":
        assert stats["preemptions"] > 0 and stats["fetches"] > 0
    if case == "kernel":
        assert stats["attn_pages_read"] > 0
    weights = W.make(7, leaves, cfg)
    for rid, prompt, out in zip(rids, prompts, outs):
        assert len(out) == 48
        seq = prompt + out[:-1]
        want = np.asarray(ref.logits(weights, jnp.asarray(seq, jnp.int32),
                                     ref.model_key(TINY)))
        got = [seen[tuple(prompt)]] + [seen[(rid, n)]
                                       for n in range(len(prompt), len(seq))]
        np.testing.assert_allclose(np.stack(got), want[len(prompt) - 1:],
                                   atol=2e-4, rtol=2e-4)
        # greedy tokens: each served token is the reference's argmax
        assert out == [int(t) for t in want[len(prompt) - 1:].argmax(-1)]
