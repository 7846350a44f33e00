"""The plain reference for DeepSeek-V2: latent attention (MLA) with YaRN
rotary positions, shared and routed experts, leading dense layers, in
float32, written from the published equations (``modeling_deepseek.py``:
``DeepseekV2Attention``, ``DeepseekV2YarnRotaryEmbedding``,
``DeepseekV2MoE``) and nothing of the program.

Per layer: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)), the FFN a dense
SwiGLU MLP in the leading layers and the MoE after them.

MLA in the expanded form, without query LoRA: q = x W_q, split per head
into a 128-wide part without position and a 64-wide rotary part;
[c, k_pe] = x W_kva, c RMS-normed; [k_nope, v] = c W_kvb per head; the key
is [k_nope, k_pe] with the one k_pe shared by every head; scores are
q.k times (qk_nope + qk_rope)^-0.5 times YaRN's mscale squared; causal
softmax; the heads' outputs through W_o. The rotary part is read as pairs
(2i, 2i+1), each rotated by its YaRN frequency: the original below the
blend range, 1/factor of it above, a linear blend inside.

The MoE: a softmax over the router's logits, the top-k experts by weight
(renormalised only where ``norm_topk_prob``; ``routed_scaling_factor`` is 1),
every expert computed for every token and weighted by its (mostly zero) gate;
plus the shared experts, one SwiGLU MLP that sees every token. RMSNorm
scales by (1 + weight). Logits come from the final RMSNorm and the head.

Matrix products run at ``highest`` precision. Attention runs in blocks of
512 queries (where the length is a multiple of 512, as the check's padded
lengths are), so a sequence of 8,192 tokens fits beside the weights. One
sequence at a time, one layer at a time (a scan), experts upcast one at a
time. ``quant="fp8"`` rounds both operands of every matrix product to
float8 (e4m3), as in ``moe_lm.py``: the control.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from chipbench.reference.moe_lm import NEG, _fp8, _mm, _rmsnorm

Q_BLOCK = 512
# the ends of YaRN's blend range, in rotations over the original context
# (DeepSeek-V2's ``rope_scaling``: ``beta_fast``, ``beta_slow``)
BETA_FAST = 32
BETA_SLOW = 1


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary(m: dict) -> tuple[np.ndarray, float]:
    """The rotary part's inverse frequencies (YaRN-blended) and the factor
    on its cos and sin."""
    d, base = m["qk_rope_head_dim"], m["rope_theta"]
    freq_extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    factor = m["yarn_factor"]
    if not factor:
        return freq_extra.astype(np.float32), 1.0
    freq_inter = freq_extra / factor

    def dim_of(rotations):
        return (d * math.log(m["yarn_original_max_pos"] / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(BETA_FAST)), 0)
    high = min(math.ceil(dim_of(BETA_SLOW)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    extra_mask = 1.0 - ramp
    inv = freq_inter * (1.0 - extra_mask) + freq_extra * extra_mask
    cs = (_yarn_mscale(factor, m["yarn_mscale"])
          / _yarn_mscale(factor, m["yarn_mscale_all_dim"]))
    return inv.astype(np.float32), cs


def softmax_scale(m: dict) -> float:
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    if m["yarn_factor"] and m["yarn_mscale_all_dim"]:
        scale *= _yarn_mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2
    return scale


def _rope(x, m):
    """x: (S, H, d) at positions 0 .. S-1, pairs (2i, 2i+1) rotated; the
    result is [rotated evens, rotated odds], as DeepSeek orders it."""
    s = x.shape[0]
    inv, cs = rotary(m)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * cs, jnp.sin(ang)[:, None, :] * cs
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, p, m, quant):
    s = h.shape[0]
    nh, r = m["n_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = _mm(h, p["wq"], quant).reshape(s, nh, nope + rope)
    kv_a = _mm(h, p["wkv_a"], quant)
    c = _rmsnorm(kv_a[:, :r], p["kv_norm"], m["norm_eps"])
    k_pe = _rope(kv_a[:, None, r:], m)                              # (S, 1, rope)
    kv = _mm(c, p["wkv_b"], quant).reshape(s, nh, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (s, nh, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], m)], -1)
    if quant == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    scale = softmax_scale(m)
    size = Q_BLOCK if s % Q_BLOCK == 0 else s

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * size, size)
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision=jax.lax.Precision.HIGHEST) * scale
        causal = i * size + jnp.arange(size)[:, None] >= jnp.arange(s)[None, :]
        w = jax.nn.softmax(jnp.where(causal[None], scores, NEG), axis=-1)
        if quant == "fp8":
            w = _fp8(w, -1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, jnp.arange(s // size)).reshape(s, nh * vd)
    return _mm(o, p["wo"], quant)


def _mlp(h, wg, wu, wd, quant):
    wg, wu, wd = (w.astype(jnp.float32) for w in (wg, wu, wd))
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def _moe(h, p, m, quant):
    e, k = m["moe_experts"], m["moe_topk"]
    probs = jax.nn.softmax(_mm(h, p["router"], None), axis=-1)     # (S, E)
    top_w, top_i = jax.lax.top_k(probs, k)
    if m["moe_norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(top_i, e) * top_w[..., None], axis=1)  # (S, E)

    def expert(y, xs):
        wg, wu, wd, g = xs
        return y + g[:, None] * _mlp(h, wg, wu, wd, quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return y + _mlp(h, p["shared/w_gate"], p["shared/w_up"], p["shared/w_down"], quant)


def _stack(weights: dict, prefix: str) -> dict:
    """The weights of one stack of layers, stacked over its layers, by their
    names below ``prefix``."""
    return {k[len(prefix):]: v for k, v in weights.items() if k.startswith(prefix)}


BIG = ("w_gate", "w_up", "w_down")          # upcast where they are used


def _layers(x, stack, m, quant, ffn):
    eps = m["norm_eps"]

    def layer(x, p):
        p = {k: (v if k.split("/")[-1] in BIG else v.astype(jnp.float32))
             for k, v in p.items()}
        a = {k[5:]: v for k, v in p.items() if k.startswith("attn/")}
        x = x + _attention(_rmsnorm(x, p["norm"], eps), a, m, quant)
        h = _rmsnorm(x, p["ffn_norm"], eps)
        if ffn == "mlp":
            x = x + _mlp(h, p["mlp/w_gate"], p["mlp/w_up"], p["mlp/w_down"], quant)
        else:
            x = x + _moe(h, {k[4:]: v for k, v in p.items() if k.startswith("moe/")},
                         m, quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, stack)
    return x


@functools.partial(jax.jit, static_argnames=("model", "quant"))
def hidden(weights: dict, tokens: jax.Array, model: tuple,
           quant: str | None = None) -> jax.Array:
    """tokens: (S,) int32 -> (S, d_model) float32, after the final norm."""
    m = dict(model)
    x = weights["embed"][tokens].astype(jnp.float32)
    if m["n_dense_layers"]:
        x = _layers(x, _stack(weights, "dense/0/"), m, quant, "mlp")
    x = _layers(x, _stack(weights, "blocks/0/"), m, quant, "moe")
    return _rmsnorm(x, weights["final_norm"].astype(jnp.float32), m["norm_eps"])


@functools.partial(jax.jit, static_argnames=("quant",))
def head(weights: dict, x: jax.Array, quant: str | None = None) -> jax.Array:
    """(S, d_model) -> (S, vocab) float32 logits."""
    return _mm(x, weights["lm_head"].astype(jnp.float32), quant)


def logits(weights: dict, tokens: jax.Array, model: tuple,
           quant: str | None = None) -> jax.Array:
    """tokens: (S,) int32 -> (S, vocab) float32 logits at every position."""
    return head(weights, hidden(weights, tokens, model, quant), quant)


def model_key(model: dict) -> tuple:
    """The hashable subset of a configuration the reference reads."""
    keys = ("n_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "yarn_factor", "yarn_original_max_pos",
            "yarn_mscale", "yarn_mscale_all_dim", "moe_experts", "moe_topk",
            "moe_norm_topk", "n_dense_layers", "norm_eps")
    defaults = {"moe_norm_topk": True}
    return tuple((k, model.get(k, defaults.get(k, 0))) for k in keys)
