"""The plain reference: a decoder of attention + mixture-of-experts blocks in
float32, written from the published description and nothing of the program.

Per layer: x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x)). Attention is causal
multi-head with grouped K/V heads, rotary positions (the half-split rotation,
frequencies theta^(-2i/head_dim)) and, where the configuration says
``qk_norm``, an RMSNorm of each query and key head before the rotation
(OLMoE). The MoE layer routes each token to its top-k experts by a softmax
over the router's logits, renormalises the k weights, and sums the weighted
SwiGLU experts. RMSNorm scales by (1 + weight). Logits come from the final
RMSNorm and the output head (the embedding's transpose when tied).

Every expert is computed for every token and weighted by its (mostly zero)
gate: the plain form, with no dispatch to get wrong. Matrix products run at
``highest`` precision, so float32 is float32 on a TPU too. One sequence at a
time, one layer at a time (a scan), so the weights are upcast a layer at a
time and the reference fits beside nothing else.

``quant="fp8"`` rounds both operands of every matrix product to float8
(e4m3, amax-scaled per tensor for weights and per row for activations): the
control, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG = -1e30


def _fp8(x: jax.Array, axis) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a: jax.Array, w: jax.Array, quant: str | None) -> jax.Array:
    if quant == "fp8":
        a = _fp8(a, axis=-1)
        w = _fp8(w, axis=None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, theta):
    """x: (S, H, hd); positions 0 .. S-1."""
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(h, p, m, quant):
    s = h.shape[0]
    nh, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _mm(h, p["wq"], quant).reshape(s, nh, hd)
    k = _mm(h, p["wk"], quant).reshape(s, kvh, hd)
    v = _mm(h, p["wv"], quant).reshape(s, kvh, hd)
    if m.get("qk_norm"):
        q = _rmsnorm(q, p["q_norm"], m["norm_eps"])
        k = _rmsnorm(k, p["k_norm"], m["norm_eps"])
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, nh // kvh, axis=1)
    v = jnp.repeat(v, nh // kvh, axis=1)
    if quant == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(causal[None], scores, NEG), axis=-1)
    if quant == "fp8":
        w = _fp8(w, -1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=jax.lax.Precision.HIGHEST)
    return _mm(o.reshape(s, nh * hd), p["wo"], quant)


def _moe(h, p, m, quant):
    e, k = m["moe_experts"], m["moe_topk"]
    probs = jax.nn.softmax(_mm(h, p["router"], None), axis=-1)     # (S, E)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(top_i, e) * top_w[..., None], axis=1)  # (S, E)

    def expert(y, xs):
        wg, wu, wd, g = xs
        wg, wu, wd = (w.astype(jnp.float32) for w in (wg, wu, wd))
        out = _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return y


LAYER_KEYS = ("norm", "ffn_norm", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
              "attn/q_norm", "attn/k_norm", "moe/router", "moe/w_gate",
              "moe/w_up", "moe/w_down")


def layer_weights(weights: dict, prefix: str = "blocks/0/") -> dict:
    """The per-layer weights, stacked over layers, by name."""
    out = {}
    for key in LAYER_KEYS:
        if prefix + key in weights:
            out[key.split("/")[-1]] = weights[prefix + key]
    return out


@functools.partial(jax.jit, static_argnames=("model", "quant"))
def logits(weights: dict, tokens: jax.Array, model: tuple, quant: str | None = None):
    """tokens: (S,) int32 -> (S, vocab) float32 logits at every position."""
    m = dict(model)
    eps = m["norm_eps"]
    x = weights["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        p = {k: (v if k in ("w_gate", "w_up", "w_down") else v.astype(jnp.float32))
             for k, v in p.items()}
        x = x + _attention(_rmsnorm(x, p["norm"], eps), p, m, quant)
        x = x + _moe(_rmsnorm(x, p["ffn_norm"], eps), p, m, quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, layer_weights(weights))
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), eps)
    head = (weights["embed"].T if m["tie_embeddings"] else weights["lm_head"])
    return _mm(x, head.astype(jnp.float32), quant)


def model_key(model: dict) -> tuple:
    """The hashable subset of a configuration the reference reads."""
    keys = ("n_heads", "n_kv_heads", "head_dim", "qk_norm", "norm_eps",
            "rope_theta", "moe_experts", "moe_topk", "tie_embeddings")
    return tuple((k, model.get(k, False)) for k in keys)
