"""Neural-net building blocks: norms, RoPE/M-RoPE/YaRN, attention (GQA /
sliding window / softcap / qk-norm / cross / latent), MLPs. Pure functions
over param pytrees.

Conventions:
  * activations (B, S, D); attention heads materialized as (B, S, H, hd);
  * params are dicts of jnp arrays; init fns take an ``rng`` and return them;
  * math in the config dtype (bf16 on TPU), softmax/logits accumulate in f32;
  * long sequences use a lax.scan chunked attention (online softmax) — this is
    also the pure-jnp oracle for the flash_attention Pallas kernel.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rmsnorm(d: int) -> dict:
    return {"scale": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# Positions: RoPE and M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: tuple[int, ...] = ()) -> jax.Array:
    """x: (B, S, H, hd). positions: (B, S) or (B, 3, S) for M-RoPE.

    M-RoPE (qwen2-vl): the hd/2 rotary frequencies are split into sections
    (t, h, w); each section rotates by its own position stream.
    """
    b, s, h, hd = x.shape
    inv = rope_freqs(hd, theta)                      # (hd/2,)
    if mrope_sections:
        assert positions.ndim == 3, "M-RoPE needs (B, 3, S) positions"
        sec_id = jnp.repeat(jnp.arange(len(mrope_sections)),
                            jnp.array(mrope_sections), total_repeat_length=hd // 2)
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),           # (B, 3, S)
            jnp.broadcast_to(sec_id[None, :, None], (b, hd // 2, s)).astype(jnp.int32),
            axis=1,
        )                                            # (B, hd/2, S)
        angles = pos.transpose(0, 2, 1) * inv[None, None, :]     # (B, S, hd/2)
    else:
        angles = positions.astype(jnp.float32)[..., None] * inv  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings; positions (B, S) -> (B, S, D)."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(rng, cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k = jax.random.split(rng, 4)
    scale = d ** -0.5
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": (jax.random.normal(k[0], (d, h * hd)) * scale).astype(dt),
        "wk": (jax.random.normal(k[1], (d, kv * hd)) * scale).astype(dt),
        "wv": (jax.random.normal(k[2], (d, kv * hd)) * scale).astype(dt),
        "wo": (jax.random.normal(k[3], (h * hd, d)) * scale).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return cap * jnp.tanh(x / cap)
    return x


def _dense_attention(q, k, v, *, causal: bool, window: int, softcap: float,
                     q_offset: int | jax.Array = 0,
                     sm_scale: float | None = None) -> jax.Array:
    """Materialized attention. q/k: (B,S,H|KV,hd) v: (B,Sk,KV,hd_v).

    Inputs stay in their stored dtype; the logits dot accumulates in f32
    (preferred_element_type) — no f32 copies of Q/K in HBM (§Perf)."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    scale = hd ** -0.5 if sm_scale is None else sm_scale
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qf = qf.reshape(b, sq, kvh, rep, hd)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qf, k,
                        preferred_element_type=jnp.float32)
    logits = _softcap(logits, softcap)
    if causal or window:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(skv)[None, :]
        mask = jnp.ones((sq, skv), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _chunked_attention(q, k, v, *, causal: bool, window: int, softcap: float,
                       kv_chunk: int = 1024,
                       sm_scale: float | None = None) -> jax.Array:
    """Flash-style online-softmax attention, scanning KV chunks.

    Memory O(Sq * kv_chunk) instead of O(Sq * Skv). Oracle for the Pallas
    flash kernel; used for long prefill.
    """
    b, sq, h, hd = q.shape
    skv, kvh, hv = k.shape[1], k.shape[2], v.shape[-1]
    rep = h // kvh
    scale = hd ** -0.5 if sm_scale is None else sm_scale
    n_chunks = -(-skv // kv_chunk)
    pad = n_chunks * kv_chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, kv_chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, kvh, hv).transpose(1, 0, 2, 3, 4)
    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, kvh, rep, hd)
    qpos = jnp.arange(sq)[:, None]

    def step(carry, inp):
        m, l, acc = carry
        ci, (kb, vb) = inp
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kb.astype(jnp.float32))
        logits = _softcap(logits, softcap)
        kpos = ci * kv_chunk + jnp.arange(kv_chunk)[None, :]
        mask = kpos < skv
        if causal:
            mask = mask & (kpos <= qpos)
        if window:
            mask = mask & (kpos > qpos - window)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, rep, sq, hv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(n_chunks), (kc, vc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hv).astype(q.dtype)


def multihead_attention(x, params, cfg: ModelConfig, *, positions,
                        window: int = 0, causal: bool = True,
                        kv_override=None, q_offset=0,
                        dense_threshold: int | None = None) -> jax.Array:
    """Full self-attention (or cross-attention via kv_override=(k,v))."""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    if kv_override is None:
        k = (x @ params["wk"]).reshape(b, s, kvh, hd)
        v = (x @ params["wv"]).reshape(b, s, kvh, hd)
    else:
        k, v = kv_override
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        if kv_override is None:
            k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if cfg.rope_theta and kv_override is None:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    skv = k.shape[1]
    if dense_threshold is None:
        dense_threshold = cfg.attn_dense_threshold
    if max(s, skv) > dense_threshold:
        out = _chunked_attention(q, k, v, causal=causal, window=window,
                                 softcap=cfg.attn_softcap)
    else:
        out = _dense_attention(q, k, v, causal=causal, window=window,
                               softcap=cfg.attn_softcap, q_offset=q_offset)
    return out.reshape(b, s, h * hd) @ params["wo"]


def project_kv(x, params, cfg: ModelConfig, positions) -> tuple[jax.Array, jax.Array]:
    """K/V projections only (prefill cache write, cross-attn memory)."""
    b, s, _ = x.shape
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    k = (x @ params["wk"]).reshape(b, s, kvh, hd)
    v = (x @ params["wv"]).reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if cfg.rope_theta and positions is not None:
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return k, v


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2 ``DeepseekV2Attention``, no query
# LoRA). A token's cache entry is one row of ``cfg.mla_cache_width`` lanes:
# the RMS-normed latent c (kv_lora_rank), the roped key k_pe shared by every
# head (qk_rope_head_dim), zeros. Prefill expands c through wkv_b into
# per-head keys and values; decode absorbs wkv_b into the query and the
# output instead, so it attends over the cache entries as they are.
# ---------------------------------------------------------------------------

# YaRN's blend range ends (DeepSeek-V2's rope_scaling: beta_fast, beta_slow)
YARN_BETA_FAST = 32.0
YARN_BETA_SLOW = 1.0


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(dim: int, cfg: ModelConfig) -> np.ndarray:
    """Rotary frequencies of a ``dim``-wide key; with YaRN, frequency pair i
    is the original below the blend range, 1/factor of it above, and a
    linear blend inside (``DeepseekV2YarnRotaryEmbedding``)."""
    base = cfg.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra = 1.0 / base
    if not cfg.yarn_factor:
        return extra.astype(np.float32)
    lo, hi = yarn_blend_range(dim, cfg)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    inter = 1.0 / (cfg.yarn_factor * base)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_blend_range(dim: int, cfg: ModelConfig) -> tuple[int, int]:
    """The frequency pairs YaRN blends: from the one that turns beta_fast
    times over the original context to the one that turns beta_slow times."""
    def pair(turns):
        return (dim * math.log(cfg.yarn_original_max_pos / (turns * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))
    return (max(math.floor(pair(YARN_BETA_FAST)), 0),
            min(math.ceil(pair(YARN_BETA_SLOW)), dim - 1))


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times YaRN's mscale squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def rope_pairs(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """DeepSeek's rotation: x (B, S, H, d) is read as pairs (2i, 2i+1) and
    returned as [rotated evens, rotated odds] (the order q and k share)."""
    d = x.shape[-1]
    inv = jnp.asarray(rope_inv_freq(d, cfg))
    mscale = 1.0
    if cfg.yarn_factor:
        mscale = (_yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
                  / _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim or 1.0))
    angles = positions.astype(jnp.float32)[..., None] * inv   # (B, S, d/2)
    cos = (jnp.cos(angles) * mscale)[:, :, None, :]
    sin = (jnp.sin(angles) * mscale)[:, :, None, :]
    xf = x.astype(jnp.float32)
    ev, od = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([ev * cos - od * sin, od * cos + ev * sin], axis=-1)
    return out.astype(x.dtype)


def init_mla(rng, cfg: ModelConfig) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k = jax.random.split(rng, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": (jax.random.normal(k[0], (d, h * (nope + rope))) * d ** -0.5).astype(dt),
        "wkv_a": (jax.random.normal(k[1], (d, r + rope)) * d ** -0.5).astype(dt),
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wkv_b": (jax.random.normal(k[2], (r, h * (nope + vd))) * r ** -0.5).astype(dt),
        "wo": (jax.random.normal(k[3], (h * vd, d)) * (h * vd) ** -0.5).astype(dt),
    }


def mla_latent(x, p, cfg: ModelConfig, positions) -> jax.Array:
    """Cache entries of x (B, S, D): (B, S, mla_cache_width)."""
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = x @ p["wkv_a"]
    c = rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope_pairs(kv[..., None, r:], positions, cfg)[:, :, 0]
    pad = jnp.zeros(kv.shape[:-1] + (cfg.mla_cache_width - r - rope,), kv.dtype)
    return jnp.concatenate([c, k_pe, pad], axis=-1)


def _mla_query(x, p, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    nope = cfg.qk_nope_head_dim
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, nope + cfg.qk_rope_head_dim)
    return q[..., :nope], rope_pairs(q[..., nope:], positions, cfg)


def _mla_wkv_b(p, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """wkv_b as W_UK (r, H, qk_nope) and W_UV (r, H, v_head_dim)."""
    nope = cfg.qk_nope_head_dim
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, nope + cfg.v_head_dim)
    return w[..., :nope], w[..., nope:]


def mla_attention(x, p, cfg: ModelConfig, *, positions,
                  dense_threshold: int | None = None) -> jax.Array:
    """Causal self-attention in the expanded form (prefill, training)."""
    b, s, _ = x.shape
    h, r, rope, vd = (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    q_nope, q_pe = _mla_query(x, p, cfg, positions)
    lat = mla_latent(x, p, cfg, positions)
    w_uk, w_uv = _mla_wkv_b(p, cfg)
    c = lat[..., :r]
    k_nope = jnp.einsum("bsc,chd->bshd", c, w_uk)
    v = jnp.einsum("bsc,chd->bshd", c, w_uv)
    k_pe = jnp.broadcast_to(lat[:, :, None, r:r + rope], (b, s, h, rope))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    if dense_threshold is None:
        dense_threshold = cfg.attn_dense_threshold
    attend = _chunked_attention if s > dense_threshold else _dense_attention
    out = attend(q, k, v, causal=True, window=0, softcap=cfg.attn_softcap,
                 sm_scale=mla_softmax_scale(cfg))
    return out.reshape(b, s, h * vd) @ p["wo"]


def mla_absorbed_query(x, p, cfg: ModelConfig, positions) -> jax.Array:
    """Decode query (B, H, mla_cache_width) in float32, to score against the
    cache entries as they are: q_nope through W_UK into the latent's lanes,
    the roped q_pe into the key's."""
    q_nope, q_pe = _mla_query(x, p, cfg, positions)
    w_uk, _ = _mla_wkv_b(p, cfg)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32)
    pad = cfg.mla_cache_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    return jnp.concatenate([q_lat, q_pe[:, 0].astype(jnp.float32),
                            jnp.zeros(q_lat.shape[:2] + (pad,), jnp.float32)],
                           axis=-1)


def mla_absorbed_output(o, p, cfg: ModelConfig) -> jax.Array:
    """o: (B, H, >= kv_lora_rank) attention over the latent -> (B, 1, D)."""
    b, h = o.shape[:2]
    _, w_uv = _mla_wkv_b(p, cfg)
    v = jnp.einsum("bhc,chd->bhd", o[..., :cfg.kv_lora_rank].astype(jnp.float32),
                   w_uv.astype(jnp.float32))
    return v.reshape(b, 1, h * cfg.v_head_dim).astype(p["wo"].dtype) @ p["wo"]


# ---------------------------------------------------------------------------
# Decode-time attention over gathered KV (the paged pool path lives in
# serving/paged_model.py; this consumes already-gathered dense KV windows).
# ---------------------------------------------------------------------------

def decode_attention(q, k_ctx, v_ctx, *, lengths, softcap: float = 0.0,
                     kpos=None, sm_scale: float | None = None) -> jax.Array:
    """q: (B,1,H,hd); k_ctx/v_ctx: (B,S,KV,hd); lengths: (B,) valid KV count.

    kpos optionally gives absolute key positions (B,S) for windowed caches
    where the gathered window is a rotating buffer.

    The KV cache is consumed in its STORED dtype with f32 accumulation
    (preferred_element_type) — never materialize an f32 copy of the cache,
    which would triple decode HBM traffic (§Perf, gemma2 decode cell).
    """
    b, _, h, hd = q.shape
    skv, kvh = k_ctx.shape[1], k_ctx.shape[2]
    rep = h // kvh
    scale = hd ** -0.5 if sm_scale is None else sm_scale
    qf = (q.astype(jnp.float32) * scale).reshape(b, kvh, rep, hd)
    logits = jnp.einsum("bgrd,bkgd->bgrk", qf.astype(k_ctx.dtype), k_ctx,
                        preferred_element_type=jnp.float32)
    logits = _softcap(logits, softcap)
    if kpos is None:
        valid = jnp.arange(skv)[None, :] < lengths[:, None]
    else:
        valid = kpos < lengths[:, None]
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", w.astype(v_ctx.dtype), v_ctx,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h * v_ctx.shape[-1]).astype(q.dtype)


def decode_cross_attention(x, params, cfg: ModelConfig, kv) -> jax.Array:
    """Decode-time cross-attention (whisper): x (B,1,D) attends over the full
    precomputed encoder KV ({"k","v"}: (B, enc_seq, KV, hd)); every key valid."""
    b = x.shape[0]
    h, hd = cfg.n_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, 1, h, hd)
    skv = kv["k"].shape[1]
    out = decode_attention(q, kv["k"], kv["v"],
                           lengths=jnp.full((b,), skv, jnp.int32))
    return out @ params["wo"]


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(rng, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k = jax.random.split(rng, 3)
    dt = jnp.dtype(cfg.dtype)
    s_in, s_out = d ** -0.5, f ** -0.5
    return {
        "w_gate": (jax.random.normal(k[0], (d, f)) * s_in).astype(dt),
        "w_up": (jax.random.normal(k[1], (d, f)) * s_in).astype(dt),
        "w_down": (jax.random.normal(k[2], (f, d)) * s_out).astype(dt),
    }


def mlp(x, params, act: str = "silu") -> jax.Array:
    a = jax.nn.silu if act == "silu" else partial(jax.nn.gelu, approximate=True)
    return (a(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]
