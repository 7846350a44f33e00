"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0,
                        q_offset=0):
    """Materialized-softmax attention. q: (B,Sq,H,hd); k/v: (B,Skv,KV,hd)."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    qf = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, sq, kvh, rep, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, k.astype(jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no valid key produce uniform weights in softmax; zero them
    any_valid = mask.any(axis=-1)
    p = jnp.where(any_valid[None, None, None, :, None], p, 0.0)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, page_table, lengths, *,
                        softcap=0.0, sm_scale=None):
    """Decode attention over a paged pool.

    q: (B, H, hd); k/v_pages: (P, page, KV, hd), or (P, page, hd) for one KV
    head; page_table: (B, max_pages) int32 (entries beyond the sequence are
    arbitrary); lengths: (B,). Scores are scaled by ``sm_scale`` (default
    hd^-0.5).
    """
    b, h, hd = q.shape
    if k_pages.ndim == 3:
        k_pages, v_pages = k_pages[:, :, None], v_pages[:, :, None]
    n_pages, page, kvh, _ = k_pages.shape
    max_pages = page_table.shape[1]
    rep = h // kvh
    k_ctx = k_pages[page_table]                  # (B, max_pages, page, KV, hd)
    v_ctx = v_pages[page_table]
    k_ctx = k_ctx.reshape(b, max_pages * page, kvh, hd)
    v_ctx = v_ctx.reshape(b, max_pages * page, kvh, hd)
    scale = hd ** -0.5 if sm_scale is None else sm_scale
    qf = (q.astype(jnp.float32) * scale).reshape(b, kvh, rep, hd)
    s = jnp.einsum("bgrd,bkgd->bgrk", qf, k_ctx.astype(jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = jnp.arange(max_pages * page)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p, v_ctx.astype(jnp.float32))
    return o.reshape(b, h, hd).astype(q.dtype)


def flush_scores_ref(hits, clock, valid):
    """Paper §3.3.1 (vectorized): distance_score = hits*set_size + distance;
    flush score = set_size - 1 - rank(distance_score), -1 for invalid slots.

    hits: (num_sets, set_size) int32; clock: (num_sets,) int32;
    valid: (num_sets, set_size) bool.
    """
    ns, ss = hits.shape
    pos = jnp.arange(ss, dtype=jnp.int32)[None, :]
    dist = jnp.mod(pos - clock[:, None], ss)
    d = hits.astype(jnp.int32) * ss + dist
    big = jnp.iinfo(jnp.int32).max
    d = jnp.where(valid, d, big)
    di = d[:, :, None]
    dj = d[:, None, :]
    idx = jnp.arange(ss, dtype=jnp.int32)
    lt = (dj < di) | ((dj == di) & (idx[None, None, :] < idx[None, :, None]))
    rank = lt.sum(axis=-1).astype(jnp.int32)
    fs = ss - 1 - rank
    return jnp.where(valid, fs, -1)
