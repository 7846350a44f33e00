"""JAX data plane for paged serving: one decode step over the page pool.

Mirrors ``models/transformer.decode_step`` but attention layers read/write
the shared HBM page pool through a per-sequence page table instead of dense
per-sequence ring buffers. Mamba/conv states stay per-row ("pinned pages",
DESIGN.md §5). The whole step jits; the pool arrays are donated so page
writes are in-place on device.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.moe import moe_ffn
from repro.models.ssm import init_mamba_state, mamba_decode_step
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref


def init_pools(cfg: ModelConfig, *, num_pages: int, page_size: int,
               max_batch: int):
    """Device arrays: per block position, stacked over n_blocks."""
    dt = jnp.dtype(cfg.dtype)
    nb = cfg.n_blocks
    pools = []
    for spec in cfg.block:
        if spec.kind == "attn":
            shape = (nb, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
            pools.append({"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)})
        else:
            st = init_mamba_state(max_batch, cfg)
            pools.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (nb, *a.shape)).copy(), st))
    return tuple(pools)


def make_paged_decode_step(cfg: ModelConfig, *, page_size: int,
                           use_kernel: bool = False, interpret: bool = False,
                           mesh=None):
    """Returns jitted ``step(params, pools, tokens, lengths, page_table,
    active) -> (logits, new_pools)``.

    tokens: (B, 1); lengths: (B,); page_table: (B, max_pages) pool ids;
    active: (B,) bool — inactive rows compute but their state is masked out.
    ``use_kernel`` runs attention through the Pallas paged kernel, which
    compiles for the TPU only unless ``interpret`` is set.
    """

    def attn_sublayer(x, p, layer_pool, lengths, page_table, active, positions):
        b = x.shape[0]
        h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = (x @ p["wq"]).reshape(b, 1, h, hd)
        k = (x @ p["wk"]).reshape(b, 1, kvh, hd)
        v = (x @ p["wv"]).reshape(b, 1, kvh, hd)
        if cfg.qk_norm:
            q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps)
        if cfg.rope_theta:
            q = L.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = L.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        pids = page_table[jnp.arange(b), lengths // page_size]     # (B,)
        offs = lengths % page_size
        # inactive rows park their write in the reserved scratch page 0 slot?
        # No: mask by writing their own current values (no-op via where).
        k_pool = layer_pool["k"].at[pids, offs].set(
            jnp.where(active[:, None, None], k[:, 0],
                      layer_pool["k"][pids, offs]))
        v_pool = layer_pool["v"].at[pids, offs].set(
            jnp.where(active[:, None, None], v[:, 0],
                      layer_pool["v"][pids, offs]))
        if use_kernel:
            out = paged_attention(q[:, 0], k_pool, v_pool, page_table,
                                  lengths + 1, softcap=cfg.attn_softcap,
                                  interpret=interpret)
            out = out.reshape(b, 1, h * hd)
        else:
            out = paged_attention_ref(q[:, 0], k_pool, v_pool, page_table,
                                      lengths + 1,
                                      softcap=cfg.attn_softcap)
            out = out.reshape(b, 1, h * hd)
        return out @ p["wo"], {"k": k_pool, "v": v_pool}

    def step(params, pools, tokens, lengths, page_table, active):
        b = tokens.shape[0]
        positions = lengths[:, None]
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(lengths[:, None, None], (b, 3, 1))
        x = params["embed"][tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

        def block_body(xc, scanned):
            block_params, layer_pools = scanned
            new_pools = []
            for i, spec in enumerate(cfg.block):
                p = block_params[i]
                h = L.rmsnorm(xc, p["norm"], cfg.norm_eps)
                if spec.kind == "attn":
                    h, np_ = attn_sublayer(h, p["attn"], layer_pools[i],
                                           lengths, page_table, active,
                                           positions)
                else:
                    h, st = mamba_decode_step(h, layer_pools[i], p["attn"], cfg)
                    np_ = jax.tree.map(
                        lambda new, old: jnp.where(
                            active.reshape((-1,) + (1,) * (new.ndim - 1)),
                            new, old), st, layer_pools[i])
                if cfg.post_norms:
                    h = L.rmsnorm(h, p["post_norm"], cfg.norm_eps)
                xc = xc + h
                new_pools.append(np_)
                if spec.ffn == "mlp":
                    hh = L.rmsnorm(xc, p["ffn_norm"], cfg.norm_eps)
                    hh = L.mlp(hh, p["mlp"], cfg.act)
                    if cfg.post_norms:
                        hh = L.rmsnorm(hh, p["ffn_post_norm"], cfg.norm_eps)
                    xc = xc + hh
                elif spec.ffn == "moe":
                    hh = L.rmsnorm(xc, p["ffn_norm"], cfg.norm_eps)
                    hh, _ = moe_ffn(hh, p["moe"], cfg, mesh=mesh)
                    xc = xc + hh
            return xc, tuple(new_pools)

        x, new_pools = jax.lax.scan(block_body, x, (params["blocks"], pools))
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = T.logits_fn(params, x, cfg)
        return logits, new_pools

    # NOTE: pools are NOT donated. The background flusher DMAs pages out of
    # the previous pool arrays concurrently with the next step; donation
    # would let XLA reuse those buffers mid-copy. On TPU the production fix
    # is a device-side staging copy of flush candidates + donation; here
    # (CPU, correctness-first) we keep the immutable-buffer guarantee.
    return jax.jit(step)
