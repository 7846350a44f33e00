"""JAX data plane for paged serving: one decode step over the page pool.

Mirrors ``models/transformer.decode_step`` but attention layers read/write
the shared HBM page pool through a per-sequence page table instead of dense
per-sequence ring buffers. A multi-head attention layer's pool holds K and
V pages, a latent attention (MLA) layer's one pool of cache entries
(``cfg.mla_cache_width`` lanes a token), which decode reads as keys and as
values in the absorbed form. Mamba/conv states stay per-row ("pinned
pages"). The whole step jits; the pools are not donated (see below).
"""
from __future__ import annotations

from functools import partial

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
    """Device arrays: per position of ``cfg.layer_specs``, stacked over its
    stack's layers. A paged leaf's axis 1 is the page id."""
    dt = jnp.dtype(cfg.dtype)
    pools = []
    for _, specs, nb in cfg.stacks:
        for spec in specs:
            if spec.kind == "attn":
                shape = (nb, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
                pools.append({"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)})
            elif spec.kind == "mla":
                shape = (nb, num_pages, page_size, cfg.mla_cache_width)
                pools.append({"kv": jnp.zeros(shape, dt)})
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
    reads only each row's pages below its length and compiles for the TPU
    only unless ``interpret`` is set; without it, ``paged_attention_ref``
    gathers the whole table.
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
        # a row that does not decode reads no page; its output is discarded
        attn_lengths = jnp.where(active, lengths + 1, 0)
        if use_kernel:
            out = paged_attention(q[:, 0], k_pool, v_pool, page_table,
                                  attn_lengths, softcap=cfg.attn_softcap,
                                  interpret=interpret)
        else:
            out = paged_attention_ref(q[:, 0], k_pool, v_pool, page_table,
                                      attn_lengths, softcap=cfg.attn_softcap)
        out = out.reshape(b, 1, h * hd)
        return out @ p["wo"], {"k": k_pool, "v": v_pool}

    def mla_sublayer(x, p, layer_pool, lengths, page_table, active, positions):
        """Absorbed latent attention: the query through W_UK scores the
        pages' entries (latent and roped key); the weighted latent goes
        through W_UV. The pool is the kernel's K and V both."""
        b = x.shape[0]
        q = L.mla_absorbed_query(x, p, cfg, positions)          # (B, H, W)
        lat = L.mla_latent(x, p, cfg, positions)[:, 0]          # (B, W)
        pids = page_table[jnp.arange(b), lengths // page_size]
        offs = lengths % page_size
        pool = layer_pool["kv"]
        pool = pool.at[pids, offs].set(
            jnp.where(active[:, None], lat, pool[pids, offs]))
        attn_lengths = jnp.where(active, lengths + 1, 0)
        attend = partial(paged_attention, interpret=interpret) if use_kernel \
            else paged_attention_ref
        out = attend(q, pool, pool, page_table, attn_lengths,
                     softcap=cfg.attn_softcap, sm_scale=L.mla_softmax_scale(cfg))
        return L.mla_absorbed_output(out, p, cfg), {"kv": pool}

    def step(params, pools, tokens, lengths, page_table, active):
        b = tokens.shape[0]
        positions = lengths[:, None]
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(lengths[:, None, None], (b, 3, 1))
        x = params["embed"][tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

        def block_body(specs, xc, scanned):
            block_params, layer_pools = scanned
            new_pools = []
            for i, spec in enumerate(specs):
                p = block_params[i]
                h = L.rmsnorm(xc, p["norm"], cfg.norm_eps)
                if spec.kind in ("attn", "mla"):
                    sub = attn_sublayer if spec.kind == "attn" else mla_sublayer
                    h, np_ = sub(h, p["attn"], layer_pools[i], lengths,
                                 page_table, active, positions)
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

        new_pools = ()
        for name, specs, _, start in T.stack_positions(cfg):
            x, new = jax.lax.scan(partial(block_body, specs), x,
                                  (params[name], pools[start:start + len(specs)]))
            new_pools += new
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = T.logits_fn(params, x, cfg)
        return logits, new_pools

    # NOTE: pools are NOT donated. The background flusher DMAs pages out of
    # the previous pool arrays concurrently with the next step; donation
    # would let XLA reuse those buffers mid-copy. On TPU the production fix
    # is a device-side staging copy of flush candidates + donation; here
    # (CPU, correctness-first) we keep the immutable-buffer guarantee.
    return jax.jit(step)
