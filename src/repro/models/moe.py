"""Mixture-of-Experts FFN: top-k routing, capacity-bounded sort-free dispatch,
expert parallelism over the data axis (all-to-all) + tensor parallelism over
the model axis (psum) via shard_map.

Design notes (DESIGN.md §4):
  * dispatch is gather/scatter based — FLOPs are exactly the active-expert
    FLOPs (one-hot einsum dispatch would be quadratic in expert count);
  * expert weights are sharded E over 'data' (EP) and d_ff over 'model' (TP);
    the pod axis replicates experts (grad all-reduce syncs them);
  * ``mesh=None`` (or 1-device) falls back to the identical local math —
    smoke tests and the reduced configs use that path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import init_mlp, mlp


def init_moe(rng, cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.moe_experts, cfg.moe_d_ff
    k = jax.random.split(rng, 4)
    dt = jnp.dtype(cfg.dtype)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "router": (jax.random.normal(k[0], (d, e)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k[1], (e, d, f)) * s_in).astype(dt),
        "w_up": (jax.random.normal(k[2], (e, d, f)) * s_in).astype(dt),
        "w_down": (jax.random.normal(k[3], (e, f, d)) * s_out).astype(dt),
    }
    if cfg.moe_shared_d_ff:
        p["shared"] = init_mlp(jax.random.fold_in(rng, 1), cfg,
                               d_ff=cfg.moe_shared_d_ff)
    return p


def _capacity(tokens: int, cfg: ModelConfig, factor: float | None = None) -> int:
    f = cfg.moe_capacity_factor if factor is None else factor
    c = int(tokens * cfg.moe_topk / cfg.moe_experts * f)
    return max(8, -(-c // 8) * 8)


def moe_local(x, params, cfg: ModelConfig, *, ep_axis: str | None = None,
              tp_axis: str | None = None, ep_size: int = 1,
              capacity_factor: float | None = None,
              stats_axes: tuple[str, ...] = ()):
    """Per-shard MoE math. x: (B, S, d) local. Returns (y, aux_losses)."""
    b, s, d = x.shape
    e, k_top = cfg.moe_experts, cfg.moe_topk
    t = b * s
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ params["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k_top)                          # (T, K)
    if cfg.moe_norm_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    # aux losses (switch-style load balance + router z-loss), averaged over
    # every axis that shards tokens so the scalar is truly replicated
    me = probs.mean(axis=0)                                        # (E,)
    one = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)         # (T, E)
    ce = one.mean(axis=0) / k_top
    if stats_axes:
        me = jax.lax.pmean(me, stats_axes)
        ce = jax.lax.pmean(ce, stats_axes)
    lb_loss = e * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    if stats_axes:
        z_loss = jax.lax.pmean(z_loss, stats_axes)

    # sort-free capacity dispatch
    cap = _capacity(t, cfg, capacity_factor)
    e_flat = idx.reshape(-1)                                       # (T*K,)
    oh = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                              e_flat[:, None], axis=1)[:, 0]       # (T*K,)
    keep = pos < cap
    dest = jnp.where(keep, e_flat * cap + pos, e * cap)            # overflow slot
    buf = jnp.full((e * cap + 1,), t, dtype=jnp.int32)
    buf = buf.at[dest].set(jnp.arange(t * k_top, dtype=jnp.int32) // k_top)
    buf = buf[:e * cap]

    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xg = x_pad[buf].reshape(e, cap, d)                             # (E, C, d)

    if ep_axis and ep_size > 1:
        # EP: ship each expert's rows to its owner shard.
        xg = jax.lax.all_to_all(xg, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)                        # (E/D, C*D, d)

    g = jnp.einsum("ecd,edf->ecf", xg, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xg, params["w_up"])
    act = jax.nn.silu if cfg.act == "silu" else partial(jax.nn.gelu, approximate=True)
    h = act(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    if ep_axis and ep_size > 1:
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                               tiled=True)                         # (E, C, d)

    y_flat = y.reshape(e * cap, d)
    out_tk = y_flat[jnp.where(keep, dest, 0)]                      # (T*K, d)
    out_tk = jnp.where(keep[:, None], out_tk, 0.0)
    out = (out_tk.reshape(t, k_top, d) * w[..., None].astype(y.dtype)).sum(axis=1)
    if tp_axis:
        # deferred past the token combine: psum of (T, d) instead of the
        # 1.25*topk-x padded (E, C, d) capacity buffer (§Perf iteration 2)
        out = jax.lax.psum(out, tp_axis)
    return out.reshape(b, s, d).astype(x.dtype), {"lb": lb_loss, "z": z_loss}


def moe_ffn(x, params, cfg: ModelConfig, mesh=None, **kw):
    """MoE FFN with optional distribution. x: (B, S, d) global. Shared
    experts (``params["shared"]``, one MLP) see every token and add to the
    routed experts' output."""
    routed = {k: v for k, v in params.items() if k != "shared"}
    y, aux = _routed_ffn(x, routed, cfg, mesh, **kw)
    if "shared" in params:
        y = y + mlp(x, params["shared"], cfg.act)
    return y, aux


def _routed_ffn(x, params, cfg: ModelConfig, mesh=None,
                dp_axes: tuple[str, ...] | None = None, ep_axis: str = "data",
                tp_axis: str = "model"):
    if mesh is None or ep_axis not in mesh.shape:
        return moe_local(x, params, cfg)
    if dp_axes is None:
        dp_axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    if x.shape[0] % dp_size != 0:
        # batch too small to shard (single-sequence decode): plain GSPMD path
        return moe_local(x, params, cfg)
    tp_size = mesh.shape.get(tp_axis, 1)
    mode = cfg.moe_parallel
    if mode == "tp" and (tp_size <= 1 or cfg.moe_d_ff % tp_size != 0):
        mode = "ep"
    ep_size = mesh.shape[ep_axis]
    if cfg.moe_experts % ep_size != 0:
        ep_size = 1  # fall back to pure TP when E doesn't divide the axis

    dp = P(dp_axes, None, None)
    if mode == "tp":
        # expert-TP: every shard holds a d_ff/TP slice of EVERY expert and
        # processes its LOCAL tokens end-to-end; one (T_local, d) psum
        # replaces the two (E, C, d) all-to-alls. Wire bytes per layer drop
        # from 2*E*C*d to T*d (~10-20x on the 16x16 mesh); the cost is
        # skinnier per-expert matmuls (d_ff/16 wide), noted in §Perf.
        fn = partial(moe_local, cfg=cfg, ep_axis=None, tp_axis=tp_axis,
                     ep_size=1, stats_axes=dp_axes)
        wspec_up = P(None, None, tp_axis)
        wspec_dn = P(None, tp_axis, None)
    else:
        fn = partial(moe_local, cfg=cfg,
                     ep_axis=ep_axis if ep_size > 1 else None,
                     tp_axis=tp_axis if tp_size > 1 else None,
                     ep_size=ep_size, stats_axes=dp_axes)
        wspec_up = P(ep_axis if ep_size > 1 else None, None, tp_axis)
        wspec_dn = P(ep_axis if ep_size > 1 else None, tp_axis, None)
    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(dp, {"router": P(), "w_gate": wspec_up,
                       "w_up": wspec_up, "w_down": wspec_dn}),
        out_specs=(dp, {"lb": P(), "z": P()}),
        check_vma=False,
    )(x, params)
    return out
