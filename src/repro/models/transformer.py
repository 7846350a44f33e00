"""Full model assembly for every assigned architecture.

One code path covers dense / MoE / SSM / hybrid / enc-dec / VLM:

* the layer stack is ``n_blocks`` repetitions of the config's ``block``
  pattern (1, 2 or 8 sublayers), after the config's leading dense layers
  (``cfg.stacks``). Parameters for pattern position ``i`` are stacked with
  leading dim ``n_blocks`` so each stack is a single ``lax.scan`` — compact
  HLO at 80 layers and scan-level remat.
* train/prefill forward, single-token decode with KV / SSM-state caches
  (ring buffers for sliding-window layers), whisper cross-attention, and
  qwen2-vl M-RoPE with stubbed patch embeddings.

All functions are pure; distribution comes from the shardings pjit places on
``params`` / ``cache`` (see distributed/sharding.py) plus the shard_map inside
``moe_ffn``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from . import layers as L
from .moe import init_moe, moe_ffn
from .ssm import init_mamba, init_mamba_state, mamba_chunked, mamba_decode_step

KPOS_INVALID = jnp.iinfo(jnp.int32).max // 2  # empty ring slot: always masked


def _constrain_batch(x, mesh):
    """Pin activations to data-parallel batch sharding (replicated elsewhere).

    Without this GSPMD happily propagates the embedding table's layout into
    the residual stream — d_model sharded over the FSDP axis and NO batch
    parallelism. One constraint per block boundary re-anchors the layout."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    if x.shape[0] % size != 0:
        return x
    spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _constrain_seq(x, mesh, cfg):
    """Sequence-parallel residual layout: P(dp, model, None). The sublayer
    boundaries re-constrain to P(dp, None, None), so GSPMD lowers the TP
    all-reduces as reduce-scatter (into this layout) + all-gather (out of
    it) and every norm/residual op runs on a 1/TP sequence slice."""
    if mesh is None or not cfg.seq_parallel or x.ndim != 3:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    if x.shape[0] % dp_size != 0 or x.shape[1] % tp != 0 or tp <= 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, "model", None)))


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _init_sublayer(rng, spec: LayerSpec, cfg: ModelConfig, cross: bool) -> dict:
    ks = jax.random.split(rng, 4)
    p: dict = {"norm": L.init_rmsnorm(cfg.d_model)["scale"]}
    if spec.kind == "attn":
        p["attn"] = L.init_attention(ks[0], cfg)
    elif spec.kind == "mla":
        p["attn"] = L.init_mla(ks[0], cfg)
    else:
        p["attn"] = init_mamba(ks[0], cfg)
    if cfg.post_norms:
        p["post_norm"] = L.init_rmsnorm(cfg.d_model)["scale"]
    if cross and spec.kind == "attn":
        p["cross_norm"] = L.init_rmsnorm(cfg.d_model)["scale"]
        p["cross"] = L.init_attention(ks[1], cfg)
    if spec.ffn == "mlp":
        p["ffn_norm"] = L.init_rmsnorm(cfg.d_model)["scale"]
        p["mlp"] = L.init_mlp(ks[2], cfg)
        if cfg.post_norms:
            p["ffn_post_norm"] = L.init_rmsnorm(cfg.d_model)["scale"]
    elif spec.ffn == "moe":
        p["ffn_norm"] = L.init_rmsnorm(cfg.d_model)["scale"]
        p["moe"] = init_moe(ks[2], cfg)
        if cfg.post_norms:
            p["ffn_post_norm"] = L.init_rmsnorm(cfg.d_model)["scale"]
    return p


def init_params(rng, cfg: ModelConfig) -> dict:
    """Returns the full parameter pytree. blocks[i] leaves have leading
    dim n_blocks (stacked for lax.scan); dense[0] leaves, n_dense_layers."""
    k_embed, k_blocks, k_head, k_enc = jax.random.split(rng, 4)
    dt = jnp.dtype(cfg.dtype)
    params: dict = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dt),
        "final_norm": L.init_rmsnorm(cfg.d_model)["scale"],
    }
    cross = cfg.encoder_layers > 0
    for name, specs, count in cfg.stacks:
        first = 0 if name == "blocks" else 1000       # a key stream per stack
        stacked = []
        for i, spec in enumerate(specs):
            keys = jax.random.split(jax.random.fold_in(k_blocks, first + i),
                                    count)
            stacked.append(jax.vmap(
                lambda k: _init_sublayer(k, spec, cfg, cross))(keys))
        params[name] = tuple(stacked)
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab))
                             * cfg.d_model ** -0.5).astype(dt)
    if cfg.encoder_layers:
        enc_spec = LayerSpec(kind="attn", ffn="mlp")
        keys = jax.random.split(k_enc, cfg.encoder_layers)
        params["encoder"] = {
            "blocks": jax.vmap(
                lambda k: _init_sublayer(k, enc_spec, cfg, cross=False))(keys),
            "final_norm": L.init_rmsnorm(cfg.d_model)["scale"],
        }
    return params


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig, vis_embeds=None):
    x = params["embed"][tokens]          # (B, S, D)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if vis_embeds is not None:
        nv = vis_embeds.shape[1]
        x = jnp.concatenate([vis_embeds.astype(x.dtype), x[:, nv:]], axis=1)
    if cfg.rope_theta == 0.0 and cfg.encoder_layers:   # whisper: absolute pos
        pos = jnp.arange(x.shape[1])[None, :]
        x = x + L.sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)
    return x


def head_weight(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def logits_fn(params, x, cfg: ModelConfig):
    logits = (x @ head_weight(params, cfg)).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# Forward (train / prefill trunk): scan over blocks
# ---------------------------------------------------------------------------

def _apply_sublayer(x, p, spec: LayerSpec, cfg: ModelConfig, *, positions,
                    mesh, enc_out, aux):
    """One sublayer (attn/mamba + ffn) in train/prefill form."""
    h = L.rmsnorm(x, p["norm"], cfg.norm_eps)
    h = _constrain_batch(h, mesh)            # seq-parallel: AG into sublayer
    if spec.kind == "attn":
        h = L.multihead_attention(h, p["attn"], cfg, positions=positions,
                                  window=spec.window, causal=True)
    elif spec.kind == "mla":
        h = L.mla_attention(h, p["attn"], cfg, positions=positions)
    else:
        h = mamba_chunked(h, p["attn"], cfg)
    if cfg.post_norms:
        h = L.rmsnorm(h, p["post_norm"], cfg.norm_eps)
    x = x + h
    if enc_out is not None and "cross" in p:
        h = L.rmsnorm(x, p["cross_norm"], cfg.norm_eps)
        kv = L.project_kv(enc_out, p["cross"], cfg, positions=None)
        h = L.multihead_attention(h, p["cross"], cfg, positions=None,
                                  kv_override=kv, causal=False)
        x = x + h
    if spec.ffn == "mlp":
        h = L.rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
        h = _constrain_batch(h, mesh)
        h = L.mlp(h, p["mlp"], cfg.act)
        if cfg.post_norms:
            h = L.rmsnorm(h, p["ffn_post_norm"], cfg.norm_eps)
        x = x + h
    elif spec.ffn == "moe":
        h = L.rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
        h = _constrain_batch(h, mesh)
        h, losses = moe_ffn(h, p["moe"], cfg, mesh=mesh)
        aux = {"lb": aux["lb"] + losses["lb"], "z": aux["z"] + losses["z"]}
        x = x + h
    return x, aux


def _encoder_forward(params, frames, cfg: ModelConfig, mesh=None):
    """Whisper encoder over precomputed conv frames (B, enc_seq, D)."""
    pos = jnp.arange(frames.shape[1])[None, :]
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + L.sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)
    x = _constrain_batch(x, mesh)

    def body(xc, p):
        xc = _constrain_batch(xc, mesh)
        h = L.rmsnorm(xc, p["norm"], cfg.norm_eps)
        h = L.multihead_attention(h, p["attn"], cfg, positions=None, causal=False)
        xc = xc + h
        h = L.rmsnorm(xc, p["ffn_norm"], cfg.norm_eps)
        xc = xc + L.mlp(h, p["mlp"], cfg.act)
        return xc, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
    return L.rmsnorm(x, params["encoder"]["final_norm"], cfg.norm_eps)


def forward(params, tokens, cfg: ModelConfig, *, positions=None,
            enc_frames=None, vis_embeds=None, mesh=None,
            remat: bool = True):
    """Trunk forward. Returns (final_hidden (B,S,D), aux losses dict)."""
    b, s = tokens.shape
    if positions is None:
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(jnp.arange(s)[None, None, :], (b, 3, s))
        else:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = embed_tokens(params, tokens, cfg, vis_embeds)
    enc_out = (_encoder_forward(params, enc_frames, cfg, mesh)
               if cfg.encoder_layers else None)

    def make_body(specs):
        def block_body(carry, block_params):
            xc, aux = carry
            xc = (_constrain_seq(xc, mesh, cfg) if cfg.seq_parallel
                  else _constrain_batch(xc, mesh))
            for i, spec in enumerate(specs):
                xc, aux = _apply_sublayer(xc, block_params[i], spec, cfg,
                                          positions=positions, mesh=mesh,
                                          enc_out=enc_out, aux=aux)
            return (xc, aux), None

        if remat == "dots":
            # plenty of HBM headroom in most cells: save matmul outputs and
            # recompute only elementwise chains in bwd (SSPerf: removes the
            # full-block fwd recompute)
            return jax.checkpoint(
                block_body, policy=jax.checkpoint_policies.dots_saveable)
        if remat:
            return jax.checkpoint(block_body)
        return block_body

    aux = {"lb": jnp.zeros((), jnp.float32), "z": jnp.zeros((), jnp.float32)}
    x = _constrain_batch(x, mesh)
    for name, specs, _ in cfg.stacks:
        (x, aux), _ = jax.lax.scan(make_body(specs), (x, aux), params[name])
    return L.rmsnorm(x, params["final_norm"], cfg.norm_eps), aux


def forward_logits(params, tokens, cfg: ModelConfig, **kw):
    """Materialized logits — smoke tests / tiny configs only."""
    x, aux = forward(params, tokens, cfg, **kw)
    return logits_fn(params, x, cfg), aux


# ---------------------------------------------------------------------------
# Loss (chunked over tokens so (B,S,vocab) is never materialized)
# ---------------------------------------------------------------------------

def lm_loss(params, cfg: ModelConfig, hidden, labels, mask=None,
            mesh=None, seq_chunk: int = 1024):
    """Cross-entropy over the vocab, scanning SEQUENCE chunks so (B,S,vocab)
    is never materialized — peak O(B * chunk * vocab_shard) — while the batch
    dim keeps its data-parallel sharding through the scan."""
    b, s, d = hidden.shape
    w = head_weight(params, cfg)
    hidden = _constrain_batch(hidden, mesh)
    c = min(seq_chunk, s)
    if s % c:
        c = s
    nc = s // c
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    xs = jnp.moveaxis(hidden.reshape(b, nc, c, d), 1, 0)     # (nc, B, c, D)
    ls = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)
    ms = jnp.moveaxis(mask.astype(jnp.float32).reshape(b, nc, c), 1, 0)

    @jax.checkpoint
    def body(tot, inp):
        xc, lc, mc = inp
        logits = (xc @ w).astype(jnp.float32)                # (B, c, V)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return tot + ((lse - tgt) * mc).sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls, ms))
    return total / jnp.maximum(ms.sum(), 1.0)


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

class DecodeCache(NamedTuple):
    lengths: jax.Array       # (B,) tokens already in cache
    layers: tuple            # per position of cfg.layer_specs: stacked leaves
    cross: Any = None        # whisper: {"k","v"}: (n_layers, B, enc_seq, KV, hd)


def _attn_cache_cap(spec: LayerSpec, max_seq: int) -> int:
    return min(spec.window, max_seq) if spec.window else max_seq


def stack_positions(cfg: ModelConfig):
    """(params key, pattern, repeats, first position in cfg.layer_specs)."""
    start = 0
    for name, specs, count in cfg.stacks:
        yield name, specs, count, start
        start += len(specs)


def init_decode_cache(cfg: ModelConfig, batch: int, max_seq: int) -> DecodeCache:
    dt = jnp.dtype(cfg.dtype)
    layer_caches = []
    for _, specs, nb in cfg.stacks:
        for spec in specs:
            if spec.kind in ("attn", "mla"):
                cap = _attn_cache_cap(spec, max_seq)
                if spec.kind == "attn":
                    shape = (nb, batch, cap, cfg.n_kv_heads, cfg.head_dim)
                    entry = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
                else:
                    entry = {"kv": jnp.zeros((nb, batch, cap, cfg.mla_cache_width), dt)}
                entry["kpos"] = jnp.full((nb, batch, cap), KPOS_INVALID, jnp.int32)
                layer_caches.append(entry)
            else:
                st = init_mamba_state(batch, cfg)
                layer_caches.append(jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (nb, *a.shape)), st))
    cross = None
    if cfg.encoder_layers:
        cross = {
            "k": jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq,
                            cfg.n_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq,
                            cfg.n_kv_heads, cfg.head_dim), dt),
        }
    return DecodeCache(lengths=jnp.zeros((batch,), jnp.int32),
                       layers=tuple(layer_caches), cross=cross)


def encode_cross_kv(params, enc_frames, cfg: ModelConfig, mesh=None):
    """Whisper: run the encoder once, project K/V for every decoder layer.

    Returns {"k","v"}: (n_layers, B, enc_seq, KV, hd). Cross-attn assumes a
    homogeneous decoder block (whisper: block = (attn,)).
    """
    if len(cfg.block) != 1 or cfg.block[0].kind != "attn":
        raise NotImplementedError("cross-attn assumes homogeneous decoder block")
    enc_out = _encoder_forward(params, enc_frames, cfg, mesh)
    cross_p = params["blocks"][0]["cross"]          # leaves: (n_layers, ...)

    def kv(pp):
        k, v = L.project_kv(enc_out, pp, cfg, positions=None)
        return {"k": k, "v": v}

    return jax.vmap(kv)(cross_p)


# ---------------------------------------------------------------------------
# Decode step (one new token per sequence)
# ---------------------------------------------------------------------------

def _decode_attn_sublayer(x, p, spec: LayerSpec, cfg: ModelConfig, cache,
                          lengths, positions):
    """x: (B,1,D). cache: {"k","v","kpos"} for THIS layer (no n_blocks dim)."""
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
    cap = cache["k"].shape[1]
    slot = lengths % cap                                   # (B,)
    bidx = jnp.arange(b)
    k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
    v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
    kpos = cache["kpos"].at[bidx, slot].set(lengths)
    out = L.decode_attention(q, k_cache, v_cache, lengths=lengths + 1,
                             softcap=cfg.attn_softcap, kpos=kpos)
    return out @ p["wo"], {"k": k_cache, "v": v_cache, "kpos": kpos}


def _decode_mla_sublayer(x, p, cfg: ModelConfig, cache, lengths, positions):
    """Absorbed latent attention over a ring cache {"kv","kpos"}."""
    b = x.shape[0]
    q = L.mla_absorbed_query(x, p, cfg, positions)          # (B, H, W)
    lat = L.mla_latent(x, p, cfg, positions)                # (B, 1, W)
    slot = lengths % cache["kv"].shape[1]
    bidx = jnp.arange(b)
    kv = cache["kv"].at[bidx, slot].set(lat[:, 0])
    kpos = cache["kpos"].at[bidx, slot].set(lengths)
    out = L.decode_attention(q[:, None], kv[:, :, None], kv[:, :, None],
                             lengths=lengths + 1, softcap=cfg.attn_softcap,
                             kpos=kpos, sm_scale=L.mla_softmax_scale(cfg))
    out = out.reshape(b, cfg.n_heads, cfg.mla_cache_width)
    return L.mla_absorbed_output(out, p, cfg), {"kv": kv, "kpos": kpos}


def decode_step(params, tokens, cache: DecodeCache, cfg: ModelConfig, *,
                positions=None, mesh=None):
    """tokens: (B, 1) -> (logits (B,1,V), new cache)."""
    b = tokens.shape[0]
    lengths = cache.lengths
    if positions is None:
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(lengths[:, None, None], (b, 3, 1))
        else:
            positions = lengths[:, None]
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.rope_theta == 0.0 and cfg.encoder_layers:
        x = x + L.sinusoidal_positions(lengths[:, None], cfg.d_model).astype(x.dtype)

    def block_body(specs, xc, scanned):
        block_params, layer_cache = scanned[0], scanned[1]
        cross_kv = scanned[2] if len(scanned) > 2 else None
        xc = _constrain_batch(xc, mesh)
        new_caches = []
        for i, spec in enumerate(specs):
            p = block_params[i]
            h = L.rmsnorm(xc, p["norm"], cfg.norm_eps)
            if spec.kind == "attn":
                h, nc = _decode_attn_sublayer(h, p["attn"], spec, cfg,
                                              layer_cache[i], lengths, positions)
            elif spec.kind == "mla":
                h, nc = _decode_mla_sublayer(h, p["attn"], cfg, layer_cache[i],
                                             lengths, positions)
            else:
                h, nc = mamba_decode_step(h, layer_cache[i], p["attn"], cfg)
            if cfg.post_norms:
                h = L.rmsnorm(h, p["post_norm"], cfg.norm_eps)
            xc = xc + h
            new_caches.append(nc)
            if cross_kv is not None and "cross" in p:
                h = L.rmsnorm(xc, p["cross_norm"], cfg.norm_eps)
                h = L.decode_cross_attention(h, p["cross"], cfg, cross_kv)
                xc = xc + h
            if spec.ffn == "mlp":
                h = L.rmsnorm(xc, p["ffn_norm"], cfg.norm_eps)
                h = L.mlp(h, p["mlp"], cfg.act)
                if cfg.post_norms:
                    h = L.rmsnorm(h, p["ffn_post_norm"], cfg.norm_eps)
                xc = xc + h
            elif spec.ffn == "moe":
                h = L.rmsnorm(xc, p["ffn_norm"], cfg.norm_eps)
                h, _ = moe_ffn(h, p["moe"], cfg, mesh=mesh)
                xc = xc + h
        return xc, tuple(new_caches)

    new_layers = ()
    for name, specs, _, start in stack_positions(cfg):
        xs = (params[name], cache.layers[start:start + len(specs)])
        if cfg.encoder_layers:
            xs = xs + (cache.cross,)
        x, new = jax.lax.scan(partial(block_body, specs), x, xs)
        new_layers += new
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, x, cfg)
    new_cache = DecodeCache(lengths=lengths + 1, layers=new_layers,
                            cross=cache.cross)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill: trunk forward + cache construction
# ---------------------------------------------------------------------------

def _ring(x, cap: int, fill):
    """The ring-buffer layout of (B, S, ...) rows in ``cap`` slots: row t in
    slot t % cap, only the last ``cap`` rows kept, empty slots ``fill``.

    Built from a pad or a roll (the layout is static): the equivalent
    scatter crashes the TPU compiler's fusion pass (jax 0.9.0, v5e)."""
    s = x.shape[1]
    if s <= cap:
        pad = [(0, 0)] * x.ndim
        pad[1] = (0, cap - s)
        return jnp.pad(x, pad, constant_values=fill)
    return jnp.roll(x[:, -cap:], s % cap, axis=1)


def prefill(params, tokens, cfg: ModelConfig, max_seq: int, *,
            positions=None, enc_frames=None, vis_embeds=None, mesh=None):
    """Process the prompt, build the decode cache. Returns (last_logits, cache)."""
    b, s = tokens.shape
    if positions is None:
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(jnp.arange(s)[None, None, :], (b, 3, s))
        else:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = embed_tokens(params, tokens, cfg, vis_embeds)
    enc_out = (_encoder_forward(params, enc_frames, cfg, mesh)
               if cfg.encoder_layers else None)
    aux0 = {"lb": jnp.zeros((), jnp.float32), "z": jnp.zeros((), jnp.float32)}

    def block_body(specs, carry, block_params):
        xc, aux = carry
        xc = _constrain_batch(xc, mesh)
        caches = []
        for i, spec in enumerate(specs):
            p = block_params[i]
            if spec.kind in ("attn", "mla"):
                hpre = L.rmsnorm(xc, p["norm"], cfg.norm_eps)
                cap = _attn_cache_cap(spec, max_seq)
                pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                       (b, s))
                if spec.kind == "attn":
                    k, v = L.project_kv(hpre, p["attn"], cfg, positions)
                    entry = {"k": _ring(k, cap, 0), "v": _ring(v, cap, 0)}
                else:
                    lat = L.mla_latent(hpre, p["attn"], cfg, positions)
                    entry = {"kv": _ring(lat, cap, 0)}
                entry["kpos"] = _ring(pos, cap, KPOS_INVALID)
                caches.append(entry)
                xc, aux = _apply_sublayer(xc, p, spec, cfg, positions=positions,
                                          mesh=mesh, enc_out=enc_out, aux=aux)
            else:
                h = L.rmsnorm(xc, p["norm"], cfg.norm_eps)
                h, state = mamba_chunked(h, p["attn"], cfg, return_state=True)
                # conv tail: rebuild the last (cw-1) conv inputs
                xr = L.rmsnorm(xc, p["norm"], cfg.norm_eps)
                tail = xr[:, -(cfg.ssm_conv - 1):, :]
                conv_x = tail @ p["attn"]["in_x"]
                conv_bc = tail @ p["attn"]["in_bc"]
                caches.append({"conv_x": conv_x.astype(jnp.dtype(cfg.dtype)),
                               "conv_bc": conv_bc.astype(jnp.dtype(cfg.dtype)),
                               "ssm": state})
                if cfg.post_norms:
                    h = L.rmsnorm(h, p["post_norm"], cfg.norm_eps)
                xc = xc + h
                if spec.ffn == "mlp":
                    hh = L.rmsnorm(xc, p["ffn_norm"], cfg.norm_eps)
                    hh = L.mlp(hh, p["mlp"], cfg.act)
                    if cfg.post_norms:
                        hh = L.rmsnorm(hh, p["ffn_post_norm"], cfg.norm_eps)
                    xc = xc + hh
                elif spec.ffn == "moe":
                    hh = L.rmsnorm(xc, p["ffn_norm"], cfg.norm_eps)
                    hh, losses = moe_ffn(hh, p["moe"], cfg, mesh=mesh)
                    aux = {"lb": aux["lb"] + losses["lb"],
                           "z": aux["z"] + losses["z"]}
                    xc = xc + hh
        return (xc, aux), tuple(caches)

    carry, layer_caches = (x, aux0), ()
    for name, specs, _ in cfg.stacks:
        carry, caches = jax.lax.scan(partial(block_body, specs), carry,
                                     params[name])
        layer_caches += caches
    x = L.rmsnorm(carry[0], params["final_norm"], cfg.norm_eps)
    last = x[:, -1:, :]
    logits = logits_fn(params, last, cfg)
    cross = (encode_cross_kv(params, enc_frames, cfg, mesh)
             if cfg.encoder_layers else None)
    cache = DecodeCache(lengths=jnp.full((b,), s, jnp.int32),
                        layers=layer_caches, cross=cross)
    return logits, cache
