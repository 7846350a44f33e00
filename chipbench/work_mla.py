"""The work each call requires for a model of latent attention (MLA) layers,
from shapes alone: decode in the absorbed form, prefill in the expanded
form, as ``chipbench/work.py`` counts it for multi-head attention.

What the algorithm needs, whatever implements it: every weight the call
touches read once, each decoding row's cache entries (the latent and the
roped key, ``kv_lora_rank + qk_rope_head_dim`` values a token and layer)
read once, the new entries written, and the FLOPs of the matrix products
(2 per multiply-add) and of attention over the actual lengths. The zero
lanes a cache entry is padded with, a second read of the latent as values,
pool copies and capacity slack are not required work.

A decode step of ``rows`` rows: per token and layer, q = x W_q, the latent
x W_kva, the query absorbed through W_UK (H x r x qk_nope), the output
through W_UV (H x r x v) and W_o; per (row, key) pair, H x (r + qk_rope)
multiply-adds for the scores and H x r for the values. The FFN: a dense MLP
in the leading layers; the router, the top-k experts and the shared MLP in
the others. Weights are read once a step: every expert that some row's
token routes to (``work.distinct_experts``, uniform routing), all of W_kvb.

In a backlog the rows stay full, so the mean rows and the mean context of
the window's steps (the engine's counters ``decode_rows`` and
``decode_context_tokens`` over the steps) give each step's work exactly:
the work is linear in both, but for the distinct-experts term, which is
exact where every step has the same number of rows.

``model`` is the ``model`` object of a configuration file.
"""
from __future__ import annotations

from chipbench.work import Work, distinct_experts, _bytes_per


def _mla_params(model: dict) -> int:
    """W_q, W_kva, the latent's norm, W_kvb, W_o of one layer."""
    d, h, r = model["d_model"], model["n_heads"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return d * h * (nope + rope) + d * (r + rope) + r + r * h * (nope + v) + h * v * d


def _decode_attn_matmul(model: dict) -> int:
    """Multiply-adds of one token's attention projections, absorbed."""
    d, h, r = model["d_model"], model["n_heads"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return d * h * (nope + rope) + d * (r + rope) + h * r * nope + h * r * v + h * v * d


def _prefill_attn_matmul(model: dict) -> int:
    """Multiply-adds of one token's attention projections, expanded."""
    d, h, r = model["d_model"], model["n_heads"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) + h * v * d


def entry_bytes(model: dict) -> int:
    """One token's cache entries over all layers: latent and roped key."""
    return (model["n_layers"] * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * _bytes_per(model))


def _expert(model: dict) -> int:
    return 3 * model["d_model"] * model["moe_d_ff"]


def _ffn_matmul(model: dict) -> tuple[int, int]:
    """Multiply-adds of one token's FFN: (a dense layer, an MoE layer)."""
    d = model["d_model"]
    moe = (d * model["moe_experts"] + model["moe_topk"] * _expert(model)
           + 3 * d * model["moe_shared_d_ff"])
    return 3 * d * model["d_ff"], moe


def _weight_bytes(model: dict, tokens: float) -> float:
    """Every weight ``tokens`` tokens touch, read once."""
    b = _bytes_per(model)
    d, dense, n = model["d_model"], model["n_dense_layers"], model["n_layers"]
    moe = (d * model["moe_experts"] * 4
           + distinct_experts(model, tokens) * _expert(model) * b
           + 3 * d * model["moe_shared_d_ff"] * b)
    return (n * _mla_params(model) * b + dense * 3 * d * model["d_ff"] * b
            + (n - dense) * moe + d * model["vocab"] * b)


def decode_step(model: dict, rows: float, context: float) -> Work:
    """One decode step of ``rows`` rows whose contexts, the new token
    included, sum to ``context``."""
    n, dense = model["n_layers"], model["n_dense_layers"]
    h, r, rope = model["n_heads"], model["kv_lora_rank"], model["qk_rope_head_dim"]
    f_dense, f_moe = _ffn_matmul(model)
    per_token = (n * _decode_attn_matmul(model) + dense * f_dense
                 + (n - dense) * f_moe + model["d_model"] * model["vocab"])
    attn = 2.0 * context * h * (2 * r + rope) * n
    flops = 2.0 * rows * per_token + attn
    cache = (context + rows) * entry_bytes(model)     # read the context, write the new
    return Work(flops, _weight_bytes(model, rows) + cache)


def prefill(model: dict, tokens: float, pairs: float, prompts: int = 1) -> Work:
    """``prompts`` prompts of ``tokens`` tokens in all, whose causal
    (query, key) pairs sum to ``pairs``: every layer for every token,
    logits for each prompt's last position; the entries written once."""
    n, dense = model["n_layers"], model["n_dense_layers"]
    h, nope, rope, v = (model["n_heads"], model["qk_nope_head_dim"],
                        model["qk_rope_head_dim"], model["v_head_dim"])
    f_dense, f_moe = _ffn_matmul(model)
    per_token = n * _prefill_attn_matmul(model) + dense * f_dense + (n - dense) * f_moe
    flops = (2.0 * tokens * per_token + 2.0 * prompts * model["d_model"] * model["vocab"]
             + 2.0 * pairs * h * (nope + rope + v) * n)
    per_prompt = _weight_bytes(model, tokens / max(prompts, 1))
    return Work(flops, prompts * per_prompt + tokens * entry_bytes(model))
