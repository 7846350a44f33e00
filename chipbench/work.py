"""The work each call requires, from shapes alone.

What the algorithm needs, whatever implements it: every weight read once per
call, the K/V of the active rows' actual lengths read, the new K/V written,
and the FLOPs of the matrix products (2 per multiply-add) and of attention
over the actual lengths. Pool copies, padding, capacity slack and gathers of
unused table entries are not required work, so a roofline share computed
from these counts the same work whatever implements it.

``model`` is the ``model`` object of a configuration file.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def _bytes_per(model: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[model["dtype"]]


def _layers(model: dict) -> int:
    return model["n_layers"]


def attn_params(model: dict) -> int:
    d, h, kvh, hd = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                     model["head_dim"])
    return d * h * hd + 2 * d * kvh * hd + h * hd * d


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["moe_d_ff"]


def head_params(model: dict) -> int:
    return model["d_model"] * model["vocab"]


def active_matmul_params(model: dict) -> int:
    """Weights a token multiplies by: attention, router, its top-k experts in
    every layer, and the output head."""
    per_layer = (attn_params(model) + model["d_model"] * model["moe_experts"]
                 + model["moe_topk"] * expert_params(model))
    return _layers(model) * per_layer + head_params(model)


def kv_bytes_per_token(model: dict) -> int:
    return (_layers(model) * 2 * model["n_kv_heads"] * model["head_dim"]
            * _bytes_per(model))


def distinct_experts(model: dict, tokens: int) -> float:
    """Expected experts touched by ``tokens`` tokens routed uniformly."""
    e, k = model["moe_experts"], model["moe_topk"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def attention_flops(model: dict, keys: float) -> float:
    """Q.K and P.V over ``keys`` (query, key) pairs, in every layer."""
    return 4.0 * keys * model["n_heads"] * model["head_dim"] * _layers(model)


def decode_step(model: dict, rows: int, context: int) -> Work:
    """One decode step of ``rows`` rows whose contexts, the new token
    included, sum to ``context``."""
    b = _bytes_per(model)
    flops = (2.0 * rows * active_matmul_params(model)
             + attention_flops(model, context))
    layer_w = (attn_params(model) * b + model["d_model"] * model["moe_experts"] * 4
               + distinct_experts(model, rows) * expert_params(model) * b)
    weights = _layers(model) * layer_w + head_params(model) * b
    kv = context * kv_bytes_per_token(model)     # read the context, write the new
    return Work(flops, weights + kv)


def prefill(model: dict, length: int) -> Work:
    """A prompt of ``length`` tokens: every layer for every token, causal
    attention, logits for the last position; its K/V written once."""
    b = _bytes_per(model)
    layer_mm = (attn_params(model) + model["d_model"] * model["moe_experts"]
                + model["moe_topk"] * expert_params(model))
    flops = (2.0 * length * _layers(model) * layer_mm
             + 2.0 * head_params(model)
             + attention_flops(model, length * (length + 1) / 2))
    layer_w = (attn_params(model) * b + model["d_model"] * model["moe_experts"] * 4
               + distinct_experts(model, length) * expert_params(model) * b)
    weights = _layers(model) * layer_w + head_params(model) * b
    return Work(flops, weights + length * kv_bytes_per_token(model))


def roofline_seconds(work: Work, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = work.flops / peak["flops_per_s"]
    t_bytes = work.bytes / peak["bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
