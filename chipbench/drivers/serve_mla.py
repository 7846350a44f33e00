"""Serving driver for a model of latent attention (MLA) layers: the serving
driver (``serve.py``) as it is, without its reference check, then

* the window's work from the engine's own counters (``decode_rows``,
  ``decode_context_tokens``, ``prefill_tokens``, ``prefill_attn_pairs``)
  and ``chipbench/work_mla.py``, in place of ``work.py``'s multi-head
  counts, so ``decode_step_roofline`` and ``serve_mfu`` read the work of
  this model. Each decode step gets the window's mean rows and context
  (``work_mla`` says why that is exact in a backlog);
* the comparison that decides ``correct``: the sample ``serve.py`` chose
  (``check.choose_sample``), each prompt with its served tokens through
  ``reference/deepseek_v2.py`` in one forward pass, the gap of each served
  token below the reference's best logit (``check.gap_stats``), compared
  with the cell's limits; with ``ctx.control`` the control's gaps instead.
"""
from __future__ import annotations

import time

import numpy as np

import jax.numpy as jnp

from chipbench import weights as W, work_mla
from chipbench.drivers import serve
from chipbench.model import model_config
from chipbench.reference import check, deepseek_v2

ROWS = 512          # positions a logits block of the check holds


def window_work(model: dict, counters: dict, steps: dict) -> dict:
    """``record["steps"]`` with its work recomputed by ``work_mla``."""
    n = steps["decode"]
    per_step = []
    if n:
        w = work_mla.decode_step(model, counters["decode_rows"] / n,
                                 counters["decode_context_tokens"] / n)
        per_step = [(w.flops, w.bytes)] * n
    pre = work_mla.prefill(model, counters["prefill_tokens"],
                           counters["prefill_attn_pairs"], steps["prefills"])
    return dict(steps, per_step=per_step, prefill_flops=pre.flops)


def gaps(weights: dict, key: tuple, prompt: list, out: list,
         control: str | None = None) -> np.ndarray:
    """At each position of a prompt and its served tokens, the reference's
    gap below its best logit: for the served token, or with ``control`` for
    the token that the lower precision puts first."""
    seq = list(prompt) + list(out[:-1])
    n = len(seq)
    tokens = np.zeros(check.bucket(n), np.int32)
    tokens[:n] = seq
    targets = np.zeros(len(tokens), np.int32)
    targets[len(prompt) - 1:n] = out
    x = deepseek_v2.hidden(weights, jnp.asarray(tokens), key)
    low = (deepseek_v2.hidden(weights, jnp.asarray(tokens), key, control)
           if control else None)
    found = []
    for at in range(0, len(tokens), ROWS):
        ref = deepseek_v2.head(weights, x[at:at + ROWS])
        pick = (jnp.argmax(deepseek_v2.head(weights, low[at:at + ROWS], control), -1)
                if control else jnp.asarray(targets[at:at + ROWS]))
        found.append(np.asarray(check._gap(ref, pick)))
    return np.concatenate(found)[len(prompt) - 1:n]


def compare(seed: int, leaves, cfg, model: dict, served: list, limits: dict,
            control: str | None = None):
    """(checks, readings, a line that says what was compared), as
    ``check.serve_checks`` gives them."""
    t0 = time.perf_counter()
    weights = W.make(seed, leaves, cfg)
    key = deepseek_v2.model_key(model)
    readings = {"program": check.gap_stats(
        [gaps(weights, key, prompt, out) for prompt, out, _ in served])}
    if control:
        readings["control"] = check.gap_stats(
            [gaps(weights, key, prompt, out, control) for prompt, out, _ in served])
    del weights
    stats = readings["control" if control else "program"]
    checks = [{"name": k, "value": v, "limit": limits[k]}
              for k, v in stats.items() if k in limits]
    n_pre = sum(p for _, _, p in served)
    info = (f"reference check: {len(served)} requests ({n_pre} preempted and "
            f"resumed), {sum(len(o) for _, o, _ in served)} served tokens; "
            + "; ".join(f"{who} " + ", ".join(f"{k} {v:.6g}" for k, v in st.items())
                        for who, st in readings.items())
            + f"; {time.perf_counter() - t0:.2f} s")
    return checks, readings, info


def run(ctx) -> dict:
    checked, ctx.check = ctx.check, False
    try:
        record = serve.run(ctx)
    finally:
        ctx.check = checked
    model = ctx.cell.config["model"]
    record["steps"] = window_work(model, record["counters"], record["steps"])
    if checked:
        record["checks"], record["readings"], info = compare(
            ctx.seed, record["leaves"], model_config(model), model,
            record["served"], ctx.cell.cell["check"]["limits"], ctx.control)
        ctx.say(info)
    return record
