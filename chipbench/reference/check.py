"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of the
finished requests, drawn from the seed, is run through the plain reference:
each prompt with its served tokens, in one forward pass. For every served
token the number read is the gap by which the reference's logit for that
token lies below the reference's best logit at its position; the run's
numbers are the widest and the mean gap over the sample. Served tokens are
greedy, so a sound program reads a gap only where its rounding picked one
of two nearly tied tokens.

The sample always holds the longest request the window finished and, where
there are any, finished requests that were preempted (their pages went to
the host tier and were fetched back), then others until it holds enough
tokens.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.reference import moe_lm


def choose_sample(finished: list, total_len, preempted: set, rng,
                  out_len, *, max_requests: int, min_tokens: int,
                  preempted_max: int) -> list:
    finished = sorted(finished)
    if not finished:
        return []
    chosen = [max(finished, key=lambda r: (total_len(r), -r))]
    pre = [r for r in finished if r in preempted and r not in chosen]
    if pre:
        take = rng.permutation(len(pre))[:preempted_max]
        chosen += [pre[i] for i in sorted(take)]
    rest = [r for r in finished if r not in chosen]
    for i in rng.permutation(len(rest)):
        if (len(chosen) >= max_requests
                or sum(out_len(r) for r in chosen) >= min_tokens):
            break
        chosen.append(rest[i])
    return chosen


def bucket(n: int) -> int:
    b = 512
    while b < n:
        b *= 2
    return b


@jax.jit
def _gap(logits: jax.Array, targets: jax.Array) -> jax.Array:
    return jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]


def served_gaps(ref_weights: dict, model_key: tuple, prompt: list,
                out: list) -> np.ndarray:
    """The reference's gap below its best logit for each served token."""
    seq = list(prompt) + list(out[:-1])
    n = len(seq)
    tokens = np.zeros(bucket(n), np.int32)
    tokens[:n] = seq
    logits = moe_lm.logits(ref_weights, jnp.asarray(tokens), model_key)
    targets = np.zeros(bucket(n), np.int32)
    targets[len(prompt) - 1:n] = out
    gaps = np.asarray(_gap(logits, jnp.asarray(targets)))
    return gaps[len(prompt) - 1:n]


@jax.jit
def _control_gap(ref_logits: jax.Array, low_logits: jax.Array) -> jax.Array:
    pick = jnp.argmax(low_logits, axis=-1)
    return _gap(ref_logits, pick)


def control_gaps(ref_weights: dict, model_key: tuple, prompt: list,
                 out: list, quant: str = "fp8") -> np.ndarray:
    """At each position of the same prompt and served tokens: the
    reference's gap for the token that the lower precision puts first."""
    seq = list(prompt) + list(out[:-1])
    n = len(seq)
    tokens = np.zeros(bucket(n), np.int32)
    tokens[:n] = seq
    tokens = jnp.asarray(tokens)
    ref = moe_lm.logits(ref_weights, tokens, model_key, None)
    low = moe_lm.logits(ref_weights, tokens, model_key, quant)
    return np.asarray(_control_gap(ref, low))[len(prompt) - 1:n]


def gap_stats(gaps: list[np.ndarray]) -> dict:
    """The numbers compared, from the gaps of every served token: the widest
    gap, the mean gap, and the share of tokens that are not the reference's
    first choice (gap above 0)."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {"widest_logit_gap": 1e30, "mean_logit_gap": 1e30,
                "off_argmax_share": 1.0}
    return {"widest_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
            "off_argmax_share": float(np.mean(g > 0))}


def serve_checks(seed: int, leaves, cfg, model: dict, served: list,
                 limits: dict, control: str | None = None):
    """Returns (checks, readings, a line that says what was compared). A
    number is compared where the cell gives it a limit. ``readings`` holds
    the program's numbers and, with ``control`` (a lower precision), the
    control's: the reference in that precision put in the program's place,
    read at each position of the same prompts and served tokens. The checks
    are then the control's, so a sound limit makes them fail."""
    t0 = time.perf_counter()
    ref_weights = W.make(seed, leaves, cfg)
    key = moe_lm.model_key(model)
    readings = {"program": gap_stats(
        [served_gaps(ref_weights, key, prompt, out) for prompt, out, _ in served])}
    if control:
        readings["control"] = gap_stats(
            [control_gaps(ref_weights, key, prompt, out, control)
             for prompt, out, _ in served])
    del ref_weights
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
