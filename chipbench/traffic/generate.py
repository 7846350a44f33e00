"""The one traffic generator. It reads a mix's parameters from
``chipbench/traffic/<mix>.json`` and makes every request from ``--seed``.

Every seed gets the same sizes and gaps, in another order: the requests come
in blocks of ``block`` (default 16), and each block holds the same ``block``
stratified quantiles of the length distributions and of the exponential
inter-arrival gaps, paired and ordered by the seed. So a block of arrivals
always spans the same time, any window of a few blocks holds nearly the same
mix, and two seeds differ in order and token ids, not in the work.

Mix parameters:

  arrivals      "poisson" (open loop at ``rate_per_s``) or "backlog" (a
                queue that never drains: the driver tops it up)
  rate_per_s    mean arrival rate (poisson)
  prompt        {"median", "sigma", "min", "max", "grid"}: lognormal length,
                clipped to [min, max] and rounded up to the next of ``grid``
                lengths spaced evenly in log between min and max (multiples
                of 16), so prefill sees ``grid`` shapes at most
  output        {"median", "sigma", "min", "max"}: lognormal, clipped
  block         requests per stratified block
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float            # arrival time from the start of the schedule
    prompt: tuple           # token ids
    max_new: int


def _ndtri(p: np.ndarray) -> np.ndarray:
    """Inverse of the standard normal CDF (Acklam's rational approximation,
    relative error below 1.2e-9), so the generator needs nothing but numpy."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / \
        ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / \
        ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0]*r+a[1])*r+a[2])*r+a[3])*r+a[4])*r+a[5])*q / \
        (((((b[0]*r+b[1])*r+b[2])*r+b[3])*r+b[4])*r+1)
    return out


def grid(spec: dict) -> list[int]:
    """The prompt lengths a mix can produce: ``grid`` lengths, evenly spaced
    in log between min and max, each a multiple of 16."""
    lo, hi, n = spec["min"], spec["max"], spec["grid"]
    pts = np.exp(np.linspace(math.log(lo), math.log(hi), n))
    return sorted({int(-(-round(p) // 16) * 16) for p in pts})


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles (i + 0.5) / n, clipped."""
    p = (np.arange(n) + 0.5) / n
    x = spec["median"] * np.exp(spec["sigma"] * _ndtri(p))
    return np.clip(x, spec["min"], spec["max"])


def block_sizes(traffic: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One block's prompt lengths, output lengths and gaps (seconds), in
    stratified order; the seed only reorders them."""
    n = traffic.get("block", 16)
    g = np.asarray(grid(traffic["prompt"]))
    raw = lognormal_quantiles(traffic["prompt"], n)
    prompts = g[np.searchsorted(g, raw - 1e-9)]
    outputs = np.round(lognormal_quantiles(traffic["output"], n)).astype(int)
    if traffic["arrivals"] == "poisson":
        p = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-p) / traffic["rate_per_s"]
    else:
        gaps = np.zeros(n)
    return prompts, outputs, gaps


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for ``(seed, stream)``; any whole seed works."""
    return np.random.default_rng(np.random.SeedSequence([stream, seed]))


def requests(traffic: dict, seed: int, vocab: int, count: int,
             start_index: int = 0) -> list[Request]:
    """Requests ``start_index`` .. ``start_index + count - 1`` of the seed's
    schedule. The schedule is made block by block, so any slice of it is the
    same whichever slice was asked for before."""
    n = traffic.get("block", 16)
    prompts, outputs, gaps = block_sizes(traffic)
    block_span = float(gaps.sum())
    out = []
    first_block = start_index // n
    last_block = (start_index + count - 1) // n
    for blk in range(first_block, last_block + 1):
        rng = rng_for(seed, blk)
        order_p = rng.permutation(n)
        order_o = rng.permutation(n)
        order_g = rng.permutation(n)
        due = blk * block_span + np.concatenate([[0.0], np.cumsum(gaps[order_g])[:-1]])
        for j in range(n):
            i = blk * n + j
            if i < start_index or i >= start_index + count:
                continue
            s = int(prompts[order_p[j]])
            ids = rng_for(seed, 1_000_000 + i).integers(1, vocab, s)
            out.append(Request(index=i, due_s=float(due[j]),
                               prompt=tuple(int(t) for t in ids),
                               max_new=int(outputs[order_o[j]])))
    return out


def mean_sizes(traffic: dict) -> tuple[float, float]:
    """Mean prompt and output length of a block."""
    prompts, outputs, _ = block_sizes(traffic)
    return float(prompts.mean()), float(outputs.mean())

