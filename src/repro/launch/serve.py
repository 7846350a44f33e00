"""Serving driver: continuous batching over the paged KV pool.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --preset smoke \
      --requests 12 --max-new 24

``--preset full`` serves the configuration at its published widths and
depth (one v5e chip holds granite-moe-1b-a400m, not qwen3-8b).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from repro.configs import get_config, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import init_params
from repro.serving import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--num-sets", type=int, default=16)
    ap.add_argument("--set-size", type=int, default=4)
    ap.add_argument("--no-flusher", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = reduced(cfg)
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    eng = ServeEngine(cfg, params, max_batch=args.max_batch,
                      page_size=args.page, num_sets=args.num_sets,
                      set_size=args.set_size,
                      use_flusher=not args.no_flusher)
    rng = np.random.default_rng(args.seed)
    rids = []
    for _ in range(args.requests):
        n = int(rng.integers(4, 48))
        rids.append(eng.submit([int(x) for x in rng.integers(1, cfg.vocab, n)],
                               max_new=args.max_new))
    t0 = time.time()
    eng.run(max_steps=5000)
    dt = time.time() - t0
    done = sum(eng.result(r).state == "done" for r in rids)
    toks = sum(len(eng.result(r).out) for r in rids)
    print(f"{done}/{len(rids)} done, {toks} tokens in {dt:.1f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s)")
    print("pool stats:", eng.stats())
    eng.close()
    return eng


if __name__ == "__main__":
    use_compile_cache()
    main()
