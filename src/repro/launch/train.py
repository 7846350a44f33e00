"""End-to-end training driver.

Runs on whatever devices exist, on a (devices, 1) ``("data", "model")``
mesh: ``--preset smoke`` trains a reduced config (CPU: 1 device);
``--preset full`` trains at published widths (granite-moe-1b-a400m needs the
four chips of a v5e host for its optimizer state). Wires together every
substrate: data pipeline (prefetched), pjit'd train step with the sharding
rules, AdamW,
async checkpointing (the paper's flusher/queues), gradient compression on
multi-pod meshes, and restart/resume.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --preset smoke --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import functools
import time

import numpy as np

import jax
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced
from repro.data import Prefetcher, SyntheticLM, make_global_batch
from repro.distributed.sharding import data_spec, param_specs, shape_shardings
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models.transformer import init_params
from repro.optim import AdamWState, adamw_init


def init_state(cfg, mesh, seed: int):
    """Parameters and AdamW state, each created on the devices its sharding
    rule names: at published widths the optimizer state fits no one chip.

    Returns (params, opt, (param_shardings, opt_shardings))."""
    key = jax.random.PRNGKey(seed)
    init = functools.partial(init_params, cfg=cfg)
    shapes = jax.eval_shape(init, key)
    p_specs = param_specs(shapes, mesh)
    opt_shapes = jax.eval_shape(adamw_init, shapes)
    opt_specs = AdamWState(step=P(), m=p_specs, v=p_specs,
                           master=p_specs if opt_shapes.master is not None
                           else None)
    p_sh, opt_sh = shape_shardings(p_specs, mesh), shape_shardings(opt_specs, mesh)
    params = jax.jit(init, out_shardings=p_sh)(key)
    opt = jax.jit(adamw_init, out_shardings=opt_sh)(params)
    return params, opt, (p_sh, opt_sh)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation slices (HBM stash / N)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = reduced(cfg, max_seq=max(args.seq, 128))
    mesh = make_host_mesh()

    params, opt, shardings = init_state(cfg, mesh, args.seed)

    step_fn = jax.jit(
        make_train_step(cfg, mesh, peak_lr=args.lr, total_steps=args.steps,
                        microbatches=args.microbatches),
        donate_argnums=(0, 1))

    ckpt = None
    start_step = 0
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir)
        if args.resume and ckpt.latest_step() is not None:
            start_step, (params, opt) = ckpt.restore(
                (params, opt), shardings=shardings)
            start_step += 1
            print(f"resumed from step {start_step - 1}")

    data = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=args.seed)
    dspec = data_spec(mesh, args.batch)
    it = Prefetcher(
        ({"step": s, **data.batch(s)} for s in range(start_step, args.steps)),
        depth=4)

    def add_modality_stubs(raw, s):
        """Precomputed frontend stand-ins (assignment: frontends are stubs)."""
        rng_np = np.random.default_rng((args.seed << 16) ^ s)
        if cfg.encoder_layers:
            raw["enc_frames"] = rng_np.normal(
                size=(args.batch, cfg.encoder_seq, cfg.d_model)
            ).astype(np.float32)
        if cfg.vis_tokens:
            raw["vis_embeds"] = rng_np.normal(
                size=(args.batch, cfg.vis_tokens, cfg.d_model)
            ).astype(np.float32)
            raw["positions"] = np.broadcast_to(
                np.arange(args.seq, dtype=np.int32)[None, None, :],
                (args.batch, 3, args.seq)).copy()
        return raw

    t0 = time.time()
    losses = []
    for raw in it:
        s = raw.pop("step")
        raw = add_modality_stubs(raw, s)
        batch = make_global_batch(raw, mesh, P(dspec[0] if len(dspec) else None,
                                               None))
        params, opt, metrics = step_fn(params, opt, batch)
        losses.append(float(metrics["ce"]))
        if ckpt and (s + 1) % args.ckpt_every == 0:
            ckpt.save_async(s, (params, opt))
        if (s + 1) % args.log_every == 0 or s == args.steps - 1:
            dt = time.time() - t0
            tok_s = (s - start_step + 1) * args.batch * args.seq / max(dt, 1e-9)
            print(f"step {s + 1:5d}  ce={losses[-1]:.4f}  "
                  f"gnorm={float(metrics['grad_norm']):.3f}  "
                  f"lr={float(metrics['lr']):.2e}  tok/s={tok_s:,.0f}")
    if ckpt:
        ckpt.save_async(args.steps - 1, (params, opt))
        ckpt.drain()
        print("ckpt stats:", ckpt.stats)
        ckpt.close()
    it.close()
    if len(losses) > 10:
        a, b = float(np.mean(losses[:5])), float(np.mean(losses[-5:]))
        print(f"loss first5={a:.4f} last5={b:.4f} ({'DOWN' if b < a else 'UP'})")
    return losses


if __name__ == "__main__":
    use_compile_cache()
    main()
