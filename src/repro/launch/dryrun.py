"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, and extract the roofline terms from the compiled artifact.

No data is allocated: inputs are ShapeDtypeStructs, parameters are
eval_shape'd. Success proves the sharding config is coherent (no mismatched
specs, no unsupported collectives, per-device buffers fit); the printed
memory/cost analysis feeds EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all                 # 40 cells x 2 meshes
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single   # roofline table mesh
"""
import argparse
import json
import os
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, cells, get_config, ARCH_IDS
from repro.distributed.sharding import cache_specs, data_spec, param_specs
from repro.launch import specs as S
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.optim import AdamWState, adamw_init

# v5e-class hardware constants (per chip) — §Roofline.
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

# The CPU backend canonicalizes bf16 -> f32, so every byte count in the
# compiled HLO is 2x what the SAME program moves on TPU (which keeps bf16).
# Bulk traffic (weights, activations, grads, KV, MoE payloads) is bf16 by
# declaration; the f32 remainder (optimizer moments, softmax internals) is a
# small, fused fraction. §Roofline reports TPU-dtype bytes = raw * 0.5 and
# keeps the raw number alongside.
BF16_CANONICALIZATION_CORRECTION = 0.5

def _opt_specs_like(p_specs, opt_struct):
    master = p_specs if opt_struct.master is not None else None
    return AdamWState(step=P(), m=p_specs, v=p_specs, master=master)


def build_cell(arch: str, shape_name: str, mesh, *, remat: bool = True,
               cfg_overrides: dict | None = None, microbatches: int = 1):
    """Returns (jitted_fn, example_args tuple of ShapeDtypeStructs)."""
    import dataclasses
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    # serving layout for decode: stationary TP weights (no per-token FSDP AG)
    p_specs = param_specs(S.params_specs(cfg), mesh,
                          fsdp=(shape.mode != "decode"))
    dspec = data_spec(mesh, shape.global_batch)
    ins = S.input_specs(arch, shape)

    def batch_specs(batch, dp):
        out = {}
        for k, v in batch.items():
            if k == "positions" and v.ndim == 3:
                out[k] = P(dp[0] if len(dp) else None, None, None)
            elif v.ndim >= 2:
                out[k] = P(dp[0] if len(dp) else None,
                           *([None] * (v.ndim - 1)))
            else:
                out[k] = P()
        return out

    bspecs = batch_specs(ins["batch"], dspec)
    sh = lambda spec_tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))

    if shape.mode == "train":
        step = make_train_step(cfg, mesh, remat=remat,
                               microbatches=microbatches)
        opt_struct = jax.eval_shape(adamw_init, ins["params"])
        opt_specs = _opt_specs_like(p_specs, opt_struct)
        fn = jax.jit(step,
                     in_shardings=(sh(p_specs), sh(opt_specs), sh(bspecs)),
                     out_shardings=(sh(p_specs), sh(opt_specs), None),
                     donate_argnums=(0, 1))
        args = (ins["params"], opt_struct, ins["batch"])
    elif shape.mode == "prefill":
        step = make_prefill_step(cfg, shape.seq_len, mesh)
        cache_struct = S.cache_specs_struct(cfg, shape.global_batch,
                                            shape.seq_len)
        c_specs = cache_specs(cache_struct, mesh, shape.global_batch)
        fn = jax.jit(step,
                     in_shardings=(sh(p_specs), sh(bspecs)),
                     out_shardings=(None, sh(c_specs)))
        args = (ins["params"], ins["batch"])
    else:
        step = make_decode_step(cfg, mesh)
        c_specs = cache_specs(ins["cache"], mesh, shape.global_batch)
        fn = jax.jit(step,
                     in_shardings=(sh(p_specs), sh(c_specs), sh(bspecs)),
                     out_shardings=(None, sh(c_specs)),
                     donate_argnums=(1,))
        args = (ins["params"], ins["cache"], ins["batch"])
    return fn, args, cfg, shape


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             out_dir: Path | None = None, remat: bool = True,
             verbose: bool = True, cfg_overrides: dict | None = None,
             tag: str = "", microbatches: int = 1) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    with mesh:
        fn, args, cfg, shape = build_cell(arch, shape_name, mesh, remat=remat,
                                          cfg_overrides=cfg_overrides,
                                          microbatches=microbatches)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        cost = compiled.cost_analysis()
        try:
            mem = compiled.memory_analysis()
            mem_d = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            }
        except Exception as e:       # CPU backend may not implement it
            mem_d = {"error": str(e)}
        hlo = compiled.as_text()
        struct = hlo_analyze(hlo)    # loop-aware: flops/bytes x trip counts

    corr = BF16_CANONICALIZATION_CORRECTION
    flops = float(struct["flops"])              # per-device (partitioned HLO)
    bytes_raw = float(struct["memory_bytes"])
    bytes_acc = bytes_raw * corr                # TPU-dtype bytes
    coll = {"bytes": {k: int(v * corr)
                      for k, v in struct["collective_bytes"].items()},
            "counts": struct["collective_counts"],
            "total_bytes": int(struct["collective_total"] * corr),
            "raw_f32_total_bytes": struct["collective_total"]}
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    collective_s = coll["total_bytes"] / ICI_BW

    # useful-FLOPs yardstick
    n_active = cfg.active_param_count()
    if shape.mode == "train":
        tok = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tok
    elif shape.mode == "prefill":
        tok = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tok
    else:
        tok = shape.global_batch
        model_flops = 2 * n_active * tok
    model_flops_per_dev = model_flops / n_chips

    res = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": n_chips, "mode": shape.mode, "tag": tag,
        "overrides": cfg_overrides or {},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": flops, "bytes_per_device": bytes_acc,
        "bytes_per_device_raw_f32": bytes_raw,
        "collectives": coll,
        "memory": mem_d,
        "loops": struct["loops"],
        "xla_cost_flops_unscaled": float(cost.get("flops", 0.0)),
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s,
            "bottleneck": max(
                [("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)], key=lambda kv: kv[1])[0],
        },
        "model_flops_per_device": model_flops_per_dev,
        "useful_flop_ratio": (model_flops_per_dev / flops) if flops else None,
    }
    if verbose:
        r = res["roofline"]
        print(f"[{arch} x {shape_name} x {mesh_kind}] chips={n_chips} "
              f"compile={t_compile:.0f}s flops/dev={flops:.3e} "
              f"bytes/dev={bytes_acc:.3e} coll/dev={coll['total_bytes']:.3e}B "
              f"| compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms -> {r['bottleneck']} "
              f"| useful={res['useful_flop_ratio'] and round(res['useful_flop_ratio'], 3)}")
        if mem_d.get("peak_bytes"):
            print(f"    peak={mem_d['peak_bytes']/2**30:.2f} GiB/dev "
                  f"args={mem_d['argument_bytes']/2**30:.2f} GiB/dev")
    if out_dir:
        out_dir.mkdir(parents=True, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        (out_dir / f"{arch}_{shape_name}_{mesh_kind}{suffix}.json").write_text(
            json.dumps(res, indent=1, default=float))
    return res


def main():
    # before the first device query; importing this module changes nothing
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    todo = []
    if args.all:
        for arch in ARCH_IDS:
            for shp in cells(arch):
                todo.extend((arch, shp, m) for m in meshes)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape, m) for m in meshes]

    failures = []
    for arch, shp, m in todo:
        try:
            run_cell(arch, shp, m, out_dir=out_dir,
                     remat=not args.no_remat)
        except Exception as e:
            failures.append((arch, shp, m, repr(e)[:300]))
            print(f"FAIL [{arch} x {shp} x {m}]: {e!r}"[:500])
    print(f"\n{len(todo) - len(failures)}/{len(todo)} cells passed")
    if failures:
        for f in failures:
            print("  FAIL", *f)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
