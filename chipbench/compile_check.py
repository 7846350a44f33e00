"""Memory rehearsal without a chip: compiles each serving cell's decode step
(at its pool size and batch) and its prefill at the longest prompt length of
its traffic for a described TPU v5e, and prints ``memory_analysis()``.

  JAX_PLATFORMS=cpu python -m chipbench.compile_check [--workload NAME ...]

A compile that passes is not a chip run: it shows what one program asks of
the device, not what the process keeps there besides (the weights, the
pools, and the pools' second copy while a step runs).
"""
from __future__ import annotations

import argparse
import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import bench  # noqa: E402


def shapes_on(tree, sharding):
    import jax
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sharding), tree)


def check_cell(name: str, device) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models import transformer as T
    from repro.serving.paged_model import init_pools, make_paged_decode_step
    from chipbench.model import model_config
    from chipbench.traffic import generate

    cell = bench.load_cell(name)
    cfg = model_config(cell.config["model"])
    eng = cell.cell["engine"]
    one = SingleDeviceSharding(device)
    params = shapes_on(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                      jax.random.PRNGKey(0)), one)
    pages = eng["num_sets"] * eng["set_size"] + 1
    pools = shapes_on(jax.eval_shape(functools.partial(
        init_pools, cfg, num_pages=pages, page_size=eng["page_size"],
        max_batch=eng["max_batch"])), one)
    b, mp = eng["max_batch"], eng["max_pages"]
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one)
    step = make_paged_decode_step(cfg, page_size=eng["page_size"])
    compiled = step.lower(params, pools, i32((b, 1)), i32((b,)), i32((b, mp)),
                          jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one)).compile()
    gb = 1e9
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    pool_b = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))
    m = compiled.memory_analysis()
    print(f"{name}: weights {weights / gb:.3f} GB, pool {pool_b / gb:.3f} GB "
          f"({pages} pages)")
    print(f"  decode step: arguments {m.argument_size_in_bytes / gb:.3f} GB, "
          f"outputs {m.output_size_in_bytes / gb:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / gb:.3f} GB, aliased "
          f"{m.alias_size_in_bytes / gb:.3f} GB", flush=True)
    longest = max(generate.grid(cell.traffic["prompt"]))
    used = max(int(p) for p in generate.block_sizes(cell.traffic)[0])
    pad = (used // eng["page_size"] + 1) * eng["page_size"]
    prefill = jax.jit(functools.partial(T.prefill, cfg=cfg), static_argnames="max_seq")
    m = prefill.lower(params, i32((1, used)), max_seq=pad).compile().memory_analysis()
    print(f"  prefill of {used} tokens (longest the traffic sends; grid max "
          f"{longest}): arguments {m.argument_size_in_bytes / gb:.3f} GB, outputs "
          f"{m.output_size_in_bytes / gb:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / gb:.3f} GB", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="*")
    args = ap.parse_args(argv)
    bench.use_program()
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in bench.benchmark()["workloads"]
                              if w["chips"] == 1]
    for name in names:
        check_cell(name, topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
