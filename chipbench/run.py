"""Runs one cell of the chip benchmark and prints its result line.

  python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Without a TPU, or with fewer chips than the cell asks for, it exits nonzero
and prints no result. With ``--trace 0`` the result's metrics are the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the metrics are the cell's per-layer metrics, each computed by
``chipbench/metrics/<name>.py``. The last line of standard output is the
result; the numbers compared with the reference, each beside its limit, are
the last lines of standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

if __package__ in (None, ""):            # run as a script: python chipbench/run.py
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import bench  # noqa: E402

TRACE_ROOT = bench.ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(chips: int, require_tpu: bool = True):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise bench.BenchError(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise bench.BenchError(f"the cell needs {chips} chips, JAX found "
                               f"{len(devices)}")
    return devices[:chips]


def metrics_of(cell: bench.Cell, record: dict, trace: bool) -> dict:
    out = {}
    if not trace:
        for m in cell.end_to_end:
            name = m["name"]
            value = record["setup_s"] if name == "setup_s" else record["e2e"].get(name)
            if value is None:
                raise bench.BenchError(f"the driver did not measure {name}")
            out[name] = {"value": value, "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        value = bench.metric_reader(m["name"])(record)
        if value is None:
            # only a trace with no device plane (a run on the CPU) may leave
            # a device reading out; any other gap is a reader that no longer
            # finds what the program used to give it
            if m["source"] == "device_trace" and not record["trace"]["devices"]:
                continue
            raise bench.BenchError(f"metric {m['name']} found nothing to read")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(args, *, cell: bench.Cell | None = None, require_tpu: bool = True,
            peak_table: dict | None = None,
            control: str | None = None) -> tuple[str, dict]:
    """Runs the cell; returns (result line, record). Tests pass their own
    cell, ``require_tpu=False`` and a peak table. With ``control`` (a lower
    precision, such as "fp8") the numbers compared are the control's: the
    reference in that precision put in the program's place."""
    cell = bench.load_cell(args.workload) if cell is None else cell
    bench.use_program()
    devices = check_devices(cell.entry["chips"], require_tpu)
    if require_tpu:
        bench.enable_compile_cache()
    ctx = bench.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START,
                        compiles=bench.CompileCounter(), control=control)
    ctx.say(f"device: {devices[0].device_kind} x {len(devices)} "
            f"({devices[0].platform})")
    if ctx.trace:
        ctx.out_dir = TRACE_ROOT / f"{cell.name}-{args.seed}"
        shutil.rmtree(ctx.out_dir, ignore_errors=True)
        ctx.out_dir.mkdir(parents=True)
    record = bench.driver(cell.cell["driver"]).run(ctx)
    from chipbench import peaks
    kind = devices[0].device_kind
    record["peak"] = peak_table[kind] if peak_table else peaks.peak(kind)
    breakdown = None
    device = record["device"]
    if ctx.trace:
        from chipbench import trace_reduce, work
        record["trace"] = trace_reduce.reduce_file(
            trace_reduce.find_xplane(record["trace_dir"]))
        shutil.rmtree(ctx.out_dir, ignore_errors=True)
        device = dict(device, busy_s=record["trace"]["busy_s"],
                      window_s=record["trace"]["window_s"])
        breakdown = trace_reduce.breakdown(record["trace"])
        bounds = [work.roofline_seconds(work.Work(f, b), record["peak"])[1]
                  for f, b in record["steps"]["per_step"]]
        ctx.say(f"decode step roofline: {bounds.count('memory')} of "
                f"{len(bounds)} steps bound by memory bandwidth, the rest by "
                f"FLOP/s")
    metrics = metrics_of(cell, record, ctx.trace)
    checks = record["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks)
    print(f"compilations in the window: {record['compiles_in_window']}",
          flush=True)
    for c in checks:
        ctx.say(f"{c['name']}: {c['value']!r} (limit {c['limit']!r})")
    line = bench.result_line(correct=correct, attempted=record["attempted"],
                             failed=record["failed"], metrics=metrics,
                             device=device, checks=checks, breakdown=breakdown)
    return line, record


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line, _ = execute(args)
    except bench.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
