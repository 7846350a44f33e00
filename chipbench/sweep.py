"""Finds the knee of an open-loop serving cell once, on the chip: the
highest Poisson rate the system sustains with no growing backlog.

  python -m chipbench.sweep --workload granite-serve-pressure --seed 1 \\
      --seconds 40 --rates 0.4 0.6 0.8 1.0

Each rate runs the cell's driver in this one process (set-up and warm-up as
in a benchmark run, no reference check) and prints one line: time to first
token (median and 90th percentile), the 95th percentile inter-token gap,
tokens/s, and the waiting queue and active rows at the window's open and
close. A rate sustains when the queue does not grow over the window and the
requests due in it all got their first token within the drain.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

from chipbench import bench, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    base = bench.load_cell(args.workload)
    bench.use_program()
    devices = run.check_devices(base.entry["chips"])
    bench.enable_compile_cache()
    counter = bench.CompileCounter()
    rows = []
    for rate in args.rates:
        cell = copy.deepcopy(base)
        cell.traffic["rate_per_s"] = rate
        ctx = bench.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                            trace=False, t_start=bench.now(), compiles=counter,
                            check=False)
        rec = bench.driver(cell.cell["driver"]).run(ctx)
        q = rec["queue"]
        row = {"rate_per_s": rate, **rec["e2e"], "due": rec["requests"]["due"],
               "failed": rec["failed"], "slo_met": rec["requests"]["slo_met"],
               "queue_open": q["open"], "queue_close": q["close"],
               "rows_open": q["rows_open"], "rows_close": q["rows_close"],
               "preemptions": rec["counters"]["preemptions"],
               "compiles_in_window": rec["compiles_in_window"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"device": devices[0].device_kind, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
