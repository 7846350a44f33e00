"""The readings a cell's limit is set from, on the chip, in one process.

  python -m chipbench.control_check --workload olmoe-serve-offline \\
      --seconds 20 --seeds 11 12 13

For each seed the cell runs through the harness as in a benchmark run (a
shorter window), with the float8 control in the program's place when the
numbers are compared: the reference computed in float8, at each position of
the same prompts and served tokens, puts its first token, and the gap of
that token below the float32 reference's best is read. It prints the
result's ``correct`` and ``checks`` (the control's numbers, which a sound
limit fails) and the program's numbers over the same sample. The program's
largest reading over a dozen seeds is a limit's lower reading, the
control's smallest its upper one.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        line, rec = run.execute(one, control="fp8")
        out = json.loads(line)
        print(json.dumps({
            "seed": seed, "correct": out["correct"], "checks": out["checks"],
            "program": rec["readings"]["program"],
            "control_fp8": rec["readings"]["control"],
            "requests": len(rec["served"]),
            "preempted": sum(p for _, _, p in rec["served"]),
            "tokens": sum(len(o) for _, o, _ in rec["served"]),
            "device": out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
