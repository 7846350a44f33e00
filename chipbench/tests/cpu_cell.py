"""A cell of the benchmark cut to a size the CPU runs in seconds: the same
files, drivers and readers, a tiny model, a tiny pool and fast traffic."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

from chipbench import bench

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "head_dim": 16,
              "d_ff": 32, "vocab": 256, "moe_d_ff": 32, "max_seq": 512,
              "dtype": "float32"}
TINY_ENGINE = {"max_batch": 4, "page_size": 16, "num_sets": 6, "set_size": 4,
               "max_pages": 16}
TINY_PROMPT = {"median": 40, "sigma": 0.6, "min": 16, "max": 160, "grid": 4}
TINY_OUTPUT = {"median": 12, "sigma": 0.5, "min": 4, "max": 40}
# the cell's own limits at the tiny size, where the program runs in float32:
# sound runs read 0, the faults and the float8 control 0.03 and more
# (test_chipbench_faults.py)
TINY_LIMITS = {"widest_logit_gap": 1e-3, "mean_logit_gap": 1e-4}

# cells whose files are in chipbench/ but that are not in BENCHMARK.json yet
# (not proved on the chip); their drivers and readers are tested all the same
UNLISTED = {
    "granite-serve-pressure": {
        "workload": {"name": "granite-serve-pressure",
                     "config": "granite-moe-1b-a400m",
                     "traffic": "open-chat-4k", "chips": 1, "why": "-"},
        "config": {"name": "granite-moe-1b-a400m", "source": "-",
                   "file": "chipbench/configs/granite-moe-1b-a400m.json",
                   "reduced": [], "why": "-"}},
}


# the one-chip cells, listed or not, that the CPU tests run
CELLS = [w["name"] for w in bench.benchmark()["workloads"] if w["chips"] == 1]
CELLS += sorted(UNLISTED)


def load(name: str) -> bench.Cell:
    """A cell of BENCHMARK.json, or one of ``UNLISTED`` with every per-layer
    metric whose reader exists."""
    b = bench.benchmark()
    if name in UNLISTED:
        b["workloads"].append(UNLISTED[name]["workload"])
        b["configs"].append(UNLISTED[name]["config"])
        cell = bench.load_cell(name, bench=b)
        listed = {m["name"]: m for m in b["per_layer"]}
        cell.per_layer = [listed.get(p.stem, {"name": p.stem, "unit": "-",
                                              "source": "host_clock"})
                          for p in sorted((bench.PKG / "metrics").glob("*.py"))]
        return cell
    return bench.load_cell(name, bench=b)


def tiny_cell(name: str, *, rate: float = 8.0) -> bench.Cell:
    """``name`` from BENCHMARK.json, its widths, pool and traffic cut down."""
    cell = copy.deepcopy(load(name))
    model = cell.config["model"]
    experts = min(model["moe_experts"], 4)
    model.update(TINY_MODEL, n_kv_heads=min(model["n_kv_heads"], 2),
                 moe_experts=experts, moe_topk=2,
                 moe_capacity_factor=experts / 2)
    cell.cell["engine"] = dict(TINY_ENGINE)
    cell.cell["warmup"] = {"population_s": min(cell.cell["warmup"]["population_s"], 0.4),
                           "seconds": 1.0}
    cell.cell["check"]["sample"] = {"max_requests": 3, "min_tokens": 20,
                                    "preempted_max": 1}
    cell.cell["check"]["limits"] = {k: TINY_LIMITS[k]
                                    for k in cell.cell["check"]["limits"]}
    cell.traffic.update(prompt=dict(TINY_PROMPT), output=dict(TINY_OUTPUT))
    if cell.traffic["arrivals"] == "poisson":
        cell.traffic["rate_per_s"] = rate
    return cell
