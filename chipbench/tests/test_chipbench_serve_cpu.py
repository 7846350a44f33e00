"""The serving driver end to end on the CPU at a tiny size: the same code
path as on the chip but for the look for a TPU. Every metric the cell names
in BENCHMARK.json is computed by name; without a TPU, or without the program,
a run exits nonzero and prints no result."""
import argparse
import json
import shutil
import subprocess
import sys

import pytest

from chipbench import bench, run
from chipbench.tests.cpu_cell import CELLS, UNLISTED, tiny_cell

CPU_PEAK = {"cpu": {"flops_per_s": 1e12, "bytes_per_s": 1e11}}


def execute(name, trace, seed=2 ** 40 + 99, cell=None):
    cell = cell or tiny_cell(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=1.5, trace=trace)
    line, record = run.execute(args, cell=cell, require_tpu=False,
                               peak_table=CPU_PEAK)
    return json.loads(line), record, cell


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end_on_cpu(name, trace):
    out, record, cell = execute(name, trace)
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["platform"] == "cpu"
    assert record["compiles_in_window"] == 0
    if trace:
        assert set(out["device"]) >= {"busy_s", "window_s"}
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        # on the CPU the trace has no device plane: the device readers find
        # nothing and say so; every other per-layer metric is there
        host = {m["name"] for m in cell.per_layer if m["source"] != "device_trace"}
        if name in UNLISTED:       # an open loop: the service and pool readers
            host = {"ttft_p90_s", "itl_p95_ms", "slo_met_share",
                    "offload_stall_ms_per_step", "blocking_offload_share"}
        assert host <= set(out["metrics"])
    else:
        assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(v["value"] > 0 for v in out["metrics"].values())
    assert record["served"], "nothing was checked against the reference"


def test_exits_nonzero_without_a_tpu(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0 and captured.out == ""
    assert "no TPU" in captured.err


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(bench.BENCHMARK, tmp_path / "BENCHMARK.json")
    shutil.copytree(bench.PKG, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "program is not in this checkout" in p.stderr
