"""BENCHMARK.json against the benchmark's contract, every metric by name,
and a configuration, cell, traffic mix or metric that is added as a new file
is found without editing the harness."""
import json
import re
import shutil

import pytest

from chipbench import bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = bench.benchmark()


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (bench.ROOT / p).is_dir()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (bench.ROOT / c["file"]).exists()
        assert set(c["reduced"]) == set(bench.load_json(bench.ROOT / c["file"])["reduced"])
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert set(m["workloads"]) <= cells
    for w in cells:
        cell = bench.load_cell(w)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        reported = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in reported for m in cell.per_layer)


RECORD = {
    "e2e": {"tokens_per_s": 100.0, "ttft_p90_s": 2.5, "itl_p95_ms": 300.0},
    "requests": {"due": 40, "slo_met": 30, "with_first_token": 40},
    "steps": {"decode": 100, "per_step": [(1e12, 5e9)] * 100,
              "prefill_flops": 4e13},
    "spans_s": {"bench.offload_now": 0.5, "bench.fetch": 0.25},
    "spans_n": {"bench.prefill": 20},
    "counters": {"offloads": 200, "blocking_offloads": 50},
    "window_s": 10.0,
    "peak": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
    "trace": {"window_s": 10.0, "busy_s": 6.0, "devices": 1,
              "module_s": {"jit_step": 4.0, "jit_prefill": 1.0},
              "module_s_in_span": {"bench.prefill": {"jit_prefill": 1.0,
                                                     "jit_scatter": 0.6}}},
}


READERS = sorted(p.stem for p in (bench.PKG / "metrics").glob("*.py"))


def test_every_listed_metric_has_a_reader():
    assert {m["name"] for m in BENCH["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("metric", READERS)
def test_every_metric_has_a_reader(metric):
    value = bench.metric_reader(metric)(RECORD)
    assert isinstance(value, float) and value >= 0
    if "roofline" in metric or "mfu" in metric:
        assert value <= 100


def test_reader_values():
    r = lambda name: bench.metric_reader(name)(RECORD)  # noqa: E731
    assert r("slo_met_share") == pytest.approx(75.0)
    assert r("offload_stall_ms_per_step") == pytest.approx(7.5)
    assert r("blocking_offload_share") == pytest.approx(25.0)
    assert r("prefill_ms_per_req") == pytest.approx(80.0)
    assert r("decode_step_ms") == pytest.approx(40.0)
    assert r("device_idle_share.serve") == pytest.approx(40.0)
    least = 100 * max(1e12 / 197e12, 5e9 / 819e9)
    assert r("decode_step_roofline") == pytest.approx(100 * least / 4.0)
    assert r("serve_mfu") == pytest.approx(100 * (1e14 + 4e13) / (10 * 197e12))


def test_readers_return_nothing_without_a_trace():
    untraced = {k: v for k, v in RECORD.items() if k != "trace"}
    for m in BENCH["per_layer"]:
        if m["source"] == "device_trace":
            assert bench.metric_reader(m["name"])(untraced) is None


def test_new_files_are_found_without_editing_the_harness(tmp_path):
    """A later change adds a configuration, a traffic mix, a cell and a
    per-layer metric as files plus entries: the harness finds them."""
    for sub in ("configs", "workloads", "traffic", "metrics"):
        shutil.copytree(bench.PKG / sub, tmp_path / "chipbench" / sub)
    b = json.loads(json.dumps(BENCH))
    cfg = bench.load_json(bench.PKG / "configs" / "granite-moe-1b-a400m.json")
    cfg["name"] = "granite-shallow"
    (tmp_path / "chipbench" / "configs" / "granite-shallow.json").write_text(json.dumps(cfg))
    (tmp_path / "chipbench" / "traffic" / "bursty.json").write_text(json.dumps(
        dict(bench.load_json(bench.PKG / "traffic" / "open-chat-4k.json"), rate_per_s=3.0)))
    (tmp_path / "chipbench" / "workloads" / "granite-bursty.json").write_text(
        (bench.PKG / "workloads" / "granite-serve-pressure.json").read_text())
    (tmp_path / "chipbench" / "metrics" / "steps_seen.py").write_text(
        "def read(record):\n    return float(record['steps']['decode'])\n")
    b["configs"].append({"name": "granite-shallow", "source": "x",
                         "file": "chipbench/configs/granite-shallow.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "granite-bursty", "config": "granite-shallow",
                           "traffic": "bursty", "chips": 1, "why": "test"})
    # tokens_per_s and setup_s are reported by every cell
    b["per_layer"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                           "source": "program_counter", "layer": "decode step",
                           "moves": "tokens_per_s", "workloads": ["granite-bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = bench.load_cell("granite-bursty", root=tmp_path)
    assert cell.config["name"] == "granite-shallow"
    assert cell.traffic["rate_per_s"] == 3.0
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    assert bench.metric_reader("steps_seen", root=tmp_path)(RECORD) == 100.0
    # and the existing cells are as they were
    assert [m["name"] for m in bench.load_cell("olmoe-serve-offline", root=tmp_path).per_layer] \
        == [m["name"] for m in bench.load_cell("olmoe-serve-offline").per_layer]


def test_a_listed_metric_that_reads_nothing_is_an_error():
    """On a trace with a device plane, a per-layer metric that the cell
    lists and whose reader finds nothing stops the run; it is not left out
    of the line unseen."""
    from chipbench import run
    cell = bench.load_cell("olmoe-serve-offline")
    record = json.loads(json.dumps(RECORD))
    record["trace"]["module_s"] = {"jit_renamed_step": 4.0}
    with pytest.raises(bench.BenchError, match="decode_step_ms"):
        run.metrics_of(cell, record, trace=True)
    # a trace with no device plane (a run on the CPU) leaves the device
    # readings out and keeps the others
    record["trace"]["devices"] = 0
    out = run.metrics_of(cell, record, trace=True)
    assert "serve_mfu" in out and "decode_step_ms" not in out
