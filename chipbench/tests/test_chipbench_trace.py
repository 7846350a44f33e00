"""Trace reduction: busy union, time per module and per span, idle gaps."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

DATA = Path(__file__).resolve().parents[1] / "testdata" / "tpu_v5e_small.xplane.pb"


def test_union_merges_overlaps_and_touching():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_reduce_synthetic():
    modules = {"/device:TPU:0": [("jit_step", 1.0, 2.0), ("jit_scatter", 2.5, 3.0),
                                 ("jit_scatter", 2.8, 3.2), ("jit_step", 9.5, 11.0)],
               "/device:TPU:1": [("jit_step", 1.0, 3.0)]}
    spans = [("bench.window", 0.0, 10.0), ("bench.step", 0.5, 3.5),
             ("bench.prefill", 2.4, 3.4), ("bench.step", 4.0, 6.0)]
    r = tr.reduce(modules, spans, slack=0.0)
    assert r["window_s"] == 10.0
    # device 0: [1, 2] + [2.5, 3.2] + [9.5, 10] = 2.2 s; device 1: 2 s
    assert r["busy_s"] == pytest.approx((2.2 + 2.0) / 2)
    assert r["module_s"]["jit_step"] == pytest.approx(1.0 + 0.5 + 2.0)
    assert r["module_s"]["jit_scatter"] == pytest.approx(0.5 + 0.4)
    assert r["module_s_in_span"]["bench.prefill"] == {"jit_scatter": pytest.approx(0.9)}
    assert r["module_s_in_span"]["bench.step"]["jit_step"] == pytest.approx(3.0)
    idle = r["idle_gap_s"]
    assert sum(idle.values()) == pytest.approx(10.0 - r["busy_s"])
    # device 0: [0, 1] (middle 0.5, the step's start) and [2, 2.5] in the
    # step, [3.2, 9.5] (middle 6.35) in none; device 1: [0, 1] in the step,
    # [3, 10] (middle 6.5) in none. Averaged over the two devices.
    assert idle["bench.step"] == pytest.approx((1.0 + 0.5 + 1.0) / 2)
    assert idle["none"] == pytest.approx((6.3 + 7.0) / 2)
    assert r["span_count"] == {"bench.step": 2, "bench.prefill": 1}


def test_innermost_picks_the_shortest_span():
    spans = {"bench.step": [(0.0, 10.0)], "bench.fetch": [(2.0, 3.0)]}
    assert tr.innermost(spans, 2.5) == "bench.fetch"
    assert tr.innermost(spans, 5.0) == "bench.step"
    assert tr.innermost(spans, 11.0) == "none"


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three jitted steps and three bursts of
    four eager page writes, each inside a harness span."""
    modules, spans = tr.read_events(DATA)
    assert list(modules) == ["/device:TPU:0"]
    r = tr.reduce(modules, spans)
    events = modules["/device:TPU:0"]
    lo = min(a for _, a, _ in events)
    hi = max(b for _, _, b in events)
    assert r["window_s"] == pytest.approx(hi - lo)
    # busy: the union, here no two modules overlap, so it is their sum
    assert r["busy_s"] == pytest.approx(sum(b - a for _, a, b in events))
    assert r["busy_s"] == pytest.approx(sum(r["module_s"].values()))
    assert sum(1 for n, _, _ in events if n == "jit_step") == 3
    assert sum(1 for n, _, _ in events if n == "jit_scatter") == 12
    assert r["span_count"] == {"bench.step": 3, "bench.admit": 3}
    # every page write ran inside an admission span
    assert r["module_s_in_span"]["bench.admit"]["jit_scatter"] == pytest.approx(
        r["module_s"]["jit_scatter"])
    assert sum(r["idle_gap_s"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    b = tr.breakdown(r)
    assert b["device_ops"][0][0] == "jit_scatter"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
