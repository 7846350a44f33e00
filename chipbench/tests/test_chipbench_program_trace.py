"""The program's spans reduced per host thread: self time per span path,
device idle put down to the main thread's open spans, the four readings,
the shared clock; and the existing trace reduction left as it was."""
import argparse
from pathlib import Path

import pytest

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.tests.cpu_cell import tiny_cell

DATA = Path(__file__).resolve().parents[1] / "testdata" / "tpu_v5e_small.xplane.pb"
CPU_PEAK = {"cpu": {"flops_per_s": 1e12, "bytes_per_s": 1e11}}

MODULES = {"/device:TPU:0": [("jit_step", 1.0, 2.0), ("jit__argmax", 2.1, 2.2),
                             ("jit_scatter", 3.0, 3.5), ("jit_step", 6.0, 7.0),
                             ("jit__argmax", 7.05, 7.1)]}
WINDOW = [("bench.window", 0.0, 10.0)]
MAIN = [("serve.step", 0.8, 2.7, {}), ("serve.admit", 0.8, 0.9, {}),
        ("serve.dispatch", 0.9, 1.0, {}), ("serve.sync", 1.0, 2.3, {}),
        ("serve.bookkeep", 2.3, 2.7, {}), ("serve.pump", 2.5, 2.7, {}),
        ("serve.step", 2.8, 7.5, {}), ("serve.admit", 2.8, 5.8, {}),
        ("serve.prefill", 2.85, 5.7, {"rid": 4}),
        ("serve.prefill.forward", 2.85, 2.95, {}),
        ("serve.prefill.page_write", 2.95, 5.5, {}),
        ("serve.dispatch", 5.8, 5.9, {}), ("serve.sync", 5.9, 7.2, {})]
# a worker's offload, open while the device idles under the main thread's
# bookkeeping: the idle still goes to the main thread's path
WORKER = [("serve.offload_io", 2.2, 2.6, {"tag": 1025}),
          ("serve.fetch_io", 8.0, 8.5, {"tag": 4})]


def test_tree_nests_spans_of_one_thread():
    roots = pt.tree([s[:3] for s in MAIN])
    assert [r[0] for r in roots] == ["serve.step", "serve.step"]
    assert [c[0] for c in roots[0][3]] == ["serve.admit", "serve.dispatch",
                                           "serve.sync", "serve.bookkeep"]
    assert roots[0][3][3][3][0][0] == "serve.pump"
    assert pt.path_at(roots, 4.0) == ("serve.step/serve.admit/serve.prefill/"
                                      "serve.prefill.page_write")
    assert pt.path_at(roots, 2.75) == "none"
    assert pt.path_at(roots, 0.2) == "none"


def test_self_time_per_path_and_thread():
    p = pt.reduce(MODULES, WINDOW, [WORKER, MAIN], main=1)
    main = p["main"]["self_s"]
    # the first step is all children; the second 4.7 s less 3.0 + 0.1 + 1.3
    assert main["serve.step"] == pytest.approx(0.3)
    assert main["serve.step/serve.admit"] == pytest.approx(0.1 + 0.15)
    assert main["serve.step/serve.admit/serve.prefill"] == pytest.approx(0.2)
    assert main["serve.step/serve.admit/serve.prefill/"
                "serve.prefill.page_write"] == pytest.approx(2.55)
    assert main["serve.step/serve.bookkeep"] == pytest.approx(0.2)
    assert main["serve.step/serve.bookkeep/serve.pump"] == pytest.approx(0.2)
    assert main["serve.step/serve.sync"] == pytest.approx(2.6)
    assert p["main"]["n"]["serve.step"] == 2
    assert p["main"]["n"]["serve.step/serve.sync"] == 2
    assert p["worker"]["self_s"] == {"serve.offload_io": pytest.approx(0.4),
                                     "serve.fetch_io": pytest.approx(0.5)}
    assert "serve.offload_io" not in main


def test_idle_goes_to_the_main_threads_innermost_path():
    p = pt.reduce(MODULES, WINDOW, [WORKER, MAIN], main=1)
    idle = p["idle_gap_s"]
    # the same stretches, and the same total, as the existing reduction
    spans = WINDOW + [(n, a, b) for n, a, b, _ in MAIN]
    assert sum(idle.values()) == pytest.approx(
        sum(tr.reduce(MODULES, spans)["idle_gap_s"].values()))
    assert sum(idle.values()) == pytest.approx(10.0 - 2.65)
    assert idle["none"] == pytest.approx(1.0 + 2.9)   # [0, 1] and [7.1, 10]
    assert idle["serve.step/serve.sync"] == pytest.approx(0.1 + 0.05)
    # [2.2, 3.0] lies under the first step's pump on the main thread, and
    # under an offload on a worker's: the idle goes to the main thread
    assert idle["serve.step/serve.bookkeep/serve.pump"] == pytest.approx(0.8)
    assert idle["serve.step/serve.admit/serve.prefill/"
                "serve.prefill.page_write"] == pytest.approx(2.5)
    assert not any("offload_io" in k or "fetch_io" in k for k in idle)


def test_readings():
    p = pt.reduce(MODULES, WINDOW, [WORKER, MAIN], main=1)
    r = pt.readings(p, {"decode": 2, "prefills": 1})
    assert r["sched_idle_ms_per_step"] == pytest.approx(1e3 * (0.15 + 0.8) / 2)
    assert r["admit_idle_ms_per_req"] == pytest.approx(1e3 * 2.5)
    # step 0.3, admit 0.25, dispatch 0.2, bookkeep 0.2, pump 0.2; neither
    # the sync nor the prefill
    assert r["sched_host_ms_per_step"] == pytest.approx(1e3 * 1.15 / 2)
    assert r["offload_io_ms_per_step"] == pytest.approx(1e3 * 0.9 / 2)
    # no device plane: the idle readings find nothing, the host ones read
    host_only = pt.reduce({}, WINDOW, [WORKER, MAIN], main=1)
    r = pt.readings(host_only, {"decode": 2, "prefills": 1})
    assert r["sched_idle_ms_per_step"] is None and r["admit_idle_ms_per_req"] is None
    assert r["sched_host_ms_per_step"] == pytest.approx(1e3 * 1.15 / 2)
    assert pt.readings(pt.reduce(MODULES, WINDOW, [], None),
                       {"decode": 2, "prefills": 1}) == dict.fromkeys(r)


def test_counter_readings():
    p = pt.reduce(MODULES, WINDOW, [WORKER, MAIN], main=1)
    c = {"flush_requests": 8, "stale_discards": 2, "offloads": 7,
         "blocking_offloads": 2, "allocs": 20, "alloc_failures": 5,
         "unflushed_at_preempt": 1}
    assert pt.counter_readings(p, c) == {
        "stale_share": 25.0,
        "flushed_share": 62.5,                  # 7 - 2 background offloads
        "offload_spans_per_offload": 0.2,       # one span in the trace
        "alloc_failure_share": 25.0,
        "unflushed_share_at_preempt": 50.0}
    # an engine without the new counters, and nothing blocking
    old = {"offloads": 3, "blocking_offloads": 0, "stale_discards": 0}
    assert pt.counter_readings(p, old) == {
        "stale_share": None, "flushed_share": None,
        "offload_spans_per_offload": pytest.approx(1 / 3),
        "alloc_failure_share": None, "unflushed_share_at_preempt": None}


def test_main_line_is_the_window_holder_else_the_stepper():
    assert pt.main_line([WORKER, MAIN], None) == 1
    assert pt.main_line([MAIN, WORKER], 1) == 1
    assert pt.main_line([WORKER], None) is None


def test_sync_lag():
    lag = pt.sync_lag(MODULES, MAIN)
    # each sync ends 0.1 s after the argmax it waits on
    assert lag["n"] == 2 and lag["share_within_slack"] == 1.0
    assert lag["lag_ms"]["p50"] == pytest.approx(100.0)
    # a host clock 0.5 s behind the device's: each sync seems to end early
    early = [(n, a - 0.5, b - 0.5, x) for n, a, b, x in MAIN]
    assert pt.sync_lag(MODULES, early)["share_within_slack"] == 0.0


def test_existing_reduction_of_the_recorded_trace_is_unchanged():
    """Every key of ``trace_reduce.reduce`` on the recorded v5e trace, as the
    accepted benchmark computes it: the readers of the accepted metrics
    read these."""
    r = tr.reduce_file(DATA)
    assert r == {
        "window_s": 0.275327499, "busy_s": 0.005454607000000174, "devices": 1,
        "module_s": {
            "jit_step": 0.0005798770000000134,
            "jit_dynamic_slice": 0.0003039319999999235,
            "jit_squeeze": 3.70599999996446e-06,
            "jit_convert_element_type": 2.909700000019999e-05,
            "jit_broadcast_in_dim": 1.2398999999996274e-05,
            "jit__squeeze": 1.7058000000041984e-05,
            "jit_scatter": 0.004508538000000034},
        "module_s_in_span": {
            "bench.step": {
                "jit_step": 0.0005798770000000134,
                "jit_dynamic_slice": 3.5516999999957166e-05,
                "jit_squeeze": 1.8629999999630886e-06,
                "jit_convert_element_type": 7.453000000046117e-06,
                "jit_broadcast_in_dim": 3.0709999999933846e-06,
                "jit__squeeze": 4.586000000000867e-06,
                "jit_scatter": 0.00030097600000000835},
            "bench.admit": {
                "jit_convert_element_type": 2.909700000019999e-05,
                "jit_broadcast_in_dim": 1.2398999999996274e-05,
                "jit__squeeze": 1.7058000000041984e-05,
                "jit_scatter": 0.004508538000000034,
                "jit_dynamic_slice": 0.0002684149999999663,
                "jit_squeeze": 1.8430000000013713e-06}},
        "idle_gap_s": {"bench.step": 0.1113037730000001,
                       "bench.admit": 0.13398780499999974,
                       "none": 0.024581313999999965},
        "span_count": {"bench.step": 3, "bench.admit": 3}}
    # a trace without program spans: all its idle is the program's "none"
    p = pt.reduce_file(DATA)
    assert p["main"]["n"] == {} and set(p["idle_gap_s"]) == {"none"}
    assert p["idle_gap_s"]["none"] == pytest.approx(sum(r["idle_gap_s"].values()))


def test_traced_cell_on_cpu_reads_the_host_readings():
    """The OLMoE cell at a tiny size, traced on the CPU: the program's spans
    are on the window's thread and on the offload workers' threads, and the
    two host readings read; the trace has no device, so the idle ones do
    not."""
    args = argparse.Namespace(workload="olmoe-serve-offline", seed=2 ** 40 + 7,
                              seconds=1.5, trace=1)
    _, record, p = pt.traced(args, cell=tiny_cell("olmoe-serve-offline"),
                             require_tpu=False, peak_table=CPU_PEAK)
    steps = record["steps"]
    assert p["main"]["n"]["serve.step/serve.sync"] == steps["decode"]
    assert p["main"]["n"]["serve.step/serve.admit/serve.prefill"] == steps["prefills"]
    assert record["counters"]["offloads"] > 0
    assert p["worker"]["n"]["serve.offload_io"] > 0
    r = p["readings"]
    assert r["sched_host_ms_per_step"] > 0 and r["offload_io_ms_per_step"] > 0
    assert r["sched_idle_ms_per_step"] is None and r["admit_idle_ms_per_req"] is None
    c = p["counters"]
    assert c["flushed_share"] > 0 and 0 <= c["stale_share"] <= 100
    assert c["offload_spans_per_offload"] > 0
