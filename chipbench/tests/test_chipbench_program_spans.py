"""The serving program's spans as the profiler records them on the CPU: a
tiny engine under pool pressure (it preempts, offloads and fetches back),
once under the profiler and once without. The trace is read back as
``chipbench/program_trace.py`` reads it on the chip."""
import numpy as np
import pytest

import jax

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.tests import cpu_cell  # noqa: F401  (puts src/ on the path)

from repro.configs import get_config, reduced
from repro.models import transformer as T
from repro.serving import ServeEngine

# the children each span may have, in the order they may come
CHILDREN = {
    "serve.step": ["serve.admit", "serve.grow", "serve.dispatch", "serve.sync",
                   "serve.bookkeep", "serve.requeue"],
    "serve.admit": ["serve.prefill", "serve.resume_fetch"],
    "serve.prefill": ["serve.prefill.forward", "serve.prefill.page_write",
                      "serve.prefill.first_token", "serve.prefill.mark_full"],
    "serve.prefill.mark_full": ["serve.pump"],
    "serve.grow": ["serve.preempt"],
    "serve.bookkeep": ["serve.pump"],
}
PER_REQUEST = ("serve.prefill", "serve.preempt", "serve.resume_fetch")


def serve(cfg, params, trace_dir=None):
    eng = ServeEngine(cfg, params, max_batch=4, page_size=8, num_sets=4,
                      set_size=3)
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(1, 250, int(rng.integers(3, 20)))]
               for _ in range(6)]
    rids = [eng.submit(p, max_new=16) for p in prompts]
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        eng.run(800)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
        eng.close()
    return [eng.result(r).out for r in rids], eng.stats(), eng._steps


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    cfg = reduced(get_config("tinyllama-1.1b"))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    plain = serve(cfg, params)
    trace_dir = tmp_path_factory.mktemp("trace")
    traced = serve(cfg, params, trace_dir)
    lines, main = pt.read_lines(tr.find_xplane(trace_dir))
    return plain, traced, lines, main


def test_same_tokens_with_the_profiler_on_and_off(runs):
    plain, traced, _, _ = runs
    assert traced[1]["preemptions"] > 0 and traced[1]["fetches"] > 0
    assert traced[0] == plain[0]
    assert traced[1] == plain[1]


def test_span_names_and_nesting_on_the_main_thread(runs):
    _, (_, stats, steps), lines, main = runs
    roots = pt.tree(lines[main])
    assert [r[0] for r in roots] == ["serve.step"] * steps
    seen = set()

    def check(node):
        name, a, b, children = node
        seen.add(name)
        order = CHILDREN.get(name, [])
        kinds = [c[0] for c in children]
        assert set(kinds) <= set(order), (name, kinds)
        assert kinds == sorted(kinds, key=order.index), (name, kinds)
        for c in children:
            assert a <= c[1] <= c[2] <= b
            check(c)

    for r in roots:
        check(r)
    assert seen == set(CHILDREN) | {"serve.dispatch", "serve.sync",
                                    "serve.requeue", "serve.pump",
                                    "serve.preempt", "serve.resume_fetch",
                                    "serve.prefill.forward",
                                    "serve.prefill.page_write",
                                    "serve.prefill.first_token"}
    n = {k: sum(1 for s in lines[main] if s[0] == k) for k in PER_REQUEST}
    assert n["serve.prefill"] == 6
    assert n["serve.preempt"] == stats["preemptions"]
    assert n["serve.resume_fetch"] == n["serve.preempt"]


def test_per_request_spans_carry_the_request(runs):
    _, _, lines, main = runs
    rids = {name: [args["rid"] for nm, _, _, args in lines[main] if nm == name]
            for name in PER_REQUEST}
    assert sorted(rids["serve.prefill"]) == list(range(6))
    assert set(rids["serve.preempt"]) == set(rids["serve.resume_fetch"])
    for nm, _, _, args in (s for line in lines for s in line):
        if nm in PER_REQUEST:
            assert set(args) == {"rid"}


def test_offload_and_fetch_spans_are_on_the_worker_threads(runs):
    _, (_, stats, _), lines, main = runs
    assert not any(s[0] in pt.IO_SPANS for s in lines[main])
    io = [s for i, line in enumerate(lines) if i != main for s in line]
    assert {s[0] for s in io} == set(pt.IO_SPANS)
    fetches = [s for s in io if s[0] == "serve.fetch_io"]
    assert len(fetches) == stats["fetches"]
    for _, _, _, args in io:
        assert set(args) == {"tag"}
