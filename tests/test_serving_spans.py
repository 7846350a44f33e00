"""The serving engine's counters and spans: what the pool counts under
pressure, the name of the jitted prefill, and what the spans cost when no
profiler runs. The spans as the profiler records them are tested with the
benchmark's reader (chipbench/tests/test_chipbench_program_spans.py)."""
import contextlib
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs import get_config, reduced
from repro.models import transformer as T
from repro.serving import Request, ServeEngine


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced(get_config("tinyllama-1.1b"))
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def test_counters_under_pool_pressure(tiny_model):
    cfg, params = tiny_model
    eng = ServeEngine(cfg, params, max_batch=4, page_size=8, num_sets=4,
                      set_size=3)
    rng = np.random.default_rng(5)
    for _ in range(8):
        eng.submit([int(x) for x in rng.integers(1, 250, 16)], max_new=24)
    eng.run(1200)
    eng.close()
    st = eng.stats()
    assert st["preemptions"] > 0 and st["unflushed_at_preempt"] > 0
    # every discard was a queued flush; a page the flusher had not cleaned
    # when its sequence was preempted is offloaded on the spot
    assert st["flush_requests"] >= st["stale_discards"]
    assert st["flush_requests"] > 0
    assert st["unflushed_at_preempt"] <= st["blocking_offloads"]
    assert st["allocs"] >= st["alloc_failures"] > 0
    assert "stall_steps" not in Request.__dataclass_fields__


def test_jitted_prefill_is_named(tiny_model):
    cfg, params = tiny_model
    eng = ServeEngine(cfg, params, max_batch=1, page_size=8, num_sets=4,
                      set_size=2)
    toks = jnp.ones((1, 5), jnp.int32)
    text = eng._prefill.lower(params, toks, max_seq=8).as_text()
    eng.close()
    assert "@jit_prefill" in text


def test_spans_of_a_decode_step_cost_under_20us():
    """With no profiler active: the spans one decode step of the OLMoE cell
    opens (the step, its six phases and about three flusher pumps), plus one
    per-request span with its ``rid`` and one offload span with its ``tag``,
    opened as the engine and the pool open them. Best of seven rounds in the
    thread's CPU time, so that other processes' load does not count; the
    same step with no-op context managers, timed in the same rounds, is the
    yardstick of the bare ``with`` blocks."""
    def noop(name, **kwargs):
        return contextlib.nullcontext()

    def one_step(k, step_span, span):
        with step_span("serve.step", step_num=k):
            for name in ("serve.admit", "serve.grow", "serve.dispatch",
                         "serve.sync"):
                with span(name):
                    pass
            with span("serve.prefill", rid=k):
                pass
            with span("serve.bookkeep"):
                for _ in range(3):
                    with span("serve.pump"):
                        pass
            with span("serve.requeue"):
                pass
            with span("serve.offload_io", tag=k):
                pass

    spans, bare = [], []
    for _ in range(7):
        for times, step_span, span in ((spans, StepTraceAnnotation, TraceAnnotation),
                                       (bare, noop, noop)):
            t0 = time.thread_time()
            for k in range(2000):
                one_step(k, step_span, span)
            times.append((time.thread_time() - t0) / 2000)
    assert min(spans) < 20e-6, spans
    # a span costs about what a no-op context manager does
    assert min(spans) < 3 * min(bare), (spans, bare)
