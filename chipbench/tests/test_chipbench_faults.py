"""``correct`` comes out false when the timed path is broken underneath, and
when the control (the reference in float8, the precision below the
configured one) takes the program's place: through the harness itself, on
every serving cell and the number each compares, at a tiny size on the CPU.
The on-chip readings at the cells' own sizes are in PERF.md."""
import argparse
import json

import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests.cpu_cell import CELLS, tiny_cell

CPU_PEAK = {"cpu": {"flops_per_s": 1e12, "bytes_per_s": 1e11}}


def broken_step_factory(real, fault):
    def factory(*args, **kwargs):
        step = real(*args, **kwargs)

        def broken(params, pools, tokens, lengths, table, active):
            b = active.shape[0]
            logits, new_pools = step(params, pools, tokens, lengths, table, active)
            if fault == "state_unchanged":
                new_pools = pools
            elif fault == "half_batch":
                # the second half of the rows is left out: it gets the
                # first half's outputs and writes nothing
                logits = jnp.concatenate([logits[:b // 2]] * 2)[:b]
                _, new_pools = step(params, pools, tokens, lengths, table,
                                    active.at[b // 2:].set(False))
            elif fault == "token_altered":
                v = logits.shape[-1]
                top = jnp.argmax(logits[:, -1], axis=-1)
                logits = logits.at[jnp.arange(b), -1, (top + 1) % v].set(
                    jnp.max(logits) + 1.0)
            return logits, new_pools
        return broken
    return factory


def run_cell(name, cell, seed=31, control=None):
    args = argparse.Namespace(workload=name, seed=seed, seconds=1.5, trace=0)
    line, record = run.execute(args, cell=cell, require_tpu=False,
                               peak_table=CPU_PEAK, control=control)
    return json.loads(line), record


def checked_everything(cell):
    cell.cell["check"]["sample"] = {"max_requests": 64, "min_tokens": 10 ** 6,
                                    "preempted_max": 64}
    return cell


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "token_altered"])
def test_broken_path_is_not_correct(name, fault, monkeypatch):
    from repro.serving import engine
    monkeypatch.setattr(engine, "make_paged_decode_step",
                        broken_step_factory(engine.make_paged_decode_step, fault))
    cell = checked_everything(tiny_cell(name))
    out, record = run_cell(name, cell)
    assert record["served"]
    assert set(out["checks"]) == set(cell.cell["check"]["limits"])
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_above_the_limit(name):
    cell = checked_everything(tiny_cell(name))
    out, record = run_cell(name, cell, control="fp8")
    assert record["served"]
    assert set(out["checks"]) == set(cell.cell["check"]["limits"])
    # the program's own numbers over the same sample keep to every limit;
    # the control's fail one
    program = record["readings"]["program"]
    assert all(program[k] <= v for k, v in cell.cell["check"]["limits"].items())
    assert out["correct"] is False, out["checks"]
