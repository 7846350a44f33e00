"""Serving engine: exactness vs dense decode, pool pressure, flusher effect."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.models import transformer as T
from repro.serving import ServeEngine
from repro.serving.kv_pool import PagedAllocator

pytestmark = pytest.mark.slow  # end-to-end engine runs: nightly tier

RNG = jax.random.PRNGKey(0)


def _dense_greedy(params, cfg, prompt, n):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    last, cache = T.prefill(params, toks, cfg, max_seq=128)
    out = []
    t = jnp.argmax(last[:, -1, :], -1)[:, None].astype(jnp.int32)
    for _ in range(n):
        out.append(int(t[0, 0]))
        lg, cache = T.decode_step(params, t, cache, cfg)
        t = jnp.argmax(lg[:, -1, :], -1)[:, None].astype(jnp.int32)
    return out


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced(get_config("tinyllama-1.1b"))
    return cfg, T.init_params(RNG, cfg)


def test_engine_matches_dense_decode(tiny_model):
    cfg, params = tiny_model
    eng = ServeEngine(cfg, params, max_batch=3, page_size=8, num_sets=16,
                      set_size=4)
    prompts = [[5, 7, 11, 13, 17], [2, 3],
               [21, 22, 23, 24, 25, 26, 27, 28, 29]]
    rids = [eng.submit(p, max_new=10) for p in prompts]
    eng.run(200)
    for rid, p in zip(rids, prompts):
        assert eng.result(rid).out == _dense_greedy(params, cfg, p, 10)
    eng.close()


def test_engine_exact_under_pool_pressure(tiny_model):
    """Preemption + offload + resume must be lossless."""
    cfg, params = tiny_model
    eng = ServeEngine(cfg, params, max_batch=4, page_size=8, num_sets=4,
                      set_size=3)
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(1, 250, int(rng.integers(3, 20)))]
               for _ in range(6)]
    rids = [eng.submit(p, max_new=24) for p in prompts]
    eng.run(800)
    st = eng.stats()
    assert st["preemptions"] > 0, "test must exercise the pressure path"
    assert st["offloads"] > 0 and st["fetches"] > 0
    for rid, p in zip(rids, prompts):
        r = eng.result(rid)
        assert r.state == "done"
        assert r.out == _dense_greedy(params, cfg, p, 24), f"rid{rid}"
    eng.close()


def test_engine_with_paged_kernel(tiny_model):
    """Same outputs when attention runs through the Pallas paged kernel."""
    cfg, params = tiny_model
    eng = ServeEngine(cfg, params, max_batch=2, page_size=8, num_sets=16,
                      set_size=4, use_kernel=True, interpret=True)
    prompts = [[5, 7, 11], [40, 41, 42, 43, 44]]
    rids = [eng.submit(p, max_new=6) for p in prompts]
    eng.run(100)
    for rid, p in zip(rids, prompts):
        assert eng.result(rid).out == _dense_greedy(params, cfg, p, 6)
    eng.close()


def test_mamba_engine(tiny_model):
    """Attention-free arch: state pages instead of KV pages."""
    cfg = reduced(get_config("mamba2-780m"))
    params = T.init_params(RNG, cfg)
    eng = ServeEngine(cfg, params, max_batch=2, page_size=8, num_sets=8,
                      set_size=2)
    p = [3, 1, 4, 1, 5, 9, 2, 6]
    rid = eng.submit(p, max_new=8)
    eng.run(100)
    assert eng.result(rid).out == _dense_greedy(params, cfg, p, 8)
    eng.close()


def test_flusher_precleaning_reduces_blocking_offloads(tiny_model):
    """The paper's claim, transplanted: background pre-cleaning turns blocking
    (dirty) evictions into instant (clean) ones."""
    cfg, params = tiny_model
    results = {}
    for use_flusher in (True, False):
        eng = ServeEngine(cfg, params, max_batch=4, page_size=8, num_sets=4,
                          set_size=3, use_flusher=use_flusher)
        rng = np.random.default_rng(5)
        prompts = [[int(x) for x in rng.integers(1, 250, 16)]
                   for _ in range(8)]
        rids = [eng.submit(p, max_new=24) for p in prompts]
        eng.run(1200)
        assert all(eng.result(r).state == "done" for r in rids)
        results[use_flusher] = eng.stats()
        eng.close()
    # pre-cleaning converts blocking (dirty) evictions into instant (clean)
    # ones: with the flusher ON, strictly more clean evictions and no more
    # blocking offload work on the critical path
    assert results[True]["offloads"] > 0
    assert results[True]["clean_evictions"] >= \
        results[False]["clean_evictions"]
    assert results[True]["blocking_offloads"] <= \
        results[False]["blocking_offloads"]


def test_allocator_never_evicts_pinned():
    a = PagedAllocator(num_sets=2, set_size=2)
    tags = []
    # fill the pool, all pinned
    t = 0
    while len(tags) < 4:
        pid, ev, _ = a.alloc(t)
        if pid is not None:
            tags.append(t)
        t += 1
        if t > 100:
            break
    # further allocation in a full-pinned set must fail, never evict
    before = dict(a.where)
    for tt in range(200, 260):
        pid, ev, _ = a.alloc(tt)
        if pid is not None:          # only possible if a set had room
            pytest.fail("alloc succeeded in fully pinned pool")
    assert dict(a.where) == before
    # unpin one -> allocation succeeds by evicting exactly that page
    a.set_pinned([tags[0]], False)
    s = a.set_of(tags[0])
    for tt in range(300, 400):
        if a.set_of(tt) == s:
            pid, ev, _ = a.alloc(tt)
            assert pid is not None and ev == tags[0]
            break
