"""Serving engine: exactness vs dense decode, pool pressure, flusher effect."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.models import transformer as T
from repro.serving import ServeEngine
from repro.serving.engine import MAX_PAGES_PER_SEQ
from repro.serving.kv_pool import PagedAllocator

pytestmark = pytest.mark.slow  # end-to-end engine runs: nightly tier

RNG = jax.random.PRNGKey(0)


def _dense_greedy(params, cfg, prompt, n, decode=None):
    decode = decode or functools.partial(T.decode_step, cfg=cfg)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    last, cache = T.prefill(params, toks, cfg, max_seq=128)
    out = []
    t = jnp.argmax(last[:, -1, :], -1)[:, None].astype(jnp.int32)
    for _ in range(n):
        out.append(int(t[0, 0]))
        lg, cache = decode(params, t, cache)
        t = jnp.argmax(lg[:, -1, :], -1)[:, None].astype(jnp.int32)
    return out


@functools.cache
def _jit_decode(cfg):
    return jax.jit(functools.partial(T.decode_step, cfg=cfg))


def _jit_greedy(params, cfg, prompt, n):
    """``_dense_greedy`` with its decode step compiled once per config."""
    return _dense_greedy(params, cfg, prompt, n, _jit_decode(cfg))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced(get_config("tinyllama-1.1b"))
    return cfg, T.init_params(RNG, cfg)


# an attention pool and a latent (MLA) pool
@pytest.fixture(scope="module", params=["tinyllama-1.1b", "deepseek-v2-lite"])
def pool_model(request):
    cfg = reduced(get_config(request.param))
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


@pytest.mark.parametrize("backend,interpret,want", [
    ("cpu", False, False), ("tpu", False, True), ("cpu", True, True)])
def test_engine_picks_the_kernel_on_tpu(tiny_model, monkeypatch, backend,
                                        interpret, want):
    """By default decode attention runs through the Pallas kernel on a TPU
    (or where interpret mode is asked for) and the reference elsewhere.
    Only the choice is checked: nothing runs."""
    cfg, params = tiny_model
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    eng = ServeEngine(cfg, params, max_batch=2, page_size=8, num_sets=4,
                      set_size=2, interpret=interpret)
    assert eng.use_kernel is want
    eng.close()
    eng = ServeEngine(cfg, params, max_batch=2, page_size=8, num_sets=4,
                      set_size=2, use_kernel=False)
    assert eng.use_kernel is False
    eng.close()


@pytest.mark.parametrize("use_kernel", [True, False])
def test_attention_page_counters(tiny_model, use_kernel):
    """``attn_pages_read`` adds up, over the decode steps, the pages below
    each decoding row's length + 1 when the kernel runs (0 on the reference
    path); ``attn_pages_spanned`` adds up the decoding rows' table entries."""
    cfg, params = tiny_model
    page, max_pages = 8, 8
    eng = ServeEngine(cfg, params, max_batch=2, page_size=page, num_sets=16,
                      set_size=4, max_pages=max_pages, use_kernel=use_kernel,
                      interpret=use_kernel)
    seen = {"read": 0, "spanned": 0}
    decode = eng.step_fn

    def counting_step(params, pools, tokens, lengths, table, active):
        lens = np.asarray(lengths)[np.asarray(active)]
        seen["read"] += int(np.sum(-(-(lens + 1) // page)))
        seen["spanned"] += len(lens) * max_pages
        return decode(params, pools, tokens, lengths, table, active)

    eng.step_fn = counting_step
    # contexts cross page edges: 3 + 12 and 14 + 12 tokens
    for prompt in ([5, 7, 11], list(range(40, 54))):
        eng.submit(prompt, max_new=12)
    eng.run(100)
    st = eng.stats()
    assert st["attn_pages_spanned"] == seen["spanned"] > 0
    assert st["attn_pages_read"] == (seen["read"] if use_kernel else 0)
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


def test_host_copies_dropped_while_workers_offload():
    """A finished request's host-tier copies are dropped while the IO
    workers go on inserting offloaded pages into the tier (with 160 rows an
    unlocked scan of the tier failed on the chip: the dict changed size
    during the iteration)."""
    import threading
    from repro.serving.kv_pool import PagedKVPool
    pool = PagedKVPool(2, 2, copy_out=lambda tag: (), copy_in=lambda t, d: None)
    done = threading.Event()

    def offload_many():
        for tag in range(200_000):
            pool._do_io(tag % 2, {"op": "offload", "tag": tag})
        done.set()

    worker = threading.Thread(target=offload_many)
    worker.start()
    try:
        # bounded: the lock is not fair, and the worker may wait on it
        for _ in range(3000):
            if done.is_set():
                break
            pool.drop_host_copies(lambda t: t % 2 == 0)
    finally:
        worker.join()
        pool.close()
    pool.drop_host_copies(lambda t: t % 2 == 0)
    assert pool.host_tier and all(t % 2 for t in pool.host_tier)


# ------------------------------------------- one decode step in flight
def _log_order(eng):
    """Logs, in order, each decode step's dispatch (with the rows it decodes
    and how many of each row's tokens the host held at the call) and each
    host read of a step's tokens."""
    log = []
    decode, book = eng.step_fn, eng._book

    def dispatch(params, pools, tokens, lengths, table, active):
        rows = {rid: (len(eng.result(rid).out), int(lengths[i]))
                for i, rid in enumerate(eng._rows) if rid is not None}
        log.append(("dispatch", rows))
        return decode(params, pools, tokens, lengths, table, active)

    def read(step, toks):
        log.append(("read", None))
        return book(step, toks)

    eng.step_fn, eng._book = dispatch, read
    return log


def test_next_step_dispatched_before_the_last_tokens_are_read(pool_model):
    """Step N+1 is dispatched before the host reads step N's tokens: at
    each dispatch a row that decoded in the step before has its latest
    token on the device only, and the host reads each step after the next
    one is dispatched. Rows finish at different steps and a waiting
    request takes a freed row; the tokens equal dense decode."""
    cfg, params = pool_model
    eng = ServeEngine(cfg, params, max_batch=2, page_size=8, num_sets=16,
                      set_size=4)
    log = _log_order(eng)
    prompts = [[5, 7, 11, 13, 17], [2, 3], [21, 22, 23, 24, 25, 26, 27]]
    news = [9, 5, 7]
    rids = [eng.submit(p, max_new=n) for p, n in zip(prompts, news)]
    eng.run(200)
    for rid, p, n in zip(rids, prompts, news):
        assert eng.result(rid).out == _jit_greedy(params, cfg, p, n)
    dispatched, seen_at_read, last = 0, [], {}
    for kind, rows in log:
        if kind == "read":
            seen_at_read.append(dispatched)
            continue
        dispatched += 1
        for rid, (held, length) in rows.items():
            consumed = length - len(eng.result(rid).prompt)
            # a row carried over from the step before: its input token is
            # that step's, not yet on the host; a row just admitted or
            # resumed holds every token it has
            behind = 1 if last.get(rid) == dispatched - 1 else 0
            assert held == consumed + 1 - behind, (dispatched, rid)
            last[rid] = dispatched
    # read k follows dispatch k + 1; the last read follows the last dispatch
    assert seen_at_read == list(range(2, dispatched + 1)) + [dispatched]
    st = eng.stats()
    assert st["overlapped_steps"] == dispatched - 1 and st["drains"] == 0
    eng.close()


def test_request_ending_at_max_new_frees_its_row(pool_model):
    """A request whose last token the step in flight computes gives its row
    up at the next call: the next request is admitted into that row and
    decoded in the very next step, and decodes what dense decode does."""
    cfg, params = pool_model
    eng = ServeEngine(cfg, params, max_batch=1, page_size=8, num_sets=16,
                      set_size=4)
    log = _log_order(eng)
    first = eng.submit([5, 7, 11, 13, 17], max_new=6)
    second = eng.submit([9, 8, 7, 6, 5, 4, 3, 2, 1], max_new=5)
    eng.run(100)
    decoded = [list(rows) for kind, rows in log if kind == "dispatch"]
    # the prefill gives each request its first token, each step one more
    assert decoded == [[first]] * 5 + [[second]] * 4
    assert eng._steps == len(decoded) + 1     # one call books the last step
    assert eng.result(first).out == _jit_greedy(params, cfg, [5, 7, 11, 13, 17], 6)
    assert eng.result(second).out == _jit_greedy(
        params, cfg, [9, 8, 7, 6, 5, 4, 3, 2, 1], 5)
    assert not any(t // MAX_PAGES_PER_SEQ == first for t in eng.pool.alloc.where)
    assert eng._rows == [None]
    eng.close()


@pytest.mark.parametrize("pressure", [False, True])
def test_overlap_and_drain_counters(pool_model, pressure):
    """``overlapped_steps`` counts the steps dispatched with the step before
    still unread, ``drains`` the steps in flight read early. Without
    pressure nothing is drained; under it every preemption finds no step
    in flight (it was drained) and the tokens still equal dense decode."""
    cfg, params = pool_model
    pool = {"num_sets": 4, "set_size": 3} if pressure else \
        {"num_sets": 16, "set_size": 4}
    eng = ServeEngine(cfg, params, max_batch=4, page_size=8, **pool)
    log = _log_order(eng)
    in_flight_at_preempt, preempting_calls = [], set()
    preempt = eng._preempt

    def recording_preempt(req):
        preempt(req)
        in_flight_at_preempt.append(eng._inflight is not None)
        preempting_calls.add(eng._steps)

    eng._preempt = recording_preempt
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(1, 250, int(rng.integers(3, 20)))]
               for _ in range(6)]
    rids = [eng.submit(p, max_new=24) for p in prompts]
    eng.run(800)
    st = eng.stats()
    dispatched = sum(kind == "dispatch" for kind, _ in log)
    for rid, p in zip(rids, prompts):
        assert eng.result(rid).out == _jit_greedy(params, cfg, p, 24), rid
    assert st["preemptions"] == len(in_flight_at_preempt)
    if not pressure:
        assert st["preemptions"] == 0 and st["drains"] == 0
        assert st["overlapped_steps"] == dispatched - 1
    else:
        assert st["preemptions"] > 0 and not any(in_flight_at_preempt)
        # the first preemption of a call drains the step in flight
        assert st["drains"] >= len(preempting_calls) > 0
        assert st["overlapped_steps"] == dispatched - 1 - st["drains"]
    eng.close()


def test_resumed_row_preempted_right_after_writing_a_page_with_a_host_copy(
        pool_model):
    """A row is preempted (its partial page goes to the host tier), resumed
    (the page is fetched back and keeps its host copy), writes one token
    into that page and is preempted again while that step is in flight, as
    the grow phase preempts when the pool is full. The preemption books the
    step first and the write re-dirtied the page, so the page goes to the
    host tier again with the new token, and the row's tokens equal dense
    decode."""
    cfg, params = pool_model
    eng = ServeEngine(cfg, params, max_batch=2, page_size=8, num_sets=4,
                      set_size=3)
    prompt = [3, 1, 4, 1]
    rid = eng.submit(prompt, max_new=12)
    req = eng.result(rid)
    page0 = rid * MAX_PAGES_PER_SEQ
    eng.step()
    eng.step()                        # positions 4 and 5; the second in flight
    eng._preempt(req)
    assert req.state == "preempted" and len(req.out) == 3
    assert page0 in eng.pool.host_tier
    eng.step()                        # requeued
    eng.step()                        # resumed: fetched, writes position 6
    assert req.state == "active" and req.length == 7 and len(req.out) == 3
    pid = eng.pool.alloc.where[page0]
    assert eng.pool.alloc.dirty[pid] and page0 not in eng.pool.host_tier
    eng._preempt(req)
    assert len(req.out) == 4 and page0 in eng.pool.host_tier
    eng.run(100)
    assert req.out == _jit_greedy(params, cfg, prompt, 12)
    st = eng.stats()
    assert st["preemptions"] == 2 and st["drains"] == 2 and st["fetches"] >= 2
    eng.close()
