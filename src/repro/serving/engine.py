"""Continuous-batching serve engine over the paged KV pool.

Scheduler loop (host) + jitted paged decode step (device):

  submit() -> waiting queue -> admit into free batch rows (prefill writes the
  prompt's KV pages) -> decode all active rows each step -> pages that fill
  trigger the dirty-page flusher (background offload, LOW priority) ->
  finished sequences free their pages (queued offloads become stale and are
  discarded) -> page-pool exhaustion preempts the youngest sequence
  (clean pages drop instantly thanks to pre-cleaning; dirty ones cost a
  blocking offload — counted) -> preempted sequences resume via HIGH-priority
  fetches.

This is the paper's cache+flusher+queues stack serving as a first-class
inference feature; stats expose exactly the quantities the paper reports
(extra writeback, stall counts, queue discards).

One decode step stays in flight ahead of the host's bookkeeping: a step
dispatches step N before it reads step N-1's tokens, so the host books N-1
while the device decodes N. Nothing the host decides for N depends on a
token value: which rows decode, the page each writes and when a row ends
(``max_new`` is a count) are known at dispatch, and N's input tokens are
N-1's argmax, which stays on the device. What needs the values (``out``, the
GClock touch of the pages read, freeing finished rows) runs after the next
dispatch. Before a preemption, and at the end of ``run()`` and in
``close()``, the step in flight is read and booked first (a drain).

Each phase of a step and of an admission opens a ``jax.profiler`` span
named ``serve.*`` (``serve.step`` > ``serve.admit`` > ``serve.prefill`` ...,
``serve.grow``, ``serve.dispatch``, ``serve.sync``, ``serve.bookkeep``,
``serve.requeue``); the pool's IO workers open ``serve.offload_io`` and
``serve.fetch_io``. A running profiler writes them on the device trace's
clock; without one a span costs about a microsecond.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from .kv_pool import PagedKVPool
from .paged_model import init_pools, make_paged_decode_step

MAX_PAGES_PER_SEQ = 512


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int = 16
    out: list[int] = field(default_factory=list)
    state: str = "waiting"         # waiting | active | preempted | done
    row: int = -1
    length: int = 0
    pages: list[int] = field(default_factory=list)     # tags, in order


@dataclass
class _InFlight:
    """A dispatched decode step whose tokens the host has not read."""
    tokens: jax.Array                 # (max_batch,) int32, on the device
    rows: list[tuple[int, int]]       # (row, rid) of each decoding row
    finishing: list[int]              # rids whose last token it computes


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 page_size: int = 16, num_sets: int = 32, set_size: int = 4,
                 max_pages: int = 64, use_flusher: bool = True,
                 use_kernel: Optional[bool] = None, interpret: bool = False,
                 seed: int = 0):
        """``use_kernel=None`` runs decode attention through the Pallas
        paged kernel on a TPU (or wherever ``interpret`` is set) and through
        ``paged_attention_ref`` elsewhere."""
        assert cfg.has_attention or cfg.family == "ssm"
        if use_kernel is None:
            use_kernel = interpret or jax.default_backend() == "tpu"
        self.use_kernel = use_kernel
        self.cfg = cfg
        self.params = params
        self.page = page_size
        self.max_batch = max_batch
        self.max_pages = max_pages
        self.use_flusher = use_flusher
        n_data_pages = num_sets * set_size
        self.scratch_page = n_data_pages                  # reserved, never allocated
        self.pools = init_pools(cfg, num_pages=n_data_pages + 1,
                                page_size=page_size, max_batch=max_batch)
        self.pool = PagedKVPool(num_sets, set_size, n_targets=2,
                                copy_out=self._copy_out, copy_in=self._copy_in,
                                # paper: trigger at half the set (6 of 12)
                                flush_trigger=max(0, set_size // 2 - 1))
        def prefill(params, tokens, max_seq):
            return T.prefill(params, tokens, cfg, max_seq=max_seq)

        # one compile per (prompt length, padded length), not one per call;
        # the module is named jit_prefill
        self._prefill = jax.jit(prefill, static_argnames="max_seq")

        def next_tokens(carried, host, carry):
            # a row that goes on decoding takes the token of the step in
            # flight; a row admitted or resumed since takes the host's
            return jnp.where(carry, carried, host)[:, None]

        self._next_tokens = jax.jit(next_tokens)
        self.step_fn = make_paged_decode_step(cfg, page_size=page_size,
                                              use_kernel=use_kernel,
                                              interpret=interpret)
        # pool positions whose leaves are paged (axis 1 = page id)
        self._paged_positions = [i for i, s in enumerate(cfg.layer_specs)
                                 if s.kind in ("attn", "mla")]
        self._reqs: dict[int, Request] = {}
        self._waiting: list[int] = []
        self._rows: list[Optional[int]] = [None] * max_batch
        self._rid = itertools.count()
        self._lengths = np.zeros(max_batch, np.int32)
        self._tables = np.full((max_batch, max_pages), self.scratch_page,
                               np.int32)
        self._last_tok = np.zeros(max_batch, np.int32)
        self._inflight: Optional[_InFlight] = None
        self._pools_lock = __import__("threading").Lock()
        self.preemptions = 0
        self.blocking_offloads = 0
        self.unflushed_at_preempt = 0    # full pages the flusher had not cleaned
        self.attn_pages_read = 0         # live pages the paged kernel attended
        self.attn_pages_spanned = 0      # table entries of the decoding rows
        self.decode_rows = 0             # decoding rows, summed over steps
        self.decode_context_tokens = 0   # their lengths + 1, summed
        self.prefill_tokens = 0          # prompt tokens prefilled
        self.prefill_attn_pairs = 0      # causal (query, key) pairs prefilled
        self.overlapped_steps = 0        # dispatched with the last step unread
        self.drains = 0                  # steps in flight read early
        self._steps = 0

    # ------------------------------------------------------------- tags
    def _tag(self, rid: int, page_idx: int) -> int:
        return rid * MAX_PAGES_PER_SEQ + page_idx

    # -------------------------------------------------- device<->host copies
    def _copy_out(self, tag: int, page_id: int | None = None):
        """One page of every paged leaf, as host arrays: [{leaf: array}]
        in the order of ``_paged_positions``."""
        pid = self.pool.alloc.where.get(tag) if page_id is None else page_id
        if pid is None:
            return None
        return [{name: np.asarray(leaf[:, pid])
                 for name, leaf in self.pools[pos].items()}
                for pos in self._paged_positions]

    def _copy_in(self, tag: int, data) -> None:
        # serialized: concurrent fetch workers would lose each other's
        # read-modify-write of the pools pytree
        with self._pools_lock:
            pid = self.pool.alloc.where.get(tag)
            if pid is None:
                return
            new_pools = list(self.pools)
            for pos, page in zip(self._paged_positions, data):
                new_pools[pos] = {
                    name: leaf.at[:, pid].set(jnp.asarray(page[name]))
                    for name, leaf in self.pools[pos].items()}
            self.pools = tuple(new_pools)

    # ------------------------------------------------------------ public
    def submit(self, prompt: list[int], max_new: int = 16) -> int:
        rid = next(self._rid)
        self._reqs[rid] = Request(rid, list(prompt), max_new)
        self._waiting.append(rid)
        return rid

    def result(self, rid: int) -> Request:
        return self._reqs[rid]

    # -------------------------------------------------------- page control
    def _alloc_page(self, req: Request, page_idx: int,
                    allow_preempt: bool = True) -> bool:
        """Allocate (tag); on a fully-pinned set optionally preempt a victim.

        Admission passes allow_preempt=False (a waiting request never kicks
        out an active one — that's the thrash the paper's deep queues avoid);
        only an ACTIVE row growing into its next page may preempt.
        """
        tag = self._tag(req.rid, page_idx)
        while True:
            pid, ev_tag, ev_dirty = self.pool.alloc.alloc(tag)
            if pid is not None:
                if ev_tag is not None and ev_dirty:
                    # blocking offload of the victim's content (stall)
                    self.pool.offload_now_evicted(ev_tag, pid, self._copy_out)
                    self.blocking_offloads += 1
                req.pages.append(tag)
                return True
            if not allow_preempt:
                return False
            victim = self._pick_victim(exclude=req.rid)
            if victim is None:
                return False
            self._preempt(victim)

    def _pick_victim(self, exclude: int) -> Optional[Request]:
        # a request released by the step in flight holds no row
        active = [r for r in self._reqs.values()
                  if r.state == "active" and r.row >= 0 and r.rid != exclude]
        if not active:
            return None
        return max(active, key=lambda r: r.rid)        # youngest first (LIFO)

    def _preempt(self, req: Request) -> None:
        # book the step in flight first: the victim's tokens, pages and
        # dirty bits must be current when its pages go to the host tier
        self._drain()
        with TraceAnnotation("serve.preempt", rid=req.rid):
            self.preemptions += 1
            # partial (dirty, non-full) pages + any un-offloaded full pages
            # must reach the host tier before their slots can be reused
            for tag in req.pages:
                pid = self.pool.alloc.where.get(tag)
                if pid is not None and self.pool.alloc.dirty[pid]:
                    if self.pool.alloc.full[pid] and self.use_flusher:
                        self.unflushed_at_preempt += 1
                    self.pool.offload_now(tag)
                    self.blocking_offloads += 1
            self.pool.alloc.set_pinned(req.pages, False)
            self._rows[req.row] = None
            self._tables[req.row, :] = self.scratch_page
            req.state = "preempted"
            req.row = -1

    def _release(self, req: Request) -> None:
        """Gives a finished request's row and pages back (once)."""
        if req.row >= 0:
            self._rows[req.row] = None
            self._tables[req.row, :] = self.scratch_page
            req.row = -1
            self._free(req)

    def _free(self, req: Request) -> None:
        self.pool.alloc.free(req.pages)
        # scan by rid: host-tier copies of pages evicted while preempted are
        # no longer listed in req.pages but must not leak
        self.pool.drop_host_copies(lambda t: t // MAX_PAGES_PER_SEQ == req.rid)
        req.pages.clear()

    # ------------------------------------------------------------- admit
    def _admit(self, rid: int) -> bool:
        req = self._reqs[rid]
        row = next((i for i, r in enumerate(self._rows) if r is None), None)
        if row is None:
            return False
        resume = req.state == "preempted"
        tokens = req.prompt + req.out
        # consumed tokens occupy positions [0, c); the next decode writes
        # position c -> pages 0 .. c // page must exist.
        consumed = req.length if resume else len(req.prompt)
        n_pages = consumed // self.page + 1
        req.pages = [t for t in req.pages
                     if self.pool.alloc.where.get(t) is not None]
        # re-pin surviving pages FIRST: the alloc loop below must not evict
        # this request's own residents
        self.pool.alloc.set_pinned(req.pages, True)
        survivors = list(req.pages)
        newly: list[int] = []
        for i in range(n_pages):
            tag = self._tag(rid, i)
            if self.pool.alloc.where.get(tag) is None:
                if not self._alloc_page(req, i, allow_preempt=False):
                    # ROLL BACK this attempt's allocations: they hold garbage
                    # (content is only restored by the post-success fetch);
                    # leaving them dirty would later clobber the good host
                    # copies via eviction writeback
                    self.pool.alloc.free(newly)
                    req.pages = survivors
                    self.pool.alloc.set_pinned(survivors, False)
                    return False
                newly.append(tag)
        self.pool.alloc.set_pinned(req.pages, True)
        req.row, req.state = row, "active"
        self._rows[row] = rid
        if resume:
            with TraceAnnotation("serve.resume_fetch", rid=rid):
                # fetch by LOGICAL page index, not by the (lossy) tag list —
                # a page evicted while preempted lives only in the host tier
                fetchable = [self._tag(rid, i) for i in range(n_pages)
                             if self._tag(rid, i) in self.pool.host_tier]
                self.pool.fetch(fetchable)
                self._refill_row(req, tokens)
        else:
            self._prefill_row(req, tokens)
        return True

    def _prefill_row(self, req: Request, tokens: list[int]) -> None:
        with TraceAnnotation("serve.prefill", rid=req.rid):
            cfg, row = self.cfg, req.row
            s = len(tokens)
            pad = len(req.pages) * self.page
            with TraceAnnotation("serve.prefill.forward"):
                toks = jnp.asarray(tokens, jnp.int32)[None]
                logits, cache = self._prefill(self.params, toks, max_seq=pad)
            with TraceAnnotation("serve.prefill.page_write"):
                new_pools = list(self.pools)
                for i, spec in enumerate(cfg.layer_specs):
                    lc = cache.layers[i]
                    if i in self._paged_positions:
                        written = {}
                        for name, leaf in new_pools[i].items():
                            new = lc[name][:, 0]       # (nb, pad, ...)
                            for tag in req.pages:
                                pi = tag % MAX_PAGES_PER_SEQ  # page index
                                pid = self.pool.alloc.where[tag]
                                sl = slice(pi * self.page, (pi + 1) * self.page)
                                leaf = leaf.at[:, pid].set(new[:, sl])
                            written[name] = leaf
                        new_pools[i] = written
                    else:
                        st = new_pools[i]
                        new_pools[i] = jax.tree.map(
                            lambda pool, new: pool.at[:, row].set(new[:, 0]),
                            st, {k: lc[k] for k in st})
                # NOTE: prefill caches beyond ``s`` are zeros — masked by lengths.
                self.pools = tuple(new_pools)
                self.prefill_tokens += s
                self.prefill_attn_pairs += s * (s + 1) // 2
                self._lengths[row] = s
                self._tables[row, :] = self.scratch_page
                for tag in req.pages:
                    self._tables[row, tag % MAX_PAGES_PER_SEQ] = \
                        self.pool.alloc.where[tag]
            # the prompt's last-position logits emit the FIRST generated token
            with TraceAnnotation("serve.prefill.first_token"):
                first = int(jnp.argmax(logits[0, -1]))
            req.out.append(first)
            self._last_tok[row] = first
            req.length = s
            # full prompt pages are immediately flushable
            if self.use_flusher:
                with TraceAnnotation("serve.prefill.mark_full"):
                    for tag in req.pages:
                        pi = tag % MAX_PAGES_PER_SEQ
                        if (pi + 1) * self.page <= s:
                            self.pool.alloc.mark_full(tag)
                            self.pool.note_page_full(self.pool.alloc.set_of(tag))

    def _refill_row(self, req: Request, tokens: list[int]) -> None:
        """Resume: pages were fetched back by tag; rebuild the table/row."""
        row = req.row
        self._lengths[row] = req.length          # consumed tokens
        self._tables[row, :] = self.scratch_page
        for pi_tag in req.pages:
            pi = pi_tag % MAX_PAGES_PER_SEQ
            self._tables[row, pi] = self.pool.alloc.where[pi_tag]
        self._last_tok[row] = tokens[-1]         # the one unconsumed token

    # --------------------------------------------------------------- loop
    def step(self) -> None:
        self._steps += 1
        with StepTraceAnnotation("serve.step", step_num=self._steps):
            self._step()

    def _step(self) -> None:
        # rows whose last token the step in flight computes are free now
        if self._inflight is not None:
            for rid in self._inflight.finishing:
                self._release(self._reqs[rid])
        with TraceAnnotation("serve.admit"):
            for rid in list(self._waiting):
                if self._admit(rid):
                    self._waiting.remove(rid)
        active_rows = [i for i, r in enumerate(self._rows) if r is not None]
        # ensure a page exists for the next position of every active row
        with TraceAnnotation("serve.grow"):
            for i in active_rows:
                rid = self._rows[i]
                if rid is None:                  # preempted as a victim above
                    continue
                req = self._reqs[rid]
                pi = int(self._lengths[i]) // self.page
                tag = self._tag(req.rid, pi)
                if self.pool.alloc.where.get(tag) is None:
                    if not self._alloc_page(req, pi):
                        self._preempt(req)
                        continue
                    self._tables[i, pi] = self.pool.alloc.where[tag]
        active_rows = [i for i, r in enumerate(self._rows) if r is not None]
        prev, filled = self._inflight, []
        if active_rows:
            with TraceAnnotation("serve.dispatch"):
                filled = self._dispatch(active_rows)
        else:
            self._inflight = None
        if active_rows or prev is not None:
            with TraceAnnotation("serve.sync"):
                toks = None if prev is None else np.asarray(prev.tokens)
            with TraceAnnotation("serve.bookkeep"):
                if prev is not None:
                    self._book(prev, toks)
                for set_idx in filled:
                    self.pool.note_page_full(set_idx)
        # resumption of preempted requests
        with TraceAnnotation("serve.requeue"):
            for req in list(self._reqs.values()):
                if req.state == "preempted":
                    self._waiting.append(req.rid) if req.rid not in self._waiting else None

    def _dispatch(self, active_rows: list[int]) -> list[int]:
        """Dispatches one decode step of ``active_rows`` and does at once
        what depends on no token value: lengths, re-dirtying the pages
        written, full pages, the finish decision. Returns the sets of the
        pages the step fills, for the flusher."""
        active = np.zeros(self.max_batch, bool)
        active[active_rows] = True
        self.attn_pages_spanned += len(active_rows) * self.max_pages
        self.decode_rows += len(active_rows)
        # the step attends over lengths + 1 tokens
        self.decode_context_tokens += int(np.sum(
            self._lengths[active_rows] + 1))
        if self.use_kernel:
            self.attn_pages_read += int(np.sum(
                self._lengths[active_rows] // self.page + 1))
        # snapshots: the host edits these arrays while the step runs, and a
        # device array may alias the numpy buffer it was made from
        host = jnp.asarray(self._last_tok.copy())
        carry = np.zeros(self.max_batch, bool)
        prev = self._inflight
        if prev is not None:
            self.overlapped_steps += 1
            for i, rid in prev.rows:
                carry[i] = self._rows[i] == rid
        tokens = self._next_tokens(host if prev is None else prev.tokens,
                                   host, jnp.asarray(carry))
        logits, self.pools = self.step_fn(
            self.params, self.pools, tokens, jnp.asarray(self._lengths.copy()),
            jnp.asarray(self._tables.copy()), jnp.asarray(active))
        toks = jnp.argmax(logits[:, -1, :], axis=-1)
        toks.copy_to_host_async()
        filled, finishing = [], []
        for i in active_rows:
            req = self._reqs[self._rows[i]]
            # the page written this step diverged from any host copy
            cur_tag = self._tag(req.rid, int(self._lengths[i]) // self.page)
            self.pool.mark_redirtied(cur_tag)
            self._lengths[i] += 1
            req.length += 1
            if self._lengths[i] % self.page == 0 and self.use_flusher:
                tag = self._tag(req.rid, int(self._lengths[i]) // self.page - 1)
                self.pool.alloc.mark_full(tag)
                filled.append(self.pool.alloc.set_of(tag))
            # tokens served once this step is booked: one per consumed
            # token past the prompt, plus the one the step computes
            if req.length - len(req.prompt) + 1 >= req.max_new:
                finishing.append(req.rid)
        self._inflight = _InFlight(
            toks, [(i, self._rows[i]) for i in active_rows], finishing)
        return filled

    def _book(self, step: _InFlight, toks: np.ndarray) -> None:
        """The host's part of a step whose tokens ``toks`` it has read."""
        # GClock touch: every resident page of every decoding row was read
        for _, rid in step.rows:
            self.pool.alloc.touch(self._reqs[rid].pages)
        for i, rid in step.rows:
            req = self._reqs[rid]
            req.out.append(int(toks[i]))
            if self._rows[i] == rid:
                self._last_tok[i] = toks[i]
            if len(req.out) >= req.max_new:
                req.state = "done"
                self._release(req)

    def _drain(self) -> None:
        """Reads and books the step in flight, if there is one."""
        step, self._inflight = self._inflight, None
        if step is not None:
            self.drains += 1
            self._book(step, np.asarray(step.tokens))

    def run(self, max_steps: int = 1000) -> None:
        for _ in range(max_steps):
            if all(r.state == "done" for r in self._reqs.values()):
                break
            self.step()
        self._drain()

    def stats(self) -> dict:
        s = self.pool.alloc.stats
        return {
            "offloads": s.offloads, "fetches": s.fetches,
            "stale_discards": s.stale_discards,
            "clean_evictions": s.clean_evictions,
            "dirty_evictions": s.dirty_evictions,
            "alloc_failures": s.alloc_failures,
            "preemptions": self.preemptions,
            "blocking_offloads": self.blocking_offloads,
            "flush_requests": s.flush_requests,
            "allocs": s.allocs,
            "unflushed_at_preempt": self.unflushed_at_preempt,
            "attn_pages_read": self.attn_pages_read,
            "attn_pages_spanned": self.attn_pages_spanned,
            "decode_rows": self.decode_rows,
            "decode_context_tokens": self.decode_context_tokens,
            "prefill_tokens": self.prefill_tokens,
            "prefill_attn_pairs": self.prefill_attn_pairs,
            "overlapped_steps": self.overlapped_steps,
            "drains": self.drains,
        }

    def close(self):
        self._drain()
        self.pool.close()
