"""Paged decode attention (TPU Pallas): one new token per sequence attends
over its KV pages scattered through the SA-cache-managed HBM pool.

The pages are read where they lie: no gathered copy of the table is made in
HBM, which is the whole point of paged attention (the pool never has to be
contiguous per sequence; the paper's set-associative placement stays).

A block is ``pages_per_block`` table entries (about 512 tokens). The grid
has one step per block that holds a live token, rows in order (a row of
length 0 gets one step that attends nothing), so its length is traced: a
schedule made from the lengths and the page table, scalar-prefetched,
names each step's row, block and pool pages. The pool is passed once per
slot of a block, each with its own (1, page, KV, hd) BlockSpec, and the
pipeline copies the next step's pages while this step attends. A slot past
the row's length keeps the page it held the step before (``_schedule``):
the pipeline sees an unchanged block index and copies nothing, so only live
pages leave HBM. Inside a decode step's layer scan the schedule depends on
the lengths and the table alone, and XLA makes it once a step.

The products run on the MXU, K and V as the bf16 values stored in the pool.
A float32 operand (the scaled query, the softmax weights) enters as three
bf16 terms whose sum is exactly that float32 value, stacked as rows of one
matmul; every product is exact and the MXU accumulates in float32, so
scores, softmax and P.V are float32 as in ``paged_attention_ref``. One
matmul scores every query head against every KV head of the block's tokens;
only the pairs of the same KV head are kept (the rest are masked before the
softmax and so weigh 0 in P.V).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 512                 # tokens a block of pages aims at
KV_VMEM_BYTES = 8 * 2 ** 20        # K and V blocks, double-buffered


def pages_per_block(page: int, kvh: int, hd: int, itemsize: int,
                    max_pages: int) -> int:
    """Table entries per grid step: ``BLOCK_TOKENS`` worth of pages, as far
    as two buffers each of K and V fit ``KV_VMEM_BYTES``."""
    fits = KV_VMEM_BYTES // (4 * page * kvh * hd * itemsize)
    return max(1, min(BLOCK_TOKENS // page, fits, max_pages))


def _schedule(page_table, lengths, *, page: int, ppb: int):
    """The grid's steps: their number, and for each step its row, its block
    of the row, and the pool page of each of its ``ppb`` slots (flattened).
    Steps past the number are never run."""
    b, max_pages = page_table.shape
    block_tokens = ppb * page
    blocks = jnp.maximum((lengths + block_tokens - 1) // block_tokens, 1)
    ends = jnp.cumsum(blocks)                          # each row's last step + 1
    step = jnp.arange(b * pl.cdiv(max_pages, ppb), dtype=jnp.int32)
    rows = jnp.minimum(jnp.sum(step[:, None] >= ends[None, :], axis=1), b - 1)
    blks = step - (ends - blocks)[rows]
    p = blks[:, None] * ppb + jnp.arange(ppb, dtype=jnp.int32)    # (steps, ppb)
    live = p * page < lengths[rows][:, None]
    pages = page_table[rows[:, None], jnp.minimum(p, max_pages - 1)]
    # A dead slot holds the page of the slot's last live step before it, so
    # the pipeline copies nothing; before the slot's first live step, that
    # step's page (copied once, up front); in a slot never live, the first
    # live page of all. So every slot holds a live page, and the masked
    # tokens of a block are finite values that weigh 0.
    n = step.shape[0]
    prev = jax.lax.cummax(jnp.where(live, step[:, None], -1), axis=0)
    nxt = jax.lax.cummin(jnp.where(live, step[:, None], n), axis=0,
                         reverse=True)
    src = jnp.where(prev >= 0, prev, nxt)
    first_live = pages.reshape(-1)[jnp.argmax(live.reshape(-1))]
    pages = jnp.where(src < n, jnp.take_along_axis(
        pages, jnp.minimum(src, n - 1), axis=0), first_live)
    return (ends[-1].astype(jnp.int32), rows.astype(jnp.int32),
            blks.astype(jnp.int32), pages.reshape(-1))


def _split3(x):
    """float32 (n, d) -> bf16 (3n, d): three row slabs summing exactly to x."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _sum3(x, n: int):
    return x[:n] + x[n:2 * n] + x[2 * n:]


def _paged_kernel(lengths_ref, rows_ref, blks_ref, pages_ref, q_ref, *refs,
                  page: int, ppb: int, kvh: int, softcap: float,
                  sm_scale: float):
    del pages_ref                                      # read by the index maps
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * ppb:]
    heads, hd = q_ref.shape[1:]
    step = pl.program_id(0)
    blk = blks_ref[step]
    length = lengths_ref[rows_ref[step]]
    start = blk * ppb * page

    @pl.when(blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(start < length)
    def _attend():
        cols = ppb * page * kvh
        # (token, KV head) pairs as rows: (ppb * page * KV, hd)
        k = jnp.concatenate([r[0].reshape(page * kvh, hd) for r in k_refs])
        v = jnp.concatenate([r[0].reshape(page * kvh, hd) for r in v_refs])
        q = q_ref[0].astype(jnp.float32) * sm_scale
        s = _sum3(jax.lax.dot_general(
            _split3(q), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), heads)    # (heads, cols)
        col = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 0)
        keep = jnp.logical_and(col % kvh == head % kvh,
                               start + col // kvh < length)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[...]                                # (heads, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jnp.dot(_split3(p), v, preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + _sum3(pv, heads)
        m_scr[...] = m_new

    @pl.when(start + ppb * page >= length)              # the row's last step
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "sm_scale", "interpret"))
def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    softcap: float = 0.0, sm_scale: float | None = None,
                    interpret: bool = False):
    """q: (B, H, hd); k/v_pages: (P, page, KV, hd), or (P, page, hd) for a
    single KV head; page_table: (B, max_pages) int32; lengths: (B,) ->
    (B, H, hd). Scores are scaled by ``sm_scale`` (default hd^-0.5).

    Table entries at or past a row's length are never read; a row of
    length 0 gives zeros."""
    b, h, hd = q.shape
    page = k_pages.shape[1]
    kvh = k_pages.shape[2] if k_pages.ndim == 4 else 1
    max_pages = page_table.shape[1]
    group = h // kvh
    ppb = pages_per_block(page, kvh, hd, k_pages.dtype.itemsize, max_pages)
    n_steps, rows, blks, pages = _schedule(page_table, lengths, page=page,
                                           ppb=ppb)
    # heads as (group, KV): row r * KV + g of a step's queries is KV head g's
    qg = q.reshape(b, kvh, group, hd).transpose(0, 2, 1, 3).reshape(b, h, hd)

    kernel = functools.partial(
        _paged_kernel, page=page, ppb=ppb, kvh=kvh, softcap=softcap,
        sm_scale=hd ** -0.5 if sm_scale is None else sm_scale)
    row = pl.BlockSpec((1, h, hd),
                       lambda s, lens, rows, blks, pages: (rows[s], 0, 0))
    trailing = (0,) * (k_pages.ndim - 1)
    slots = [pl.BlockSpec((1, *k_pages.shape[1:]),
                          lambda s, lens, rows, blks, pages, j=j:
                          (pages[s * ppb + j], *trailing))
             for j in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                   # lengths and the schedule
        grid=(n_steps,),
        in_specs=[row, *slots, *slots],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(lengths, rows, blks, pages, qg, *[k_pages] * ppb, *[v_pages] * ppb)
    return out.reshape(b, group, kvh, hd).transpose(0, 2, 1, 3).reshape(b, h, hd)
