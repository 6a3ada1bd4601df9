"""Pallas TPU kernel: decode attention straight out of the paged pool.

The XLA form of a decode pass's attention (models/transformer.py
``_attend_pool_rung``) gathers every slot's blocks into a contiguous
[R, rung, Hkv, hd] buffer and reads that: K and V move three times a
layer (the gather's read and write, attention's read), as far as the
ladder's rung for every slot. This kernel reads them once, where the pool
lies and as far as each slot's own context:

- The stacked planes ``[L, NB, bs, Hkv, hd]`` stay in HBM in the layout
  they have. A page ``[bs, Hkv, hd]`` is contiguous there (64 KB at
  Ouro-2.6B, 32 KB at mistral-7b) and is fetched as it lies, by (plane,
  block-table entry), with ``pltpu.make_async_copy``: nothing is
  transposed or copied ahead of the call, nothing K- or V-sized is
  written back.
- One call walks a flat list of work items (``pool_walk``: a slot and a
  run of up to ``pages`` of its block-table columns, live slots only,
  each as far as its own last page and, under a sliding window, from its
  window's first page). Item i + 1's pages are in flight while item i is
  computed, across slots too, so a call has one exposed fetch and not
  one a slot. The list is made once a chunk, from the lengths the pool
  holds for the whole chunk.
- A page's rows are (position, kv head) pairs, 128 or 256 of them. One
  MXU product of all query heads against those rows as they lie,
  ``[H, hd] x [rows, hd]^T``, gives every (query head, kv head) pair; the
  pairs whose kv head is not the query head's own are masked to -inf, so
  their probabilities are exactly 0 and ``p @ V`` over the same rows is
  the grouped-query sum. No head is ever sliced out of a page (a strided
  sublane read) and G query heads share their kv head's rows at no cost.
- The mathematics is ops/attention.attend's: bf16 K and V as stored,
  float32 scores, one online softmax in float32, float32 probabilities
  times V in float32 (the probabilities go through the MXU as three bf16
  terms whose sum is the float32 value, so nothing is rounded), the
  static sliding window, the result in ``q.dtype``. The decode chunk's
  side rows (its own K and V of this and earlier passes, [K, Hkv, hd] a
  slot) start each slot's softmax state, so pool and side meet in one
  softmax inside the kernel.

``paged_flash_decode`` is the stepwise path's entry (one layer's pool, no
side rows: paged_kvcache.paged_attend_decode with an explicit pallas
backend). The decode chunk calls ``paged_attend`` (models/transformer.py
``_pool_kernel`` says where).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128

# K (and V) bytes fetched a work item; two items are in VMEM at a time
_STEP_BYTES = 512 * 1024
# (position, kv head) rows a compute step, one softmax update over
# [H, rows] scores: the MXU products of a step are independent, the steps
# are a chain, so wide steps are what streams (2048 rows: 69-81 % of a
# v5e's HBM peak, 512: 49-54 %; scripts/bench_paged_attend.py) ...
_STEP_ROWS = 2048
# ... and a slot's last pages, short of a step, go in narrow ones
_TAIL_ROWS = 1024


def _pages(bs: int, hkv: int, hd: int, itemsize: int, mb: int):
    """(pages a tail step, pages a step, pages a work item) for a page
    of [bs, hkv, hd] and block tables of ``mb`` columns: each a multiple
    of the one before."""
    rows = bs * hkv
    tail = max(1, min(_TAIL_ROWS // rows, mb))
    step = tail * max(1, min(_STEP_ROWS // (tail * rows), -(-mb // tail)))
    item = step * max(1, min(_STEP_BYTES // (step * rows * hd * itemsize),
                             -(-mb // step)))
    return tail, step, item


def supported(hkv: int, hd: int, dtype) -> bool:
    """Whether a pool of ``hkv`` heads of ``hd`` in ``dtype`` is one the
    kernel reads as it lies: rows of whole 128-lane tiles, and kv heads
    that fill a tile's sublanes, so that a page is contiguous in HBM and
    [bs, Hkv, hd] reads as [bs * Hkv, hd] without a copy."""
    return (hd % LANES == 0 and hkv % 8 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


class PoolWalk(NamedTuple):
    """A call's work items (pool_walk). All int32."""
    slot: jax.Array      # [W] the item's slot
    col: jax.Array       # [W] its first block-table column
    n: jax.Array         # [W] its pages, 1..pages
    count: jax.Array     # [1] items to do; the rest of [W] is padding


def pool_walk(context_lens, live, planes, max_blocks: int, *,
              sliding_window: Optional[int] = None, q_pos=None) -> PoolWalk:
    """The work items of paged_attend's walk over every live slot's pool
    positions [first, context_lens) in ``planes`` ([..., bs, Hkv, hd]: K
    or V as paged_attend takes them) under block tables of
    ``max_blocks`` columns: as many columns an item as the kernel
    fetches for a pool of this shape (_pages), a slot's items in order,
    slots in order, a slot that is not ``live`` (or holds nothing) none.
    Under a window ``first`` is the first page a
    query at ``q_pos`` can reach (default ``context_lens``: a chunk's
    first pass; its later passes see less); the kernel's mask is the
    exact cut."""
    block_size, hkv, hd = planes.shape[-3:]
    pages = _pages(block_size, hkv, hd, planes.dtype.itemsize,
                   max_blocks)[-1]
    r = context_lens.shape[0]
    cl = jnp.where(live, context_lens, 0).astype(jnp.int32)
    first = jnp.zeros_like(cl)
    if sliding_window is not None:
        q_pos = cl if q_pos is None else q_pos.astype(jnp.int32)
        first = jnp.clip(q_pos - sliding_window + 1, 0, cl) // block_size
    n_pages = -(-cl // block_size) - first
    n_items = -(-n_pages // pages)
    ends = jnp.cumsum(n_items)
    w = jnp.arange(r * -(-max_blocks // pages), dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, w, side="right"),
                       r - 1).astype(jnp.int32)
    j = w - (ends - n_items)[slot]
    return PoolWalk(slot, first[slot] + j * pages,
                    jnp.clip(n_pages[slot] - j * pages, 0, pages),
                    ends[-1:])


def _split3(p):
    """float32 ``p`` as three bf16 terms whose sum is ``p``: 8 mantissa
    bits a term, so the MXU's bf16 products with a bf16 V are exact and
    their float32 sum is the float32 product."""
    hi = p.astype(jnp.bfloat16)
    r1 = p - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _batch(x):
    """dot_general's batch dimensions for operands [..., rows, cols]."""
    lead = tuple(range(x.ndim - 2))
    return lead, lead


def _pv(p, v):
    """``p`` [..., H, S] float32 times ``v`` [..., S, hd] as stored, in
    float32."""
    dims = (((p.ndim - 1,), (v.ndim - 2,)), _batch(p))
    if v.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            p, v.astype(jnp.float32), dims,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    h = p.shape[-2]
    # the three terms ride one product, so V's rows load once
    out = jax.lax.dot_general(
        jnp.concatenate(_split3(p), axis=-2), v, dims,
        preferred_element_type=jnp.float32)
    return (out[..., :h, :] + out[..., h:2 * h, :] + out[..., 2 * h:, :])


def _scores(q, k, scale):
    """All query heads [..., H, hd] against ``k``'s rows [..., S, hd] as
    they lie: [..., H, S]."""
    both_bf16 = q.dtype == jnp.bfloat16 and k.dtype == jnp.bfloat16
    return jax.lax.dot_general(
        q, k, (((q.ndim - 1,), (k.ndim - 1,)), _batch(q)),
        preferred_element_type=jnp.float32,
        precision=None if both_bf16 else jax.lax.Precision.HIGHEST) * scale


def _div(x, n: int):
    """``x // n`` for x >= 0: a shift where n is a power of two (the
    VPU has no integer divide)."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1)
    return x // n


def _kernel(slot_ref, col_ref, n_ref, count_ref, bt_ref, len_ref, qpos_ref,
            misc_ref, q_ref, *refs, bs, hkv, g, mb, plan, side_rows, scale,
            window):
    if side_rows:
        sk_ref, sv_ref, k_hbm, v_hbm, o_ref = refs[:5]
    else:
        k_hbm, v_hbm, o_ref = refs[:3]
    kbuf, vbuf, sem, m_scr, l_scr, acc_scr = refs[-6:]
    r, h, _ = q_ref.shape
    page = bs * hkv                      # rows of one page
    tail_pages, step_pages, pages = plan
    plane, t = misc_ref[0], misc_ref[1]
    count = count_ref[0]

    def page_copy(w, b, i, hbm, buf, which):
        blk = bt_ref[slot_ref[w] * mb + col_ref[w] + i]
        return pltpu.make_async_copy(
            hbm.at[plane, blk], buf.at[b, pl.ds(i * page, page)],
            sem.at[which, b])

    def start(w, b):
        """Start item ``w``'s page copies into buffer ``b``. (Loops, not
        straight lines, here and in the side rows below: a decode
        program traces and lowers this kernel at every start of a
        worker, compile cache or not, and unrolled it cost a cell 6-10 s
        of set-up; PERF.md section 6, PR 40.)"""
        def one(i, carry):
            page_copy(w, b, i, k_hbm, kbuf, 0).start()
            page_copy(w, b, i, v_hbm, vbuf, 1).start()
            return carry
        jax.lax.fori_loop(0, n_ref[w], one, 0)

    def wait(w, b):
        """Wait for them: a DMA semaphore counts bytes, a wait takes one
        page's."""
        def one(i, carry):
            for buf, which in ((kbuf, 0), (vbuf, 1)):
                got = buf.at[b, pl.ds(0, page)]
                pltpu.make_async_copy(got, got, sem.at[which, b]).wait()
            return carry
        jax.lax.fori_loop(0, n_ref[w], one, 0)

    # rows of a buffer that no copy has written yet may hold anything,
    # and 0 x NaN is NaN: V's start as zeros (K's scores are masked)
    vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(count > 0)
    def _first():
        start(0, 0)

    def head_mask(n_rows):
        """[H, n_rows] of (the row's kv head is the query head's own,
        the row's position in its run of rows)."""
        qh = jax.lax.broadcasted_iota(jnp.int32, (h, n_rows), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (h, n_rows), 1)
        return _div(qh, g) == row - _div(row, hkv) * hkv, _div(row, hkv)

    def softmax_step(state, scores, mask, v):
        """One online-softmax step: state (m, l [..., H, 1], acc
        [..., H, hd]) over ``scores`` [..., H, S] and ``v`` [..., S, hd]."""
        m_prev, l_prev, acc = state
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        return (m_new, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + _pv(p, v))

    def put(at, state):
        m, l, acc = state
        lanes = m.shape[:-1] + (LANES,)
        m_scr[at] = jnp.broadcast_to(m, lanes)
        l_scr[at] = jnp.broadcast_to(l, lanes)
        acc_scr[at] = acc

    state = (jnp.full((r, h, 1), NEG_INF, jnp.float32),
             jnp.zeros((r, h, 1), jnp.float32),
             jnp.zeros(acc_scr.shape, jnp.float32))
    if side_rows:
        # the chunk's own rows, every slot's at once: entry j is
        # position len + j, written on pass j, real on pass t iff j <= t
        own, j = head_mask(side_rows * hkv)
        side_mask = own & (j <= t)
        if window is not None:
            side_mask &= (t - j) < window
        state = softmax_step(
            state, _scores(q_ref[...], sk_ref[...], scale), side_mask[None],
            sv_ref[...])
    put(slice(None), state)

    def steps(n_pages):
        """f(slot, buffer, first row, first position, lengths) -> one
        softmax update over ``n_pages`` pages of the buffer."""
        own, pos_in_step = head_mask(n_pages * page)

        def one(s, b, row0, pos0, length, q_pos):
            pos = pos0 + pos_in_step
            mask = own & (pos < length)
            if window is not None:
                mask &= (q_pos - pos) < window
            rows = pl.ds(pl.multiple_of(row0, tail_pages * page),
                         n_pages * page)
            put(s, softmax_step(
                (m_scr[s][:, :1], l_scr[s][:, :1], acc_scr[s]),
                _scores(q_ref[s], kbuf[b, rows], scale), mask,
                vbuf[b, rows]))
        return one
    wide, narrow = steps(step_pages), steps(tail_pages)

    def item(w, carry):
        b = w % 2

        @pl.when(w + 1 < count)
        def _next():
            start(w + 1, 1 - b)
        wait(w, b)
        s, c0, n = slot_ref[w], col_ref[w], n_ref[w]
        lens = (len_ref[s], qpos_ref[s])
        # with one width of step the wide ones take a short end too
        n_wide = (n // step_pages if tail_pages < step_pages
                  else pl.cdiv(n, step_pages))

        def wide_one(i, carry):
            wide(s, b, i * (step_pages * page),
                 (c0 + i * step_pages) * bs, *lens)
            return carry
        jax.lax.fori_loop(0, n_wide, wide_one, 0)
        if tail_pages < step_pages:
            done = n_wide * step_pages

            def narrow_one(i, carry):
                first = done + i * tail_pages
                narrow(s, b, first * page, (c0 + first) * bs, *lens)
                return carry
            jax.lax.fori_loop(0, pl.cdiv(n - done, tail_pages), narrow_one,
                              0)
        return carry
    jax.lax.fori_loop(0, count, item, 0)

    l = l_scr[...][:, :, :1]
    o_ref[...] = jnp.where(
        l > 0, acc_scr[...] / jnp.where(l > 0, l, 1.0), 0.0
    ).astype(o_ref.dtype)


def paged_attend(q, k_planes, v_planes, plane, block_tables, context_lens,
                 q_pos, walk: PoolWalk, side=None, *,
                 sliding_window: Optional[int] = None,
                 scale: Optional[float] = None, interpret: bool = False):
    """One query token a slot over the pool's positions
    [0, context_lens) of plane ``plane`` and, with ``side`` =
    (side_k, side_v, t), over the chunk's own rows.

    q [R, 1, H, hd]; k_planes, v_planes [L, NB, bs, Hkv, hd], read where
    they lie; plane: int32 scalar (traced under a layer scan; a looped
    model's ``u * L + l``); block_tables [R, MB]; context_lens [R]: the
    pool's horizon; q_pos [R]: each query's position (the window's
    anchor); walk: pool_walk(...) of the same lengths, planes, table
    width and window. side_k, side_v
    [R, K, Hkv, hd]: this layer's rows of the chunk's side buffers;
    entry j is position context_lens + j, real for j <= t (int32
    scalar: the chunk's pass). (The layer's rows and not the side stack
    with the plane's index: handed the stack, XLA moved all of it into
    VMEM and back around every layer's call, 32 MiB a layer at
    mistral-7b; PERF.md section 6, PR 40.) A slot the walk leaves out
    attends its side rows alone (zeros without them). Returns
    [R, 1, H, hd] in q.dtype."""
    bs, hkv, hd = k_planes.shape[2:]
    # the plan is a static argument: a program lowers the kernel once
    # however many layers' bodies call it (jit's cache), and a plan set
    # by hand (tests, the microbenchmark's sweep) is traced anew
    return _paged_attend(
        q, k_planes, v_planes, plane, block_tables, context_lens, q_pos,
        walk, side, sliding_window=sliding_window,
        scale=float(hd ** -0.5) if scale is None else scale,
        interpret=interpret,
        plan=_pages(bs, hkv, hd, k_planes.dtype.itemsize,
                    block_tables.shape[1]))


@functools.partial(jax.jit, static_argnames=(
    "sliding_window", "scale", "interpret", "plan"))
def _paged_attend(q, k_planes, v_planes, plane, block_tables, context_lens,
                  q_pos, walk, side, *, sliding_window, scale, interpret,
                  plan):
    r, one, h, hd = q.shape
    assert one == 1, "paged_attend takes exactly one query token a slot"
    n_planes, nb, bs, hkv, _ = k_planes.shape
    g = h // hkv
    mb = block_tables.shape[1]
    pages = plan[-1]

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    operands, in_specs = [q.reshape(r, h, hd)], [whole(r, h, hd)]
    side_rows, t = 0, 0
    if side is not None:
        side_k, side_v, t = side
        side_rows = side_k.shape[1]
        # [K, Hkv] -> K * Hkv rows, [bs, Hkv] -> bs * Hkv below: the
        # same bytes where the heads fill a tile's sublanes (supported),
        # so a bitcast and not a copy
        rows = (r, side_rows * hkv, hd)
        operands += [side_k.reshape(rows), side_v.reshape(rows)]
        in_specs += [whole(*rows)] * 2
    flat = (n_planes, nb, bs * hkv, hd)
    operands += [k_planes.reshape(flat), v_planes.reshape(flat)]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    kernel = functools.partial(
        _kernel, bs=bs, hkv=hkv, g=g, mb=mb, plan=plan, side_rows=side_rows,
        scale=scale, window=sliding_window)

    def i32(x):
        return jnp.asarray(x, jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8, grid=(1,), in_specs=in_specs,
            out_specs=whole(r, h, hd),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs * hkv, hd), k_planes.dtype),
                pltpu.VMEM((2, pages * bs * hkv, hd), v_planes.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((r, h, LANES), jnp.float32),   # running max
                pltpu.VMEM((r, h, LANES), jnp.float32),   # denominator
                pltpu.VMEM((r, h, hd), jnp.float32),      # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((r, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_pool_attend",
    )(walk.slot, walk.col, walk.n, walk.count,
      i32(block_tables).reshape(-1), i32(context_lens), i32(q_pos),
      jnp.stack([i32(plane), i32(t)]), *operands)
    return out[:, None]


def paged_flash_decode(
    q,                    # [R, 1, H, hd] — one query token per slot
    k_pool,               # [NB, bs, Hkv, hd] — one layer's block pool
    v_pool,               # [NB, bs, Hkv, hd]
    block_tables,         # [R, MB] int32 — pool block ids per slot
    context_lens,         # [R] int32 — fill AFTER this token's write
    *,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
):
    """Paged single-token attention without gather materialization: the
    stepwise path's entry on ``paged_attend`` (a one-plane stack, no side
    rows; the query's own K and V are in the pool, at context_lens - 1)."""
    walk = pool_walk(
        context_lens, context_lens > 0, k_pool, block_tables.shape[1],
        sliding_window=sliding_window, q_pos=context_lens - 1)
    return paged_attend(
        q, k_pool[None], v_pool[None], 0, block_tables, context_lens,
        context_lens - 1, walk, sliding_window=sliding_window,
        interpret=interpret)
