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
- A page's rows are (position, kv head) pairs, 128 or 256 of them (64
  at 4 heads as an axis: an (8, 128) tile of rows is then two
  positions' heads, where XLA stores (4, 128) tiles of one position's;
  the bytes and their order are the same, ``supported``; a one-device
  batcher's pool of so few heads holds flat rows instead, below). One
  MXU product of all query heads against those rows as they lie,
  ``[H, hd] x [rows, hd]^T``, gives every (query head, kv head) pair; the
  pairs whose kv head is not the query head's own are masked to -inf, so
  their probabilities are exactly 0 and ``p @ V`` over the same rows is
  the grouped-query sum. No head is ever sliced out of a page (a strided
  sublane read) and G query heads share their kv head's rows at no cost,
  G a power of two or not.
- The mathematics is ops/attention.attend's: bf16 K and V as stored,
  float32 scores, one online softmax in float32, float32 probabilities
  times V in float32 (the probabilities go through the MXU as three bf16
  terms whose sum is the float32 value, so nothing is rounded), the
  static sliding window, the result in ``q.dtype``. The decode chunk's
  side rows (its own K and V of this and earlier passes, [K, Hkv, hd] a
  slot) start each slot's softmax state, so pool and side meet in one
  softmax inside the kernel.
- A latent pool (MLA: models/transformer.py ``_mla_absorbed``) is the
  same walk over ONE plane ``[L, NB, bs, 1, w]`` whose rows are K and V
  at once (kanana: w = 640, a page 20 KB): a page is fetched once and
  the same rows in VMEM give the scores and the weighted sum. With one
  kv head every query head owns every row, so no head mask is built.
  Steps and items are sized in bytes, so wide rows come fewer a step;
  a call whose whole-block q, output and softmax state pass Mosaic's
  scoped VMEM (kanana's 64 slots x 32 heads x 640: 21 MiB) asks for
  what it needs and starts and finishes its slots in a loop.
- Flat rows (ops/paged_kvcache.py ``flat_rows``: a one-device pool of
  fewer K/V heads than a tile has sublanes, a model with layer kinds)
  are the same walk again over K and V planes
  ``[L, NB, bs, 1, Wk]`` and ``[L, NB, bs, 1, Wv]`` whose one row a
  position holds its K/V heads side by side, a head a column offset,
  and whose widths may differ (mimo-v2.5: 4 heads of 192 and of 128,
  rows of 768 and 512 columns, a page 24 KB + 16 KB; falcon-h1: 4 of
  128 and of 128, rows of 512 under 20 query heads, 5 a K/V head).
  Pages, steps, items
  and the two buffers are sized from each plane's own row bytes. The
  query comes zero-expanded to a K row (each query head's values in its
  own K/V head's columns), so the one product ``[H, Wk] x [rows, Wk]^T``
  is the head-by-head scores and no head mask is built; ``p @ V`` is
  taken a K/V head at a time, that head's query heads against its own
  ``v_head_dim`` columns of the rows (whole lanes: a static, aligned
  slice), so the softmax state and the output are ``[R, H,
  v_head_dim]`` and the other heads' columns are never multiplied.
  (Picked outside the call instead, as the XLA form picks, the state
  and the q and output blocks are as wide as a V row: 34 MB of VMEM at
  mimo-v2.5's 64 slots x 64 heads against 25 here, four times the
  accumulator's traffic a step and four times the streamed rows of
  ``p @ V``; PERF.md section 6, PR 46.)

The decode chunk calls ``paged_attend``, the one entry
(models/transformer.py ``_pool_kernel`` says where).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llm_inferencing_tpu.utils.profiler import pallas_call_site

NEG_INF = -1e30
LANES = 128

# K and V bytes (every plane's) fetched a work item; two items are in VMEM
# at a time
_ITEM_BYTES = 1024 * 1024
# K and V bytes of a compute step, one softmax update over [H, rows]
# scores: the MXU products of a step are independent, the steps are a
# chain, so wide steps are what streams (K and V heads of 128 in bf16:
# 2048 rows, 69-81 % of a v5e's HBM peak, 512: 49-54 %;
# scripts/bench_paged_attend.py). In bytes and not in rows, since a row's
# arithmetic and its room in VMEM both go by its width: a latent pool's
# 640-wide rows, K and V at once, come 1024 a step ...
_STEP_BYTES = 1024 * 1024
# ... and a slot's last pages, short of a step, go in narrow ones (as
# many pages as hold this, rounded up to a power of two: whole MXU tiles
# of rows, and one bulk wait for a full item)
_TAIL_BYTES = 512 * 1024
# an item of up to this many pages starts and awaits its copies a page a
# loop iteration; a longer one (small pages: 20 KB at kanana, 50 an item)
# starts them in unrolled groups and awaits them in bulk: the scalar
# core's work a page, which runs beside no vector work, was a third of
# the latent kernel's time (PERF.md section 6, PR 42)
_LOOP_PAGES = 16
_GROUP = 8
# Mosaic's scoped VMEM on a v5e without asking; a call that needs more
# asks for what it needs (_paged_attend)
_SCOPED_VMEM = 16 * 1024 * 1024
# every slot's softmax state [R, H, hd] float32 up to this is started and
# finished as one value; a larger one a slot at a time, in a loop
_STATE_AT_ONCE = 1024 * 1024


def _pages(bs: int, hkv: int, hd: int, itemsize: int, mb: int,
           n_planes: int = 2, vw: Optional[int] = None):
    """(pages a tail step, pages a step, pages a work item) for a page
    of [bs, hkv, hd] in each of ``n_planes`` planes (``vw``: the width
    of V's rows where it is not K's, flat rows) and block tables of
    ``mb`` columns: each a multiple of the one before."""
    page = bs * hkv * itemsize * (
        hd if n_planes == 1 else hd + (hd if vw is None else vw))
    tail = min(1 << (max(_TAIL_BYTES // page, 1) - 1).bit_length(), mb)
    step = tail * max(1, min(-(-_STEP_BYTES // (tail * page)),
                             -(-mb // tail)))
    item = step * max(1, min(_ITEM_BYTES // (step * page), -(-mb // step)))
    return tail, step, item


def supported(hkv: int, hd: int, dtype) -> bool:
    """Whether a pool of ``hkv`` heads of ``hd`` in ``dtype`` is one the
    kernel reads as it lies: rows of whole 128-lane tiles, and kv heads
    that fill a tile's 8 sublanes (8, 16, ...) or divide them: 4 or 2
    (XLA stores such planes in (4, 128) or (2, 128) tiles, heads by
    lanes, and two or four consecutive ones hold one (8, 128) tile's
    bytes in the same order, bf16's packed row pairs included, since a
    pair never straddles two of them) or one head alone (MQA, a latent
    pool's shared row: the positions fill them). A page is then
    contiguous in HBM and [bs, Hkv, hd] reads as [bs * Hkv, hd] without
    a copy: a ``bitcast`` in the program's text, which
    tests/test_tpu_compile.py holds at 4 heads. 3, 6 or 12 heads are
    padded to a tile by the layout, so their flat view is a copy."""
    return (hd % LANES == 0 and (hkv % 8 == 0 or 8 % hkv == 0)
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


class PoolWalk(NamedTuple):
    """A call's work items (pool_walk). All int32."""
    slot: jax.Array      # [W] the item's slot
    col: jax.Array       # [W] its first block-table column
    n: jax.Array         # [W] its pages, 1..pages
    count: jax.Array     # [1] items to do; the rest of [W] is padding


def pool_walk(context_lens, live, planes, max_blocks: int, *,
              sliding_window: Optional[int] = None,
              n_planes: int = 2, v_planes=None) -> PoolWalk:
    """The work items of paged_attend's walk over every live slot's pool
    positions [first, context_lens) in ``planes`` ([..., bs, Hkv, hd]: K
    or V as paged_attend takes them; ``n_planes`` 1 for a latent pool,
    whose one plane is both; ``v_planes`` where V's rows are not as wide
    as K's: flat rows) under block tables of
    ``max_blocks`` columns: as many columns an item as the kernel
    fetches for a pool of this shape (_pages), a slot's items in order,
    slots in order, a slot that is not ``live`` (or holds nothing) none.
    Under a window ``first`` is the first page a
    query at ``context_lens`` can reach (a chunk's first pass; its later
    passes see less); the kernel's mask is the exact cut."""
    block_size, hkv, hd = planes.shape[-3:]
    pages = _pages(block_size, hkv, hd, planes.dtype.itemsize, max_blocks,
                   n_planes,
                   None if v_planes is None else v_planes.shape[-1])[-1]
    r = context_lens.shape[0]
    cl = jnp.where(live, context_lens, 0).astype(jnp.int32)
    first = jnp.zeros_like(cl)
    if sliding_window is not None:
        first = jnp.clip(cl - sliding_window + 1, 0, cl) // block_size
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
    if v.shape[-2] == 1:         # one row (_scores): float32 on the VPU
        return p * v.astype(jnp.float32)
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
    if k.shape[-2] == 1:
        # one row (a chunk of one pass over a one-head pool): Mosaic
        # refuses the bf16 product with a one-row operand, and the VPU's
        # float32 sum of the same products is as exact
        return jnp.sum(q.astype(jnp.float32) * k.astype(jnp.float32),
                       axis=-1, keepdims=True) * scale
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
            window, n_planes, row_heads):
    # n_planes 2: K and V planes (and side rows); 1: one plane whose rows
    # are K and V at once, fetched once into one buffer. row_heads > 1:
    # flat rows, a position's K/V heads side by side in its one row (q
    # zero-expanded to K's width, a query head's own values in its K/V
    # head's columns), V's rows perhaps narrower than K's; the state and
    # the output hold a query head's own K/V head's columns of V alone
    side, hbm, o_ref = (refs[:n_planes], refs[n_planes:2 * n_planes],
                        refs[2 * n_planes])
    bufs, (sem, m_scr, l_scr, acc_scr) = refs[-4 - n_planes:-4], refs[-4:]
    r, h, _ = q_ref.shape
    hd = o_ref.shape[-1]                 # a context's width
    page = bs * hkv                      # rows of one page
    tail_pages, step_pages, pages = plan
    plane, t = misc_ref[0], misc_ref[1]
    count = count_ref[0]
    at_once = r * h * hd * 4 <= _STATE_AT_ONCE

    # an item of many small pages starts its copies in unrolled groups
    # and awaits them in bulk (_LOOP_PAGES)
    grouped = pages > _LOOP_PAGES

    def start(w, b):
        """Start item ``w``'s page copies into buffer ``b``. (Loops, not
        straight lines, here and in the side rows below: a decode
        program traces and lowers this kernel at every start of a
        worker, compile cache or not, and unrolled it cost a cell 6-10 s
        of set-up; PERF.md section 6, PR 40.)"""
        def first():
            return slot_ref[w] * mb + col_ref[w]
        if grouped:           # the item's first table entry, read once
            at, first = first(), lambda: at

        def one(i, carry):
            for which in range(n_planes):
                pltpu.make_async_copy(
                    hbm[which].at[plane, bt_ref[first() + i]],
                    bufs[which].at[b, pl.ds(i * page, page)],
                    sem.at[which, b]).start()
            return carry
        n = n_ref[w]
        if not grouped:
            jax.lax.fori_loop(0, n, one, 0)
            return

        def group(j, carry):
            for u in range(_GROUP):
                one(j * _GROUP + u, carry)
            return carry
        jax.lax.fori_loop(0, n // _GROUP, group, 0)
        jax.lax.fori_loop(n // _GROUP * _GROUP, n, one, 0)

    def wait(w, b):
        """Wait for them: a DMA semaphore counts bytes, a wait takes as
        many as its descriptor holds: a page's, or (many small pages)
        those of 1, 2, 4, ... pages, one wait a set bit of the item's
        page count."""
        def pages_wait(n_pages):
            for which in range(n_planes):
                got = bufs[which].at[b, pl.ds(0, n_pages * page)]
                pltpu.make_async_copy(got, got, sem.at[which, b]).wait()

        if not grouped:
            def one(i, carry):
                pages_wait(1)
                return carry
            jax.lax.fori_loop(0, n_ref[w], one, 0)
            return
        for bit in (1 << i for i in range(pages.bit_length())):
            pl.when((n_ref[w] & bit) != 0)(
                functools.partial(pages_wait, bit))

    # rows of a buffer that no copy has written yet may hold anything,
    # and 0 x NaN is NaN: V's start as zeros (K's scores are masked)
    bufs[-1][...] = jnp.zeros_like(bufs[-1])

    @pl.when(count > 0)
    def _first():
        start(0, 0)

    def head_mask(n_rows):
        """[H, n_rows] of (the row's kv head is the query head's own,
        the row's position in its run of rows). With one kv head every
        query head owns every row: no mask (None) is built."""
        if hkv == 1:
            return None, jax.lax.broadcasted_iota(jnp.int32, (h, n_rows), 1)
        qh = jax.lax.broadcasted_iota(jnp.int32, (h, n_rows), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (h, n_rows), 1)
        if g & (g - 1) == 0:
            return _div(qh, g) == row - _div(row, hkv) * hkv, _div(row, hkv)
        # a group that is no power of two (falcon-h1: 20 heads over 4),
        # by products: the VPU has no integer divide
        first = (row - _div(row, hkv) * hkv) * g
        return (first <= qh) & (qh < first + g), _div(row, hkv)

    def both(own, mask):
        return mask if own is None else own & mask

    def pv(p, v):
        if row_heads == 1:
            return _pv(p, v)
        # flat rows: a query head's context is its own K/V head's
        # columns of the row, whole lanes of it; the other heads' columns
        # are never multiplied
        n = h // row_heads
        return jnp.concatenate(
            [_pv(p[..., j * n:(j + 1) * n, :], v[..., j * hd:(j + 1) * hd])
             for j in range(row_heads)], axis=-2)

    def softmax_step(state, scores, mask, v):
        """One online-softmax step: state (m, l [..., H, 1], acc
        [..., H, hd]) over ``scores`` [..., H, S] and ``v`` [..., S, hd]
        (flat rows: [..., S, row_heads * hd])."""
        m_prev, l_prev, acc = state
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        return (m_new, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv(p, v))

    def put(at, state):
        m, l, acc = state
        lanes = m.shape[:-1] + (LANES,)
        m_scr[at] = jnp.broadcast_to(m, lanes)
        l_scr[at] = jnp.broadcast_to(l, lanes)
        acc_scr[at] = acc

    def get(ref, at):
        """Slot ``at``'s block of ``ref``, or (None) all of it."""
        return ref[...] if at is None else ref[at]

    def slots(lead):
        """The softmax state before any row, and the slots it is for:
        all at once (``lead`` = (r,)) or one (())."""
        return (jnp.full(lead + (h, 1), NEG_INF, jnp.float32),
                jnp.zeros(lead + (h, 1), jnp.float32),
                jnp.zeros(lead + (h, hd), jnp.float32))

    def begin(at, state):
        """Slot ``at`` (None: every slot at once) starts from the
        chunk's own rows: entry j is position len + j, written on pass
        j, real on pass t iff j <= t."""
        own, j = head_mask(side_rows * hkv)
        side_mask = both(own, j <= t)
        if window is not None:
            side_mask &= (t - j) < window
        put(slice(None) if at is None else at, softmax_step(
            state, _scores(get(q_ref, at), get(side[0], at), scale),
            side_mask[None] if at is None else side_mask,
            get(side[-1], at)))

    if at_once:
        begin(None, slots((r,)))
    else:
        def begin_one(s, carry):
            begin(s, slots(()))
            return carry
        jax.lax.fori_loop(0, r, begin_one, 0)

    def steps(n_pages):
        """f(slot, buffer, first row, first position, lengths) -> one
        softmax update over ``n_pages`` pages of the buffer."""
        own, pos_in_step = head_mask(n_pages * page)

        def one(s, b, row0, pos0, length, q_pos):
            pos = pos0 + pos_in_step
            mask = both(own, pos < length)
            if window is not None:
                mask &= (q_pos - pos) < window
            rows = pl.ds(pl.multiple_of(row0, tail_pages * page),
                         n_pages * page)
            state = (m_scr[s][:, :1], l_scr[s][:, :1], acc_scr[s])
            q, k = q_ref[s], bufs[0][b, rows]
            scores = _scores(q, k, scale)
            # one plane: the rows just read are V too
            put(s, softmax_step(
                state, scores, mask,
                k if n_planes == 1 else bufs[1][b, rows]))
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

    def finish(at):
        l = get(l_scr, at)[..., :1]
        o_ref[... if at is None else at] = jnp.where(
            l > 0, get(acc_scr, at) / jnp.where(l > 0, l, 1.0), 0.0
        ).astype(o_ref.dtype)

    if at_once:
        finish(None)
    else:
        def finish_one(s, carry):
            finish(s)
            return carry
        jax.lax.fori_loop(0, r, finish_one, 0)


def paged_attend(q, k_planes, v_planes, plane, block_tables, context_lens,
                 q_pos, walk: PoolWalk, side, *,
                 sliding_window: Optional[int] = None,
                 scale: Optional[float] = None,
                 v_head_dim: Optional[int] = None, interpret: bool = False):
    """One query token a slot over the pool's positions
    [0, context_lens) of plane ``plane`` and over the chunk's own rows,
    ``side`` = (side_k, side_v, t).

    q [R, 1, H, hd]; k_planes, v_planes [L, NB, bs, Hkv, hd], read where
    they lie; plane: int32 scalar (traced under a layer scan; a looped
    model's ``u * L + l``; a constant where layers are held one by one,
    which goes in as an array all the same, so that every layer's call
    is one trace and one lowering); block_tables [R, MB]; context_lens
    [R]: the pool's horizon; q_pos [R]: each query's position (the
    window's anchor); walk: pool_walk(...) of the same lengths, planes,
    table width and window. side_k, side_v
    [R, K, Hkv, hd]: this layer's rows of the chunk's side buffers;
    entry j is position context_lens + j, real for j <= t (int32
    scalar: the chunk's pass). (The layer's rows and not the side stack
    with the plane's index: handed the stack, XLA moved all of it into
    VMEM and back around every layer's call, 32 MiB a layer at
    mistral-7b; PERF.md section 6, PR 40.) A slot the walk leaves out
    attends its side rows alone.

    A latent pool (``v_planes is k_planes``, and ``side_v is side_k``):
    one plane [L, NB, bs, 1, w] whose rows are K and V at once. A page
    is fetched once and the same rows in VMEM give the scores and the
    weighted sum, so the rows cross HBM once for both. ``q`` may be
    narrower than ``w`` (MLA's rd + r of a lane_width row): the pool's
    columns past it are zeros and so are q's; the context comes back
    ``w`` wide. Pass ``scale``: the default is the row's width's.

    Flat rows (``v_head_dim``; ops/paged_kvcache.flat_rows): k_planes
    [L, NB, bs, 1, Wk], v_planes [L, NB, bs, 1, Wv], a position's K/V
    heads side by side in its one row of each, ``Wv // v_head_dim`` of
    them, Wk and Wv whole lanes, the same (falcon-h1) or not
    (mimo-v2.5); side_k and side_v as wide as their planes' rows. ``q``
    [R, 1, H, Wk] comes zero-expanded (each query head's values in its
    own K/V head's columns, zeros in the others': one contraction over
    the row is then that head's scores), ``scale`` is the head's own,
    and of ``p @ V`` a query head keeps its own K/V head's
    ``v_head_dim`` columns: the context comes back [R, 1, H,
    v_head_dim].

    Returns [R, 1, H, hd] in q.dtype."""
    bs, hkv, hd = k_planes.shape[2:]
    row_heads = 1
    if v_head_dim is not None:
        assert hkv == 1 and v_head_dim % LANES == 0 and scale is not None
        row_heads = v_planes.shape[-1] // v_head_dim
        assert q.shape[2] % row_heads == 0, (q.shape, row_heads)
    if q.shape[-1] < hd:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, hd - q.shape[-1])])
    if v_planes is k_planes:
        v_planes = None
        assert side[1] is side[0], "one plane has one side buffer"
        side = (side[0], None, side[2])
    # the plan is a static argument: a program lowers the kernel once
    # however many layers' bodies call it (jit's cache), and a plan set
    # by hand (tests, the microbenchmark's sweep) is traced anew
    return _paged_attend(
        q, k_planes, v_planes, jnp.asarray(plane, jnp.int32), block_tables,
        context_lens, q_pos, walk, side, sliding_window=sliding_window,
        scale=float(hd ** -0.5) if scale is None else scale,
        interpret=interpret, row_heads=row_heads,
        plan=_pages(bs, hkv, hd, k_planes.dtype.itemsize,
                    block_tables.shape[1],
                    *((1,) if v_planes is None
                      else (2, v_planes.shape[-1]))))


@functools.partial(jax.jit, static_argnames=(
    "sliding_window", "scale", "interpret", "plan", "row_heads"))
def _paged_attend(q, k_planes, v_planes, plane, block_tables, context_lens,
                  q_pos, walk, side, *, sliding_window, scale, interpret,
                  plan, row_heads=1):
    r, one, h, hd = q.shape
    assert one == 1, "paged_attend takes exactly one query token a slot"
    planes = [p for p in (k_planes, v_planes) if p is not None]
    n_planes, nb, bs, hkv, _ = k_planes.shape
    # a row's width, plane by plane (K's is q's; flat rows: V's is its
    # own), and a context's: a row of V, or of flat rows one head's part
    widths = [p.shape[-1] for p in planes]
    out_w = widths[-1] // row_heads
    g = h // hkv
    mb = block_tables.shape[1]
    pages = plan[-1]
    rows_bytes = bs * hkv * sum(widths) * k_planes.dtype.itemsize
    # what the call holds in VMEM: q, the side rows and the output as
    # whole blocks (the pipeline keeps two of each), two items a plane,
    # the softmax state
    vmem = (2 * r * h * (hd + out_w) * q.dtype.itemsize
            + 2 * pages * rows_bytes + 4 * r * h * (out_w + 2 * LANES))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    operands, in_specs = [q.reshape(r, h, hd)], [whole(r, h, hd)]
    side_k, side_v, t = side
    side_rows = side_k.shape[1]
    # [K, Hkv] -> K * Hkv rows, [bs, Hkv] -> bs * Hkv below: the same
    # bytes where the heads fill a tile's sublanes or divide them
    # (supported), so a bitcast and not a copy
    sides = [s_ for s_ in (side_k, side_v) if s_ is not None]
    operands += [s_.reshape(r, side_rows * hkv, w)
                 for s_, w in zip(sides, widths)]
    in_specs += [whole(r, side_rows * hkv, w) for w in widths]
    vmem += 2 * r * side_rows * hkv * sum(widths) * q.dtype.itemsize
    operands += [p.reshape(n_planes, nb, bs * hkv, w)
                 for p, w in zip(planes, widths)]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(planes)
    kernel = functools.partial(
        _kernel, bs=bs, hkv=hkv, g=g, mb=mb, plan=plan, side_rows=side_rows,
        scale=scale, window=sliding_window, n_planes=len(planes),
        row_heads=row_heads)

    def i32(x):
        return jnp.asarray(x, jnp.int32)
    pallas_call_site()   # utils/profiler.py: counted as traced
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8, grid=(1,), in_specs=in_specs,
            out_specs=whole(r, h, out_w),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs * hkv, w), p.dtype)
                for p, w in zip(planes, widths)] + [
                pltpu.SemaphoreType.DMA((len(planes), 2)),
                pltpu.VMEM((r, h, LANES), jnp.float32),   # running max
                pltpu.VMEM((r, h, LANES), jnp.float32),   # denominator
                pltpu.VMEM((r, h, out_w), jnp.float32),   # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((r, h, out_w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # (kanana's 64 slots of 32 heads over 640-wide rows: 21 MiB)
            vmem_limit_bytes=(None if vmem <= _SCOPED_VMEM * 3 // 4
                              else vmem * 5 // 4)),
        interpret=interpret,
        name="paged_pool_attend",
    )(walk.slot, walk.col, walk.n, walk.count,
      i32(block_tables).reshape(-1), i32(context_lens), i32(q_pos),
      jnp.stack([i32(plane), i32(t)]), *operands)
    return out[:, None]
