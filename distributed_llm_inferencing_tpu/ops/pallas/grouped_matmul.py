"""Pallas TPU kernel: the experts' grouped matmul at decode size.

A decode pass of an MoE model carries a handful of rows an expert (64
tokens x top-6 over 128 experts: 3 rows on the mean, 10 at the fullest),
so a projection is bound by reading each hit expert's [D, F] weights
once from HBM. XLA's ``ragged-dot`` works in 128-row tiles and reaches
35-46 % of a v5e's HBM peak there, this kernel 87-90 % (PERF.md section
6, PR 32). It walks the experts in its grid: an expert's [D, tF] block
is brought into VMEM once by the pipeline's double buffer and multiplied
with that expert's run of ``rows`` in row tiles of one packed sublane
group (16 rows of bf16, 8 of float32), masked at the run's ends. The
grid's steps take the HIT experts in order, so an expert with an empty
run costs neither a step's work nor a read (the steps left over name the
last hit expert's block again, and the pipeline issues no DMA for an
index that has not changed). A run longer than a tile loops over tiles,
so nothing is dropped at any imbalance. Accumulation is float32 and the
result is written in the rows' dtype, as ``lax.ragged_dot`` does; a
row's result depends on no other row, and rows past the last run come
back zero.

``rows`` and the output stay whole in VMEM (a decode pass's 384 x 2048
bf16 rows are 1.5 MiB), which is what bounds the row count this form
takes: models/transformer.py:_grouped_linear takes it only where the
rows are few against the experts, and keeps ``lax.ragged_dot``
elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llm_inferencing_tpu.utils.profiler import pallas_call_site

# one expert's weight block in VMEM; the pipeline holds two. Trinity's
# [2048, 1024] bf16 gate block is exactly this.
_BLOCK_BYTES = 4 * 2 ** 20
# rows + output resident in VMEM, each double-buffered by the pipeline
_RESIDENT_BYTES = 16 * 2 ** 20


def _row_tile(dtype) -> int:
    """Rows of one packed sublane group: the unit a run is cut into."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _col_tile(d: int, f: int, itemsize: int) -> int:
    """Output-column tile: all of F where an expert's [D, F] fits the
    block budget (one contiguous DMA an expert), else the most 128-lane
    columns that do (the grid is a ceil-div; Mosaic pads the last block
    and drops its out-of-bounds store)."""
    if d * f * itemsize <= _BLOCK_BYTES:
        return f
    return max(128, _BLOCK_BYTES // (d * itemsize) // 128 * 128)


def supported(m: int, d: int, f: int, dtype) -> bool:
    """Whether the kernel's VMEM plan holds: a 128-column block of one
    expert inside the block budget, and rows + output resident."""
    size = jnp.dtype(dtype).itemsize
    tf = _col_tile(d, f, size)
    return (d * tf * size <= _BLOCK_BYTES
            and 2 * m * (d + tf) * size <= _RESIDENT_BYTES)


def _kernel(offs_ref, ids_ref, n_hit_ref, x_ref, w_ref, o_ref, *, tile):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_hit_ref[0])
    def _run():
        e = ids_ref[i]
        start, end = offs_ref[e], offs_ref[e + 1]
        first = start // tile

        def one_tile(t, carry):
            r0 = pl.multiple_of((first + t) * tile, tile)
            acc = jnp.dot(x_ref[pl.ds(r0, tile), :], w_ref[...],
                          preferred_element_type=jnp.float32)
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            mine = jnp.logical_and(row >= start, row < end)
            o_ref[pl.ds(r0, tile), :] = jnp.where(
                mine, acc.astype(o_ref.dtype), o_ref[pl.ds(r0, tile), :])
            return carry

        jax.lax.fori_loop(0, pl.cdiv(end, tile) - first, one_tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(rows, w, group_sizes, *, interpret: bool = False):
    """``lax.ragged_dot(rows, w, group_sizes)`` for few rows an expert.

    rows [M, D] sorted by expert, w [E, D, F] of the same dtype,
    group_sizes [E] int32 (their sum may fall short of M: the rows
    behind belong to no expert and come back zero). Returns [M, F] in
    the rows' dtype."""
    m, d = rows.shape
    n_exp, _, f = w.shape
    size = jnp.dtype(rows.dtype).itemsize
    tile, tf = _row_tile(rows.dtype), _col_tile(d, f, size)
    pad = -m % tile
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    mp = m + pad
    group_sizes = group_sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(group_sizes)])
    # grid step i works on the i-th HIT expert, so that the next hit
    # expert's block is always the next step's and its DMA runs under
    # this step's product (with the empty experts left in place, half of
    # them empty cost 25 % on a v5e: the read behind an empty step
    # overlaps nothing); the steps behind the last hit expert name its
    # block again and do nothing
    hit = group_sizes > 0
    ids = jnp.arange(n_exp, dtype=jnp.int32)
    rank = jnp.cumsum(hit.astype(jnp.int32)) - 1
    n_hit = rank[-1:] + 1
    hit_ids = jnp.sum(jnp.where(
        jnp.logical_and(hit[None, :], rank[None, :] == ids[:, None]),
        ids[None, :], 0), axis=1)
    hit_ids = jnp.where(ids < n_hit, hit_ids, jnp.max(jnp.where(hit, ids, 0)))
    pallas_call_site()   # utils/profiler.py: counted as traced
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        out_shape=jax.ShapeDtypeStruct((mp, f), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(f, tf), n_exp),
            in_specs=[
                pl.BlockSpec((mp, d), lambda j, i, offs, ids, n: (0, 0)),
                pl.BlockSpec((None, d, tf),
                             lambda j, i, offs, ids, n: (ids[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((mp, tf),
                                   lambda j, i, offs, ids, n: (0, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * _BLOCK_BYTES + _RESIDENT_BYTES + 8 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * d * f, transcendentals=0,
            bytes_accessed=(n_exp * d * f + mp * (d + f)) * size),
        interpret=interpret,
        name="expert_stream_matmul",
    )(offs, hit_ids, n_hit, rows, w)
    return out[:m] if pad else out
