"""Pallas TPU kernel: GEMV/matmul over nibble-packed int4 weights.

Decode is HBM-bandwidth-bound on the weight stream, so halving the bytes
(int8 -> packed int4) should halve step time — but XLA cannot fuse the
nibble unpack into a dot-operand read: every XLA formulation tried
(interleave, 2-axis contraction, split matmuls, native-S4 bitcast)
materializes the unpacked weights to HBM first, which makes int4 2-5x
SLOWER than int8 at model scale. Hence this kernel: stream the packed
[din/2, tile] uint8 tile into VMEM, unpack on the VPU, and feed the MXU
— nothing unpacked ever touches HBM. Measured on a v5e chip (chained
6400x6400 GEMVs, RTT-corrected): int8 XLA 0.0513 ms (799 GB/s, the
roofline), this kernel 0.0277 ms — **1.85x faster**, 741 GB/s effective
on the packed bytes.

Packing is split-half along din — byte row i holds din rows i (low
nibble) and i + din/2 (high) — so unpacking needs NO interleave: the two
nibble planes each feed their own MXU dot against the matching half of
x. Nibbles are stored BIASED (value + 8, i.e. 0..15): the bf16 fast
path unpacks with just AND / SHIFT / convert and folds the -8 bias into
one per-row correction term ``8 * sum(x)`` (exact: bf16 x nibble
products are <= 12 mantissa bits, accumulated in f32). For non-bf16
activations the MXU would truncate x to bf16 inside the dot while the
f32 correction sum would not, so that path sign-extends the nibbles
instead (2 extra VPU ops, still 1.4x over int8) and needs no
correction.

The reference has no counterpart at any level (SURVEY.md §2.5 — its
native compute was vendored torch/CUDA kernels behind HF generate).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributed_llm_inferencing_tpu.utils.profiler import pallas_call_site

# Only the decode-shaped path belongs here: at prefill (many rows per
# weight read) XLA's materialize-once strategy is the right one, and the
# fallback in ops/quant.py handles it.
MAX_PALLAS_ROWS = 32

# Budget for one packed weight tile. What Mosaic allocates is about six
# times the tile: two DMA buffers of it plus the int32 widening and the
# two unpacked nibble planes (compiled for a v5e at din=14336: a 3.7 MB
# tile needed 21 MB of scoped VMEM against the 16 MB limit), so the tile
# stays under 2.5 MB.
_TILE_BYTES_BUDGET = 5 * 512 * 1024


def _biased_kernel(x_ref, w_ref, s_ref, o_ref):
    p = w_ref[:].astype(jnp.int32)                     # bytes 0..255
    lo = (p & 0xF).astype(x_ref.dtype)                 # biased nibble 0..15
    hi = (p >> 4).astype(x_ref.dtype)                  # mask-free: p < 256
    half = x_ref.shape[1] // 2
    acc = jnp.dot(x_ref[:, :half], lo, preferred_element_type=jnp.float32)
    acc += jnp.dot(x_ref[:, half:], hi, preferred_element_type=jnp.float32)
    corr = 8.0 * jnp.sum(x_ref[:].astype(jnp.float32), axis=1, keepdims=True)
    o_ref[:] = ((acc - corr) * s_ref[:]).astype(o_ref.dtype)


def _signed_kernel(x_ref, w_ref, s_ref, o_ref):
    p = w_ref[:].astype(jnp.int32)
    lo = ((p & 0xF) - 8).astype(x_ref.dtype)           # unbias in the VPU
    hi = ((p >> 4) - 8).astype(x_ref.dtype)
    half = x_ref.shape[1] // 2
    acc = jnp.dot(x_ref[:, :half], lo, preferred_element_type=jnp.float32)
    acc += jnp.dot(x_ref[:, half:], hi, preferred_element_type=jnp.float32)
    o_ref[:] = (acc * s_ref[:]).astype(o_ref.dtype)


def _pick_tile(din: int) -> int:
    """Output-column tile: as wide as the VMEM budget allows. The grid is
    a ceil-div — Mosaic pads the final partial block and drops the
    out-of-bounds store, so dout need not divide."""
    tile = 512
    while (din // 2) * tile > _TILE_BYTES_BUDGET and tile > 128:
        tile //= 2
    return tile


def _mode() -> str:
    return os.environ.get("DLI_INT4_PALLAS", "auto")


def supported(rows: int, din: int, dout: int,
              row_sharded: bool = False) -> bool:
    """Trace-time gate for the pallas path. Falls back to the XLA unpack
    (ops/quant.py) when the shape or platform doesn't fit: prefill-sized
    row counts, odd dims, a non-TPU backend, or a ROW-parallel
    (contraction-axis-sharded) weight in a multi-device program.

    The kernel carries a GSPMD/shardy partitioning rule (see
    ``_q4_matmul_p``) that shards the OUTPUT channel axis, so
    column-parallel leaves (q/k/v/up/gate, untied lm_head — the
    megatron layout in parallel/sharding.py) run the kernel per-shard on
    tp meshes. A din-sharded (row-parallel: o/down) leaf would force the
    partitioner to all-gather the weight to satisfy the rule — worse
    than the XLA unpack — and the split-half packing means its shards
    don't unpack to contiguous din ranges anyway, so those leaves keep
    the XLA path when tp > 1 (models/transformer.py threads the hint).

    ``DLI_INT4_PALLAS``: ``never`` forces the XLA fallback everywhere;
    ``interpret`` runs the kernel in pallas interpret mode on any
    backend (CPU-mesh dryruns/tests of the partitioned path); ``auto``
    (default) uses the kernel on TPU. (The historical ``always``
    override predates the partitioning rule and now means ``auto``.)
    """
    mode = _mode()
    if mode == "never":
        return False
    return (
        rows <= MAX_PALLAS_ROWS
        and din % 2 == 0
        and din // 2 >= 32            # int8 sublane tile
        and dout >= 128               # lane width
        and not row_sharded
        and (jax.default_backend() == "tpu" or mode == "interpret")
    )


def _q4_pallas(x, p4, scale, interpret: bool):
    """The raw pallas call: x [b, din] (b pre-padded to the sublane
    tile), p4 [din//2, dout], scale [dout]."""
    b, din = x.shape
    dout = p4.shape[-1]
    tile_o = _pick_tile(din)
    kernel = _biased_kernel if x.dtype == jnp.bfloat16 else _signed_kernel
    pallas_call_site()   # utils/profiler.py: counted as traced
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(dout, tile_o),),
        in_specs=[
            pl.BlockSpec((b, din), lambda o: (0, 0)),
            pl.BlockSpec((din // 2, tile_o), lambda o: (0, o)),
            pl.BlockSpec((1, tile_o), lambda o: (0, o)),
        ],
        out_specs=pl.BlockSpec((b, tile_o), lambda o: (0, o)),
        out_shape=jax.ShapeDtypeStruct((b, dout), x.dtype),
        interpret=interpret,
    )(x, p4, scale.reshape(1, dout).astype(jnp.float32))


# ---- GSPMD/shardy partitioning -----------------------------------------
#
# Factors: m = rows, k = din, h = din//2 (the packed axis), n = dout.
# k and h must be replicated (one kernel instance needs the full
# contraction); m and n may shard freely — n over tp is the column-
# parallel case the kernel exists for (llama-8B tp / 70B pp+tp regimes).
# The partition callback re-lowers the SAME pallas call on the local
# shard: the grid is a ceil-div over the local dout and Mosaic pads the
# final block, so any per-shard dout >= 128 works.

from jax.experimental.custom_partitioning import (  # noqa: E402
    custom_partitioning)
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


def _spec_of(shape_with_sharding):
    sh = getattr(shape_with_sharding, "sharding", None)
    spec = getattr(sh, "spec", None)
    return tuple(spec) if spec is not None else ()


def _pad_spec(spec, rank):
    spec = tuple(spec)[:rank]
    return spec + (None,) * (rank - len(spec))


def _q4_infer(interpret, mesh, arg_shapes, result_shape):
    m = _pad_spec(_spec_of(arg_shapes[0]), 2)[0]
    n = _pad_spec(_spec_of(arg_shapes[1]), 2)[1]
    return NamedSharding(mesh, P(m, n))


def _q4_partition(interpret, mesh, arg_shapes, result_shape):
    m = _pad_spec(_spec_of(arg_shapes[0]), 2)[0]
    n = _pad_spec(_spec_of(arg_shapes[1]), 2)[1]
    arg_shardings = (
        NamedSharding(mesh, P(m, None)),     # x: contraction replicated
        NamedSharding(mesh, P(None, n)),     # p4: dout sharded
        NamedSharding(mesh, P(n)),           # scale follows dout
    )
    out_sharding = NamedSharding(mesh, P(m, n))

    def lower(x, p4, scale):
        return _q4_pallas(x, p4, scale, interpret)

    return mesh, lower, out_sharding, arg_shardings


@functools.partial(custom_partitioning, static_argnums=(3,))
def _q4_matmul_p(x, p4, scale, interpret):
    return _q4_pallas(x, p4, scale, interpret)


_q4_matmul_p.def_partition(
    partition=_q4_partition,
    infer_sharding_from_operands=_q4_infer,
    sharding_rule="m k, h n, n -> m n",
    need_replication_factors=("k", "h"))


# ---- row-parallel (din-sharded) variant --------------------------------
#
# For the megatron row-parallel leaves (o/down under tp) the weight's
# CONTRACTION axis is sharded. With the leaf repacked chunk-locally
# (ops/quant.py repack_int4_rows, chunk count == the axis size), each
# shard's p4 slice is a self-contained split-half packing of its own din
# rows, so the local lowering is the SAME pallas kernel on the local
# shard followed by one psum over the sharding axis — the full megatron
# row-parallel pattern with int4 reads.


def _axis_of(spec, dim):
    if spec is None or len(spec) <= dim:
        return None
    ax = spec[dim]
    if isinstance(ax, (tuple, list)):
        return ax[0] if ax else None
    return ax


def _q4_row_infer(interpret, chunks, mesh, arg_shapes, result_shape):
    m = _pad_spec(_spec_of(arg_shapes[0]), 2)[0]
    return NamedSharding(mesh, P(m, None))


def _q4_row_partition(interpret, chunks, mesh, arg_shapes, result_shape):
    kx = _axis_of(_pad_spec(_spec_of(arg_shapes[0]), 2), 1)
    kw = _axis_of(_pad_spec(_spec_of(arg_shapes[1]), 2), 0)
    axis = kw or kx
    m = _axis_of(_pad_spec(_spec_of(arg_shapes[0]), 2), 0)
    arg_shardings = (
        NamedSharding(mesh, P(m, axis)),     # x: contraction sharded
        NamedSharding(mesh, P(axis, None)),  # p4: din chunks sharded
        NamedSharding(mesh, P(None)),        # scale replicated
    )
    out_sharding = NamedSharding(mesh, P(m, None))

    def lower(x, p4, scale):
        if axis is None:
            # nothing actually sharded the contraction: the local p4 is
            # the GLOBAL chunked layout, which the kernel's split-half
            # assumption does not match — use the chunk-aware unpack
            from distributed_llm_inferencing_tpu.ops.quant import (
                unpack_int4)
            w = unpack_int4(p4, chunks).astype(jnp.float32)
            return ((x.astype(jnp.float32) @ w)
                    * scale[None, :]).astype(x.dtype)
        # the per-shard chunk is a self-contained split-half pack, so
        # the plain kernel runs locally; one psum combines the partials
        return jax.lax.psum(_q4_pallas(x, p4, scale, interpret), axis)

    return mesh, lower, out_sharding, arg_shardings


@functools.partial(custom_partitioning, static_argnums=(3, 4))
def _q4_matmul_row_p(x, p4, scale, interpret, chunks):
    # unpartitioned body (single device / fully replicated): honor the
    # CHUNKED layout via the XLA unpack — the kernel's split-half
    # assumption only matches a chunked leaf per-shard, never globally.
    # Result dtype must match the partitioned lowering's (x.dtype).
    from distributed_llm_inferencing_tpu.ops.quant import unpack_int4
    w = unpack_int4(p4, chunks).astype(jnp.float32)
    return ((x.astype(jnp.float32) @ w) * scale[None, :]).astype(x.dtype)


_q4_matmul_row_p.def_partition(
    partition=_q4_row_partition,
    infer_sharding_from_operands=_q4_row_infer,
    sharding_rule="m k, h n, n -> m n",
    reduction_factors=("k", "h"))


@functools.partial(jax.jit, static_argnames=("interpret", "chunks"))
def q4_matmul_row(x, p4, scale, interpret: bool = False, chunks: int = 1):
    """Row-parallel twin of q4_matmul for CHUNK-LOCALLY packed leaves
    (ops/quant.py repack_int4_rows): x [b, din] with din (and p4's rows)
    sharded over one mesh axis; each shard runs the kernel on its
    self-contained chunk and one psum combines the partials. ``chunks``
    must equal the sharding axis size (the shard-time repack guarantees
    it, parallel/sharding.py)."""
    b, din = x.shape
    pad = (-b) % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = _q4_matmul_row_p(x, p4, scale.astype(jnp.float32), interpret,
                           chunks)
    return out[:b] if pad else out


@functools.partial(jax.jit, static_argnames=("interpret",))
def q4_matmul(x, p4, scale, interpret: bool = False):
    """x [b, din] @ unpack(p4 [din//2, dout]) * scale [dout] -> [b, dout].

    ``p4`` uses the split-half biased packing of ops/quant.py pack_int4.
    Rows are padded to the sublane tile; callers gate with supported().
    Safe inside multi-device GSPMD programs: the partitioning rule above
    shards the output-channel axis and replicates the contraction.
    """
    b, din = x.shape
    pad = (-b) % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = _q4_matmul_p(x, p4, scale.astype(jnp.float32), interpret)
    return out[:b] if pad else out


def q4_linear(x, p, row_sharded: bool = False):
    """Quantized linear over an int4 leaf ``{"p4", "scale"[, "b"]}`` with
    arbitrary leading dims on x. Dispatch:

    - chunk-local leaf (``chunked`` marker, shard-time repack of
      row-parallel o/down under tp — parallel/sharding.py): the
      row-parallel partitioned kernel (local pallas + one psum);
    - plain leaf, decode-shaped on TPU: the column-partitioned kernel;
    - otherwise the XLA unpack. ``row_sharded`` marks a din-sharded leaf
      that was NOT repacked (e.g. loaded pre-round-5 checkpoints): the
      output-axis rule would all-gather the weight, so keep XLA."""
    from distributed_llm_inferencing_tpu.ops.quant import (
        pack_chunks, unpack_int4)

    din = x.shape[-1]
    dout = p["p4"].shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    chunks = pack_chunks(p)
    if (chunks > 1 and p["p4"].ndim == 2
            and supported(rows, din // chunks, dout)):
        y = q4_matmul_row(x.reshape(rows, din), p["p4"], p["scale"],
                          interpret=_mode() == "interpret", chunks=chunks)
        y = y.reshape(*lead, dout)
    elif (chunks == 1 and p["p4"].ndim == 2
            and supported(rows, din, dout, row_sharded)):
        y = q4_matmul(x.reshape(rows, din), p["p4"], p["scale"],
                      interpret=_mode() == "interpret")
        y = y.reshape(*lead, dout)
    else:
        y = jnp.einsum("...d,df->...f", x,
                       unpack_int4(p["p4"], chunks).astype(x.dtype))
        y = y * p["scale"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"]
    return y.astype(x.dtype)
