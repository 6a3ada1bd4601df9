"""Pallas TPU kernels: flash attention (prefill) and flash decode.

TPU-native replacement for the vendored-CUDA attention inside the
reference's ``model.generate()`` hot loop (reference: worker/app.py:297-305).
Two regimes, two kernels:

- **flash_attention** (prefill, Sq == Skv): classic tiled online-softmax
  attention. Grid ``(B, H, nq, nkv)`` with the kv dimension innermost so the
  running max / denominator / accumulator live in VMEM scratch across kv
  steps. Query/key tiles hit the MXU as [bq,hd]x[hd,bkv]; softmax runs on
  the VPU in f32; causal + sliding-window masking is index arithmetic on
  broadcasted iotas. Upper-triangular kv tiles skip their FLOPs via
  ``pl.when``.
- **flash_decode** (Sq == 1 over a cached KV): bandwidth-bound streaming of
  the [S,hd] cache tiles through VMEM, one (batch, kv-head) pair per grid
  row, grouped-query heads [G,hd] resident. Tiles entirely past the
  sequence length skip their FLOPs.

Both kernels are causal-only by construction (this is an autoregressive
inference framework). GQA is handled by the index maps — kv tiles are
fetched per kv-head and queries arrive pre-grouped — so no repeat_kv
materialization happens anywhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llm_inferencing_tpu.utils.profiler import pallas_call_site

NEG_INF = -1e30


def _pick_block(n: int, target: int) -> int:
    """Largest power-of-two block <= target that divides n (n is a power of
    two in practice: engine buckets and cache sizes are powers of two)."""
    b = min(n, target)
    while n % b:
        b //= 2
    return max(b, 1)


# ----------------------------------------------------------------------
# Prefill: causal self-attention over the fresh (uncached) K/V block
# ----------------------------------------------------------------------

def _prefill_kernel(*refs, block_q: int, block_kv: int, scale: float,
                    sliding_window: Optional[int], alibi: bool):
    if alibi:
        sl_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    i, j = pl.program_id(2), pl.program_id(3)
    head = pl.program_id(1)
    nkv = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    kv_start = j * block_kv

    # Tiles strictly above the diagonal contribute nothing (causal).
    @pl.when(kv_start <= q_start + block_q - 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)            # [bkv, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bkv]

        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        if alibi:
            # linear position bias on the VPU, after scale (matching the
            # xla formulation ops/attention.py attend): this head's slope
            # arrives as an SMEM scalar, rel = kv - q is never positive
            # at attended positions
            s += sl_ref[head, 0] * (kv_pos - q_pos).astype(jnp.float32)
        mask = kv_pos <= q_pos
        if sliding_window is not None:
            mask &= (q_pos - kv_pos) < sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                           # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                 # [bq, 1]
        p = jnp.exp(s - m_new)                          # [bq, bkv]

        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)             # [bkv, hd]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, hd]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, :1] = m_new

    @pl.when(j == nkv - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0, 0] = jnp.where(
            l > 0, acc_scr[:] / jnp.where(l > 0, l, 1.0), 0.0
        ).astype(o_ref.dtype)


def flash_attention(
    q,                    # [B, Sq, H, hd]
    k,                    # [B, Sq, Hkv, hd] — the fresh per-block K
    v,                    # [B, Sq, Hkv, hd]
    *,
    sliding_window: Optional[int] = None,
    alibi=None,           # [H] f32 slopes (ops/attention.py alibi_slopes)
    block_q: int = 256,
    block_kv: int = 512,
    interpret: bool = False,
):
    """Causal flash attention for prefill (query block == kv block).

    Positions are the block-local indices 0..Sq-1 (the engine prefills from
    slot 0). Rows past a sequence's real length compute garbage that the
    caller never reads (logits are gathered at length-1) — exactly the
    semantics of ops/attention.py's reference path in prefill mode.
    ``alibi`` adds the BLOOM/Falcon-RW/MPT linear bias inside the tile
    loop (one SMEM scalar per head), so the ALiBi families run the same
    kernel as the rotary ones.
    """
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    bq = _pick_block(Sq, block_q)
    bkv = _pick_block(Sq, block_kv)
    scale = float(1.0 / (hd ** 0.5))

    qt = jnp.transpose(q, (0, 2, 1, 3))   # [B, H, Sq, hd]
    kt = jnp.transpose(k, (0, 2, 1, 3))   # [B, Hkv, Sq, hd]
    vt = jnp.transpose(v, (0, 2, 1, 3))

    grid = (B, H, Sq // bq, Sq // bkv)
    kernel = functools.partial(
        _prefill_kernel, block_q=bq, block_kv=bkv, scale=scale,
        sliding_window=sliding_window, alibi=alibi is not None)

    in_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bkv, hd),
                     lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        pl.BlockSpec((1, 1, bkv, hd),
                     lambda b, h, i, j, g=group: (b, h // g, j, 0)),
    ]
    args = (qt, kt, vt)
    if alibi is not None:
        # whole [H, 1] table in SMEM, indexed by the head's program id
        # (Mosaic refuses a (1, 1) block of it: rows must tile by 8)
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        args = (alibi.astype(jnp.float32).reshape(H, 1),) + args

    pallas_call_site()   # utils/profiler.py: counted as traced
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(*args)
    return jnp.transpose(out, (0, 2, 1, 3))


# ----------------------------------------------------------------------
# Decode: one query token per sequence against the cached K/V
# ----------------------------------------------------------------------

def _decode_kernel(*refs, block_kv: int, scale: float,
                   sliding_window: Optional[int], alibi: bool):
    if alibi:
        sl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, \
            acc_scr = refs
    else:
        len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(2)
    kv_head = pl.program_id(1)
    nkv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0), 0]   # valid kv slots: [0, length)
    kv_start = j * block_kv

    # Tiles entirely past the sequence skip their FLOPs (their DMA is the
    # price of a static grid; cache buckets keep it bounded).
    @pl.when(kv_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)            # [bkv, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, bkv]

        G = q.shape[0]
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_kv), 1)
        if alibi:
            # per-group-head slopes from SMEM (G scalar reads, G static;
            # G == 1 for the MHA ALiBi families BLOOM/Falcon-RW/MPT);
            # query position == length - 1, so rel = kv - (length-1)
            sl = jnp.stack([sl_ref[kv_head, g] for g in range(G)])[:, None]
            s += sl * (kv_pos - (length - 1)).astype(jnp.float32)
        mask = kv_pos < length          # causal: q position == length - 1
        if sliding_window is not None:
            mask &= ((length - 1) - kv_pos) < sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, :1] = m_new

    @pl.when(j == nkv - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0, 0] = jnp.where(
            l > 0, acc_scr[:] / jnp.where(l > 0, l, 1.0), 0.0
        ).astype(o_ref.dtype)


def flash_decode(
    q,                    # [B, 1, H, hd] — the new token's queries
    k,                    # [B, S, Hkv, hd] — cache (already holds the new kv)
    v,                    # [B, S, Hkv, hd]
    lengths,              # [B] int32 — cache fill AFTER this token's write
    *,
    sliding_window: Optional[int] = None,
    alibi=None,           # [H] f32 slopes (ops/attention.py alibi_slopes)
    block_kv: int = 512,
    interpret: bool = False,
):
    """Cached single-token attention (the decode hot loop).

    The query sits at position ``lengths - 1``; valid kv slots are
    ``[0, lengths)`` (slot index == absolute position, the engine's cache
    invariant — models/transformer.py ``forward`` docstring). ``alibi``
    adds the linear position bias inside the tile loop (SMEM slopes), so
    ALiBi families run this kernel too.
    """
    B, one, H, hd = q.shape
    assert one == 1, "flash_decode takes exactly one query token"
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    bkv = _pick_block(S, block_kv)
    scale = float(1.0 / (hd ** 0.5))

    qt = q.reshape(B, H, hd).reshape(B, Hkv, G, hd)
    kt = jnp.transpose(k, (0, 2, 1, 3))   # [B, Hkv, S, hd]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    len2d = lengths.reshape(B, 1).astype(jnp.int32)

    grid = (B, Hkv, S // bkv)
    kernel = functools.partial(
        _decode_kernel, block_kv=bkv, scale=scale,
        sliding_window=sliding_window, alibi=alibi is not None)

    in_specs = [
        # whole [B, 1] lengths (and [Hkv, G] slopes) in SMEM, indexed by
        # program id: Mosaic refuses a 1-row block of them (rows tile by 8)
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bkv, hd), lambda b, h, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, bkv, hd), lambda b, h, j: (b, h, j, 0)),
    ]
    args = (len2d, qt, kt, vt)
    if alibi is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        args = (alibi.astype(jnp.float32).reshape(Hkv, G),) + args

    pallas_call_site()   # utils/profiler.py: counted as traced
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out.reshape(B, H, hd)[:, None]
