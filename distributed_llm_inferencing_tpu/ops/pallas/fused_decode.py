"""Pallas TPU kernel: fused dequant-GEMV -> RoPE -> paged flash decode.

The unfused decode step runs the q projection (an int8/int4 dequant-GEMV,
ops/pallas/quant_matmul.py or the XLA einsum), RoPE, and paged attention
(ops/pallas/paged_attention.py) as separate programs: q makes a full HBM
round trip between the GEMV and the attention kernel, and each op pays
its own dispatch. Decode is bandwidth-bound, so on TPU those round trips
are pure loss — this kernel chains all three in ONE ``pallas_call``:

- grid step (slot, kv-head, 0) runs the dequant-GEMV for that kv-head's
  g query heads — the weight tile streams HBM->VMEM in its STORED form
  (int8 levels + per-output-channel scale, split-half packed int4
  nibbles, or raw float) and is dequantized on the VPU feeding the MXU,
  exactly the quant_matmul trade — then applies RoPE from precomputed
  per-slot cos/sin rows and parks q in VMEM scratch;
- grid steps (slot, kv-head, j) walk the slot's block table with the
  scalar-prefetched indices driving the K/V BlockSpec index maps
  (each step DMAs its [bs, hd] tile straight from the pool: the grid the
  paged kernel had before PR 40, a 4 KB tile a step over head-major
  copies of both planes, which is why this kernel wins no cell: ROADMAP
  S8 (a)) and accumulate online softmax over the q scratch;
- the last block normalizes and writes the [g, hd] context — q never
  touches HBM.

The parity suite runs the kernel in interpret mode on the CPU and diffs
it against the unfused XLA path (tests/test_pallas_parity.py); TPU
compiles it via Mosaic. Wired behind ``DLI_FUSED_DECODE``
(models/transformer.py paged_decode_step), with the unfused path as the
always-available differential oracle. Interpret mode is never fallen
into: ``DLI_FUSED_DECODE=interpret`` (a test's request) is the only way
the serving path interprets the kernel.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def enabled() -> bool:
    """``DLI_FUSED_DECODE=1`` opts the serving decode step into the fused
    kernel (off by default; the unfused XLA formulation stays the
    oracle). ``interpret`` also enables it, interpreted — see
    ``interpret_requested``."""
    return os.environ.get("DLI_FUSED_DECODE", "0") not in ("0", "false", "")


def interpret_requested() -> bool:
    """``DLI_FUSED_DECODE=interpret``: run the kernel in pallas interpret
    mode on any backend (CPU parity tests of the serving wiring), the
    same contract as ``DLI_INT4_PALLAS=interpret``. Anything else
    compiles via Mosaic, and fails where Mosaic is not there."""
    return os.environ.get("DLI_FUSED_DECODE") == "interpret"


def eligible(cfg, quantized_cache: bool) -> bool:
    """The ONE routing predicate both serving call sites share
    (models/transformer.py paged_decode_step dispatches the kernel,
    paged_decode_chunk flips to the stepwise formulation that reaches
    it) — a single definition so the two can never drift apart and
    silently strand the kernel behind a side-buffer chunk. How many
    devices the program spans is not asked here: the batcher knows its
    mesh and refuses the kernel for a multi-device load at construction
    (the kernel is a single-program kernel with no partitioning rule)."""
    return enabled() and not quantized_cache and supported(cfg)


def supported(cfg, q_leaf=None) -> bool:
    """Static-shape gate for the fused path: the kernel implements the
    llama-family decode step — full-width non-interleaved RoPE (or no
    positional term on q), plain per-head attention over an unquantized
    paged pool, bias-free q projection. Anything else keeps the unfused
    formulation (which is always semantically complete)."""
    if (cfg.mla or cfg.qk_norm or cfg.qkv_clip is not None
            or cfg.attn_gate):
        return False
    if cfg.attn_softcap is not None or cfg.attn_sinks:
        return False
    if cfg.position_embedding == "alibi" or cfg.attn_windows is not None:
        return False
    if cfg.position_embedding == "rope" and (
            cfg.rope_pct != 1.0 or cfg.rope_interleaved
            or cfg.rope_layers is not None):
        return False
    if cfg.v_head_dim_effective != cfg.head_dim:
        return False
    if cfg.kv_quant:
        return False
    if q_leaf is not None and "b" in q_leaf:
        return False
    return True


def rope_cos_sin(cfg, positions, head_dim: int):
    """Per-slot RoPE rotation rows for the kernel: cos/sin [R, hd] in the
    rotate-half layout (ops/rope.py apply_rope non-interleaved — the two
    halves share the [hd/2] frequency ladder), with yarn's attn_factor
    folded in. Computed OUTSIDE the kernel: it is O(R * hd) elementwise
    on data already host-adjacent, while the kernel keeps the O(R * MB)
    bandwidth-bound part."""
    from distributed_llm_inferencing_tpu.ops.rope import rope_freqs
    inv = (rope_freqs(head_dim, cfg.rope_theta)
           if cfg.rope_inv_freq is None
           else jnp.asarray(cfg.rope_inv_freq, jnp.float32))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [R, hd/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    f = cfg.rope_attn_factor
    return cos * f, sin * f


def _fused_kernel(bt_ref, len_ref, x_ref, w_ref, s_ref, cos_ref, sin_ref,
                  k_ref, v_ref, o_ref, q_scr, m_scr, l_scr, acc_scr, *,
                  block_size: int, scale: float, g: int, hd: int,
                  w_form: str, rope: bool,
                  sliding_window: Optional[int]):
    j = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    r = pl.program_id(0)
    length = len_ref[r]                 # valid kv positions: [0, length)
    kv_start = j * block_size

    @pl.when(j == 0)
    def _project():
        # dequant-GEMV: x [1, D] against this kv-head's [D, g*hd] weight
        # tile, read in its stored form and dequantized in VMEM
        x = x_ref[0].astype(jnp.float32)                  # [1, D]
        if w_form == "int4":
            # split-half biased-nibble packing (ops/quant.py pack_int4):
            # byte row i holds din rows i (low nibble) and i + din/2
            # (high); see quant_matmul._signed_kernel
            p = w_ref[:].astype(jnp.int32)
            lo = ((p & 0xF) - 8).astype(jnp.float32)
            hi = ((p >> 4) - 8).astype(jnp.float32)
            half = x.shape[1] // 2
            q = jnp.dot(x[:, :half], lo,
                        preferred_element_type=jnp.float32)
            q += jnp.dot(x[:, half:], hi,
                         preferred_element_type=jnp.float32)
            q = q * s_ref[:]
        elif w_form == "int8":
            w = w_ref[:].astype(jnp.float32)
            q = jnp.dot(x, w, preferred_element_type=jnp.float32)
            q = q * s_ref[:]
        else:
            q = jnp.dot(x, w_ref[:].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        q = q.reshape(g, hd)
        if rope:
            cos = cos_ref[0, 0].astype(jnp.float32)       # [hd]
            sin = sin_ref[0, 0].astype(jnp.float32)
            half_rot = jnp.concatenate(
                [-q[:, hd // 2:], q[:, : hd // 2]], axis=-1)
            q = q * cos[None, :] + half_rot * sin[None, :]
        q_scr[:] = q
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Block-table entries past the sequence skip their FLOPs (the DMA
    # still happens — the static grid is the price of one compiled
    # program for every slot mix).
    @pl.when(kv_start < length)
    def _compute():
        q = q_scr[:]                                      # [g, hd] f32
        k = k_ref[0, 0].astype(jnp.float32)               # [bs, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [g, bs]

        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_size), 1)
        mask = kv_pos < length          # causal: query sits at length - 1
        if sliding_window is not None:
            mask &= ((length - 1) - kv_pos) < sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)               # [bs, hd]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, :1] = m_new

    @pl.when(j == n_blocks - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0, 0] = jnp.where(
            l > 0, acc_scr[:] / jnp.where(l > 0, l, 1.0), 0.0
        ).astype(o_ref.dtype)


def fused_decode_step(
    x,                    # [R, D] — post-attn-norm hidden states
    q_leaf,               # q-projection leaf: {"w"} | {"q","scale"} | {"p4","scale"}
    k_pool,               # [NB, bs, Hkv, hd] — one layer's block pool
    v_pool,               # [NB, bs, Hkv, hd]
    block_tables,         # [R, MB] int32 — pool block ids per slot
    context_lens,         # [R] int32 — fill AFTER this token's write
    *,
    rope_cos=None,        # [R, hd] rotate-half cos rows (None: no RoPE)
    rope_sin=None,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
):
    """One fused q-projection + RoPE + paged-attention decode step.

    The current token's K/V must already be written into the pool (the
    caller's ``write_token``), so the kernel attends positions
    ``[0, context_lens)`` exactly like the unfused
    ``paged_attend_decode``. Returns attn [R, H, hd] in x.dtype.
    """
    r, d = x.shape
    nb, bs, hkv, hd = k_pool.shape
    if "p4" in q_leaf:
        w, w_form = q_leaf["p4"], "int4"
        dout = w.shape[-1]
        s = q_leaf["scale"].reshape(1, dout).astype(jnp.float32)
    elif "q" in q_leaf:
        w, w_form = q_leaf["q"], "int8"
        dout = w.shape[-1]
        s = q_leaf["scale"].reshape(1, dout).astype(jnp.float32)
    else:
        w, w_form = q_leaf["w"], "float"
        dout = w.shape[-1]
        s = jnp.ones((1, dout), jnp.float32)   # unused, uniform operands
    h = dout // hd
    g = h // hkv
    ghd = g * hd
    mb = block_tables.shape[1]
    scale = float(1.0 / (hd ** 0.5))
    rope = rope_cos is not None
    if not rope:
        rope_cos = jnp.ones((r, hd), jnp.float32)
        rope_sin = jnp.zeros((r, hd), jnp.float32)

    kt = jnp.transpose(k_pool, (0, 2, 1, 3))   # [NB, Hkv, bs, hd]
    vt = jnp.transpose(v_pool, (0, 2, 1, 3))

    kernel = functools.partial(
        _fused_kernel, block_size=bs, scale=scale, g=g, hd=hd,
        w_form=w_form, rope=rope, sliding_window=sliding_window)

    wr = w.shape[0]   # D (float/int8) or D//2 (packed int4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, context_lens
        grid=(r, hkv, mb),
        in_specs=[
            # per-slot rows ride as [R, 1, n] so the block's last two
            # dims equal the array's (Mosaic refuses a 1-row block of an
            # [R, n] array: rows must tile by 8)
            pl.BlockSpec((1, 1, d), lambda ri, hi, j, bt, lens: (ri, 0, 0)),
            pl.BlockSpec((wr, ghd), lambda ri, hi, j, bt, lens: (0, hi)),
            pl.BlockSpec((1, ghd), lambda ri, hi, j, bt, lens: (0, hi)),
            pl.BlockSpec((1, 1, hd), lambda ri, hi, j, bt, lens: (ri, 0, 0)),
            pl.BlockSpec((1, 1, hd), lambda ri, hi, j, bt, lens: (ri, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda ri, hi, j, bt, lens: (bt[ri, j], hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda ri, hi, j, bt, lens: (bt[ri, j], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda ri, hi, j, bt, lens: (ri, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),    # projected+rotated q
            pltpu.VMEM((g, 128), jnp.float32),   # running max
            pltpu.VMEM((g, 128), jnp.float32),   # running denominator
            pltpu.VMEM((g, hd), jnp.float32),    # output accumulator
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, hkv, g, hd), x.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      x[:, None], w, s, rope_cos.astype(jnp.float32)[:, None],
      rope_sin.astype(jnp.float32)[:, None], kt, vt)
    return out.reshape(r, h, hd)
