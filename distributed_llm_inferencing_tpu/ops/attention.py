"""Attention: causal/cached multi-head attention with GQA and sliding window.

The reference's attention lives inside vendored HF/torch kernels
(reference: worker/app.py:297-305 just calls model.generate()). Here there
are two backends behind one dispatch:

- **xla** (this module): grouped-query einsums with kv heads kept as an
  axis, f32 scores and softmax, K and V read once in the dtype they are
  stored in. On a v5e the decode shape compiles to one fusion per dot
  (K or V streamed through the MXU as the convolution's left operand,
  scale, mask and the row maximum fused behind q.K) plus one softmax
  fusion, no temporary (tests/test_tpu_compile.py holds it to that). The
  head-expanded f32 form it replaced wrote K and V back to HBM as
  f32[B,S,Hkv,G,hd] and was 87 % of the serving decode pass; PERF.md
  section 6, PR 25, has the record. This is what the continuous batcher
  runs beside its pool kernel (ops/pallas/paged_attention.py, chosen by
  transformer._pool_kernel, not by this dispatch), the reference
  implementation, and the path on non-TPU hosts and multi-device meshes.
- **pallas** (ops/pallas/flash_attention.py): hand-tiled online-softmax
  kernels for the two hot regimes (prefill flash attention, cached flash
  decode).

Backend choice is a trace-time static: ``resolve_backend(cfg.attn_backend)``
— "auto" picks pallas on a single-device TPU backend, xla otherwise
(multi-device programs go through GSPMD, which partitions the einsum
formulation; the pallas kernels enter the sharded path via shard_map in
parallel/ring.py).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-but-finite: keeps softmax well-defined on all-masked rows


def alibi_slopes(num_heads: int):
    """Per-query-head ALiBi slopes, HF convention (BLOOM/Falcon
    build_alibi_tensor): geometric sequence from the nearest power of
    two, odd-index extras interpolated for non-power-of-two head counts.
    Returns [H] f32; the bias applied is ``slope * (kv_pos - q_pos)``
    (non-positive at attended positions)."""
    import math
    cp2 = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(cp2) - 3)))
    slopes = [base ** (i + 1) for i in range(cp2)]
    if cp2 != num_heads:
        extra = 2.0 ** (-(2.0 ** -(math.log2(2 * cp2) - 3)))
        slopes += [extra ** (2 * i + 1) for i in range(num_heads - cp2)]
    return jnp.asarray(slopes, jnp.float32)


def repeat_kv(x, n_rep: int):
    """[B,S,Hkv,hd] -> [B,S,Hkv*n_rep,hd] by repeating each kv head."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d))
    return x.reshape(b, s, h * n_rep, d)


def window_mask(q_pos, kv_pos, sliding_window):
    """Window admissibility for broadcast-aligned position arrays.

    ``sliding_window`` is either a static python int (uniform window) or
    a traced scalar — the per-layer ``attn_window`` leaf
    (models/transformer.py _layer_window), where a NEGATIVE value
    disables the window for that layer (GPT-Neo's global layers). One
    helper so the dense and ring formulations can't drift."""
    in_window = (q_pos - kv_pos) < sliding_window
    if not isinstance(sliding_window, int):
        in_window = in_window | (sliding_window < 0)
    return in_window


def _dot(spec, a, b):
    """einsum accumulated in f32 with exact products. Two bf16 operands
    multiply exactly on the MXU at default precision; any wider operand
    (f32 probabilities, an f32 model) needs HIGHEST, or the MXU rounds
    it to bf16 first. Operands go in as stored: no caller-side astype,
    so no K- or V-sized copy in a wider dtype."""
    both_bf16 = a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
    return jnp.einsum(
        spec, a, b, preferred_element_type=jnp.float32,
        precision=None if both_bf16 else jax.lax.Precision.HIGHEST)


def attend(
    q,                   # [B, Sq, H, hd]
    k,                   # [B, Skv, Hkv, hd], or a sequence of such segments
    v,                   # [B, Skv, Hkv, vd], segmented like k
    q_positions,         # [B, Sq] absolute position of each query token
    kv_positions,        # [B, Skv] absolute position of each kv slot
    kv_valid,            # [B, Skv] bool — slot holds a real token
    sliding_window: Optional[int] = None,
    alibi=None,          # [H] f32 slopes — bias slope*(kv_pos - q_pos)
    softcap: Optional[float] = None,   # gemma2: cap*tanh(scores/cap)
    sinks=None,          # [H] gpt-oss attention sinks: one learned
    # logit per head joins every row's softmax as a virtual column and
    # is dropped after normalization — it only inflates the denominator
    scale: Optional[float] = None,     # score scale; None => hd**-0.5.
    # MLA's absorbed latent decode passes the ORIGINAL qk head dim's
    # scale — its effective q/k carry the (rd + kv_lora_rank)-wide
    # latent, but the scores are mathematically the materialized
    # head_dim attention's (transformer._mla_absorbed), and it passes
    # the latent rows as k AND v: one kv head, read as stored.
    out_dtype=None,      # None => q.dtype; the accumulator is f32
):
    """Causal attention over a (possibly cached, possibly padded) KV set.

    Masking rule: query at position p may attend kv at position t iff
    t <= p, the slot is valid, and (no window or p - t < window).
    Works for prefill (Sq == Skv) and single-token decode (Sq == 1) alike.
    ``alibi`` adds the linear position bias (BLOOM/Falcon-RW) to the
    scaled scores — position-free K/V make the cache layout identical to
    the RoPE families', so every paged/chunked serving path reuses this
    one formulation.

    K and V are read once, as stored. Query heads are grouped by the kv
    head they share (q viewed as [B, Sq, Hkv, G, hd], G = H // Hkv), so
    kv heads stay an axis of both contractions and nothing K- or V-sized
    is broadcast, converted or concatenated. A KV set that lives in
    several buffers (gathered pool + the chunk's side buffer, cached
    prefix + fresh tail) is passed as sequences ``k, v, kv_positions,
    kv_valid`` of equal length: each segment's scores are computed
    against its own buffer, the segments meet only on the score axis for
    the one softmax, and their ``p @ V`` are summed. Scores, softmax and
    accumulation are f32; only the order of summation differs from the
    head-expanded f32 form this replaced (PERF.md section 6, PR 25).
    """
    if not isinstance(k, (tuple, list)):
        k, v, kv_positions, kv_valid = [k], [v], [kv_positions], [kv_valid]
    B, Sq, H, hd = q.shape
    Hkv = k[0].shape[2]
    G = H // Hkv
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qg = q.reshape(B, Sq, Hkv, G, hd)
    qp = q_positions[:, None, None, :, None]          # [B,1,1,Sq,1]

    def segment_logits(k_seg, pos, valid):            # -> [B,Hkv,G,Sq,S]
        logits = _dot("bqkgd,bskd->bkgqs", qg, k_seg) * scale
        if softcap is not None:   # pre-mask score squash (HF gemma2 order)
            logits = jnp.tanh(logits / softcap) * softcap
        kp = pos[:, None, None, None, :]              # [B,1,1,1,S]
        if alibi is not None:
            logits = logits + (alibi.reshape(Hkv, G)[None, :, :, None, None]
                               * (kp - qp).astype(jnp.float32))
        mask = (kp <= qp) & valid[:, None, None, None, :]
        if sliding_window is not None:
            mask = mask & window_mask(qp, kp, sliding_window)
        return jnp.where(mask, logits, NEG_INF)

    logits = [segment_logits(*seg) for seg in zip(k, kv_positions, kv_valid)]
    if sinks is not None:         # a score column that carries no value row
        logits.append(jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(Hkv, G)[None, :, :, None, None],
            (B, Hkv, G, Sq, 1)))
    logits = jnp.concatenate(logits, axis=-1)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)

    out, start = 0.0, 0
    for v_seg in v:
        stop = start + v_seg.shape[1]
        out = out + _dot("bkgqs,bskd->bqkgd", probs[..., start:stop], v_seg)
        start = stop
    return out.reshape(B, Sq, H, -1).astype(out_dtype or q.dtype)


# ----------------------------------------------------------------------
# Backend dispatch (trace-time static)
# ----------------------------------------------------------------------

def resolve_backend(requested: str = "auto", n_devices: int = 1) -> str:
    """'auto' | 'xla' | 'pallas' | 'pallas_interpret' -> concrete backend
    of the dense cache's attention (attend_prefill, attend_decode: the
    single-stream engine's forward passes).

    ``DLI_ATTENTION`` overrides (test/debug escape hatch). Pallas kernels
    are single-program kernels, so auto only picks them when the enclosing
    jit program spans one device.

    The continuous batcher's paged programs do not come here: it pins
    ``attn_backend="xla"`` as a constant, and its decode chunks choose
    how they read the pool themselves, from what the trace sees
    (transformer._pool_kernel: the Pallas paged kernel where the
    batcher's ``cfg.pool_kernel`` pin and the pool's shape allow, i.e.
    mistral-7b, Ouro-2.6B, kanana's latent pool, falcon-h1's 4 K/V
    heads, mimo-v2.5's full layers' flat rows; PERF.md section 6, PRs
    40, 42, 43 and 46; the gather as far as
    _pool_ladder's rung elsewhere).
    """
    requested = os.environ.get("DLI_ATTENTION", requested)
    if requested in ("xla", "pallas", "pallas_interpret"):
        return requested
    if jax.default_backend() == "tpu" and n_devices == 1:
        return "pallas"
    return "xla"


def attend_prefill(q, k, v, *, sliding_window: Optional[int] = None,
                   backend: str = "xla", alibi=None,
                   softcap: Optional[float] = None, sinks=None):
    """Causal self-attention over the fresh (uncached) K/V block.

    Prefill never needs the cache or a validity mask: causality restricts
    every real query row to real slots at or before it, and rows past a
    sequence's length are garbage the engine never reads. ALiBi rides the
    flash kernel as an in-tile additive bias (one SMEM slope per head).
    """
    if backend.startswith("pallas") and sinks is None:
        from distributed_llm_inferencing_tpu.ops.pallas import flash_attention
        return flash_attention(
            q, k, v, sliding_window=sliding_window, alibi=alibi,
            interpret=(backend == "pallas_interpret"))
    B, S, _, _ = q.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return attend(q, k, v, pos, pos, jnp.ones((B, S), bool),
                  sliding_window=sliding_window, alibi=alibi,
                  softcap=softcap, sinks=sinks)


def attend_decode(q, cache_k, cache_v, lengths, *,
                  sliding_window: Optional[int] = None,
                  backend: str = "xla", q_positions=None, alibi=None,
                  softcap: Optional[float] = None, sinks=None,
                  scale: Optional[float] = None):
    """Cached attention for decode-regime queries.

    Single-token (Sq == 1): ``lengths`` counts filled slots including the
    token just written; the query sits at ``lengths - 1``. Multi-token
    (speculative verification, ops/speculative.py): pass ``q_positions``
    [B, Sq] so each query is causally masked at its own position — the
    pallas flash-decode kernel is single-query, so multi-token always
    takes the xla formulation. ALiBi rides the flash kernel (in-tile
    bias from SMEM slopes).
    """
    if backend.startswith("pallas") and q.shape[1] == 1 and scale is None:
        from distributed_llm_inferencing_tpu.ops.pallas import flash_decode
        return flash_decode(
            q, cache_k, cache_v, lengths, sliding_window=sliding_window,
            alibi=alibi, interpret=(backend == "pallas_interpret"))
    B, S = cache_k.shape[0], cache_k.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kv_valid = kv_pos < lengths[:, None]
    q_pos = (q_positions if q_positions is not None
             else (lengths - 1)[:, None])
    return attend(q, cache_k, cache_v, q_pos, kv_pos, kv_valid,
                  sliding_window=sliding_window, alibi=alibi,
                  softcap=softcap, sinks=sinks, scale=scale)
