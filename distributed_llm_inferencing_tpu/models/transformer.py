"""Unified causal-transformer forward pass (pure JAX, functional).

One implementation covers GPT-2, OPT, Llama/Mistral and Mixtral via
ModelConfig switches — where the reference dispatched on the HF module tree
(reference: shard_model.py:40-50) and ran vendored torch kernels via
``model.generate()`` (reference: worker/app.py:297-305), this is an explicit
XLA program designed for the TPU:

- **Stacked layer parameters.** Every per-layer weight carries a leading
  layer axis ``[L, ...]`` and the block stack runs under ``lax.scan``: one
  layer gets traced/compiled once regardless of depth, and the layer axis is
  what pipeline parallelism later shards (parallel/pipeline.py).
- **Static shapes everywhere.** Prefill/decode take fixed-size token blocks
  plus explicit positions/lengths; raggedness is masking, never shape.
- **KV cache as scan xs/ys.** The cache's ``[L, ...]`` buffers flow through
  the scan as per-layer slices, so updates stay fused in one program.

Param pytree schema (all leaves jnp arrays; optional leaves absent, never None):

    {"embed": {"tokens": [V,E], "positions": [P,D]?,
               # E = embed_proj_dim or D; projections present iff
               # cfg.embed_proj_dim (opt-350m):
               "project_in": {"w": [E,D]}?, "project_out": {"w": [D,E]}?},
     "layers": {
        "attn_norm": {"scale": [L,D], "bias": [L,D]?},
        "q"|"k"|"v"|"o": {"w": [L,din,dout], "b": [L,dout]?},
        "attn_gate": {"w": [L,D,H*hd]}?,   # cfg.attn_gate (trinity/afmoe)
        "mlp_norm": {"scale": [L,D], "bias": [L,D]?},
        # dense MLP:
        "up": {"w": [L,D,I], "b"?}, "gate": {"w": [L,D,I]}?, "down": {"w": [L,I,D], "b"?},
        # MoE (cfg.num_experts > 0):
        "router": {"w": [L,D,E]},
        "experts": {"up": {"w": [L,E,D,I]}, "gate": {"w": [L,E,D,I]}, "down": {"w": [L,E,I,D]}},
     },
     "final_norm": {"scale": [D], "bias": [D]?},  # absent when cfg.post_norm
     "lm_head": {"w": [D,V]}?   # absent when tie_word_embeddings
    }
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models.config import ModelConfig
from distributed_llm_inferencing_tpu.ops import lora as lora_ops
from distributed_llm_inferencing_tpu.ops.attention import (
    attend_decode, attend_prefill, resolve_backend)
from distributed_llm_inferencing_tpu.ops.kvcache import KVCache, write_block
from distributed_llm_inferencing_tpu.ops.norms import (layer_norm, norm,
                                                       rms_norm)
from distributed_llm_inferencing_tpu.ops.rope import apply_rope


def _qw(p, dt):
    """Quantized weight as compute-dtype levels (scale still pending).
    int8 reads stay int8 in HBM (XLA fuses the convert into the dot);
    int4 via this path materializes the unpack — only the pallas kernel
    keeps the read 4-bit (ops/pallas/quant_matmul.py), so this is the
    fallback for shapes/platforms the kernel doesn't cover."""
    if "p4" in p:
        from distributed_llm_inferencing_tpu.ops.quant import (
            pack_chunks, unpack_int4)
        return unpack_int4(p["p4"], pack_chunks(p)).astype(dt)
    return p["q"].astype(dt)


def _wfull(p, dt):
    """Materialized full-precision weight for leaves used OUTSIDE
    _linear's contraction (MLA's absorbed einsums): float, int8 or int4
    forms; scale applied."""
    if "w" in p:
        return p["w"].astype(dt)
    return _qw(p, dt) * p["scale"].astype(dt)


def _linear(x, p, row_sharded: bool = False):
    if "p4" in p:   # int4 weight-only: pallas fused-unpack kernel on the
        # decode path, XLA unpack elsewhere (ops/pallas/quant_matmul.py)
        from distributed_llm_inferencing_tpu.ops.pallas.quant_matmul import (
            q4_linear)
        return q4_linear(x, p, row_sharded=row_sharded)
    if "q" in p:   # int8 weight-only (ops/quant.py): per-out-channel scale
        # commutes with the contraction, so it applies to the [.., dout]
        # output — the MXU reads the quantized levels, no dequantized
        # temporary
        y = jnp.einsum("...d,df->...f", x, _qw(p, x.dtype))
        y = y * p["scale"].astype(x.dtype)
    else:
        y = jnp.einsum("...d,df->...f", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y.astype(x.dtype)


def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "relu2":   # squared ReLU (nemotron)
        return jnp.square(jax.nn.relu(x))
    if kind == "gelu_exact":   # HF "gelu" (erf form): gpt-neox, falcon
        return jax.nn.gelu(x, approximate=False)
    return jax.nn.gelu(x, approximate=True)  # gpt2 uses gelu_new


def _lora_apply(y, x, lp, name, lora_ids):
    """Add the slot-gathered LoRA delta for projection ``name`` when the
    layer tree carries an adapter pack (``params["layers"]["lora"]``,
    sliced per layer by the scan/unroll like every other leaf).
    ``lora_ids`` [B] selects each row's adapter slot — 0 is the base
    model's all-zero slot, an exact-zero delta. None (the dense/engine
    path, where one adapter serves the whole batch) defaults every row
    to slot 0 of the attached pack. Base trees carry no ``lora`` key, so
    the base program traces no delta code at all."""
    lo = lp.get("lora") if isinstance(lp, dict) else None
    if lo is None or name not in lo:
        return y
    ids = (lora_ids if lora_ids is not None
           else jnp.zeros((x.shape[0],), jnp.int32))
    return y + lora_ops.gathered_delta(x, lo[name], ids)


@jax.named_scope("mlp")
def _mlp(x, lp, cfg: ModelConfig, lora_ids=None):
    if cfg.gated_mlp:
        gate = _lora_apply(_linear(x, lp["gate"]), x, lp, "gate", lora_ids)
        if cfg.ssm is not None:   # falcon-h1: mlp_multipliers[0]
            gate = gate * jnp.asarray(cfg.ssm.mlp_multipliers[0], gate.dtype)
        h = _act(gate, cfg.activation) \
            * _lora_apply(_linear(x, lp["up"]), x, lp, "up", lora_ids)
    else:
        h = _act(_lora_apply(_linear(x, lp["up"]), x, lp, "up", lora_ids),
                 cfg.activation)
    y = _linear(h, lp["down"], row_sharded=cfg.tp_row_sharded)
    y = _lora_apply(y, h, lp, "down", lora_ids)
    if cfg.ssm is not None:       # ... and mlp_multipliers[1]
        y = y * jnp.asarray(cfg.ssm.mlp_multipliers[1], y.dtype)
    return y


@jax.named_scope("moe_route")
def _moe_route(x, lp, cfg: ModelConfig):
    """Router -> each token's k experts and their weights: (idx [N,k]
    int32, w [N,k] f32). Selection is ``lax.top_k`` of what the family
    ranks by, so exactly k experts a token, ties to the lower index.

    "softmax" (Mixtral convention): softmax first, then top-k, then
    renormalize. "deepseek_v3" (HF modeling_deepseek_v3.py
    DeepseekV3TopkRouter): sigmoid scores; SELECTION ranks scores +
    e_score_correction_bias under group-limited top-k (groups scored by
    their top-2 sum, only the top moe_topk_group groups are eligible);
    WEIGHTS are the unbiased scores, renormalized when moe_norm_topk,
    then scaled by moe_routed_scale. Divergence from HF, deliberate:
    HF zero-fills ineligible groups (masked_fill 0.0), which can admit
    an ineligible expert when every eligible biased score is negative —
    we mask with -inf and keep selection inside the chosen groups."""
    router_logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                               lp["router"]["w"].astype(jnp.float32))
    k = cfg.num_experts_per_tok
    if cfg.moe_router in ("deepseek_v3", "ernie"):
        # ernie (ERNIE-4.5-MoE): softmax scores under the same
        # bias-corrected selection (n_group=1 makes the group stage a
        # no-op); deepseek_v3: sigmoid scores + group-limited top-k
        scores = (jax.nn.sigmoid(router_logits)
                  if cfg.moe_router == "deepseek_v3"
                  else jax.nn.softmax(router_logits, axis=-1))  # [N,E]
        ranked = scores + lp["router"]["bias"].astype(jnp.float32)
        G = cfg.moe_n_group
        if G > 1:
            gs = ranked.reshape(-1, G, cfg.num_experts // G)
            group_scores = jnp.sum(jax.lax.top_k(gs, 2)[0], axis=-1)
            gkth = jax.lax.top_k(group_scores,
                                 cfg.moe_topk_group)[0][..., -1:]
            eligible = jnp.broadcast_to(
                (group_scores >= gkth)[..., None], gs.shape)
            ranked = jnp.where(eligible.reshape(ranked.shape), ranked,
                               -jnp.inf)
        idx = jax.lax.top_k(ranked, k)[1]
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.moe_norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx, w * cfg.moe_routed_scale
    if cfg.moe_router == "topk_softmax":
        # gpt-oss: the router bias is part of the LINEAR (not a
        # selection-only correction); select top-k by the biased logits
        # and softmax over just the selected k values
        top, idx = jax.lax.top_k(
            router_logits + lp["router"]["bias"].astype(jnp.float32), k)
        return idx, jax.nn.softmax(top, axis=-1)
    w, idx = jax.lax.top_k(jax.nn.softmax(router_logits, axis=-1), k)
    if cfg.moe_norm_topk:   # dbrx moe_normalize_expert_weights=None
        w = w / jnp.sum(w, axis=-1, keepdims=True)       # skips this
    return idx, w


def _glu_h(gate, up, cfg: ModelConfig):
    """Expert hidden activation: the standard act(gate) * up, or
    gpt-oss's clamped swish GLU — gate clamped above at
    moe_swiglu_limit, up to ±limit, (up + 1) * gate * sigmoid(alpha *
    gate) (HF modeling_gpt_oss.py GptOssExperts)."""
    if cfg.moe_swiglu_limit is not None:
        lim = cfg.moe_swiglu_limit
        gate = jnp.minimum(gate, lim)
        up = jnp.clip(up, -lim, lim)
        return (up + 1.0) * (gate * jax.nn.sigmoid(
            cfg.moe_swiglu_alpha * gate))
    return _act(gate, cfg.activation) * up


def _grouped_linear(rows, p, group_sizes, row_expert, stream=None):
    """One projection of every expert over its own run of ``rows``
    (sorted by expert; ``group_sizes`` [E] says where each run ends).

    ``lax.ragged_dot``, which the TPU compiler lowers to a grouped
    matmul in 128-row tiles that reads an expert's weights only where
    its run is not empty (its time on a v5e follows the experts hit:
    0.95 ms at 115 of 128, 0.54 at 64) but, with a handful of rows an
    expert, at 46 % of the HBM peak at F 768 and 35 % at F 1024. That
    is the form for many rows an expert (admit programs), for quantized
    experts, for meshes and for the CPU. With ``stream`` ("pallas" |
    "pallas_interpret": _expert_stream decides, a decode chunk's few
    rows an expert in a one-device TPU program) the product is
    ops/pallas/grouped_matmul.py instead: each hit expert's weights
    once at nine tenths of the HBM peak, 16-row tiles, the same float32
    accumulation written in the rows' dtype, bit-equal on the chip
    (scripts/bench_expert_matmul.py; PERF.md section 6, PR 32).

    int8/int4 levels go in as the rows' dtype and each row takes its own
    expert's per-output-channel scale; gpt-oss per-expert biases
    likewise. Rows past the last run come back zero."""
    quantized = "w" not in p
    if stream:
        from distributed_llm_inferencing_tpu.ops.pallas.grouped_matmul import (
            grouped_matmul)
        y = grouped_matmul(rows, p["w"], group_sizes,
                           interpret=stream == "pallas_interpret")
    else:
        y = jax.lax.ragged_dot(
            rows, _qw(p, rows.dtype) if quantized else p["w"], group_sizes)
    if quantized:
        y = y * p["scale"][row_expert].astype(y.dtype)
    if "b" in p:
        y = y + p["b"][row_expert].astype(y.dtype)
    return y


# a decode chunk's rows an expert in the benchmark's cells are 3 (kanana:
# 64 tokens x top-6 over 128) and 4 (trinity: top-8); the smallest admit
# program's are 6 and keep lax.ragged_dot (PERF.md section 6, PR 32)
_STREAM_ROWS_PER_EXPERT = 4


def _expert_stream(cfg: ModelConfig, ex, n_rows: int, dtype):
    """Which form the experts' three grouped matmuls take in this _moe
    call, from what the trace can see: ``cfg.expert_matmul`` (pinned by
    the batcher: "pallas" only in a one-device TPU program, since GSPMD
    does not partition a Pallas call) when every projection is an
    unquantized ``w`` of the rows' dtype, the static row count is at
    most _STREAM_ROWS_PER_EXPERT an expert, and the kernel's VMEM plan
    holds at these widths; else None (``lax.ragged_dot``)."""
    if (not cfg.expert_matmul.startswith("pallas")
            or n_rows > _STREAM_ROWS_PER_EXPERT * cfg.num_experts):
        # (the pairs of ALL the experts against their count: a program
        # that holds a share of them, cfg.experts_held, sees that share
        # of the pairs, so the bound is the same rows a held expert)
        return None
    from distributed_llm_inferencing_tpu.ops.pallas import grouped_matmul
    for name in ("gate", "up", "down"):
        w = ex[name].get("w")
        if w is None or w.dtype != dtype or not grouped_matmul.supported(
                n_rows, w.shape[1], w.shape[2], dtype):
            return None
    return cfg.expert_matmul


def _held_pieces(cfg: ModelConfig, n_rows: int) -> int:
    """In how many pieces _moe walks its sorted (token, choice) rows: 1
    for a model that holds all its experts; for a held share
    (cfg.experts_held) as many as leave a piece twice the share's part of
    the rows, where that divides them (16 of 256 held: 8 pieces)."""
    if cfg.experts_held is None:
        return 1
    pieces = cfg.num_experts // (2 * cfg.experts_held[1])
    return pieces if pieces > 1 and n_rows % pieces == 0 else 1


# what _moe counts of one call, in this order (MOE_STATS names them for
# whoever sums the vectors: runtime/batcher.py). stream_passes: 1 where
# the call's grouped matmuls took the streaming kernel (a trace-time
# constant), so stream_passes / layer_passes says which form ran
# rows_away (last): choices of real tokens that fell on experts this
# program does not hold (cfg.experts_held); a model that holds all its
# experts counts the names before it alone (_n_moe_stats), its programs
# what they were
MOE_STATS = ("layer_passes", "experts_hit", "max_load", "rows", "idle_rows",
             "stream_passes", "rows_away")


def _n_moe_stats(cfg: ModelConfig) -> int:
    return len(MOE_STATS) - (cfg.experts_held is None)


def _moe(x, lp, cfg: ModelConfig, valid=None):
    """Sparse MoE, one formulation at every token count: sort the (token,
    choice) pairs by expert, one grouped matmul a projection over the
    experts' runs, unsort, combine with the router's weights in float32.
    Nothing is dropped under any imbalance, and a token's output depends
    on no other token. ``valid`` [..] bool marks the real tokens (an
    admit program's pad positions and a decode chunk's idle slots are
    not): theirs sort behind every real pair into no expert's run, so
    they cost no expert a read and weigh nothing. Plus the always-active
    DeepSeek shared-experts MLP when the layer carries
    shared_gate/up/down leaves (added OUTSIDE the routed sum, HF
    DeepseekV3MoE.forward).

    The three grouped matmuls take one form a call (_expert_stream):
    ``lax.ragged_dot``, or the streaming kernel for a decode chunk's few
    rows an expert; either way a row's product is its own.

    A program that holds a share of the experts (cfg.experts_held:
    first, count) routes over all of them and keeps the router's
    weights, and sums the chosen experts it holds alone: a choice that
    falls elsewhere sorts behind every real pair as a pad position's
    does, into no expert's run, and is counted (rows_away). ``E`` below
    is then the held count and an expert's index its place in the share.

    Returns (out like x, stats int32 [_n_moe_stats] in MOE_STATS order)."""
    *lead, D = x.shape
    xf = x.reshape(-1, D)
    N, E, k = xf.shape[0], cfg.num_experts, cfg.num_experts_per_tok
    idx, w = _moe_route(xf, lp, cfg)
    with jax.named_scope("moe_route"):
        if cfg.experts_held is not None:
            first, E = cfg.experts_held
            away = (idx < first) | (idx >= first + E)
            if valid is not None:
                away = away & valid.reshape(N, 1)
            idx = jnp.where(away, E, idx - first)
        if valid is not None:
            real = valid.reshape(N, 1)
            idx, w = jnp.where(real, idx, E), jnp.where(real, w, 0.0)
        expert = idx.reshape(N * k)
        order = jnp.argsort(expert)          # stable: ties keep token order
        row_expert = jnp.minimum(expert[order], E - 1)
        group_sizes = jnp.zeros((E,), jnp.int32).at[expert].add(
            1, mode="drop")                  # expert E (not real) is dropped
        ex = lp["experts"]
        stream = _expert_stream(cfg, ex, N * k, xf.dtype)
        pieces = 1 if stream else _held_pieces(cfg, N * k)
        if pieces == 1:
            rows = xf[order // k]                           # [N*k, D]

    def experts(rows, group_sizes, row_expert):
        h = _glu_h(_grouped_linear(rows, ex["gate"], group_sizes, row_expert,
                                   stream),
                   _grouped_linear(rows, ex["up"], group_sizes, row_expert,
                                   stream), cfg)
        return _grouped_linear(h, ex["down"], group_sizes, row_expert, stream)

    with jax.named_scope("moe_experts"):
        if pieces == 1:
            y = experts(rows, group_sizes, row_expert)
        else:
            # a held share: the real pairs are the first n_real sorted
            # rows, a sixteenth of them at MiMo's share. Walk the sorted
            # rows a piece at a time as far as the real pairs reach (one
            # piece unless the router sends this share twice its part),
            # each piece's runs cut from the experts' own: the gathered
            # rows, the three products and what lies between them are a
            # piece's, not every pair's (PERF.md section 6, PR 45)
            c = N * k // pieces
            ends = jnp.cumsum(group_sizes)

            def piece(carry):
                i, y = carry
                lo = i * c
                at = jax.lax.dynamic_slice_in_dim
                sizes = (jnp.clip(ends - lo, 0, c)
                         - jnp.clip(ends - group_sizes - lo, 0, c))
                y_c = experts(xf[at(order, lo, c) // k], sizes,
                              at(row_expert, lo, c))
                return i + 1, jax.lax.dynamic_update_slice_in_dim(
                    y, y_c, lo, 0)

            _, y = jax.lax.while_loop(
                lambda carry: carry[0] * c < ends[-1], piece,
                (jnp.int32(0), jnp.zeros((N * k, D), xf.dtype)))
    with jax.named_scope("moe_combine"):
        n_real = jnp.sum(group_sizes)
        y = jnp.where((jnp.arange(N * k) < n_real)[:, None], y, 0)
        if pieces == 1:
            y = jnp.zeros_like(y).at[order].set(y)
        else:   # a pair reads its row where it lies: no rows are scattered
            y = y[jnp.zeros_like(order).at[order].set(
                jnp.arange(N * k, dtype=order.dtype))]
        out = jnp.sum(y.reshape(N, k, D).astype(jnp.float32) * w[..., None],
                      axis=1)
        out = out.astype(x.dtype).reshape(*lead, D)
    if cfg.moe_shared_experts:
        with jax.named_scope("mlp"):
            h = _act(_linear(x, lp["shared_gate"]), cfg.activation) \
                * _linear(x, lp["shared_up"])
            out = out + _linear(h, lp["shared_down"],
                                row_sharded=cfg.tp_row_sharded)
    stats = [jnp.int32(1), jnp.sum(group_sizes > 0),
             jnp.max(group_sizes), n_real, N * k - n_real,
             jnp.int32(stream is not None)]
    if cfg.experts_held is not None:
        stats[4] = stats[4] - jnp.sum(away)   # idle rows: pads alone
        stats.append(jnp.sum(away))
    return out, jnp.stack(stats).astype(jnp.int32)


def _alibi(cfg: ModelConfig):
    """[H] ALiBi slopes when the config uses them, else None — threaded
    into every attention formulation (trace-time constant).
    cfg.alibi_scale folds in Falcon-RW's extra 1/sqrt(head_dim) (it
    scales scores + bias together where BLOOM scales scores only)."""
    if cfg.position_embedding != "alibi":
        return None
    from distributed_llm_inferencing_tpu.ops.attention import alibi_slopes
    return alibi_slopes(cfg.num_heads) * cfg.alibi_scale


def _cfg_backend(cfg: ModelConfig, n_devices: int = 1):
    """resolve_backend (the dense cache's flash kernels: forward,
    attend_prefill, attend_decode), then force the XLA formulation for
    per-layer windows (the flash kernels take static windows only,
    while the traced ``attn_window`` scalar flows through the XLA masks
    unchanged) and for attention softcapping (the kernels' online
    softmax has no tanh hook).

    ``n_devices`` is the device count of the PROGRAM's mesh: the engine
    resolves against ``mesh_spec.num_devices`` and pins the result in
    ``cfg.attn_backend``, so ``forward`` only reads that pin. A bare
    ``auto`` reaching it is a direct call (tests, dryrun), which is a
    one-device program — never the process's device count, which on a
    four-chip host says nothing about this program. The paged paths
    below (the batcher's) read it nowhere: how a decode chunk reads the
    pool is ``_pool_kernel``'s choice."""
    b = resolve_backend(cfg.attn_backend, n_devices)
    if b.startswith("pallas") and (cfg.attn_windows is not None
                                   or cfg.attn_softcap is not None
                                   or cfg.attn_sinks or cfg.mla
                                   or cfg.swa is not None):
        # mla: qk_head_dim (192) is off the kernels' 128-lane tiling and
        # v rides zero-padded — keep the XLA formulation until a
        # dedicated MLA kernel exists. Layer kinds (mimo-v2): 192-wide
        # heads again, value heads of another width, sinks in one kind
        return "xla"
    return b


def _sinks(cfg: ModelConfig, lp):
    """[H] per-layer attention-sink logits (gpt-oss) — a layer-tree leaf
    like the q/k norms, threaded into every attention formulation."""
    return lp["sinks"] if cfg.attn_sinks else None


def _layer_window(cfg: ModelConfig, lp):
    """Effective attention window for one layer.

    Per-layer windows (cfg.attn_windows, GPT-Neo's alternating
    global/local) ride the layer param tree as an int32 ``attn_window``
    leaf ([L] stacked; -1 == global) — under scan/unroll/pipeline ``lp``
    holds this layer's scalar slice, so every serving path threads it
    with no extra plumbing. Uniform-window families fall through to the
    static cfg.sliding_window, and so does a layer whose window
    scan_layer_stack could name at trace time (_static_window_cfg: its
    cfg carries no attn_windows, whatever leaf ``lp`` still holds)."""
    if (cfg.attn_windows is not None and isinstance(lp, dict)
            and "attn_window" in lp):
        return lp["attn_window"]
    return cfg.sliding_window


def _static_window_cfg(seg_cfg: ModelConfig, cfg: ModelConfig, start: int,
                       n: int) -> ModelConfig:
    """``seg_cfg`` for layers [start, start + n) of ``cfg``'s stack: where
    they share one window (always, for a layer held on its own) the
    window is a trace-time constant, cfg.sliding_window (None == global),
    and the bounded pool reads below can count its blocks. Layers of
    mixed windows under one scan keep the traced leaf."""
    if cfg.attn_windows is None:
        return seg_cfg
    wins = set(cfg.attn_windows[start:start + n])
    if len(wins) != 1:
        return seg_cfg
    return seg_cfg.replace(attn_windows=None, sliding_window=wins.pop())


def embed(params, cfg: ModelConfig, tokens, q_positions):
    """Token (+ learned position) embedding. Shared by the scanned forward
    below and the pipelined executor (parallel/pipeline.py)."""
    table = params["embed"]["tokens"]
    if isinstance(table, dict):   # int8 per-row table (cfg.embed_quant):
        # gather whole rows then one scalar multiply per row — the HBM
        # read is s rows of int8, not the float table
        x = jnp.take(table["q8"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        x = x * jnp.take(table["rscale"], tokens,
                         axis=0)[..., None].astype(x.dtype)
    else:
        x = jnp.take(table, tokens, axis=0)
    x = x.astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale is not None:   # gemma: sqrt(D) normalizer on the
        # embedding output only — the tied head reads the raw table
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    if "project_in" in params["embed"]:   # opt-350m: embed dim < hidden dim
        x = _linear(x, params["embed"]["project_in"])
    if cfg.position_embedding == "learned":
        # Positions are clipped only as jit-safety; the engine rejects
        # requests whose prompt+max_new_tokens exceed the context window
        # (runtime/engine.py), so clipping never silently engages.
        pos = jnp.take(params["embed"]["positions"],
                       jnp.clip(q_positions, 0, cfg.max_position_embeddings - 1),
                       axis=0)
        x = x + pos.astype(x.dtype)
    if cfg.embed_norm:   # bloom: layernorm on the embedding output
        x = norm(x, params["embed"]["norm"], cfg.norm_type, cfg.norm_eps)
    return x


@jax.named_scope("lm_head")
def unembed(params, cfg: ModelConfig, x, as_computed: bool = False):
    """Final norm + logits head, f32. Shared with parallel/pipeline.py.

    Post-LN models (opt-350m) have no final norm — each block already
    normalized its residual output; the embed projection (if any) maps
    back to the embedding dim before the tied head.

    ``as_computed``: the logits in the dtype the head computed them in
    (``x``'s), for ops/sampling.sample_batch, whose search walks the
    bits they have; the float32 copy holds the same values.
    """
    if not cfg.post_norm:
        x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    if "project_out" in params["embed"]:
        x = _linear(x, params["embed"]["project_out"])
    if cfg.tie_word_embeddings:
        table = params["embed"]["tokens"]
        if isinstance(table, dict):   # int8 table (cfg.embed_quant): the
            # per-row scale is per output channel here and commutes out
            logits = jnp.einsum("bsd,vd->bsv", x,
                                table["q8"].astype(x.dtype))
            logits = logits * table["rscale"].astype(x.dtype)
        else:
            logits = jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype))
    else:
        logits = _linear(x, params["lm_head"])
    # Cohere's constant logit scale and Gemma-2's final softcap
    if cfg.logit_scale is not None:
        logits = logits * cfg.logit_scale
    if cfg.logit_softcap is not None:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits if as_computed else logits.astype(jnp.float32)


def _qk_normalize(t, p, cfg: ModelConfig):
    """cfg.qk_norm on projected q or k [B,s,H,hd], pre-RoPE.

    "rms_head"/"ln_head" normalize each head over head_dim (qwen3 /
    cohere use_qk_norm); "rms_full" normalizes the flattened projection
    width (olmo2 applies the norm to the [.., H*hd] projection output
    before the head reshape)."""
    kind = cfg.qk_norm
    if kind == "rms_full":
        B, s, H, hd = t.shape
        return rms_norm(t.reshape(B, s, H * hd), p["scale"],
                        cfg.norm_eps).reshape(B, s, H, hd)
    if kind == "ln_head":   # cohere: bias-free layernorm per head, with
        # DISTINCT per-head scales (stored flat [H*hd])
        H, hd = t.shape[-2:]
        return layer_norm(t, p["scale"].reshape(H, hd),
                          jnp.zeros((), t.dtype), cfg.norm_eps)
    return rms_norm(t, p["scale"], cfg.norm_eps)


def layer_segments(params, cfg: ModelConfig):
    """Execution-ordered layer segments of a (possibly heterogeneous)
    stack: ``[(layers_tree, segment_cfg, start, count)]``.

    A homogeneous model is one segment. DeepSeek's
    ``first_k_dense_replace`` layout (cfg.dense_prefix_layers) is two:
    a dense-MLP prefix (param key ``layers_dense``) ahead of the MoE
    tail (``layers``). Attention and cache layout are identical across
    segments — only the MLP half of the block differs — so callers
    slice their [L, ...]-stacked cache/pool planes by (start, count)
    and run the same block body under each segment's cfg."""
    if cfg.swa is not None:
        return _kind_segments(params, cfg)
    if "layers_dense" not in params:
        return [(params["layers"], cfg, 0, cfg.num_layers)]
    k = cfg.dense_prefix_layers
    return [(params["layers_dense"], cfg.dense_segment_cfg(), 0, k),
            (params["layers"], cfg, k, cfg.num_layers - k)]


def _kind_segments(params, cfg: ModelConfig):
    """layer_segments of a model with layer kinds (cfg.swa, MiMo-V2):
    the runs of one kind, in the pattern's order, each under its kind's
    own config (ModelConfig.kind_cfg: K/V head count, rotary base, sink,
    window). A kind's layers are a stack of their own, their K, V and
    sink leaves of that kind's shapes (``layers`` the windowed,
    ``layers_full`` the full ones, ``layers_dense`` the leading dense
    layers), stacked [n, ...] or, in the batcher, a list of per-layer
    trees; a run takes its slice. The kinds' caches differ too: a
    caller's per-layer arrays are the dense cache's planes, as wide as
    the wider kind (_block), or each layer's index in its own kind's
    cache (ModelConfig.cache_index: the block pool, the ring)."""
    kinds, k = cfg.swa.kinds(), cfg.dense_prefix_layers
    stacks = {"swa": params.get("layers"), "full": params.get("layers_full")}
    taken = {"swa": 0, "full": 0}
    segs, i = [], 0
    if k:
        segs.append((params["layers_dense"],
                     cfg.dense_segment_cfg().kind_cfg(kinds[0], k), 0, k))
        i = k
    while i < len(kinds):
        kind, n = kinds[i], 1
        while i + n < len(kinds) and kinds[i + n] == kind:
            n += 1
        stack, a = stacks[kind], taken[kind]
        run = (stack[a:a + n] if isinstance(stack, (list, tuple))
               else jax.tree.map(lambda leaf: leaf[a:a + n], stack))
        segs.append((run, cfg.kind_cfg(kind, n), i, n))
        taken[kind] += n
        i += n
    return segs


def scan_layer_stack(make_body, x, params, cfg: ModelConfig, xs):
    """Run the block stack over ``x``, segment-aware.

    ``make_body(seg_cfg)`` returns a ``lax.scan`` body
    ``(carry, (lp, *per_layer_xs)) -> (carry, per_layer_out)``;
    ``xs`` is a tuple of [L, ...]-stacked per-layer arrays (the dense
    cache's planes, or the block pool's in paged_decode_step; the admit
    and decode-chunk programs pass ``arange(L)`` and close over the
    stacked pool, so that a segment's body gets its layers' indices in
    the whole stack and the pool is no input or output of the stack),
    and ``x`` any pytree the body carries (the decode chunks carry their
    side buffers beside the hidden state). Each segment scans its own
    stacked tree (or, for the batcher's per-layer lists of MoE layers,
    loops Python-side);
    per-layer outputs are re-stacked and concatenated back to [L, ...]
    order. A body's ``seg_cfg`` names its layers' attention window as a
    constant where it can (_static_window_cfg). Returns (carry,
    tuple_of_[L,...]_outputs)."""
    seg_outs = []
    for layers_seg, seg_cfg, start, n in layer_segments(params, cfg):
        seg_xs = tuple(p[start:start + n] for p in xs)
        if isinstance(layers_seg, (list, tuple)):
            # per-layer weight buffers (batcher._unstack_layers): the
            # grouped expert matmul takes whole buffers, and under a scan
            # each pass would first copy them out of the stack
            outs = []
            for i, lp in enumerate(layers_seg):
                body = make_body(_static_window_cfg(seg_cfg, cfg,
                                                    start + i, 1))
                x, out = body(x, (lp,) + tuple(p[i] for p in seg_xs))
                outs.append(out)
            seg_outs.append(tuple(
                jnp.stack([o[j] for o in outs])
                for j in range(len(outs[0]))))
        else:
            body = make_body(_static_window_cfg(seg_cfg, cfg, start, n))
            x, co = jax.lax.scan(body, x, (layers_seg,) + seg_xs)
            seg_outs.append(co)
    if len(seg_outs) == 1:
        return x, seg_outs[0]
    cat = tuple(jnp.concatenate([so[j] for so in seg_outs], axis=0)
                for j in range(len(seg_outs[0])))
    return x, cat


def loop_passes(cfg: ModelConfig, xs):
    """A looped model's passes over its layer stack (Ouro:
    ``cfg.loop_steps`` of them over the same weights), for a Python loop
    that runs scan_layer_stack once a pass: yields ``(u, xs_u)`` under
    ``jax.named_scope("loop_step_<u>")``. ``xs`` are stacked
    [cfg.cache_planes, ...] -- the dense cache's planes, or
    ``arange(cache_planes)`` where the program closes over the pool --
    and pass u takes rows [u * L, (u + 1) * L): a body's layer index is
    then its (step, layer) pair's plane, u * L + l, while its weights
    are layer l's, the same arrays on every pass (closed over once,
    never stacked per step). With one step: ``xs`` as they came and no
    scope, so the trace and the program text of before the loop
    existed."""
    T, L = cfg.loop_steps, cfg.num_layers
    if T == 1:
        yield 0, xs
        return
    for u in range(T):
        with jax.named_scope(f"loop_step_{u}"):
            yield u, tuple(p[u * L:(u + 1) * L] for p in xs)


def loop_pass_end(params, cfg: ModelConfig, u: int, x):
    """The final norm between passes: pass u's result is the next pass's
    input (the last pass's is unembed's to take)."""
    if u + 1 == cfg.loop_steps:
        return x
    return norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)


def loop_layer_stack(make_body, carry, params, cfg: ModelConfig, xs):
    """scan_layer_stack, once a pass of loop_passes, the final norm
    between passes. ``carry`` is the hidden state, or a tuple that holds
    it first (the decode chunks carry their side buffers behind it).
    Returns (carry, outputs concatenated back to [cache_planes, ...]
    order)."""
    outs = []
    for u, xs_u in loop_passes(cfg, xs):
        carry, out = scan_layer_stack(make_body, carry, params, cfg, xs_u)
        if isinstance(carry, tuple):
            carry = (loop_pass_end(params, cfg, u, carry[0]),) + carry[1:]
        else:
            carry = loop_pass_end(params, cfg, u, carry)
        outs.append(out)
    if len(outs) == 1:
        return carry, outs[0]
    return carry, tuple(jnp.concatenate([o[j] for o in outs], axis=0)
                        for j in range(len(outs[0])))


def _mla_qkv(h, lp, cfg: ModelConfig, q_positions):
    """DeepSeek-V3 multi-head latent attention projections (HF
    modeling_deepseek_v3.py:327-446), materialized per head. q and kv
    pass through low-rank bottlenecks with an RMSNorm at each bottleneck
    — the reason MLA cannot be pre-expanded into plain q/k/v weights at
    conversion.

    Layout choices, both score-invariant permutations of HF's:
    - per-head q/k dims are ordered [rope | nope] (HF: [nope | rope]) so
      the RoPE'd slice is contiguous at the front; conversion permutes
      the projection columns to match (models/convert.py deepseek).
    - rope uses the gptj-interleaved pairing when cfg.rope_interleaved
      (HF's apply_rotary_pos_emb_interleave permutes pairs->halves then
      half-rotates; same rotation pairs, different output layout —
      identical q·k scores since q and k transform together).

    k's rope part is computed ONCE from the hidden state (MQA-style) and
    broadcast across heads; v is zero-padded from v_head_dim to
    qk_head_dim so the materialized dense cache keeps one width (the
    block slices the attention output back before o). Returns q,k,v
    [B,s,H,qk_head_dim].
    """
    hd, vd = cfg.qk_head_dim, cfg.v_head_dim_effective
    q = _mla_q(h, lp, cfg, q_positions)
    k, v = _mla_expand(*_mla_kv_latent(h, lp, cfg, q_positions), lp, cfg)
    if vd < hd:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, hd - vd)))
    return q, k, v


def _mla_expand(k_rot, c, lp, cfg: ModelConfig):
    """Per-head K [B,s,H,qk_head_dim] ([rope | nope]) and V
    [B,s,H,v_head_dim] from the shared rope key ``k_rot`` [B,s,1,rd] and
    the normed latent ``c`` [B,s,r]: what the latent cache does not
    store, recomputed where a prefill attends."""
    B, s = c.shape[:2]
    H, rd = cfg.num_heads, cfg.qk_rope_head_dim
    k_nope = _linear(c, lp["kv_b_k"]).reshape(B, s, H, cfg.qk_nope_head_dim)
    v = _linear(c, lp["kv_b_v"]).reshape(B, s, H, cfg.v_head_dim_effective)
    k = jnp.concatenate(
        [jnp.broadcast_to(k_rot.astype(k_nope.dtype), (B, s, H, rd)),
         k_nope], axis=-1)
    return k, v


def _mla_q(h, lp, cfg: ModelConfig, q_positions):
    """MLA query projection, shared by the materialized and latent
    formulations: [B,s,H,head_dim] with per-head dims [rope | nope],
    RoPE applied to the rope slice."""
    B, s, _ = h.shape
    H, hd, rd = cfg.num_heads, cfg.qk_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = norm(_linear(h, lp["q_a"]), lp["q_a_norm"], "rmsnorm",
                  cfg.norm_eps)
        q = _linear(cq, lp["q_b"]).reshape(B, s, H, hd)
    else:
        q = _linear(h, lp["q"]).reshape(B, s, H, hd)
    q_rot = apply_rope(q[..., :rd], q_positions, cfg.rope_theta,
                       interleaved=cfg.rope_interleaved,
                       inv_freq=cfg.rope_inv_freq,
                       attn_factor=cfg.rope_attn_factor)
    return jnp.concatenate([q_rot, q[..., rd:]], axis=-1)


def _mla_kv_latent(h, lp, cfg: ModelConfig, q_positions):
    """MLA kv bottleneck, shared by the materialized and latent
    formulations: returns (k_rot [B,s,1,rd] post-RoPE, c [B,s,r]
    normed)."""
    r = cfg.kv_lora_rank
    ckv = _linear(h, lp["kv_a"])                         # [B,s,r+rd]
    k_rot = apply_rope(ckv[..., r:][:, :, None, :], q_positions,
                       cfg.rope_theta,
                       interleaved=cfg.rope_interleaved,
                       inv_freq=cfg.rope_inv_freq,
                       attn_factor=cfg.rope_attn_factor)  # [B,s,1,rd]
    c = norm(ckv[..., :r], lp["kv_a_norm"], "rmsnorm", cfg.norm_eps)
    return k_rot, c


def _mla_latent_rows(h, lp, cfg: ModelConfig, q_positions):
    """The latent cache's row for each token of ``h``: [B,s,1,rd+r],
    [k_rot (post-RoPE) | c (normed)] — all that MLA caches."""
    k_rot, c = _mla_kv_latent(h, lp, cfg, q_positions)
    return jnp.concatenate([k_rot, c[:, :, None, :]], axis=-1)


def _mla_split_rows(rows, cfg: ModelConfig):
    """Latent rows [B,s,1,rd+r] (or as a pool stores them, zeros after
    the rd + r columns) -> (k_rot [B,s,1,rd], c [B,s,r])."""
    rd = cfg.qk_rope_head_dim
    return rows[..., :rd], rows[:, :, 0, rd:rd + cfg.kv_lora_rank]


def _mla_absorbed(q, lp, cfg: ModelConfig, attend_rows):
    """MLA attention over latent rows, absorbed: scores
    q_nope·(W_uk c) == (W_uk^T q_nope)·c and outputs W_uv (Σ p c), i.e.
    multi-query attention of every head over ONE shared (rd + r)-wide
    row a token, with the per-head up-projections folded into q and
    pulled out of the weighted sum — the materialized attention's
    numbers, reassociated.

    ``attend_rows(q_eff)`` runs ops/attention.attend with the rows as
    both K and V (one kv head, read as stored: the context comes back
    rd + r wide and its first rd columns, the rope key's, are dropped
    here, so no r-wide copy of the rows is ever cut; the paged kernel
    returns it as wide as the pool stores a row, lane_width(rd + r),
    and the zero tail is dropped with them) at
    ``scale=_mla_scale(cfg)``. q [B,s,H,qk_head_dim] -> attn
    [B,s,H,v_head_dim]."""
    H, rd, r = cfg.num_heads, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    wk = _wfull(lp["kv_b_k"], q.dtype).reshape(r, H, cfg.qk_nope_head_dim)
    wv = _wfull(lp["kv_b_v"], q.dtype).reshape(r, H, cfg.v_head_dim_effective)
    with jax.named_scope("mla_absorb"):
        q_eff = jnp.concatenate(
            [q[..., :rd],
             jnp.einsum("bshn,rhn->bshr", q[..., rd:], wk)], axis=-1)
    ctx = attend_rows(q_eff)                             # [B,s,H,rd+r]
    with jax.named_scope("mla_absorb"):
        return jnp.einsum("bshr,rhv->bshv", ctx[..., rd:rd + r], wv)


def _mla_scale(cfg: ModelConfig) -> float:
    """Score scale of the absorbed form: the materialized q/k head's,
    not the (rd + r)-wide effective one's."""
    return 1.0 / float(cfg.qk_head_dim) ** 0.5


def _mla_latent_attn(h, lp, cfg: ModelConfig, q_positions, cache_k,
                     cache_v, write_starts, new_lengths, is_prefill,
                     backend):
    """MLA attention over the LATENT dense cache (cfg.mla_latent_cache,
    runtime/engine.py). The cache's k plane holds one shared row per
    token (_mla_latent_rows) and the v plane is zero-width. Prefill
    attends its fresh block with materialized per-head K/V (the O(s^2)
    regime where compute, not cache traffic, dominates) while writing
    only the latent row; decode runs the absorbed form (_mla_absorbed).

    Returns (attn [B,s,H,v_head_dim], (new_cache_k, cache_v)).
    """
    hd, vd = cfg.qk_head_dim, cfg.v_head_dim_effective
    q = _mla_q(h, lp, cfg, q_positions)                  # [B,s,H,hd]
    latent = _mla_latent_rows(h, lp, cfg, q_positions)
    ck = write_block(cache_k, latent, write_starts)      # [B,S,1,rd+r]
    if is_prefill:
        # v zero-padded to the q/k width for flash-kernel eligibility
        # (same trade as the materialized path), sliced back after
        k, v = _mla_expand(*_mla_split_rows(latent, cfg), lp, cfg)
        if vd < hd:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, hd - vd)))
        attn = attend_prefill(q, k, v, backend=backend)[..., :vd]
    else:
        attn = _mla_absorbed(q, lp, cfg, lambda q_eff: attend_decode(
            q_eff, ck, ck, new_lengths, backend="xla",
            q_positions=q_positions,   # multi-token speculative verify
            # needs per-query causal masks, not the lengths-1 default
            scale=_mla_scale(cfg)))
    return attn, (ck, cache_v)


def _attn_gate(attn_flat, h, lp, cfg: ModelConfig):
    """Trinity (afmoe) gated attention: the heads' output [B,s,H*hd]
    times sigmoid of a linear of the block's normed input, elementwise,
    ahead of the o projection. The sigmoid and the product are float32."""
    if not cfg.attn_gate:
        return attn_flat
    with jax.named_scope("attn_gate"):
        g = jax.nn.sigmoid(_linear(h, lp["attn_gate"]).astype(jnp.float32))
        return (attn_flat.astype(jnp.float32) * g).astype(attn_flat.dtype)


def _block_body(x, lp, cfg: ModelConfig, q_positions, attend_write,
                mla_latent_attend=None, lora_ids=None, valid=None,
                moe_stats=False, ssm_mix=None):
    """One transformer block: norm → QKV (+RoPE) → attend → norm → MLP/MoE.

    The single definition of the block structure, shared by the dense path
    (_block) and the paged serving paths (paged_decode_step /
    paged_prefill_tail) so the three can never diverge. ``attend_write(q,
    k, v) -> (attn [B,s,H,hd], cache_out)`` owns the regime-specific part:
    cache update + attention formulation.

    cfg.post_norm flips pre-LN (norm -> sublayer -> residual) to the
    post-LN order opt-350m uses (sublayer -> residual -> norm);
    cfg.parallel_residual is the GPT-NeoX/Phi/Falcon topology — attention
    and MLP both read (norms of) the same block input and share one
    residual add, with cfg.shared_attn_mlp_norm collapsing the two norms
    into one (Phi / Falcon-7B).

    ``valid`` and ``moe_stats`` go to _block_tail: which tokens are real
    (for the expert dispatch), and whether the layer's MOE_STATS vector
    rides out behind ``cache_out``.

    ``ssm_mix(h, lp) -> (out [B,s,D], state_out)`` (cfg.ssm, Falcon-H1):
    the block's Mamba-2 mixer (ops/ssm.py) reads the same normed input as
    the attention heads, its output joins theirs ahead of the one
    residual add (each under its multiplier), and ``state_out`` -- the
    caller's new recurrent state and conv window, in whatever form its
    regime keeps them -- rides out as the last elements of ``cache_out``.
    """
    tail = dict(valid=valid, moe_stats=moe_stats)
    B, s, _ = x.shape
    h = x if (cfg.post_norm or cfg.sublayer_postnorm_only) else norm(
        x, lp["attn_norm"], cfg.norm_type, cfg.norm_eps)
    if cfg.ssm is not None:
        mixed, state_out = ssm_mix(h, lp)
        tail["ssm"] = (mixed, tuple(state_out))
        # attention_in_multiplier on what q, k and v project
        h_attn = h * jnp.asarray(cfg.ssm.attn_in_multiplier, h.dtype)
    else:
        h_attn = h
    if mla_latent_attend is not None:
        # latent formulation (cfg.mla_latent_cache): the whole attention
        # — projections, cache or pool, absorbed decode — runs inside
        # the callback; output arrives at v_head_dim already
        attn, cache_out = mla_latent_attend(h, q_positions)
        vd = cfg.v_head_dim_effective
        attn = _linear(attn.reshape(B, s, cfg.num_heads * vd), lp["o"],
                       row_sharded=cfg.tp_row_sharded)
        return _block_tail(x, h, attn, cache_out, lp, cfg, **tail)
    if cfg.mla:
        q, k, v = _mla_qkv(h, lp, cfg, q_positions)   # rope applied inside
    else:
        # LoRA deltas on the flat projection outputs (models/lora.py
        # rejects MLA/MoE bases, so the arms above never carry a pack)
        q = _lora_apply(_linear(h_attn, lp["q"]), h_attn, lp, "q",
                        lora_ids).reshape(B, s, cfg.num_heads, cfg.head_dim)
        k = _lora_apply(_linear(h_attn, lp["k"]), h_attn, lp, "k",
                        lora_ids).reshape(B, s, cfg.num_kv_heads,
                                          cfg.head_dim)
        v = _lora_apply(_linear(h_attn, lp["v"]), h_attn, lp, "v",
                        lora_ids).reshape(B, s, cfg.num_kv_heads,
                                          cfg.v_head_dim_effective)
        if cfg.attn_value_scale is not None:   # mimo-v2: the caches
            # hold the scaled rows
            v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
        if cfg.ssm is not None:   # key_multiplier, ahead of the rotation
            k = k * jnp.asarray(cfg.ssm.key_multiplier, k.dtype)

        if cfg.qkv_clip is not None:   # dbrx clip_qkv activation clamp
            q = jnp.clip(q, -cfg.qkv_clip, cfg.qkv_clip)
            k = jnp.clip(k, -cfg.qkv_clip, cfg.qkv_clip)
            v = jnp.clip(v, -cfg.qkv_clip, cfg.qkv_clip)

        if cfg.qk_norm and not cfg.qk_norm_after_rope:
            q = _qk_normalize(q, lp["q_norm"], cfg)
            k = _qk_normalize(k, lp["k_norm"], cfg)

        if cfg.position_embedding == "rope":
            q_r = apply_rope(q, q_positions, cfg.rope_theta, cfg.rope_pct,
                             cfg.rope_interleaved,
                             inv_freq=cfg.rope_inv_freq,
                             attn_factor=cfg.rope_attn_factor)
            k_r = apply_rope(k, q_positions, cfg.rope_theta, cfg.rope_pct,
                             cfg.rope_interleaved,
                             inv_freq=cfg.rope_inv_freq,
                             attn_factor=cfg.rope_attn_factor)
            if cfg.rope_layers is not None:
                # per-layer NoPE (smollm3/exaone4): the int32 rope_on
                # leaf rides the layer tree; compute-and-select keeps
                # the scan body uniform
                on = lp["rope_on"].astype(jnp.bool_)
                q, k = jnp.where(on, q_r, q), jnp.where(on, k_r, k)
            else:
                q, k = q_r, k_r

        if cfg.qk_norm and cfg.qk_norm_after_rope:   # hunyuan ordering
            q = _qk_normalize(q, lp["q_norm"], cfg)
            k = _qk_normalize(k, lp["k_norm"], cfg)

    attn, cache_out = attend_write(q, k, v)
    vd = cfg.v_head_dim_effective
    if vd < attn.shape[-1]:   # MLA: v rode the cache zero-padded
        attn = attn[..., :vd]
    attn_flat = _attn_gate(attn.reshape(B, s, cfg.num_heads * vd), h, lp,
                           cfg)
    attn = _lora_apply(
        _linear(attn_flat, lp["o"], row_sharded=cfg.tp_row_sharded),
        attn_flat, lp, "o", lora_ids)
    return _block_tail(x, h, attn, cache_out, lp, cfg, lora_ids=lora_ids,
                       **tail)


def _block_tail(x, h, attn, cache_out, lp, cfg: ModelConfig, lora_ids=None,
                valid=None, moe_stats=False, ssm=None):
    """Post-attention half of the block: residual topology + MLP/MoE
    (shared by the materialized and MLA-latent attention dispatches).
    ``valid`` [B,s] marks the real tokens for the expert dispatch
    (_moe); with ``moe_stats`` the layer's MOE_STATS vector (zeros for a
    dense layer) rides out as one more element of ``cache_out``.
    ``ssm`` (cfg.ssm): the mixer's output, which joins the attention
    output here, and its new state, which goes out behind ``cache_out``
    (ahead of the stats)."""
    stats = jnp.zeros((_n_moe_stats(cfg),), jnp.int32)
    if ssm is not None:
        mixed, state_out = ssm
        attn = attn * jnp.asarray(cfg.ssm.attn_out_multiplier,
                                  attn.dtype) + mixed
        cache_out = tuple(cache_out) + state_out

    def mlp_or_moe(h_in):
        nonlocal stats
        if not cfg.is_moe:
            return _mlp(h_in, lp, cfg, lora_ids=lora_ids)
        out, stats = _moe(h_in, lp, cfg, valid=valid)
        return out

    def done(x_out):
        return x_out, (tuple(cache_out) + (stats,) if moe_stats
                       else cache_out)

    if cfg.post_block_norms:   # gemma2 sandwich: norm BEFORE the residual
        attn = norm(attn, lp["attn_post_norm"], cfg.norm_type, cfg.norm_eps)
    elif cfg.sublayer_postnorm_only:   # olmo2: x + norm(attn(x))
        attn = norm(attn, lp["attn_norm"], cfg.norm_type, cfg.norm_eps)
    if cfg.residual_scale is not None:   # granite residual_multiplier
        attn = attn * cfg.residual_scale

    if cfg.parallel_residual:
        h2 = h if cfg.shared_attn_mlp_norm else norm(
            x, lp["mlp_norm"], cfg.norm_type, cfg.norm_eps)
        mlp_out = mlp_or_moe(h2)
        if cfg.residual_scale is not None:
            mlp_out = mlp_out * cfg.residual_scale
        return done(x + attn + mlp_out)

    x = x + attn
    if cfg.post_norm:
        x = norm(x, lp["attn_norm"], cfg.norm_type, cfg.norm_eps)

    h = x if (cfg.post_norm or cfg.sublayer_postnorm_only) else norm(
        x, lp["mlp_norm"], cfg.norm_type, cfg.norm_eps)
    moe_out = mlp_or_moe(h)
    if cfg.post_block_norms:
        moe_out = norm(moe_out, lp["mlp_post_norm"], cfg.norm_type,
                       cfg.norm_eps)
    elif cfg.sublayer_postnorm_only:
        moe_out = norm(moe_out, lp["mlp_norm"], cfg.norm_type, cfg.norm_eps)
    if cfg.residual_scale is not None:
        moe_out = moe_out * cfg.residual_scale
    x = x + moe_out
    if cfg.post_norm:
        x = norm(x, lp["mlp_norm"], cfg.norm_type, cfg.norm_eps)
    return done(x)


def _block(x, lp, cache_k, cache_v, *, cfg: ModelConfig, q_positions,
           write_starts, new_lengths, is_prefill, backend, mesh=None,
           cache_ks=None, cache_vs=None, ssm_state=None):
    """One transformer block over the dense cache.

    x: [B,s,D]; cache_k/v: [B,S,Hkv,hd] (this layer's slice);
    write_starts: [B] int32 slot where this token block begins, per sequence.
    Returns (x_out, new_cache_k, new_cache_v[, new_k_scale, new_v_scale]).

    Two attention regimes (ops/attention.py): prefill attends the fresh
    K/V block directly — O(s^2) instead of O(s * max_seq) over the mostly
    empty cache — while decode attends the cache (dequantized at read when
    ``cache_ks``/``cache_vs`` scales are present, ops/kvcache.py).

    ``ssm_state`` (cfg.ssm): this layer's (recurrent state [B,H,P,N],
    conv window) as they stood before the block's first token; the new
    ones come back last. Positions at or past ``new_lengths`` (a
    right-padded prompt's) advance neither.
    """
    quantized = cache_ks is not None
    ssm_mix = None
    if cfg.ssm is not None:
        from distributed_llm_inferencing_tpu.ops import ssm

        def ssm_mix(h, lp):
            out, st, cw = ssm.mix_tokens(
                h, lp, cfg, *ssm_state,
                q_positions < new_lengths[:, None], _linear)
            return out, (st, cw)
    if cfg.mla_latent_cache:
        # latent-layout cache: attention runs entirely inside the
        # absorbed-formulation callback (engine enables this only on
        # eligible meshes — no sp/pp, no kv_quant)
        def mla_latent_attend(h, qp):
            return _mla_latent_attn(
                h, lp, cfg, qp, cache_k, cache_v, write_starts,
                new_lengths, is_prefill, backend)
        x, cache_out = _block_body(x, lp, cfg, q_positions, None,
                                   mla_latent_attend=mla_latent_attend)
        return (x,) + cache_out

    def attend_write(q, k, v):
        if quantized:
            from distributed_llm_inferencing_tpu.ops.kvcache import (
                dequant_kv, quant_kv)
            k8, ks_new = quant_kv(k)
            v8, vs_new = quant_kv(v)
            ck = write_block(cache_k, k8, write_starts)
            cv = write_block(cache_v, v8, write_starts)
            cks = write_block(cache_ks, ks_new, write_starts)
            cvs = write_block(cache_vs, vs_new, write_starts)
            cache_out = (ck, cv, cks, cvs)
            # decode attends the dequantized view; the convert+scale fuses
            # into the attention matmul (reads stay int8 in HBM)
            ck_at = dequant_kv(ck, cks, x.dtype)
            cv_at = dequant_kv(cv, cvs, x.dtype)
        else:
            ck = write_block(cache_k, k, write_starts)
            cv = write_block(cache_v, v, write_starts)
            cache_out = (ck, cv)
            ck_at, cv_at = ck, cv
        if is_prefill and mesh is not None and mesh.shape.get("sp", 1) > 1:
            # sequence-parallel long-context path: ring attention over sp
            # (parallel/ring.py) — K/V chunks rotate via ppermute, no device
            # ever holds the full sequence
            from distributed_llm_inferencing_tpu.parallel.ring import (
                ring_attend_prefill)
            attn = ring_attend_prefill(
                q, k, v, q_positions, new_lengths, mesh=mesh,
                sliding_window=_layer_window(cfg, lp), alibi=_alibi(cfg), softcap=cfg.attn_softcap, sinks=_sinks(cfg, lp))
        elif is_prefill:
            attn = attend_prefill(q, k, v, sliding_window=_layer_window(cfg, lp),
                                  backend=backend, alibi=_alibi(cfg), softcap=cfg.attn_softcap,
                                  sinks=_sinks(cfg, lp))
        elif mesh is not None and mesh.shape.get("sp", 1) > 1:
            # sp-sharded cache decode: flash-decoding partials per shard +
            # one combine (parallel/ring.py ring_attend_decode) — replaces
            # the dense-under-GSPMD fallback
            from distributed_llm_inferencing_tpu.parallel.ring import (
                ring_attend_decode)
            attn = ring_attend_decode(q, ck_at, cv_at, new_lengths,
                                      mesh=mesh,
                                      sliding_window=_layer_window(cfg, lp),
                                      alibi=_alibi(cfg), softcap=cfg.attn_softcap,
                                      sinks=_sinks(cfg, lp))
        else:
            # quantized caches pin the xla formulation: the dequant fuses
            # into its matmul, while a pallas kernel input would
            # materialize the bf16 copy and forfeit the int8 read
            attn = attend_decode(q, ck_at, cv_at, new_lengths,
                                 sliding_window=_layer_window(cfg, lp),
                                 backend="xla" if quantized else backend,
                                 q_positions=q_positions, alibi=_alibi(cfg), softcap=cfg.attn_softcap,
                                 sinks=_sinks(cfg, lp))
        return attn, cache_out

    x, cache_out = _block_body(x, lp, cfg, q_positions, attend_write,
                               ssm_mix=ssm_mix)
    return (x,) + cache_out


def forward(
    params,
    cfg: ModelConfig,
    tokens,                      # [B, s] int32 — a block of new tokens
    cache: KVCache,
    write_starts,                # [B] int32 — first cache slot this block occupies
    q_positions,                 # [B, s] int32 — absolute positions of `tokens`
    new_lengths,                 # [B] int32 — cache lengths after this block
    is_prefill: bool = False,    # static: fresh-KV attention regime
    mesh=None,                   # static: enables the sp ring-attention path
) -> Tuple[jax.Array, KVCache]:
    """Run the model over a block of tokens, updating the cache.

    Used for both prefill (s = padded prompt length, write_starts = 0) and
    decode (s = 1, write_starts = current lengths). Returns
    (logits [B,s,V] float32, updated cache).

    Invariant: cache slot index == absolute token position (the engine always
    writes blocks contiguously per sequence), so kv positions are the slot
    index and validity is slot < length.
    """
    B, s = tokens.shape
    x = embed(params, cfg, tokens, q_positions)

    backend = _cfg_backend(cfg)   # the engine's pin, else one device

    # one body serves both cache layouts: scale planes ride the scan xs
    # only when the cache is quantized. (The unrolled-list and
    # dense-prefix segment dispatch live in scan_layer_stack.)
    # ... and a model with state layers (cfg.ssm) carries each layer's
    # recurrent state and conv window the same way, last
    names = ("k", "v") + (("k_scale", "v_scale") if cache.quantized else ()) \
        + (("ssm", "conv") if cfg.ssm is not None else ())

    def make_body(seg_cfg):
        def body(x, layer_in):
            lp, planes = layer_in[0], dict(zip(names, layer_in[1:]))
            # layer kinds: the dense cache is as wide as the wider kind's
            # K/V heads, and the narrower kind takes its first heads
            hk = seg_cfg.num_kv_heads
            wide = planes if (seg_cfg.attn_kind
                              and planes["k"].shape[2] != hk) else None
            if wide:
                planes = {n: p[:, :, :hk] for n, p in planes.items()}
            out = _block(
                x, lp, planes["k"], planes["v"], cfg=seg_cfg,
                q_positions=q_positions,
                write_starts=write_starts, new_lengths=new_lengths,
                is_prefill=is_prefill, backend=backend, mesh=mesh,
                cache_ks=planes.get("k_scale"),
                cache_vs=planes.get("v_scale"),
                ssm_state=((planes["ssm"], planes["conv"])
                           if "ssm" in planes else None))
            if wide:
                return out[0], tuple(wide[n].at[:, :, :hk].set(o)
                                     for n, o in zip(names, out[1:]))
            return out[0], tuple(out[1:])
        return body

    cache_xs = tuple(getattr(cache, n) for n in names)
    x, cache_out = loop_layer_stack(make_body, x, params, cfg, cache_xs)
    logits = unembed(params, cfg, x)
    return logits, KVCache(lengths=new_lengths, **dict(zip(names, cache_out)))


def prefill(params, cfg: ModelConfig, tokens, lengths, cache: KVCache,
            mesh=None):
    """Prefill a right-padded prompt block. tokens [B,S0], lengths [B].

    Padding tokens beyond each sequence's length land in cache slots that the
    validity mask excludes and that later decode steps overwrite in order, so
    ragged batches need no re-packing.

    Pass ``mesh`` (with an sp axis of size > 1) to run attention
    sequence-parallel via ring attention (parallel/ring.py).
    """
    B, s = tokens.shape
    q_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (B, s))
    return forward(params, cfg, tokens, cache,
                   write_starts=jnp.zeros((B,), jnp.int32),
                   q_positions=q_pos, new_lengths=lengths, is_prefill=True,
                   mesh=mesh)


def decode_step(params, cfg: ModelConfig, tokens, cache: KVCache,
                mesh=None):
    """One decode step. tokens [B,1] — next token per sequence.

    Each sequence writes at its own slot (its current length), so ragged
    batches decode correctly. Lengths advance by 1 for every sequence.

    Pass ``mesh`` (with sp > 1) to attend the sequence-sharded cache via
    the flash-decoding combine (parallel/ring.py ring_attend_decode).
    """
    q_pos = cache.lengths[:, None]  # [B,1] — next position per sequence
    return forward(params, cfg, tokens, cache,
                   write_starts=cache.lengths, q_positions=q_pos,
                   new_lengths=cache.lengths + 1, mesh=mesh)


# ----------------------------------------------------------------------
# Paged-cache forward passes (continuous-batching serving path)
# ----------------------------------------------------------------------

def paged_decode_step(params, cfg: ModelConfig, tokens, paged,
                      block_tables, context_lens, lora_ids=None):
    """One decode step over the paged cache for R serving slots.

    tokens: [R] next token per slot; paged: ops.paged_kvcache.PagedKVCache;
    block_tables: [R, MB] int32; context_lens: [R] — cached tokens per slot
    BEFORE this step (the new token writes at that position).

    Inactive slots must point at a reserved dummy block with context_len 0
    (the batcher guarantees this); their writes land in the dummy block and
    their outputs are discarded. Returns (logits [R, V] f32, new paged).
    """
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache, paged_attend_decode, write_token)
    _no_state_layers(cfg, "paged_decode_step")
    q_pos = context_lens[:, None]                       # [R, 1]
    x = embed(params, cfg, tokens[:, None], q_pos)      # [R, 1, D]
    quantized = paged.quantized

    def make_body(seg_cfg):
        def body(x, layer_in):
            lp, ck, *rest = layer_in                    # ck: [NB, bs, Hkv, hd]
            cv, scales = (rest[0], rest[1:]) if rest else (None, ())

            if seg_cfg.mla_latent_cache:
                def mla_latent_attend(h, qp):
                    rows = _mla_latent_rows(h, lp, seg_cfg, qp)
                    with jax.named_scope("kv_write"):
                        nk = write_token(ck, rows[:, 0], block_tables,
                                         context_lens)
                    attn = _mla_absorbed(
                        _mla_q(h, lp, seg_cfg, qp), lp, seg_cfg,
                        lambda q_eff: paged_attend_decode(
                            q_eff, nk, nk, block_tables, context_lens + 1,
                            scale=_mla_scale(seg_cfg)))
                    return attn, (nk,)
                return _block_body(x, lp, seg_cfg, q_pos, None,
                                   mla_latent_attend=mla_latent_attend)

            def attend_write(q, k, v):
                if quantized:
                    from distributed_llm_inferencing_tpu.ops.kvcache import (
                        quant_kv)
                    cks, cvs = scales
                    with jax.named_scope("kv_write"):
                        k8, ks = quant_kv(k[:, 0])
                        v8, vs = quant_kv(v[:, 0])
                        nk = write_token(ck, k8, block_tables,
                                         context_lens)
                        nv = write_token(cv, v8, block_tables,
                                         context_lens)
                        nks = write_token(cks, ks, block_tables,
                                          context_lens)
                        nvs = write_token(cvs, vs, block_tables,
                                          context_lens)
                    attn = paged_attend_decode(
                        q, nk, nv, block_tables, context_lens + 1,
                        sliding_window=_layer_window(seg_cfg, lp),
                        k_scale_layer=nks, v_scale_layer=nvs,
                        alibi=_alibi(seg_cfg), softcap=seg_cfg.attn_softcap,
                        sinks=_sinks(seg_cfg, lp))
                    return attn, (nk, nv, nks, nvs)
                with jax.named_scope("kv_write"):
                    nk = write_token(ck, k[:, 0], block_tables,
                                     context_lens)
                    nv = write_token(cv, v[:, 0], block_tables,
                                     context_lens)
                attn = paged_attend_decode(
                    q, nk, nv, block_tables, context_lens + 1,
                    sliding_window=_layer_window(seg_cfg, lp),
                    alibi=_alibi(seg_cfg), softcap=seg_cfg.attn_softcap,
                    sinks=_sinks(seg_cfg, lp))
                return attn, (nk, nv)

            return _block_body(x, lp, seg_cfg, q_pos, attend_write,
                               lora_ids=lora_ids)
        return body

    x, cache_out = loop_layer_stack(make_body, x, params, cfg,
                                    paged.planes())
    logits = unembed(params, cfg, x)[:, 0]              # [R, V]
    return logits, PagedKVCache(*cache_out)


def _no_state_layers(cfg: ModelConfig, what: str):
    """The paths that carry no per-slot cache refuse a model that has
    one (cfg.ssm's state, cfg.swa's ring) by name; the decode chunk and
    the wave admission carry both."""
    if cfg.ssm is not None:
        raise NotImplementedError(
            f"{cfg.name}: {what} carries no state-space state (cfg.ssm); "
            "paged_prefill_tail and paged_decode_chunk do")
    if cfg.swa is not None:
        raise NotImplementedError(
            f"{cfg.name}: {what} carries no window ring (cfg.swa: the "
            "windowed layers' K and V lie in a ring a slot, the pool holds "
            "the full layers alone); paged_prefill_tail and "
            "paged_decode_chunk do")


# Cap for materializing the whole chunk's pool gather [L, R, P, Hkv, hd]
# up front (see paged_decode_chunk): under it, one gather per chunk; over
# it (long contexts), one transient per-layer gather per step.
_PREGATHER_MAX_BYTES = 256 * 1024 * 1024


@jax.named_scope("kv_gather")
def _pool_pregather(paged, block_tables, dt):
    """Every plane of every slot's whole block table, all layers in one
    gather each: [L, R, MB*bs, Hkv, w] a plane (K and V, or a latent
    pool's one), dequantized to ``dt`` where the pool is int8."""
    r, mb = block_tables.shape

    def gather(plane):                  # [L, NB, bs, ...] -> [L, R, MB*bs, ...]
        g = plane[:, block_tables]
        return g.reshape(g.shape[0], r, mb * g.shape[3], *g.shape[4:])
    if paged.quantized:
        from distributed_llm_inferencing_tpu.ops.kvcache import dequant_kv
        return (dequant_kv(gather(paged.k), gather(paged.k_scale), dt),
                dequant_kv(gather(paged.v), gather(paged.v_scale), dt))
    return tuple(gather(p) for p in paged.planes())


@jax.named_scope("kv_gather")
def _layer_gather(pool, scales, block_tables, dt, layer, kind=None):
    """One layer's planes gathered inside the step (long contexts, where
    the whole chunk's gather would pass _PREGATHER_MAX_BYTES). ``pool``
    and ``scales`` (an int8 pool's (k_scale, v_scale), else empty) are
    the stacked [L, NB, ...] planes as they lie and ``layer`` the
    layer's index, the scan's or a constant where layers are held one by
    one: the gather goes by (layer, block), so no layer's slice is
    copied out of the stack, on the way to a lax.switch branch or
    hoisted out of the token loop. ``kind`` (win | full) names the
    layer's kind as an inner scope where the model has both."""
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        gather_seq, kind_scope)
    with kind_scope(kind):
        got = tuple(gather_seq(p, block_tables, layer) for p in pool)
        if scales:
            from distributed_llm_inferencing_tpu.ops.kvcache import (
                dequant_kv)
            got = tuple(
                dequant_kv(g, gather_seq(sc, block_tables, layer), dt)
                for g, sc in zip(got, scales))
    return got


def _write_side(side, new, at, layer):
    """Put a pass's fresh rows ``new`` ([R, n, Hkv, w] a plane) into the
    chunk's side buffers at entry ``at`` (zeros after them where the
    buffers are as wide as a latent pool stores a row). Returns (the
    buffers to carry on, this layer's [R, K, Hkv, w] rows for
    attention's side segment).
    ``side`` is the whole [L, R, K, Hkv, w] stack riding the layer
    stack's carry and ``layer`` the layer's index: the rows are written
    in place and the stack is never sliced into per-layer inputs and
    stacked again from outputs."""
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import fit_rows
    with jax.named_scope("kv_write"):
        side = tuple(
            jax.lax.dynamic_update_slice(s_, fit_rows(n_, s_)[None],
                                         (layer, 0, at, 0, 0))
            for s_, n_ in zip(side, new))
        return side, tuple(s_[layer] for s_ in side)


def _layer_kind(cfg: ModelConfig, window):
    """win | full where the model mixes windowed and full layers and
    this layer's window is a trace-time constant; else None."""
    if cfg.attn_windows is None:
        return None
    if window is None:
        return "full"
    return "win" if isinstance(window, int) else None


def _layers_scanned(params, cfg: ModelConfig) -> bool:
    """Whether every segment of the layer stack runs under a lax.scan
    (scan_layer_stack); the batcher holds MoE layers one by one."""
    return not any(isinstance(seg, (list, tuple))
                   for seg, _, _, _ in layer_segments(params, cfg))


def _pool_ladder(mb: int, scanned: bool = True):
    """Static rungs of block counts the decode chunks may stop the pool
    at: 1, 2, 3, 4, 6 and 8 eighths of the block table's ``mb`` columns,
    so a rung is at most 1.5 times the one below it from a quarter up
    (mistral's 128 -> 16/32/48/64/96/128, a toy 6 -> 1/2/3/5/6). A rung
    is a branch of one lax.switch in the layer body, not a program. This
    is the XLA form of the pool's read: where _pool_kernel takes the
    Pallas kernel (which stops at each slot's own length: mistral-7b,
    Ouro-2.6B, kanana, falcon-h1 and mimo-v2.5's full layers among the
    benchmark's cells, so none of them runs a rung) no ladder and no
    switch are built.
    A conditional takes its operands as buffers: they are the stacked
    pool as it lies and the layer's index, and the branch gathers by
    (layer, block) (_layer_gather); handed the scan's slice of the pool
    instead, XLA copied every layer's slice out of the stack on every
    pass (PERF.md section 6, PR 36). Where layers are held one by one
    (not ``scanned``) a branch in the layer body fuses less than the
    code outside it and a switch around the whole chunk costs seconds a
    program at every start, so there the ladder is the full extent
    alone: lax.switch inlines its one branch, and the gather fuses into
    attention (trinity's full layers; kanana's, or mimo-v2.5's full
    layers', wherever the kernel is not taken: a mesh, a quantized pool,
    the CPU). PERF.md section 6, PR 30, has the chip's numbers
    for each."""
    if not scanned:
        return (mb,)
    return tuple(sorted({-(-mb * n // 8) for n in (1, 2, 3, 4, 6, 8)}))


def _pool_rung(ladder, bs: int, context_lens, live):
    """Index of the smallest rung whose positions hold every live slot's
    pool horizon, and that rung's positions. The pool only holds
    positions < context_lens during a chunk (its own tokens sit in the
    side buffer), so a position at or past the longest live context has
    weight exactly zero in every slot: leaving it out is the same
    mathematics. A slot with no budget does not count, whatever stale
    length it carries."""
    need = jnp.max(jnp.where(live, context_lens, 0))
    extents = jnp.asarray([m * bs for m in ladder], jnp.int32)
    rung = jnp.sum(need > extents[:-1]).astype(jnp.int32)
    return rung, extents[rung]


def _attend_pool_rung(rung, ladder, pre: bool, planes, scales, block_tables,
                      dt, pool_pos, pool_valid, attend_pool, layer,
                      kind=None):
    """The pool side of a decode chunk's attention, as far as ``rung``
    says (lax.switch: only the taken branch runs). Branch i takes the
    first ``ladder[i]`` columns of the block tables -- a slice of the
    pre-gathered planes when ``pre``, else this layer's gather -- and
    calls ``attend_pool(planes, positions, valid)``, which brings the
    side segment: all scores still meet in one softmax. ``planes`` (and
    ``scales``) are stacked [L, ...] and ``layer`` is the layer's index:
    the branch takes them as they lie (_layer_gather)."""
    bs = pool_pos.shape[1] // block_tables.shape[1]

    def branch(mb_i):
        def run():
            n = mb_i * bs
            if pre:
                got = tuple(p[layer, :, :n] for p in planes)
            else:
                got = _layer_gather(planes, scales, block_tables[:, :mb_i],
                                    dt, layer, kind)
            return attend_pool(got, pool_pos[:, :n], pool_valid[:, :n])
        return run
    return jax.lax.switch(rung, [branch(m) for m in ladder])


def _pool_kernel(cfg: ModelConfig, paged):
    """Which form a decode chunk's read of the pool takes, from what the
    trace can see: ``cfg.pool_kernel`` (pinned by the batcher: "pallas"
    only in a one-device TPU program, since GSPMD does not partition a
    Pallas call) where ops/pallas/paged_attention.py computes this
    model's attention and reads this pool as it lies -- planes
    unquantized and in the compute dtype; K and V heads of whole lanes
    that fill a tile's 8 sublanes (one head: MQA, or a latent pool's
    one plane of shared rows, stored lane_width wide, which the kernel
    takes as K and V at once; fewer than 8: such a pool holds flat
    rows, below), the query heads any multiple of them; no ALiBi, sinks or
    softcap; a window that is None or one trace-time integer, and none
    over a latent pool -- else None: the in-loop gather as far as
    _pool_ladder's rung (_attend_pool_rung). The stack may be scanned (the kernel takes the
    layer's index from the scan) or held layer by layer (the index is a
    constant handed in as an array: one lowering for all of them). A
    model with layer kinds (cfg.swa) is judged by its pool-backed (full)
    layers' own config (kind_cfg), its windowed layers' ring being no
    part of the pool. A pool of flat rows (ops/paged_kvcache.flat_pool:
    one row a position, its K/V heads side by side; a model with layer
    kinds' V rows narrower than its K rows) the kernel takes where both
    widths and a value head are whole lanes (a query head's context is
    then whole lanes of a V row). In
    the benchmark's cells the kernel serves mistral-7b, Ouro-2.6B,
    kanana (its latent MQA plane, 7 layers held one by one),
    falcon-h1 (20 query heads over rows of 4 K/V heads: 512 columns)
    and mimo-v2.5's two full
    layers (rows of 768 and 512 columns under 64 query heads); trinity
    (per-layer windows) keeps the XLA form, as do int8 pools, meshes,
    the speculative chunk and the CPU."""
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import flat_pool
    attn = cfg if cfg.swa is None else cfg.kind_cfg("full", 1)
    if (not cfg.pool_kernel.startswith("pallas") or paged.quantized
            or cfg.attn_windows is not None
            or attn.position_embedding == "alibi" or attn.attn_sinks
            or attn.attn_softcap is not None
            or paged.k.dtype != jnp.dtype(cfg.dtype)
            or (cfg.mla_latent_cache and cfg.sliding_window is not None)):
        return None
    from distributed_llm_inferencing_tpu.ops.pallas import paged_attention
    if not paged_attention.supported(paged.k.shape[3], paged.k.shape[4],
                                     paged.k.dtype):
        return None
    if flat_pool(cfg, paged) and not (
            cfg.v_head_dim_effective % paged_attention.LANES == 0
            and paged.v.shape[4] == cfg.num_kv_heads
            * cfg.v_head_dim_effective):
        return None
    return cfg.pool_kernel


def _ssm_kernel(cfg: ModelConfig, paged):
    """Which form a decode chunk's one-step state update takes:
    ``cfg.pool_kernel`` (the batcher's pin: "pallas" only in a
    one-device TPU program) where ops/pallas/ssm_step.py takes the state
    plane's shape (float32, a head's [d_head, d_state] in whole tiles:
    Falcon-H1-34B's 128 x 256), else None: the jax.numpy form
    (ops/ssm.mix_step)."""
    if not cfg.pool_kernel.startswith("pallas"):
        return None
    from distributed_llm_inferencing_tpu.ops.pallas import ssm_step
    c = cfg.ssm
    if not ssm_step.supported(c.n_heads, c.n_groups, c.d_head, c.d_state,
                              paged.ssm.dtype):
        return None
    return cfg.pool_kernel


def _flat_rows_q(q, hkv: int, k_rows):
    """Query heads [R, Sq, H, hd] zero-expanded to the width of
    ``k_rows``' flat rows ([..., W]: hkv heads' columns, then zeros):
    each head's own values in its K/V head's columns, so that one
    contraction over a whole row is its scores (the other heads' columns
    meet zeros). [R, Sq, H, W]."""
    b, sq, h, hd = q.shape
    wide = jnp.einsum("bqhgd,hk->bqhgkd", q.reshape(b, sq, hkv, h // hkv, hd),
                      jnp.eye(hkv, dtype=q.dtype))
    wide = wide.reshape(b, sq, h, hkv * hd).astype(k_rows.dtype)
    pad = k_rows.shape[-1] - hkv * hd   # (a ring's rows: lane_width)
    return jnp.pad(wide, [(0, 0)] * 3 + [(0, pad)]) if pad else wide


def _attend_flat_rows(q, k, v, hkv: int, vd: int, *args, **kw):
    """ops/attention.attend over caches that store a position's K/V
    heads side by side in ONE row (ops/paged_kvcache.flat_rows: a
    one-device pool of few K/V heads, a model with layer kinds' pool and
    ring), read as they lie: the XLA form (a windowed layer's ring in
    every program; gathered pool rows where _pool_kernel does not take
    the Pallas kernel, which reads the same rows in the pool by the
    same expansion of q: trinity-mini's per-layer windows, the CPU).
    ``k``, ``v``:
    segments [R, S, 1, W] (W the plane's width: hkv heads' columns, then
    zeros). Each query head goes in zero-expanded to the row's width
    (_flat_rows_q), and of the context that comes back as wide as a V
    row it keeps its own head's columns (the kernel does so inside the
    call, a head's columns being whole lanes there). Four (eight)
    times the products of the head-by-head
    form, on a handful of query rows; what it spares is the relayout of
    the gathered K and V that a view of 768 columns as 4 heads of 192
    costs (0.61 + 0.32 s of an 8 s trace, a layer; PERF.md section 6,
    PR 45). q [R, Sq, H, hd] -> [R, Sq, H, vd]."""
    from distributed_llm_inferencing_tpu.ops.attention import attend
    return _own_columns(
        attend(_flat_rows_q(q, hkv, k[0]), k, v, *args,
               scale=q.shape[-1] ** -0.5, **kw), hkv, vd)


def _own_columns(ctx, hkv: int, vd: int):
    """Of a context as wide as a flat V row, [R, Sq, H, W], each query
    head's own K/V head's columns: [R, Sq, H, vd]."""
    b, sq, h, _ = ctx.shape
    ctx = ctx[..., :hkv * vd].reshape(b, sq, hkv, h // hkv, hkv, vd)
    return jnp.einsum("bqhgkv,hk->bqhgv", ctx,
                      jnp.eye(hkv, dtype=ctx.dtype)).reshape(b, sq, h, vd)


def paged_decode_chunk(params, cfg: ModelConfig, k: int, tokens, paged,
                       block_tables, context_lens, seeds, steps0, temps,
                       tks, tps, ds, budget, eos_ids, dummy_block: int,
                       lora_ids=None):
    """Run K decode steps + sampling entirely on device for R serving slots.

    The continuous batcher's throughput lever: one dispatched program
    advances every active slot up to ``k`` tokens, so the host syncs once
    per chunk instead of once per token (the same chunked-scan trade the
    engine makes, runtime/engine.py DECODE_CHUNKS — a per-token host round
    trip is what made the reference's loop unshippable behind a network
    hop, reference worker/app.py:297-305).

    Per-slot lifecycle runs as data inside the scan:
    - ``budget[r]``: how many tokens slot r may still emit (0 = inactive).
      A slot is *alive* until its budget is spent or it samples its eos.
    - ``eos_ids[r]``: per-slot eos token (-1 = none). The eos token itself
      is not emitted (mirrors the host-side scheduler semantics).
    - Dead slots keep running (lax.scan needs static shapes) but their
      cache writes are redirected to the reserved ``dummy_block`` and
      their outputs masked out of ``emits``.

    Sampling folds ``steps0 + t`` into each slot's own PRNG stream, so a
    request's tokens stay a pure function of (params, prompt, seed) —
    bit-identical whether decoded one token or K tokens per dispatch.

    Memory-access structure: the chunk's fresh K/V accumulates in a small
    *side buffer* [L, R, K, Hkv, hd] (dynamic_update_slice at step index)
    instead of two dynamic scatters into the block pool per layer per
    step, and the whole side buffer scatters into the pool in ONE op
    after the scan. No cache state rides the layer stack as per-layer
    inputs and outputs, scanned or held one by one (the batcher's MoE
    layers): a layer takes its index, the side buffers ride the stack's
    carry whole and are written in place at (layer, 0, step, 0, 0)
    (_write_side), and the in-loop gather indexes the stacked pool by
    (layer, block). (Static slices of the loop-invariant pool were
    hoisted out of the token loop and re-laid out, the whole pool once a
    chunk: PERF.md section 6, PR 38.) Each step's attention takes two KV
    segments, the pool's positions ``< cl0`` and ``side masked <= t``:
    their scores meet in one softmax and K and V are never concatenated
    or widened. The pool is loop-invariant during the chunk, which is
    what makes the split exact. The pool's segment takes one of two
    forms (``_pool_kernel``, from what the trace can see). *The kernel*
    (a one-device TPU program, unquantized K and V planes whose heads
    fill a tile's 8 sublanes, a latent pool's one plane of whole lanes,
    or flat rows, a position's few K/V heads side by side: mistral-7b,
    Ouro-2.6B, kanana, falcon-h1, mimo-v2.5's full layers):
    ops/pallas/paged_attention.paged_attend reads each live slot's pages
    where the pool lies, by (layer, block-table entry), as far as that
    slot's own context, and keeps both segments' softmax inside the
    call: K and V cross HBM once and nothing of their size is written.
    *The in-loop gather* (everything else; ops/attention.attend over
    ``gather(pool) masked < cl0`` and the side rows): on the chip
    (PERF.md section 5) the per-layer gather of every slot's block table
    (``kv_gather``) writes a copy that attention reads, K and V crossing
    HBM three times. Both stop at the rung of ``_pool_ladder`` that holds
    the longest live context (``_pool_rung``, chosen on the device from
    ``context_lens`` and ``budget`` before the scan; ``_attend_pool_rung``
    is a lax.switch inside this one program): positions past it have
    weight zero in every slot, so the result is the full extent's.
    Where layers are held one by one (the ladder is then the full extent
    alone) a layer's window is a trace-time constant, and a windowed
    layer gathers and reads, a slot, only the block-table columns that
    hold its window behind that slot's context (``window_read``); full
    layers, a scanned stack's traced per-layer windows, and a chunk small
    enough to pre-gather read as before.

    An MLA model's pool is latent (cfg.mla_latent_cache): one plane of
    shared rows, so one side buffer, and attention is the absorbed form
    (_mla_absorbed) over (gathered rows, side rows), the rows standing
    for K and for V alike. Under the kernel the side buffer is as wide
    as the pool stores a row (lane_width: zeros after the rd + r
    columns), the query is padded with zeros to match, and a page's rows
    are fetched once for the scores and the weighted sum.

    A pool of flat rows (ops/paged_kvcache.flat_pool: a one-device pool
    of fewer K/V heads than a tile's 8 sublanes, a model with layer
    kinds' full layers) keeps side buffers as wide as its rows and is
    read as it lies: by the kernel (q zero-expanded to a K row,
    _flat_rows_q; a head's own columns of V picked inside the call) or,
    where it is not taken, by the gather (a windowed layer's bounded
    columns, the ladder's rung) and the same expansion
    (_attend_flat_rows). A model with layer kinds (cfg.swa) keeps a side
    buffer a kind and plane, and its windowed layers read their slot's
    ring below the horizon where it lies, in XLA.

    tokens: [R] last emitted token per slot; steps0: [R] tokens emitted so
    far. Returns (toks [K, R] int32, emits [K, R] bool, moe int32 [5],
    pool_positions int32, window_positions int32, new paged); the
    emitted tokens of slot r are
    ``toks[:emits[:, r].sum(), r]``, ``moe`` is the sum of _moe's
    MOE_STATS vectors over the chunk's passes and MoE layers, slots no
    longer alive counted as idle rows (zeros for a dense model), and
    ``pool_positions`` is the pool extent each slot was gathered and
    attended over on every pass of this chunk (the ladder's rung; under
    the kernel the longest live context in whole blocks, which is what
    it walks at most), ``window_positions`` what
    a windowed layer read instead (the widest, should widths differ;
    ``pool_positions`` where no layer took the bounded read).

    A looped model (cfg.loop_steps > 1) runs the stack that many times a
    pass (loop_layer_stack): the side buffers and the pool have a plane
    a (step, layer) pair, a layer's index into both is its pair's.
    """
    return decode_chunk_with_logits(
        params, cfg, k, tokens, paged, block_tables, context_lens, seeds,
        steps0, temps, tks, tps, ds, budget, eos_ids, dummy_block,
        lora_ids=lora_ids)[:-1]


def decode_chunk_with_logits(params, cfg: ModelConfig, k: int, tokens, paged,
                             block_tables, context_lens, seeds, steps0,
                             temps, tks, tps, ds, budget, eos_ids,
                             dummy_block: int, lora_ids=None):
    """paged_decode_chunk: what it returns and, last, the passes' logits
    [K, R, V] float32. No serving program takes
    the logits (a jit drops the output nothing reads, so
    paged_decode_chunk's program has none); a comparison with a plain
    reference reads them where the pool is too large to copy for a
    second path (benchmarks/chip/compare_reference_loop.py)."""
    from distributed_llm_inferencing_tpu.ops.attention import attend
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache, flat_pool, flat_rows, kind_scope, ring_read,
        window_read, write_rows)
    from distributed_llm_inferencing_tpu.ops.sampling import sample_batch

    r = tokens.shape[0]
    L = cfg.cache_planes                  # a plane a (loop step, layer)
    kinds = cfg.swa is not None           # two caches for two layer kinds
    if kinds:
        L = paged.k.shape[0]              # the pool's planes: full layers
    bs = paged.block_size
    mb = block_tables.shape[1]
    dt = jnp.dtype(cfg.dtype)             # compute dtype (pool may be int8)
    quantized = paged.quantized
    latent = cfg.mla_latent_cache         # one plane of shared rows, no V
    flat = flat_pool(cfg, paged)          # a position's heads in one row
    n_planes = 1 if latent else 2
    cl0 = context_lens                    # pool horizon, fixed this chunk
    pool_pos = jnp.broadcast_to(jnp.arange(mb * bs, dtype=jnp.int32),
                                (r, mb * bs))
    pool_valid = pool_pos < cl0[:, None]
    kernel = _pool_kernel(cfg, paged)
    ladder = _pool_ladder(mb, _layers_scanned(params, cfg))
    if kernel:
        # the kernel walks each live slot's block table to its own
        # length: no rung, no branch; the extent a pass reads at most is
        # the longest live context's, in whole blocks
        from distributed_llm_inferencing_tpu.ops.pallas import (
            paged_attention)
        pool_positions = -(-jnp.max(jnp.where(budget > 0, cl0, 0))
                           // bs) * bs
        # (layer kinds: the pool is the full layers', flat rows of K
        # and of V, and the window is the ring's layers')
        walk = paged_attention.pool_walk(
            cl0, budget > 0, paged.k, mb,
            sliding_window=None if kinds else cfg.sliding_window,
            n_planes=n_planes, v_planes=paged.v)
    else:
        rung, pool_positions = _pool_rung(ladder, bs, cl0, budget > 0)
    side_pos = cl0[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    # (the kernel reads the side rows beside a page's: as wide as those)
    side0 = (jnp.zeros(
        (L, r, k, cfg.cache_kv_heads,
         paged.k.shape[-1] if kernel else cfg.cache_head_dim),
        dt),) * n_planes
    vd = cfg.v_head_dim_effective

    def sides(planes):   # rows as flat planes store them
        return tuple(jnp.zeros((p.shape[0], r, k, 1, p.shape[-1]), dt)
                     for p in planes)
    if flat:
        side0 = sides(paged.planes())
    if kinds:
        # a side buffer a kind and plane: the full layers' rows go to the
        # pool after the scan, the windowed layers' to the ring, which
        # holds what lies below the chunk's horizon (ring_read: fixed for
        # the chunk like the pool's), slot r in row r
        assert paged.ring_k.shape[1] == r + 1, (paged.ring_k.shape, r)
        side0 = side0 + sides((paged.ring_k, paged.ring_v))
        ring = paged.ring_k.shape[2]
        ring_pos, ring_valid = ring_read(ring, cl0)
    if cfg.ssm is not None:
        # state layers: the per-slot state and conv planes ride the
        # carry behind the side buffers, whole, and each layer of each
        # pass reads its R rows and writes them back in place (slot r is
        # row r; the dummy row behind them is the admit programs')
        from distributed_llm_inferencing_tpu.ops import ssm
        assert paged.ssm.shape[1] == r + 1, (paged.ssm.shape, r)
        side0 = side0 + (paged.ssm, paged.conv)
        ssm_kernel = _ssm_kernel(cfg, paged)

    # Pool K/V is loop-invariant: gather it ONCE for the whole chunk when
    # the materialization is modest; at long contexts fall back to a
    # per-step per-layer gather (transient, one layer at a time).
    gathered_bytes = n_planes * dt.itemsize * L * r * mb * bs \
        * cfg.cache_kv_heads * cfg.cache_head_dim
    pre = gathered_bytes <= _PREGATHER_MAX_BYTES and not kernel
    if pre:
        pool, scales = _pool_pregather(paged, block_tables, dt), ()
    else:                                # gathered per layer in-loop
        pool, scales = paged.planes()[:n_planes], paged.planes()[n_planes:]
    # window -> (block ids, positions, validity) of the bounded read,
    # fixed for the chunk like the pool's horizon
    win_reads = {}
    if cfg.attn_windows is not None and not pre and len(ladder) == 1:
        for w in {w for w in cfg.attn_windows if w is not None}:
            read = window_read(w, bs, block_tables, cl0)
            if read is not None:
                win_reads[w] = read + (read[1] < cl0[:, None],)
    window_positions = (jnp.int32(max(v[1].shape[1]
                                      for v in win_reads.values()))
                        if win_reads else pool_positions)
    if kinds:   # what a windowed layer reads instead: its slot's ring
        window_positions = jnp.int32(ring)

    def body(carry, t):
        cur, side, cl, alive = carry
        q_pos = jnp.where(alive, cl, 0)[:, None]
        x = embed(params, cfg, cur[:, None], q_pos)
        # monotone aliveness: a slot alive at t wrote at every i <= t, so
        # the step-index mask alone is exact for rows that matter
        side_valid = jnp.broadcast_to(
            jnp.arange(k, dtype=jnp.int32)[None, :] <= t, (r, k))

        def make_layer(seg_cfg):
            def layer(carry, layer_in):
                (x, sd), (lp, li) = carry, layer_in

                def attend_kernel(q, rows, scale=None):
                    # pool and side rows in one softmax inside the call,
                    # the planes taken where they lie at the layer's
                    # index; a latent pool's one plane as K and V alike;
                    # flat rows under q expanded to them, a head's own
                    # columns of V picked inside the call
                    with jax.named_scope("attention"), \
                            kind_scope("attention_full" if kinds else None):
                        if flat:
                            q, scale = (_flat_rows_q(q, cfg.num_kv_heads,
                                                     rows[0]),
                                        q.shape[-1] ** -0.5)
                        return paged_attention.paged_attend(
                            q, pool[0], pool[-1], li, block_tables, cl0,
                            cl0 + t, walk, (rows[0], rows[-1], t),
                            sliding_window=seg_cfg.sliding_window,
                            scale=scale, v_head_dim=vd if flat else None,
                            interpret=kernel == "pallas_interpret")

                def attend_side(q, sd2, sliding_window=None, **kw):
                    kind = _layer_kind(cfg, sliding_window)

                    if kinds:
                        kind = "attention_full"

                    def attend_pool(got, pos, valid):
                        if latent:   # the rows' own columns (lane_width)
                            got = (got[0][..., :cfg.cache_head_dim],)
                        if flat:   # a position's heads lie in one row
                            with jax.named_scope("attention"), \
                                    kind_scope(kind):
                                return _attend_flat_rows(
                                    q, (got[0], sd2[0]), (got[1], sd2[1]),
                                    cfg.num_kv_heads, vd, q_pos,
                                    (pos, side_pos), (valid, side_valid),
                                    sliding_window=sliding_window, **kw)
                        with jax.named_scope("attention"), kind_scope(kind):
                            # a latent pool's rows stand for K and for V
                            return attend(
                                q, (got[0], sd2[0]), (got[-1], sd2[-1]),
                                q_pos, (pos, side_pos), (valid, side_valid),
                                sliding_window=sliding_window, **kw)
                    if kind == "win" and sliding_window in win_reads:
                        bt_w, pos_w, valid_w = win_reads[sliding_window]
                        return attend_pool(
                            _layer_gather(pool, scales, bt_w, dt, li, kind),
                            pos_w, valid_w)
                    return _attend_pool_rung(
                        rung, ladder, pre, pool, scales, block_tables, dt,
                        pool_pos, pool_valid, attend_pool, li, kind)

                def done(x2, out):
                    # (side buffers..., moe): the buffers go on in the
                    # stack's carry, the layer's MOE_STATS out
                    return (x2, out[:-1]), out[-1:]

                tail = dict(valid=alive[:, None], moe_stats=True)
                if cfg.ssm is not None:
                    def ssm_mix(h, lp):
                        stp, cwp = sd[n_planes:]
                        cw = jax.lax.dynamic_slice(
                            cwp, (li, 0, 0), (1, r) + cwp.shape[2:])[0]
                        out, stp, cw = ssm.mix_step(
                            h, lp, seg_cfg, stp, li, cw, alive, _linear,
                            kernel=ssm_kernel)
                        return out, (stp, jax.lax.dynamic_update_slice(
                            cwp, cw[None], (li, 0, 0)))
                    tail["ssm_mix"] = ssm_mix
                if latent:
                    def mla_latent_attend(h, qp):
                        sd2, rows = _write_side(
                            sd, (_mla_latent_rows(h, lp, seg_cfg, qp),), t,
                            li)
                        attn = _mla_absorbed(
                            _mla_q(h, lp, seg_cfg, qp), lp, seg_cfg,
                            lambda q_eff: (
                                attend_kernel if kernel else attend_side)(
                                    q_eff, rows, scale=_mla_scale(seg_cfg)))
                        return attn, sd2
                    return done(*_block_body(
                        x, lp, seg_cfg, q_pos, None,
                        mla_latent_attend=mla_latent_attend, **tail))

                def attend_ring(q, kh, vh):
                    # a windowed layer: its slot's ring below the
                    # horizon and the chunk's own rows, one softmax
                    sd2, rows = _write_side(
                        sd[2:], (flat_rows(kh), flat_rows(vh)), t, li)
                    with jax.named_scope("attention"), \
                            jax.named_scope("attention_swa"):
                        return _attend_flat_rows(
                            q, (paged.ring_k[li, :r], rows[0]),
                            (paged.ring_v[li, :r], rows[1]),
                            cfg.swa.num_kv_heads, vd, q_pos,
                            (ring_pos, side_pos), (ring_valid, side_valid),
                            sliding_window=seg_cfg.sliding_window,
                            sinks=_sinks(seg_cfg, lp)), sd[:2] + sd2

                def attend_write(q, kh, vh):
                    if seg_cfg.attn_kind == "swa":
                        return attend_ring(q, kh, vh)
                    if flat:
                        kh, vh = flat_rows(kh), flat_rows(vh)
                    sd2, rows = _write_side(sd[:n_planes], (kh, vh), t, li)
                    if kinds:
                        sd2 = sd2 + sd[2:]
                    if kernel:
                        return attend_kernel(q, rows), sd2
                    return attend_side(
                        q, rows, sliding_window=_layer_window(seg_cfg, lp),
                        alibi=_alibi(seg_cfg), softcap=seg_cfg.attn_softcap,
                        sinks=_sinks(seg_cfg, lp)), sd2
                return done(*_block_body(x, lp, seg_cfg, q_pos, attend_write,
                                         lora_ids=lora_ids, **tail))
            return layer

        (x2, side), (moe,) = loop_layer_stack(
            make_layer, (x, side), params, cfg,
            (jnp.asarray(cfg.cache_index, jnp.int32) if kinds
             else jnp.arange(L, dtype=jnp.int32),))
        logits = unembed(params, cfg, x2, as_computed=True)[:, 0]
        with jax.named_scope("sample"):
            nxt = sample_batch(logits, seeds, steps0 + t, temps, tks, tps,
                               ds)
        is_eos = alive & (eos_ids >= 0) & (nxt == eos_ids)
        emit = alive & ~is_eos
        new_cl = cl + alive.astype(cl.dtype)   # advance iff wrote this step
        new_alive = emit & (t + 1 < budget)
        # a pass nobody is alive in (the chunk outran every budget)
        # counts for nothing
        moe = jnp.sum(moe, axis=0) * jnp.any(alive)
        return (nxt, side, new_cl, new_alive), (
            nxt, emit, alive, moe, logits.astype(jnp.float32))

    (_, side, _, _), (toks, emits, wrote, moe, logits) = jax.lax.scan(
        body, (tokens, side0, context_lens, budget > 0),
        jnp.arange(k, dtype=jnp.int32))
    # the largest load of a pass and layer adds up like the others: the
    # reader divides by layer_passes
    moe = jnp.sum(moe, axis=0)

    # ONE scatter of the whole chunk's K/V into the pool (never-written
    # steps of dead/inactive slots land in the reserved dummy block)
    with jax.named_scope("kv_write"):
        pos = cl0[None, :] + jnp.arange(k, dtype=jnp.int32)[:, None]  # [K, R]
        blk = jnp.take_along_axis(block_tables,
                                  jnp.swapaxes(pos // bs, 0, 1), axis=1)
        blk = jnp.where(wrote, jnp.swapaxes(blk, 0, 1), dummy_block)  # [K, R]
        off = pos % bs
        if quantized:
            from distributed_llm_inferencing_tpu.ops.kvcache import quant_kv
            k8, ks = quant_kv(side[0])
            v8, vs = quant_kv(side[1])
            side = (k8, v8, ks, vs)
        if cfg.ssm is not None:   # the state planes, as the passes left them
            paged = paged._replace(ssm=side[n_planes], conv=side[n_planes + 1])
        if kinds:
            # the windowed layers' rows: position p at p % ring of the
            # slot's own row, a dead slot's in the dummy row
            with jax.named_scope("ring_write"):
                row = jnp.where(wrote, jnp.arange(r)[None, :], r)   # [K, R]
                paged = paged._replace(**{
                    name: write_rows(getattr(paged, name),
                                     jnp.swapaxes(sd, 1, 2), row, pos % ring)
                    for name, sd in zip(("ring_k", "ring_v"), side[2:])})
            side = side[:2]
        return (toks, emits, moe, pool_positions, window_positions,
                paged.with_planes(tuple(
                    write_rows(plane, jnp.swapaxes(sd, 1, 2), blk, off)
                    for plane, sd in zip(paged.planes(), side))),
                logits)


def paged_speculative_chunk(params, cfg: ModelConfig, k: int, gamma: int,
                            tokens, history, paged, block_tables,
                            context_lens, seeds, steps0, temps, tks, tps,
                            ds, budget, eos_ids, dummy_block: int,
                            gammas=None, lora_ids=None):
    """K speculative iterations on device for R serving slots: draft
    gamma tokens per slot by on-device prompt lookup
    (ops/speculative.py propose_ngram_device), score [cur, drafts] in one
    forward block, and keep the prefix the target distribution agrees
    with — up to gamma+1 tokens per slot per iteration, still one host
    sync per chunk.

    The engine's speculative path (ops/speculative.py verify_step) hands
    drafting to the host between steps; behind a dispatch round trip that
    forfeits the entire speedup, so here the token history rides in a
    device buffer and drafting is a compare/gather inside the scan.

    Acceptance (ops/speculative.py accept_rejection_batch): greedy rows
    (``~ds``) accept drafts matching the raw argmax — output is
    bit-identical to plain greedy decode, only faster. Sampling rows run
    exact per-row data-parameterized leave-one-out rejection against the
    warped distribution ``sample_batch`` draws from — the emitted
    distribution is preserved exactly while accepted drafts compress
    iterations, so serving-default do_sample requests speed up too.
    (Rows whose top_k exceeds sampling.PREFIX_K — no realistic serving
    config — fall back to one bit-identical sample per iteration.)

    Cache bookkeeping (the subtle part): every iteration writes K/V for
    all gamma+1 scored tokens into a side buffer at a STATIC offset
    ``t*(gamma+1)`` (dynamic_update_slice — no scatters in the loop),
    with each entry's absolute position recorded in ``side_pos``.
    Rejected entries' positions get re-written by later iterations, so
    validity cannot be position-derived: an ``accepted`` mask carry
    marks entries committed at their own iteration (entry i of the
    block is committed iff i <= n_acc — entry 0 is ``cur``, whose
    position was already owed to the cache). Attention at iteration t
    sees pool(< cl0) + accepted side entries + the current block
    (causally masked); the single post-scan pool scatter writes exactly
    the accepted entries, everything else landing in ``dummy_block``.

    tokens: [R] current token per slot (emitted, not yet cached);
    history: [R, H] all known tokens per slot (prompt + emitted; row r
    valid to context_lens[r] + 1). Block tables must cover
    ``context_lens + k*(gamma+1)`` growth.

    ``gammas`` ([R] int32 in [0, gamma], default gamma) is the per-slot
    draft WIDTH for wave-level speculation: ``gamma`` stays the compiled
    program's static maximum (one compiled program per chunk shape
    regardless of the wave's width mix) while each slot's effective
    width rides as data (ops/speculative.py accept_rejection_batch
    ``widths``). A gamma-0 slot accepts no drafts and emits exactly one
    plain-decode token per iteration — it rides the shared verify pass
    instead of forcing a wave-wide fallback; its gamma_max draft entries
    still occupy (dummy-targeted) scratch, the price of the uniform
    program shape.

    Returns (toks [K, R, gamma+1], keeps [K, R], eos_seen [K, R],
    new paged): iteration t of slot r emitted ``toks[t, r, :keeps[t,r]]``;
    ``eos_seen`` is cumulative per row, so the host can distinguish an
    eos death from simply running out of iterations (1 token/iteration
    when every draft misses covers less than the chunk's token budget).
    """
    from distributed_llm_inferencing_tpu.ops.attention import attend
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache, flat_pool, head_rows, write_rows)
    from distributed_llm_inferencing_tpu.ops.speculative import (
        accept_rejection_batch, propose_ngram_device)

    if cfg.loop_steps > 1:
        raise ValueError(
            f"{cfg.name}: paged_speculative_chunk runs the stack once a "
            "verify pass; a looped model's steps are not carried through it")
    _no_state_layers(cfg, "paged_speculative_chunk (a rejected draft "
                     "would need its state rolled back)")
    r = tokens.shape[0]
    L = cfg.num_layers
    bs = paged.block_size
    mb = block_tables.shape[1]
    g1 = gamma + 1
    E = k * g1                       # side-buffer entries per slot
    dt = jnp.dtype(cfg.dtype)
    quantized = paged.quantized
    flat = flat_pool(cfg, paged)
    cl0 = context_lens
    H = history.shape[1]

    pool_pos = jnp.broadcast_to(jnp.arange(mb * bs, dtype=jnp.int32),
                                (r, mb * bs))
    pool_valid = pool_pos < cl0[:, None]
    ladder = _pool_ladder(mb, _layers_scanned(params, cfg))
    rung, _ = _pool_rung(ladder, bs, cl0, budget > 0)
    side0 = jnp.zeros((L, r, E, cfg.num_kv_heads, cfg.head_dim), dt)
    entry_step = jnp.arange(E, dtype=jnp.int32) // g1               # [E]

    gathered_bytes = 2 * dt.itemsize * L * r * mb * bs \
        * cfg.num_kv_heads * cfg.head_dim
    pre = gathered_bytes <= _PREGATHER_MAX_BYTES
    if pre:
        pool, scales = _pool_pregather(paged, block_tables, dt), ()
    else:                                # gathered per layer in-loop
        pool, scales = paged.planes()[:2], paged.planes()[2:]

    def body(carry, t):
        (cur, hist, hist_len, side_k, side_v, side_pos, acc_mask, cl,
         emitted, alive, eos_seen) = carry
        qp0 = jnp.where(alive, cl, 0)
        qp = qp0[:, None] + jnp.arange(g1, dtype=jnp.int32)[None, :]
        drafts, _ = propose_ngram_device(hist, hist_len, gamma)
        toks_in = jnp.concatenate([cur[:, None], drafts], axis=1)  # [R, g1]
        x = embed(params, cfg, toks_in, qp)

        side_pos = jax.lax.dynamic_update_slice(side_pos, qp, (0, t * g1))
        is_cur_block = jnp.broadcast_to(entry_step == t, (r, E))
        side_valid = acc_mask | is_cur_block

        def make_layer(seg_cfg):
            def layer(carry, layer_in):   # as in paged_decode_chunk
                (x, sd), (lp, li) = carry, layer_in

                def attend_write(q, kh, vh):
                    sd2, (sk2, sv2) = _write_side(sd, (kh, vh), t * g1, li)

                    def attend_pool(got, pos, valid):
                        if flat:   # side buffers keep the heads' axis
                            got = tuple(head_rows(g_, *sk2.shape[-2:])
                                        for g_ in got)
                        with jax.named_scope("attention"):
                            return attend(
                                q, (got[0], sk2), (got[1], sv2), qp,
                                (pos, side_pos), (valid, side_valid),
                                sliding_window=_layer_window(seg_cfg, lp),
                                alibi=_alibi(seg_cfg),
                                softcap=seg_cfg.attn_softcap,
                                sinks=_sinks(seg_cfg, lp))
                    attn = _attend_pool_rung(
                        rung, ladder, pre, pool, scales, block_tables, dt,
                        pool_pos, pool_valid, attend_pool, li)
                    return attn, sd2

                x2, sd2 = _block_body(x, lp, seg_cfg, qp, attend_write,
                                      lora_ids=lora_ids)
                return (x2, sd2), ()
            return layer

        (x2, (side_k, side_v)), _ = scan_layer_stack(
            make_layer, (x, (side_k, side_v)), params, cfg,
            (jnp.arange(L, dtype=jnp.int32),))
        logits = unembed(params, cfg, x2)                 # [R, g1, V] f32

        # per-row acceptance (ops/speculative.py): greedy rows accept
        # argmax-matching drafts (bit-identical to plain greedy decode);
        # sampled rows run exact leave-one-out rejection against the same
        # warped distribution sample_batch draws from — real speedups for
        # do_sample requests with the target distribution preserved
        with jax.named_scope("sample"):
            toks_out, n_emit = accept_rejection_batch(
                logits, drafts, seeds, steps0 + emitted, temps, tks, tps,
                ds, widths=gammas)
        idx = jnp.arange(g1, dtype=jnp.int32)[None, :]

        # eos / budget clamping
        emit_sl = idx < n_emit[:, None]
        is_eos = (toks_out == eos_ids[:, None]) & (eos_ids >= 0)[:, None] \
            & emit_sl
        eos_pos = jnp.min(jnp.where(is_eos, idx, g1), axis=1)     # [R]
        rem = budget - emitted
        n_keep = jnp.minimum(jnp.minimum(n_emit, eos_pos), rem)
        n_keep = jnp.where(alive, n_keep, 0)
        # an eos "happened" only if plain decode would have reached it
        # inside this chunk's budget — when the budget clamp cut the run
        # first, the slot must survive and re-derive the tail next chunk
        hit_eos = (eos_pos < n_emit) & (eos_pos < rem)

        # commit: entry i of this block is cache-valid iff i < n_keep
        # (entry 0 = cur at position cl; kept emitted tokens cover
        # positions cl+1..cl+n_keep-1 whose KV is entries 1..n_keep-1;
        # the LAST kept token becomes next cur, its KV unwritten) — and
        # for fully-kept rows entry n_acc's draft was accepted too, so
        # commit i <= min(n_acc, n_keep-1)... conservatively i < n_keep
        # plus entry 0 for alive rows.
        commit = (idx < n_keep[:, None]) | ((idx == 0) & alive[:, None])
        acc_mask = jax.lax.dynamic_update_slice(
            acc_mask, commit, (0, t * g1))

        # history append: kept tokens at h[cl+1 .. cl+n_keep]
        rows = jnp.broadcast_to(jnp.arange(r)[:, None], (r, g1))
        cols = jnp.where(emit_sl & (idx < n_keep[:, None]),
                         cl[:, None] + 1 + idx, H)   # H -> dropped
        hist = hist.at[rows, cols].set(toks_out, mode="drop")
        hist_len = hist_len + n_keep

        new_cl = cl + n_keep
        emitted2 = emitted + n_keep
        eos_seen2 = eos_seen | (hit_eos & alive)
        new_alive = alive & ~hit_eos & (emitted2 < budget)
        new_cur = jnp.where(
            n_keep > 0,
            jnp.take_along_axis(
                toks_out, jnp.maximum(n_keep - 1, 0)[:, None], axis=1)[:, 0],
            cur)
        return ((new_cur, hist, hist_len, side_k, side_v, side_pos,
                 acc_mask, new_cl, emitted2, new_alive, eos_seen2),
                (toks_out, n_keep, eos_seen2))

    hist_len0 = cl0 + 1
    carry0 = (tokens, history, hist_len0, side0, side0,
              jnp.zeros((r, E), jnp.int32), jnp.zeros((r, E), bool),
              cl0, jnp.zeros((r,), jnp.int32), budget > 0,
              jnp.zeros((r,), bool))
    (_, _, _, side_k, side_v, side_pos, acc_mask, _, _, _, _), \
        (toks, keeps, eos_seen) = jax.lax.scan(
            body, carry0, jnp.arange(k, dtype=jnp.int32))

    # single pool scatter of the accepted side entries
    with jax.named_scope("kv_write"):
        blk = jnp.take_along_axis(block_tables, side_pos // bs,
                                  axis=1)                         # [R, E]
        blk = jnp.where(acc_mask, blk, dummy_block)
        off = side_pos % bs
        if quantized:
            from distributed_llm_inferencing_tpu.ops.kvcache import quant_kv
            k8, ks = quant_kv(side_k)
            v8, vs = quant_kv(side_v)
            side = (k8, v8, ks, vs)
        else:
            side = (side_k, side_v)
        paged = PagedKVCache(*(
            write_rows(plane, sd, blk, off)
            for plane, sd in zip(paged.planes(), side)))
    return toks, keeps, eos_seen, paged


def _kinds_prefill_tail(params, cfg: ModelConfig, tokens, tail_len,
                        tail_blocks, prefix_blocks, prefix_len, paged,
                        slots, logits_as_computed=False):
    """paged_prefill_tail for a model with layer kinds (cfg.swa,
    MiMo-V2): two caches for two kinds of layer. A full layer gathers
    its cached prefix (a chunked prompt's earlier chunks: such a model
    matches no other) from the block pool, which holds the full layers
    alone, and hands its tail's K and V rows out for the pool; a
    windowed layer reads its slot's ring (``slots`` [B], the dummy row
    for a padding row) for what lies before the tail and keeps the
    tail's last ring_positions rows for it (ops/paged_kvcache.ring_read,
    ring_take). The kinds' rows differ in shape, so they ride the layer
    stack's carry, a buffer a kind and plane written in place at the
    layer's index in its own kind's cache (cfg.cache_index), and after
    the stack each cache takes its rows in ONE scatter a plane, in
    place in the donated tree."""
    from distributed_llm_inferencing_tpu.ops.attention import attend
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        flat_rows, head_rows, paged_attend_prefix, ring_read, ring_take,
        write_blocks, write_rows)
    b, t = tokens.shape
    ring = paged.ring_k.shape[2]
    take, ring_row, ring_off = ring_take(
        t, tail_len, prefix_len, slots, paged.ring_k.shape[1] - 1, ring)
    hd, vd = cfg.head_dim, cfg.v_head_dim_effective
    dt = jnp.dtype(cfg.dtype)
    q_pos = prefix_len[:, None] + jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32), (b, t))
    tail_valid = jnp.arange(t, dtype=jnp.int32)[None, :] < tail_len[:, None]
    ring_pos, ring_valid = ring_read(ring, prefix_len)
    x = embed(params, cfg, tokens, q_pos)
    # a windowed layer's tail goes in bands: the window is no longer than
    # the ring, so a query block of the ring's length sees the block
    # before it (the ring itself, for the first) and its own, and a tail
    # of 2048 holds 16 x (128 x 256) scores a head, not 2048 x 2176. A
    # tail the ring's length does not divide is one band
    band = ring if t % ring == 0 else t

    def fold(a):          # [B, t, ...] -> a row a band
        return a.reshape(b * (t // band), band, *a.shape[2:])

    def before(ring_a, a):   # what lies before each band, folded alike
        if band == t:
            return ring_a
        return fold(jnp.concatenate([ring_a, a[:, :t - band]], axis=1))

    def make_body(seg_cfg):
        windowed = seg_cfg.attn_kind == "swa"

        def body(carry, layer_in):
            (x, full_rows, swa_rows), (lp, li) = carry, layer_in
            out = {}

            def put(bufs, rows):   # as the caches store them: flat
                return tuple(jax.lax.dynamic_update_slice(
                    buf, flat_rows(r).astype(buf.dtype)[None],
                    (li, 0, 0, 0, 0)) for buf, r in zip(bufs, rows))

            def attend_write(q, k, v):
                if not windowed:
                    attn = paged_attend_prefix(
                        q, k, v, paged.k, paged.v, prefix_blocks,
                        prefix_len, q_pos, tail_valid,
                        kind="attention_full", layer=li)
                    out["full"] = put(full_rows, (k, v))
                    return attn, ()
                with jax.named_scope("kv_gather"), \
                        jax.named_scope("attention_swa"):
                    rk = head_rows(paged.ring_k[li, slots], *k.shape[-2:])
                    rv = head_rows(paged.ring_v[li, slots], *v.shape[-2:])
                with jax.named_scope("attention"), \
                        jax.named_scope("attention_swa"):
                    attn = attend(
                        fold(q), (before(rk, k), fold(k)),
                        (before(rv, v), fold(v)), fold(q_pos),
                        (before(ring_pos, q_pos), fold(q_pos)),
                        (before(ring_valid, tail_valid), fold(tail_valid)),
                        sliding_window=seg_cfg.sliding_window,
                        sinks=_sinks(seg_cfg, lp)).reshape(b, t, -1, vd)
                with jax.named_scope("ring_write"):
                    out["swa"] = put(swa_rows, tuple(
                        jnp.take_along_axis(r, take[:, :, None, None],
                                            axis=1) for r in (k, v)))
                return attn, ()

            x, _ = _block_body(x, lp, seg_cfg, q_pos, attend_write,
                               valid=tail_valid)
            return (x, out.get("full", full_rows),
                    out.get("swa", swa_rows)), ()
        return body

    def bufs(n_layers, heads, n):
        return (jnp.zeros((n_layers, b, n, 1, heads * hd), dt),
                jnp.zeros((n_layers, b, n, 1, heads * vd), dt))
    carry = (x, bufs(paged.k.shape[0], cfg.num_kv_heads, t),
             bufs(paged.ring_k.shape[0], cfg.swa.num_kv_heads,
                  take.shape[1]))
    (x, full_rows, swa_rows), _ = scan_layer_stack(
        make_body, carry, params, cfg,
        (jnp.asarray(cfg.cache_index, jnp.int32),))
    with jax.named_scope("kv_write"):
        paged = paged.with_planes(tuple(
            write_blocks(plane, rows, tail_blocks)
            for plane, rows in zip(paged.planes(), full_rows)))
    with jax.named_scope("ring_write"):
        paged = paged._replace(
            ring_k=write_rows(paged.ring_k, swa_rows[0], ring_row, ring_off),
            ring_v=write_rows(paged.ring_v, swa_rows[1], ring_row, ring_off))
    last_x = jnp.take_along_axis(
        x, jnp.maximum(tail_len - 1, 0)[:, None, None].astype(jnp.int32),
        axis=1)
    return unembed(params, cfg, last_x, logits_as_computed)[:, 0], paged


def paged_prefill_tail(params, cfg: ModelConfig, tokens, tail_len,
                       tail_blocks, prefix_blocks, prefix_len, paged,
                       lora_ids=None, slots=None,
                       logits_as_computed: bool = False):
    """Prefill a WAVE of prompt tails into paged blocks, each attending its
    own cached prefix.

    Each row's prefix (``prefix_len[b]`` tokens in ``prefix_blocks[b]``, a
    radix-cache hit) is NOT recomputed — its K/V is gathered from shared
    blocks per layer (a layer whose window is a trace-time constant
    gathers only the columns that hold it: paged_attend_prefix). Fresh
    tail K/V is scattered into ``tail_blocks``.
    Batching admissions into one program is what keeps burst TTFT at one
    dispatch round trip instead of one per queued request (the reference
    served admissions fully serialized, worker/app.py:252-330).

    The pool is no per-layer input or output of the layer stack: a layer
    takes its index, gathers its prefix from the stacked planes where
    they lie by (layer, block), attends the fresh tail from its own
    projections (never back from the pool), and hands the tail's rows out
    ([L, B, T, Hkv, w] a plane: small). After the stack one scatter a
    plane writes them into the donated pool in place. Handed the planes
    layer by layer and given them back re-stacked, XLA could not alias
    the donated pool to the result and copied it whole, two to four
    times a wave (PERF.md section 6, PR 38).

    tokens: [B, T] right-padded tails (T a multiple of block_size);
    tail_len: [B] real tail tokens (>= 1; padding rows use 1);
    tail_blocks: [B, T // bs] int32 (padding rows all-dummy; legacy
    unbatched [T // bs] accepted when B == 1);
    prefix_blocks: [B, PB] (dummy-padded); prefix_len: [B].
    Returns (last-token logits [B, V] f32, new paged);
    ``logits_as_computed``: in the head's own dtype, for the sampler
    (``unembed``).

    A model with state layers (cfg.ssm) takes ``slots`` [B]: the serving
    slot whose state row each wave row continues (the dummy row, the
    planes' last, for a padding row). A row whose ``prefix_len`` is 0
    brings a request's first position and starts from a zero state and
    window, whatever the slot held; a later chunk of a chunked prompt
    goes on from what its slot holds. Positions past ``tail_len`` advance
    neither (ops/ssm.mix_tokens). The state planes ride the layer
    stack's carry: a layer reads its B rows by (layer, slot) and writes
    them back in place, so no [L, B, ...] stack of states is ever held.
    """
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache, flat_pool, flat_rows, paged_attend_prefix,
        write_blocks)
    if cfg.swa is not None:
        return _kinds_prefill_tail(params, cfg, tokens, tail_len,
                                   tail_blocks, prefix_blocks, prefix_len,
                                   paged, slots, logits_as_computed)
    b, t = tokens.shape
    if tail_blocks.ndim == 1:
        tail_blocks = tail_blocks[None]
    if tail_blocks.shape[0] != b:
        raise ValueError(
            f"tail_blocks batch {tail_blocks.shape[0]} != tokens batch {b}")
    q_pos = prefix_len[:, None] + jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32), (b, t))
    tail_valid = jnp.arange(t, dtype=jnp.int32)[None, :] < tail_len[:, None]
    x = embed(params, cfg, tokens, q_pos)

    has_ssm = cfg.ssm is not None
    flat = flat_pool(cfg, paged)   # (its planes' shapes stay: the loop's too)
    if has_ssm:
        from distributed_llm_inferencing_tpu.ops import ssm
        fresh = prefix_len == 0

    def make_body(seg_cfg, paged):       # its layers read this pool
        def body(x, layer_in):
            lp, li = layer_in
            ssm_mix = None
            if has_ssm:
                x, stp, cwp = x

                def ssm_mix(h, lp):
                    st = jnp.where(fresh[:, None, None, None], 0.0,
                                   stp[li, slots])
                    cw = jnp.where(fresh[:, None], 0, cwp[li, slots])
                    out, st, cw = ssm.mix_tokens(h, lp, seg_cfg, st, cw,
                                                 tail_valid, _linear)
                    return out, (stp.at[li, slots].set(st),
                                 cwp.at[li, slots].set(cw))

                def carry_state(out):
                    x2, cache_out = out
                    return (x2,) + tuple(cache_out[-2:]), cache_out[:-2]

            if seg_cfg.mla_latent_cache:
                # the pool takes the tail's latent rows and nothing else;
                # the tail attends per-head K and V expanded from rows
                # for this program alone — its own and, gathered from
                # the pool, its cached prefix's (a radix hit or the
                # earlier chunks of a chunked prefill): the O(T^2)
                # regime, where the absorbed form's (rd + r)-wide scores
                # and context would cost 3.6 times the attention FLOPs
                def expand(rows):
                    return _mla_expand(*_mla_split_rows(rows, seg_cfg), lp,
                                       seg_cfg)

                def mla_latent_attend(h, qp):
                    rows = _mla_latent_rows(h, lp, seg_cfg, qp)
                    k, v = expand(rows)
                    attn = paged_attend_prefix(
                        _mla_q(h, lp, seg_cfg, qp), k, v, paged.k, None,
                        prefix_blocks, prefix_len, qp, tail_valid,
                        expand_rows=expand, layer=li)
                    return attn, (rows,)
                return _block_body(x, lp, seg_cfg, q_pos, None,
                                   mla_latent_attend=mla_latent_attend,
                                   valid=tail_valid)

            def attend_write(q, k, v):
                win = _layer_window(seg_cfg, lp)
                scale = None
                if flat:
                    # a position's heads in one row: the tail attends its
                    # prefix's rows as they lie and its own alike, q
                    # zero-expanded to a row (4x the products of the view
                    # by heads, on a tail of 512; but with that view,
                    # head_rows of the gathered prefix, trinity-mini's
                    # wave of 2 rows over 256 prefix blocks halted a v5e
                    # core in the windowed layers' bounded gather, `Core
                    # halted unexpectedly`, as PR 38's did: PERF.md
                    # section 6, PR 49), and hands its rows out flat
                    hkv, hd, vd = k.shape[2], q.shape[-1], v.shape[-1]
                    k, v = flat_rows(k), flat_rows(v)
                    q, scale = _flat_rows_q(q, hkv, k), hd ** -0.5
                attn = paged_attend_prefix(
                    q, k, v, paged.k, paged.v, prefix_blocks, prefix_len,
                    q_pos, tail_valid, sliding_window=win,
                    k_scale_layer=paged.k_scale, v_scale_layer=paged.v_scale,
                    alibi=_alibi(seg_cfg), softcap=seg_cfg.attn_softcap,
                    sinks=_sinks(seg_cfg, lp), kind=_layer_kind(cfg, win),
                    layer=li, scale=scale)
                if flat:
                    attn = _own_columns(attn, hkv, vd)
                if not paged.quantized:
                    return attn, (k, v)
                # store int8 + scales; the tail attended its own fresh
                # bf16 K/V plus the dequantized cached prefix
                from distributed_llm_inferencing_tpu.ops.kvcache import (
                    quant_kv)
                with jax.named_scope("kv_write"):
                    k8, ks = quant_kv(k)
                    v8, vs = quant_kv(v)
                return attn, (k8, v8, ks, vs)

            out = _block_body(x, lp, seg_cfg, q_pos, attend_write,
                              lora_ids=lora_ids, valid=tail_valid,
                              ssm_mix=ssm_mix)
            return carry_state(out) if has_ssm else out
        return body

    if has_ssm:
        x = (x, paged.ssm, paged.conv)
    # ONE write a plane of every layer's tail rows, whole blocks, into
    # the pool where it lies. A looped model writes after each of its
    # steps, that step's planes alone (its rows for all steps at once
    # would be 1.5 MiB a token of the wave at Ouro-2.6B): the next
    # step's bodies close over the pool so written, whose planes they
    # read are still as they came in.
    L = cfg.num_layers
    for u, xs_u in loop_passes(
            cfg, (jnp.arange(cfg.cache_planes, dtype=jnp.int32),)):
        x, tails = scan_layer_stack(
            functools.partial(make_body, paged=paged), x, params, cfg, xs_u)
        x = loop_pass_end(params, cfg, u, x)
        if has_ssm:
            x, stp, cwp = x
            paged = paged._replace(ssm=stp, conv=cwp)
        with jax.named_scope("kv_write"):
            paged = paged.with_planes(tuple(
                write_blocks(plane, rows, tail_blocks,
                             None if cfg.loop_steps == 1 else u * L)
                for plane, rows in zip(paged.planes(), tails)))
        if cfg.loop_steps > 1:
            # the next pass waits for this write: left free, the
            # scheduler kept every pass's tail rows to the program's end
            # (3.4 GiB for a wave of 1024 tokens at Ouro-2.6B, described
            # v5e compile)
            x, paged = jax.lax.optimization_barrier((x, paged))
    # project only the last real position through the vocab head ([D,V] over
    # one row per sequence, not T padded rows)
    last_x = jnp.take_along_axis(
        x, jnp.maximum(tail_len - 1, 0)[:, None, None].astype(jnp.int32),
        axis=1)                                         # [B, 1, D]
    last = unembed(params, cfg, last_x, logits_as_computed)[:, 0]  # [B, V]
    return last, paged
