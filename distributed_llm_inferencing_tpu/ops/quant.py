"""Weight-only int8 / int4 quantization (per-output-channel symmetric).

Decode is HBM-bandwidth-bound: every step streams the full weight set
through the MXU. Storing matmul weights as int8 halves that traffic vs
bf16 — and doubles the model size that fits one chip. int4 halves it
again (the 8B flagship drops to ~4.3 GB of weights). Activations stay
bf16; per-channel weight-only int8 is accuracy-negligible for serving
(the standard vLLM/TGI weight-only trade); int4 round-to-nearest is the
throughput mode — measurably lossier per layer, so int8 stays the
accuracy-conservative default.

Scheme: for a weight ``w [..., din, dout]``, ``scale[..., dout] =
max|w|/levels`` over din (levels = 127 or 7), ``q = round(w / scale)``.
Because the scale is per *output* channel it commutes with the
contraction:

    y = x @ (q * scale) == (x @ q) * scale

so the kernel runs ``x_bf16 @ q->bf16`` (int8 reads, MXU-native
convert) and applies one cheap [dout] multiply on the output — no
weight-sized dequantized temporary ever exists.

int4 storage: this JAX build cannot carry ``jnp.int4`` arrays across a
jit boundary, so nibbles are packed two-per-byte along din in a uint8
array, split-half biased (pack_int4 below). The decode-speed win comes
from the pallas kernel in ops/pallas/quant_matmul.py — XLA itself
cannot fuse any unpack formulation into a dot-operand read (every
variant measured on the v5e materializes the bf16 weights first and
lands 2-5x SLOWER than int8), so the XLA unpack here is only the
portability/prefill fallback. Group-wise scales (the AWQ/GPTQ accuracy
trick) were measured too but turn the flat GEMV into a batched one that
XLA schedules ~2x slower at decode batch sizes, so per-channel it is.

A quantized leaf is ``{"q": int8[..., din, dout], "scale":
f32[..., dout]}`` or ``{"p4": uint8[..., din//2, dout], "scale":
f32[..., dout]}`` (+"b" unchanged); models/transformer.py's ``_linear``
and ``_moe`` dispatch on the presence of "q"/"p4". No reference
counterpart at any level (SURVEY.md §2.5 — its compute was vendored
torch/CUDA).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# leaves quantized under params["layers"] / params root
_LINEAR_LEAVES = ("q", "k", "v", "o", "attn_gate", "up", "gate", "down",
                  # deepseek MLA bottlenecks + expansions and shared
                  # experts (the q_a/kv_a latents are matmul weights like
                  # any other; their mid-stack norms stay float)
                  "q_a", "q_b", "kv_a", "kv_b_k", "kv_b_v",
                  "shared_gate", "shared_up", "shared_down",
                  # a Mamba-2 mixer's two projections (ops/ssm.py)
                  "in_proj", "out_proj")

MODES = ("int8", "int4")


def quantize_weight(w) -> dict:
    """w [..., din, dout] -> {"q": int8, "scale": f32 [..., dout]}."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2)              # [..., dout]
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]), -127, 127)
    return {"q": q.astype(jnp.int8), "scale": scale}


def pack_int4(q) -> jax.Array:
    """int8 nibbles [..., din, dout] (values in [-8,7]) -> uint8
    [..., din//2, dout], split-half biased: byte row i holds din row i
    (+8, low nibble) and din row i + din//2 (+8, high nibble). Split-half
    (not pairwise-interleaved) so unpacking is a concat — and the pallas
    kernel (ops/pallas/quant_matmul.py) needs no unpack reorder at all:
    each nibble plane dots against its own half of x."""
    din = q.shape[-2]
    assert din % 2 == 0, f"int4 packing needs even din, got {din}"
    u = (q + 8).astype(jnp.uint8)                      # biased nibble 0..15
    lo, hi = u[..., : din // 2, :], u[..., din // 2:, :]
    return lo | (hi << 4)


def unpack_int4(p4, chunks: int = 1) -> jax.Array:
    """uint8 [..., din//2, dout] -> sign-extended int8 [..., din, dout].

    ``chunks > 1``: the leaf uses CHUNK-LOCAL split-half packing
    (repack_int4_rows) — each of ``chunks`` equal row groups is its own
    split-half pack, so a din-sharded leaf unpacks shard-locally."""
    lo = (p4 & 0xF).astype(jnp.int8) - 8
    hi = ((p4 >> 4) & 0xF).astype(jnp.int8) - 8
    if chunks == 1:
        return jnp.concatenate([lo, hi], axis=-2)
    *lead, half, dout = p4.shape
    per = half // chunks
    lo = lo.reshape(*lead, chunks, per, dout)
    hi = hi.reshape(*lead, chunks, per, dout)
    return jnp.concatenate([lo, hi], axis=-2).reshape(
        *lead, 2 * half, dout)


def pack_chunks(p4) -> int:
    """Chunk count of an int4 leaf (1 = the global split-half layout).
    The marker's SECOND-TO-LAST dim carries the count — its leading dims
    mirror p4's stacked layer axes so the layer scan / unrolled loop
    slices it alongside the weight."""
    return p4["chunked"].shape[-2] if "chunked" in p4 else 1


def repack_int4_rows(p: dict, chunks: int) -> dict:
    """Re-pack a split-half int4 leaf so each of ``chunks`` equal din
    row-groups is a SELF-CONTAINED split-half packing of its own rows.

    A din-sharded (row-parallel: o/down under tp) leaf in the GLOBAL
    layout is useless per-shard — packed row i pairs din rows i and
    i + din/2, which land on different shards. After this repack, shard
    c's slice is exactly the packing of din rows [c*din/C, (c+1)*din/C),
    so the pallas kernel runs shard-local (ops/pallas/quant_matmul.py
    row-parallel rule). The zero-size ``chunked`` leaf carries C in its
    static shape; consumers (unpack_int4, dequantize_weight, the kernel
    dispatch) read it at trace time. Values are bit-identical — only
    byte placement changes."""
    if "chunked" in p:
        if p["chunked"].shape[-2] != chunks:
            raise ValueError(
                f"leaf already chunked x{p['chunked'].shape[-2]}, "
                f"asked for x{chunks}")
        return p
    p4 = p["p4"]
    *lead, half, dout = p4.shape
    din = 2 * half
    if din % (2 * chunks):
        raise ValueError(f"din={din} not divisible into {chunks} "
                         "split-half chunks")
    per = din // chunks
    # Pure NIBBLE GATHER on the packed bytes — never unpacks (a 70B-class
    # o/down stack would otherwise materialize a 4x int8 transient at
    # load). Target byte (chunk c, local row j) pairs din rows
    # rA = c*per + j and rB = rA + per/2; source nibble of din row r is
    # the low half of byte row r (r < din/2) or the high half of byte
    # row r - din/2.
    c = jnp.arange(half, dtype=jnp.int32) // (per // 2)
    j = jnp.arange(half, dtype=jnp.int32) % (per // 2)
    r_a = c * per + j
    r_b = r_a + per // 2

    def nib(r):
        lo_sel = r < half
        rows = jnp.take(p4, jnp.where(lo_sel, r, r - half), axis=-2)
        return jnp.where(lo_sel[:, None], rows & 0xF, (rows >> 4) & 0xF)

    out = dict(p)
    out["p4"] = nib(r_a) | (nib(r_b) << 4)
    out["chunked"] = jnp.zeros((*lead, chunks, 0), jnp.int8)
    return out


def quantize_weight_int4(w) -> dict:
    """w [..., din, dout] -> {"p4": packed uint8, "scale": f32 [..., dout]}."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2)              # [..., dout]
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]), -7, 7).astype(jnp.int8)
    return {"p4": pack_int4(q), "scale": scale}


def is_quantized(p: dict) -> bool:
    return isinstance(p, dict) and ("q" in p or "p4" in p)


def _quant_linear(p: dict, donate: bool, mode: str = "int8") -> dict:
    if is_quantized(p) or "w" not in p:
        return p
    quantize = quantize_weight if mode == "int8" else quantize_weight_int4
    if donate:
        # free each float leaf as soon as its quantized twin exists: peak
        # extra memory is one stacked weight, not a whole second model
        w = p.pop("w")
        q = quantize(w)
        del w
        p.update(q)
        return p
    out = dict(p)
    w = out.pop("w")
    out.update(quantize(w))
    return out


def quantize_params(params, cfg, donate: bool = False,
                    mode: str = "int8") -> dict:
    """Quantize the big matmul weights of a transformer param pytree.

    Covered: per-layer q/k/v/o, MLP up/gate/down, MoE expert weights, and
    the untied lm_head. Kept in float: embeddings (gather-addressed and,
    when tied, shared with the head), norms, biases, MoE router (tiny,
    routing-critical). Idempotent.

    ``donate=True`` mutates the input tree, dropping each float weight as
    it converts — use when the caller owns the tree and won't reuse the
    float leaves (the worker load path), so a model that only fits
    quantized can actually be loaded-then-quantized.
    """
    if not donate:
        params = dict(params)
    for seg in ("layers", "layers_dense"):
        if seg not in params:
            continue
        if not donate:
            params[seg] = dict(params[seg])
        layers = params[seg]
        for name in _LINEAR_LEAVES:
            if name in layers:
                layers[name] = _quant_linear(layers[name], donate, mode)
        if "experts" in layers:
            if not donate:
                layers["experts"] = dict(layers["experts"])
            for k in layers["experts"]:
                layers["experts"][k] = _quant_linear(
                    layers["experts"][k], donate, mode)
    if "lm_head" in params:
        params["lm_head"] = _quant_linear(params["lm_head"], donate, mode)
    return params


def maybe_quantize(params, cfg, donate: bool = False):
    """Apply cfg.quant to a (possibly already quantized) param tree."""
    if cfg.quant is None:
        return params
    if cfg.quant not in MODES:
        raise ValueError(f"unknown quant mode {cfg.quant!r}; known: {MODES}")
    return quantize_params(params, cfg, donate=donate, mode=cfg.quant)


def dequantize_weight(p: dict):
    """Materialize the float weight (tests / conversion tooling)."""
    if "p4" in p:
        return unpack_int4(p["p4"], pack_chunks(p)).astype(jnp.float32) \
            * p["scale"][..., None, :]
    return p["q"].astype(jnp.float32) * p["scale"][..., None, :]


# ----------------------------------------------------------------------
# Embedding-table quantization (cfg.embed_quant)
# ----------------------------------------------------------------------
#
# The tied-head models (gpt2 family; reference default, inference.html:22)
# pay the single largest per-token read OUTSIDE the layer stack at the
# unembed: [V, D] bf16 streams every decode step (gpt2-xl: 161 MB/token —
# comparable to several transformer layers). Per-ROW symmetric int8 works
# for BOTH uses of the table:
#   - unembed contracts d: row scale == per-output(vocab)-channel scale,
#     which commutes out of the dot exactly like the linear case above;
#   - the embedding gather takes whole rows: dequant is one scalar
#     multiply per gathered row.
# Kept separate from cfg.quant because embeddings are the most
# sensitivity-prone table and the win is model-family dependent (untied
# heads already quantize via lm_head) — opt-in via cfg.embed_quant.


def quantize_embed(emb) -> dict:
    """emb [V, D] -> {"q8": int8 [V, D], "rscale": f32 [V]} (per-row)."""
    w32 = emb.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-1)              # [V]
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale[..., None]), -127, 127)
    return {"q8": q.astype(jnp.int8), "rscale": scale}


def dequantize_embed(p: dict):
    return p["q8"].astype(jnp.float32) * p["rscale"][..., None]


def maybe_quantize_embed(params, cfg, donate: bool = False) -> dict:
    """Apply cfg.embed_quant to the token-embedding table. Idempotent."""
    if cfg.embed_quant is None:
        return params
    if cfg.embed_quant != "int8":
        raise ValueError(
            f"unknown embed_quant mode {cfg.embed_quant!r}; known: ('int8',)")
    tokens = params["embed"]["tokens"]
    if isinstance(tokens, dict):                       # already quantized
        return params
    if not donate:
        params = dict(params)
        params["embed"] = dict(params["embed"])
    params["embed"]["tokens"] = quantize_embed(tokens)
    return params
