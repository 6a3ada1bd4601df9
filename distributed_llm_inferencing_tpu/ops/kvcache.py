"""Static-shape KV cache (optionally int8-quantized).

The reference had no KV-cache management at all — it was implicit inside HF
``model.generate()`` (SURVEY.md §2.4). On TPU the cache must be a
static-shape device-resident buffer so the decode step compiles once:

- ``k``/``v``: [L, B, max_seq, Hkv, hd] stacked over layers (leading layer
  axis lines up with the stacked layer params so ``lax.scan`` over layers
  carries one cache slice per step).
- ``lengths``: [B] int32 — how many slots are filled per sequence.
- ``k_scale``/``v_scale``: [L, B, max_seq, Hkv] f32, present only under
  ``cfg.kv_quant == "int8"`` — per-token-per-head symmetric scales for
  int8-stored K/V (``quant_kv``). Decode is HBM-bound on the cache at
  long contexts; int8 halves that traffic at a ~3% scale overhead
  (4 bytes per hd=128 head-token). Reads dequantize via ``dequant_kv``;
  XLA fuses the convert+scale into the attention matmul, so the HBM read
  stays int8 (which is also why quantized caches use the xla attention
  formulation — a pallas kernel input would materialize the dequantized
  copy).

Updates use ``lax.dynamic_update_slice_in_dim`` at the current length; the
buffers are donated by the engine's jitted step functions so decode is
in-place on device.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models.config import ModelConfig


class KVCache(NamedTuple):
    k: jax.Array        # [L, B, S, Hkv, hd] (model dtype, or int8)
    v: jax.Array        # [L, B, S, Hkv, hd]
    lengths: jax.Array  # [B] int32 — filled slots (same for all layers)
    k_scale: Optional[jax.Array] = None   # [L, B, S, Hkv] f32 (int8 mode)
    v_scale: Optional[jax.Array] = None
    # a model with state layers (cfg.ssm, ops/ssm.py): each sequence's
    # recurrent state [L, B, H, P, N] float32 and conv window
    # [L, B, (d_conv - 1) * conv_dim], as they stand after `lengths`
    ssm: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None

    @property
    def max_seq(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def positions(self):
        """[B, S] absolute position of each slot (slot index)."""
        B, S = self.k.shape[1], self.k.shape[2]
        return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def valid(self):
        """[B, S] bool — slot holds a real token."""
        return self.positions() < self.lengths[:, None]


def quant_kv(x):
    """[..., Hkv, hd] -> (int8 [..., Hkv, hd], f32 scale [..., Hkv]).
    Symmetric per-(token, head): one scale per head vector."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequant_kv(q, scale, dtype):
    """Inverse of quant_kv. Fuses into the consuming matmul under XLA."""
    return (q.astype(jnp.float32) * scale[..., None].astype(
        jnp.float32)).astype(dtype)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None) -> KVCache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    # mla_latent_cache: the k plane holds one shared [k_rot | c] latent
    # row per token; the v plane is zero-width (attention reads v as the
    # rows of k — transformer._mla_absorbed)
    shape = (cfg.cache_planes, batch, max_seq, cfg.cache_kv_heads,
             cfg.cache_head_dim)
    vshape = shape[:-1] + (cfg.cache_v_head_dim,)
    if cfg.kv_quant == "int8":
        return KVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(vshape, jnp.int8),
            lengths=jnp.zeros((batch,), jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32))
    if cfg.kv_quant is not None:
        raise ValueError(f"unknown kv_quant mode {cfg.kv_quant!r}")
    cache = KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(vshape, dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
    )
    if cfg.ssm is not None:
        c = cfg.ssm
        cache = cache._replace(
            ssm=jnp.zeros((cfg.num_layers, batch, c.n_heads, c.d_head,
                           c.d_state), jnp.float32),
            conv=jnp.zeros((cfg.num_layers, batch, c.conv_elems), dtype))
    return cache


def write_block(cache_layer, new, starts):
    """Per-sequence cache write for one layer's buffer.

    cache_layer: [B,S,Hkv,hd]; new: [B,s,Hkv,hd]; starts: [B] int32 — the
    slot where each sequence's block begins. Clamps at capacity (XLA
    dynamic_update_slice semantics); the engine enforces that sequences never
    exceed max_seq.
    """
    return jax.vmap(
        lambda c, n, st: jax.lax.dynamic_update_slice_in_dim(c, n, st, axis=0)
    )(cache_layer, new.astype(cache_layer.dtype), starts)
