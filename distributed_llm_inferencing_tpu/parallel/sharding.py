"""Partition specs: how the unified-transformer pytree maps onto the mesh.

This module IS the TPU-native replacement for the reference's shard_model
CLI (reference: shard_model.py:55-109): where the reference rewrote weight
files into full-size per-range copies (and never wired the cross-shard
handoff, worker/app.py:334-336), we assign a ``PartitionSpec`` per leaf and
let GSPMD insert the ICI collectives. "Sharding a model" becomes metadata,
applied at load time, with no weight rewriting.

Scheme (megatron-style, see jax-ml.github.io/scaling-book):
- attention q/o and MLP up/gate/down shard heads/columns over ``tp``
- stacked layer axis [L, ...] optionally shards over ``pp`` (weight-
  distributed; true pipelined execution lives in parallel/pipeline.py)
- MoE experts shard over ``ep``
- vocab (embedding rows / lm_head columns) shards over ``tp``
- KV cache shards batch over ``dp`` and kv-heads over ``tp``
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_llm_inferencing_tpu.models.config import ModelConfig
from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec


def kv_head_axis(num_kv_heads: int, tp: int):
    """The one GQA kv-over-tp rule: kv heads shard over tp iff they divide
    evenly; otherwise they replicate (tp > num_kv_heads small-kv case).
    Shared by param/cache specs here and the ring path (parallel/ring.py)."""
    return "tp" if (tp <= num_kv_heads and num_kv_heads % max(tp, 1) == 0) \
        else None


def param_specs(cfg: ModelConfig, spec: MeshSpec,
                shard_layers_over_pp: bool = True) -> Dict[str, Any]:
    """PartitionSpec pytree matching models/transformer.py's param schema."""
    if cfg.dense_prefix_layers:
        # deepseek mixed stack: the dense prefix carries the plain-MLP
        # layer schema as its own stacked segment (pp would shard the
        # two segments independently — refused upstream, mesh.validate)
        tail = param_specs(cfg.moe_segment_cfg(), spec,
                           shard_layers_over_pp)
        prefix = param_specs(cfg.dense_segment_cfg(), spec,
                             shard_layers_over_pp)
        tail["layers_dense"] = prefix["layers"]
        return tail
    if cfg.swa is not None:
        # layer kinds (mimo-v2): a stack a kind, as init_params builds
        # them (the batcher serves such a model on one device only)
        specs = None
        for name, stack_cfg in cfg.kind_stacks():
            part = param_specs(stack_cfg, spec, shard_layers_over_pp)
            specs = specs or part
            specs[name] = part["layers"]
        return specs
    kv_tp = kv_head_axis(cfg.num_kv_heads, spec.tp)
    L = "pp" if shard_layers_over_pp else None

    def norm_p():
        p = {"scale": P(L, None)}
        if cfg.norm_type == "layernorm":
            p["bias"] = P(L, None)
        return p

    def lin(spec_: P) -> Dict[str, Any]:
        """Leaf specs for a linear weight; int8/int4 quant (ops/quant.py)
        adds a per-out-channel scale sharded like the weight's last axis.
        The packed-int4 leaf reuses the int8 spec (same rank, din axis
        just halved). Row-parallel (din-sharded) int4 leaves get the
        shard-time chunk-local repack (shard_params below) so each
        shard's slice is a self-contained split-half pack — the zero-
        size ``chunked`` marker it adds replicates."""
        # NB the shard-time chunk-local repack's ``chunked`` marker spec
        # is added by shard_params itself, AFTER the repack — keeping it
        # out of param_specs means every other consumer (checkpoint
        # restore trees, plans) sees the mesh-agnostic leaf schema.
        if not cfg.quant:
            return {"w": spec_}
        key = "p4" if cfg.quant == "int4" else "q"
        return {key: spec_, "scale": P(*(spec_[:-2] + spec_[-1:]))}

    if cfg.mla:
        # deepseek MLA (transformer._mla_qkv): the latent bottleneck
        # projections are small and produce per-token latents every
        # shard needs (the shared rope head and the normed c_kv feed
        # every head) — replicate them; the per-head expansions kv_b_k /
        # kv_b_v / q[_b] column-shard over tp like q/k/v, and o row-
        # shards as usual.
        layers: Dict[str, Any] = {
            "attn_norm": norm_p(),
            "kv_a": lin(P(L, None, None)),
            "kv_a_norm": {"scale": P(L, None)},
            "kv_b_k": lin(P(L, None, "tp")),
            "kv_b_v": lin(P(L, None, "tp")),
            "o": lin(P(L, "tp", None)),
        }
        if cfg.q_lora_rank:
            layers["q_a"] = lin(P(L, None, None))
            layers["q_a_norm"] = {"scale": P(L, None)}
            layers["q_b"] = lin(P(L, None, "tp"))
        else:
            layers["q"] = lin(P(L, None, "tp"))
        if cfg.attn_bias:
            layers["kv_a"]["b"] = P(L, None)
            if cfg.q_lora_rank:
                layers["q_a"]["b"] = P(L, None)
    else:
        layers = {
            "attn_norm": norm_p(),
            "q": lin(P(L, None, "tp")),
            "k": lin(P(L, None, kv_tp)),
            "v": lin(P(L, None, kv_tp)),
            "o": lin(P(L, "tp", None)),
        }
        if cfg.attn_gate:   # multiplies the heads' output: sharded as q
            layers["attn_gate"] = lin(P(L, None, "tp"))
    if cfg.ssm is not None:
        # the Mamba-2 mixer's leaves, replicated: the batcher refuses a
        # mesh of more than one device for a model with state layers
        # (the state plane has no sharding rule yet, ROADMAP R-M)
        layers["in_proj"] = lin(P(L, None, None))
        layers["conv"] = {"w": P(L, None, None)}
        if cfg.ssm.conv_bias:
            layers["conv"]["b"] = P(L, None)
        for name in ("dt_bias", "A_log", "D"):
            layers[name] = P(L, None)
        layers["ssm_norm"] = {"scale": P(L, None)}
        layers["out_proj"] = lin(P(L, None, None))
    if cfg.post_block_norms:   # gemma2 sandwich norms
        layers["attn_post_norm"] = norm_p()
        layers["mlp_post_norm"] = norm_p()
    if cfg.qk_norm:
        # norm scales replicate (tiny); for the full-width kind the
        # mean-square reduction spans every tp shard of q/k — GSPMD
        # inserts the collective, and the shard_map (pp) local views
        # carry whole heads so their local reduction is already global
        layers["q_norm"] = {"scale": P(L, None)}
        layers["k_norm"] = {"scale": P(L, None)}
    if cfg.attn_windows is not None:
        # [L] int32 per-layer window leaf: pp shards the layer axis like
        # every other stacked leaf, so each stage carries its own slice
        layers["attn_window"] = P(L)
    if cfg.rope_layers is not None:   # per-layer NoPE flag, same layout
        layers["rope_on"] = P(L)
    if getattr(cfg, "attn_sinks", False):   # [L, H]: heads over tp
        layers["sinks"] = P(L, "tp")
    if not cfg.shared_attn_mlp_norm:   # phi/falcon-7b: one norm per block
        layers["mlp_norm"] = norm_p()
    if cfg.attn_bias and not cfg.mla:   # mla biases set in its branch
        layers["q"]["b"] = P(L, "tp")
        layers["k"]["b"] = P(L, kv_tp)
        layers["v"]["b"] = P(L, kv_tp)
    if cfg.o_bias_effective:
        layers["o"]["b"] = P(L, None)
    if cfg.is_moe:
        layers["router"] = {"w": P(L, None, None)}
        if cfg.moe_router in ("deepseek_v3", "ernie", "topk_softmax"):
            layers["router"]["bias"] = P(L, None)
        layers["experts"] = {
            "gate": lin(P(L, "ep", None, "tp")),
            "up": lin(P(L, "ep", None, "tp")),
            "down": lin(P(L, "ep", "tp", None)),
        }
        if cfg.mlp_bias:   # gpt-oss per-expert biases
            layers["experts"]["gate"]["b"] = P(L, "ep", "tp")
            layers["experts"]["up"]["b"] = P(L, "ep", "tp")
            layers["experts"]["down"]["b"] = P(L, "ep", None)
        if cfg.moe_shared_experts:   # deepseek always-active shared MLP
            layers["shared_gate"] = lin(P(L, None, "tp"))
            layers["shared_up"] = lin(P(L, None, "tp"))
            layers["shared_down"] = lin(P(L, "tp", None))
            if cfg.mlp_bias:   # ernie use_bias=True
                layers["shared_gate"]["b"] = P(L, "tp")
                layers["shared_up"]["b"] = P(L, "tp")
                layers["shared_down"]["b"] = P(L, None)
    else:
        layers["up"] = lin(P(L, None, "tp"))
        if cfg.gated_mlp:
            layers["gate"] = lin(P(L, None, "tp"))
        layers["down"] = lin(P(L, "tp", None))
        if cfg.mlp_bias:
            layers["up"]["b"] = P(L, "tp")
            layers["down"]["b"] = P(L, None)

    specs = {
        # int8 embed table (cfg.embed_quant): vocab-sharded like the
        # float table, per-row scales follow the vocab axis
        "embed": {"tokens": {"q8": P("tp", None), "rscale": P("tp")}
                  if cfg.embed_quant else P("tp", None)},
        "layers": layers,
    }
    if not cfg.post_norm:
        specs["final_norm"] = (
            {"scale": P(None), "bias": P(None)}
            if cfg.norm_type == "layernorm" else {"scale": P(None)})
    if cfg.loop_steps > 1:   # Ouro's exit gate: D + 1 numbers, replicated
        specs["exit_gate"] = {"w": P(None, None), "b": P(None)}
    if cfg.embed_proj_dim:   # opt-350m embed projections: small, replicated
        specs["embed"]["project_in"] = {"w": P(None, None)}
        specs["embed"]["project_out"] = {"w": P(None, None)}
    if cfg.embed_norm:       # bloom embedding layernorm: tiny, replicated
        specs["embed"]["norm"] = {"scale": P(None), "bias": P(None)}
    if cfg.position_embedding == "learned":
        specs["embed"]["positions"] = P(None, None)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = lin(P(None, "tp"))
        if cfg.lm_head_bias:   # phi
            specs["lm_head"]["b"] = P("tp")
    return specs


def cache_specs(cfg: ModelConfig, spec: MeshSpec):
    """KVCache sharding: [L,B,S,Hkv,hd] — batch over dp, kv heads over tp,
    sequence over sp (ring attention shards the S axis)."""
    kv_tp = kv_head_axis(cfg.cache_kv_heads, spec.tp)
    L = "pp" if spec.pp > 1 else None  # stage-local cache slices
    sp = "sp" if spec.sp > 1 else None
    kv = P(L, "dp", sp, kv_tp, None)
    from distributed_llm_inferencing_tpu.ops.kvcache import KVCache
    scale = P(L, "dp", sp, kv_tp) if cfg.kv_quant else None
    return KVCache(k=kv, v=kv, lengths=P("dp"), k_scale=scale,
                   v_scale=scale)


def paged_cache_specs(cfg: ModelConfig, spec: MeshSpec):
    """PagedKVCache sharding: [L, NB, bs, Hkv, hd] — kv heads over tp,
    layers over pp (pipeline stages own their layer slice of the pool,
    parallel/paged_pipeline.py).

    The block axes (NB, bs) stay replicated: which blocks a slot owns is
    host-side scheduler state (runtime/batcher.py), identical on every
    device, so only the head dimension is worth splitting."""
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import PagedKVCache
    if cfg.mla_latent_cache:
        # one shared row a token, read by every query head: replicated
        return PagedKVCache(k=P(None, None, None, None, None))
    kv_tp = kv_head_axis(cfg.num_kv_heads, spec.tp)
    L = "pp" if spec.pp > 1 else None
    kv = P(L, None, None, kv_tp, None)
    scale = P(L, None, None, kv_tp) if cfg.kv_quant else None
    if cfg.swa is not None:
        # the full layers' pool and the windowed layers' per-slot ring,
        # replicated (one device only, as for state layers below)
        rep = P(None, None, None, None, None)
        return PagedKVCache(k=rep, v=rep, ring_k=rep, ring_v=rep)
    if cfg.ssm is not None:
        # the per-slot state planes, replicated (the batcher serves a
        # model with state layers on one device only)
        return PagedKVCache(k=kv, v=kv, ssm=P(None, None, None, None, None),
                            conv=P(None, None, None))
    return PagedKVCache(k=kv, v=kv, k_scale=scale, v_scale=scale)


def logits_spec():
    return P("dp", None, "tp")


def tokens_spec():
    return P("dp", None)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def shard_params(params, mesh: Mesh, cfg: ModelConfig, spec: MeshSpec):
    """Place a param pytree onto the mesh per param_specs.

    int4 + tp>1: row-parallel (din-sharded) packed leaves are first
    repacked chunk-locally (ops/quant.py repack_int4_rows) so each tp
    shard holds a self-contained split-half pack and the pallas kernel's
    row-parallel rule can run shard-local (ops/pallas/quant_matmul.py
    q4_matmul_row). Leaves whose din doesn't divide into 2*tp chunks
    keep the global layout (and the XLA unpack path)."""
    specs = param_specs(cfg, spec)
    if getattr(cfg, "quant", None) == "int4" and spec.tp > 1:
        from distributed_llm_inferencing_tpu.ops.quant import (
            repack_int4_rows)
        params = dict(params)
        for seg in ("layers", "layers_dense"):
            if seg not in params:
                continue
            params[seg] = dict(params[seg])
            specs[seg] = dict(specs[seg])
            for name in ("o", "down", "shared_down"):
                leaf = params[seg].get(name)
                if not (isinstance(leaf, dict) and "p4" in leaf):
                    continue
                try:
                    leaf = repack_int4_rows(leaf, spec.tp)
                except ValueError:
                    if "chunked" in leaf:
                        # chunked for a DIFFERENT tp: sharding it would
                        # be silently wrong — the caller must
                        # reload/repack
                        raise
                    # non-divisible din: keep global layout + XLA path
                params[seg][name] = leaf
                if "chunked" in leaf:
                    ls = dict(specs[seg][name])
                    # marker mirrors p4's stacked layer axis for the scan
                    ls["chunked"] = P(*(ls["p4"][:-2] + (None, None)))
                    specs[seg][name] = ls
    shardings = named(mesh, specs)
    return jax.device_put(params, shardings)
