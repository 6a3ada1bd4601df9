"""Parameter initialization (random) for the unified transformer.

Used by tests, benchmarks and the dry-run path — real checkpoints come from
models/convert.py. Shapes follow the schema documented in
models/transformer.py; every per-layer leaf is stacked with a leading [L]
axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models.config import ModelConfig

# leaves of at least this many elements are initialized slice by slice
_SLICED_INIT_ELEMS = 2 ** 29


def init_params(cfg: ModelConfig, key, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.dense_prefix_layers:
        # deepseek first_k_dense_replace: build the MoE tail and the
        # dense prefix as two independent stacked segments
        # (transformer.layer_segments runs them back to back)
        k1, k2 = jax.random.split(key)
        tail = init_params(cfg.moe_segment_cfg(), k1, dtype)
        prefix = init_params(cfg.dense_segment_cfg(), k2, dtype)
        tail["layers_dense"] = prefix["layers"]
        return tail
    if cfg.swa is not None:
        # layer kinds (MiMo-V2): a stack a kind, each of its own shapes
        # (transformer.layer_segments runs them in the pattern's order);
        # embedding, final norm and head come with the first
        params = None
        for kk, (name, stack_cfg) in zip(jax.random.split(key, 3),
                                         cfg.kind_stacks()):
            part = init_params(stack_cfg, kk, dtype)
            params = params or part
            params[name] = part["layers"]
        return params
    L, D, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    keys = iter(jax.random.split(key, 64))

    def w(shape, scale=0.02):
        if math.prod(shape) >= _SLICED_INIT_ELEMS:
            # a leaf this large (kanana's experts: 6 x 128 x 2048 x 768,
            # 2.4 GB in bf16) is drawn a leading-axis slice at a time:
            # the float32 normals of the whole leaf, twice over for the
            # scaling, would not fit a 16 GB chip beside the weights
            return jax.lax.map(
                lambda k: (jax.random.normal(k, shape[1:], jnp.float32)
                           * scale).astype(dtype),
                jax.random.split(next(keys), shape[0]))
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def w_q(shape, scale=0.02):
        # cfg.quant="int8"/"int4": emit the linear weight ALREADY
        # quantized — random quant levels with the per-output-channel
        # scale a real quantized checkpoint would carry (ops/quant.py
        # schema). Peak memory is the quantized model itself;
        # init-bf16-then-quantize would transiently need 2-4x, which for
        # the 8B flagship exceeds one chip's HBM. Values are random
        # either way — identical layout, dtypes and compute to a
        # converted quantized checkpoint.
        if cfg.quant == "int4":
            assert shape[-2] % 2 == 0, (
                f"int4 packing needs even din, got {shape[-2]}")
            # draw per-nibble biased levels in [1,15] (values [-7,7]) —
            # quantize_weight_int4 clips to that range, so level -8
            # (biased 0) never appears in a converted checkpoint and must
            # not appear here either
            half = shape[:-2] + (shape[-2] // 2, shape[-1])
            lo = jax.random.randint(next(keys), half, 1, 16, jnp.int32)
            hi = jax.random.randint(next(keys), half, 1, 16, jnp.int32)
            packed = (lo | (hi << 4)).astype(jnp.uint8)
            return {"p4": packed, "scale": jnp.full(
                shape[:-2] + shape[-1:], scale / 7.0, jnp.float32)}
        q = jax.random.randint(next(keys), shape, -127, 128, jnp.int8)
        return {"q": q, "scale": jnp.full(shape[:-2] + shape[-1:],
                                          scale / 127.0, jnp.float32)}

    def zeros(shape):
        return jnp.zeros(shape, dtype)

    def ones(shape):
        return jnp.ones(shape, dtype)

    def norm_p():
        p = {"scale": ones((L, D))}
        if cfg.norm_type == "layernorm":
            p["bias"] = zeros((L, D))
        return p

    quantized = cfg.quant in ("int8", "int4")

    def lin(din, dout, bias):
        p = w_q((L, din, dout)) if quantized else {"w": w((L, din, dout))}
        if bias:
            p["b"] = zeros((L, dout))
        return p

    def ew(shape):
        return w_q(shape) if quantized else {"w": w(shape)}

    if cfg.mla:   # deepseek-v3 latent attention (transformer._mla_qkv)
        H, hd = cfg.num_heads, cfg.qk_head_dim
        r, rd = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        vd = cfg.v_head_dim_effective
        layers = {
            "attn_norm": norm_p(),
            "kv_a": lin(D, r + rd, cfg.attn_bias),
            "kv_a_norm": {"scale": ones((L, r))},
            "kv_b_k": lin(r, H * (hd - rd), False),
            "kv_b_v": lin(r, H * vd, False),
            "o": lin(H * vd, D, cfg.o_bias_effective),
        }
        if cfg.q_lora_rank:
            layers["q_a"] = lin(D, cfg.q_lora_rank, cfg.attn_bias)
            layers["q_a_norm"] = {"scale": ones((L, cfg.q_lora_rank))}
            layers["q_b"] = lin(cfg.q_lora_rank, cfg.q_dim, False)
        else:
            layers["q"] = lin(D, cfg.q_dim, False)
    else:
        layers = {
            "attn_norm": norm_p(),
            "q": lin(D, cfg.q_dim, cfg.attn_bias),
            "k": lin(D, cfg.kv_dim, cfg.attn_bias),
            "v": lin(D, cfg.v_dim, cfg.attn_bias),
            "o": lin(cfg.num_heads * cfg.v_head_dim_effective, D,
                     cfg.o_bias_effective),
        }
        if cfg.attn_gate:   # trinity (afmoe): gate on the attention output
            layers["attn_gate"] = lin(D, cfg.q_dim, False)
    if cfg.ssm is not None:
        # Falcon-H1's Mamba-2 mixer (ops/ssm.py), with the source's own
        # initial values where it gives them (modeling_falcon_h1.py
        # FalconH1Mixer.__init__): A_log = log(1..H), D = 1, dt_bias the
        # inverse softplus of a log-uniform step in [1e-3, 1e-1]; the
        # depthwise filter and its bias as torch's Conv1d draws them,
        # uniform in +-1/sqrt(d_conv)
        c = cfg.ssm
        dt0 = jnp.maximum(jnp.exp(
            jax.random.uniform(next(keys), (L, c.n_heads), jnp.float32)
            * (math.log(0.1) - math.log(0.001)) + math.log(0.001)), 1e-4)
        layers["in_proj"] = lin(D, c.proj_dim, False)
        layers["conv"] = {"w": jax.random.uniform(
            next(keys), (L, c.d_conv, c.conv_dim), jnp.float32,
            -c.d_conv ** -0.5, c.d_conv ** -0.5).astype(dtype)}
        if c.conv_bias:
            layers["conv"]["b"] = jax.random.uniform(
                next(keys), (L, c.conv_dim), jnp.float32,
                -c.d_conv ** -0.5, c.d_conv ** -0.5).astype(dtype)
        layers["dt_bias"] = (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)
        layers["A_log"] = jnp.broadcast_to(jnp.log(jnp.arange(
            1, c.n_heads + 1, dtype=jnp.float32)), (L, c.n_heads)).astype(dtype)
        layers["D"] = ones((L, c.n_heads))
        layers["ssm_norm"] = {"scale": ones((L, c.d_ssm))}
        layers["out_proj"] = lin(c.d_ssm, D, False)
    if cfg.post_block_norms:   # gemma2 sandwich norms
        layers["attn_post_norm"] = norm_p()
        layers["mlp_post_norm"] = norm_p()
    if cfg.qk_norm:   # qwen3/olmo2/cohere q/k normalization (bias-free)
        # rms_head: ONE [hd] scale shared by every head (qwen3);
        # rms_full/ln_head: full projection width (olmo2 normalizes the
        # flat projection; cohere's ln is per-head but carries DISTINCT
        # per-head scales, stored flat [H*hd] here)
        shared = cfg.qk_norm == "rms_head"
        layers["q_norm"] = {"scale": ones(
            (L, cfg.head_dim if shared else cfg.q_dim))}
        layers["k_norm"] = {"scale": ones(
            (L, cfg.head_dim if shared else cfg.kv_dim))}
    if cfg.attn_windows is not None:
        # per-layer window leaf ([L] int32, -1 == global) — rides the
        # layer scan/unroll/pipeline machinery (transformer._layer_window)
        layers["attn_window"] = jnp.asarray(
            [-1 if w is None else w for w in cfg.attn_windows], jnp.int32)
    if cfg.rope_layers is not None:   # per-layer NoPE (smollm3/exaone4)
        layers["rope_on"] = jnp.asarray(cfg.rope_layers, jnp.int32)
    if cfg.attn_sinks:   # gpt-oss: one learned sink logit per head
        # (MiMo-V2's windowed kind: drawn, so that leaving them out shows)
        layers["sinks"] = (w((L, cfg.num_heads), 1.0) if cfg.attn_kind
                           else zeros((L, cfg.num_heads)))
    if not cfg.shared_attn_mlp_norm:   # phi/falcon-7b: one norm per block
        layers["mlp_norm"] = norm_p()
    if cfg.is_moe:
        E, I = cfg.num_experts, cfg.expert_intermediate_size
        layers["router"] = {"w": w((L, D, E))}   # kept float (ops/quant.py)
        if cfg.moe_router in ("deepseek_v3", "ernie", "topk_softmax"):
            # selection-correction bias (deepseek/ernie) or the router
            # linear's real bias (gpt-oss): small and random, as a
            # trained one is, so that selection by biased scores and
            # weighting by unbiased ones differ
            layers["router"]["bias"] = jax.random.normal(
                next(keys), (L, E), jnp.float32) * 0.02
        if cfg.experts_held is not None:   # this program's share of them
            E = cfg.experts_held[1]
        layers["experts"] = {
            "gate": ew((L, E, D, I)),
            "up": ew((L, E, D, I)),
            "down": ew((L, E, I, D)),
        }
        if cfg.mlp_bias:   # gpt-oss: per-expert biases
            layers["experts"]["gate"]["b"] = zeros((L, E, I))
            layers["experts"]["up"]["b"] = zeros((L, E, I))
            layers["experts"]["down"]["b"] = zeros((L, E, D))
        if cfg.moe_shared_experts:   # deepseek always-active shared MLP
            SI = I * cfg.moe_shared_experts
            layers["shared_gate"] = lin(D, SI, cfg.mlp_bias)
            layers["shared_up"] = lin(D, SI, cfg.mlp_bias)
            layers["shared_down"] = lin(SI, D, cfg.mlp_bias)
    else:
        layers["up"] = lin(D, I, cfg.mlp_bias)
        if cfg.gated_mlp:
            layers["gate"] = ew((L, D, I))
        layers["down"] = lin(I, D, cfg.mlp_bias)

    E = cfg.embed_proj_dim or D

    def embed_table():
        if cfg.embed_quant == "int8":
            # direct-to-int8 table (ops/quant.py quantize_embed schema):
            # same reasoning as w_q — never materialize the float table
            q = jax.random.randint(next(keys), (cfg.vocab_size, E),
                                   -127, 128, jnp.int8)
            return {"q8": q, "rscale": jnp.full((cfg.vocab_size,),
                                                0.02 / 127.0, jnp.float32)}
        return w((cfg.vocab_size, E))

    params = {
        "embed": {"tokens": embed_table()},
        "layers": layers,
    }
    if cfg.embed_norm:   # bloom: layernorm on the embedding output
        params["embed"]["norm"] = {"scale": ones((E,)), "bias": zeros((E,))}
    if not cfg.post_norm:   # post-LN models (opt-350m) have no final norm
        params["final_norm"] = (
            {"scale": ones((D,)), "bias": zeros((D,))}
            if cfg.norm_type == "layernorm" else {"scale": ones((D,))})
    if cfg.loop_steps > 1:
        # Ouro's early-exit gate, a linear D -> 1 on each pass's normed
        # result: loaded and kept, read by the plain reference alone (at
        # the published threshold 1 every token runs every step, and the
        # served path does not evaluate it)
        params["exit_gate"] = {"w": w((D, 1)), "b": zeros((1,))}
    if cfg.embed_proj_dim:
        params["embed"]["project_in"] = {"w": w((E, D))}
        params["embed"]["project_out"] = {"w": w((D, E))}
    if cfg.position_embedding == "learned":
        params["embed"]["positions"] = w((cfg.max_position_embeddings, D))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = ew((D, cfg.vocab_size))
        if cfg.lm_head_bias:   # phi
            params["lm_head"]["b"] = zeros((cfg.vocab_size,))
    if cfg.quant:
        # no-op for the leaves w_q already emitted; covers any remaining
        # float linear (and validates the quant mode)
        from distributed_llm_inferencing_tpu.ops.quant import maybe_quantize
        params = maybe_quantize(params, cfg)
    if cfg.embed_quant:
        from distributed_llm_inferencing_tpu.ops.quant import (
            maybe_quantize_embed)
        params = maybe_quantize_embed(params, cfg)   # validates the mode
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def param_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
