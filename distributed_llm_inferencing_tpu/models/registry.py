"""Model registry: name -> ModelConfig.

Replaces the reference's implicit "whatever string you type into the
dashboard goes to AutoModelForCausalLM" model selection
(reference: worker/app.py:117-121, inference.html:22) with an explicit
registry. HF checkpoints are still ingested (models/convert.py) — the
registry also knows how to derive a ModelConfig from an HF config object so
arbitrary local HF checkpoints of a supported family load too.
"""

from __future__ import annotations

from typing import Dict

from distributed_llm_inferencing_tpu.models.config import (
    ModelConfig, SSMConfig, SWAConfig)

_REGISTRY: Dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; known: {sorted(_REGISTRY)}. "
            "Use models.convert.config_from_hf for local HF checkpoints."
        )
    return _REGISTRY[name]


def list_models():
    return sorted(_REGISTRY)


def _gpt2(name, hidden, layers, heads, ctx=1024):
    return ModelConfig(
        name=name, family="gpt2", vocab_size=50257, hidden_size=hidden,
        intermediate_size=4 * hidden, num_layers=layers, num_heads=heads,
        num_kv_heads=heads, head_dim=hidden // heads,
        max_position_embeddings=ctx, norm_type="layernorm", activation="gelu",
        gated_mlp=False, position_embedding="learned", attn_bias=True,
        mlp_bias=True, tie_word_embeddings=True,
    )


def _opt(name, hidden, inter, layers, heads, ctx=2048):
    # OPT family (reference's second supported arch, shard_model.py:46-50):
    # learned positions, ReLU->gelu approx not needed: OPT uses ReLU; we keep
    # gelu/silu switch minimal and add relu.
    return ModelConfig(
        name=name, family="opt", vocab_size=50272, hidden_size=hidden,
        intermediate_size=inter, num_layers=layers, num_heads=heads,
        num_kv_heads=heads, head_dim=hidden // heads,
        max_position_embeddings=ctx, norm_type="layernorm", activation="relu",
        gated_mlp=False, position_embedding="learned", attn_bias=True,
        mlp_bias=True, tie_word_embeddings=True,
    )


def _llama(name, hidden, inter, layers, heads, kv_heads, vocab=128256,
           ctx=8192, theta=500000.0, window=None):
    return ModelConfig(
        name=name, family="llama", vocab_size=vocab, hidden_size=hidden,
        intermediate_size=inter, num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=hidden // heads,
        max_position_embeddings=ctx, norm_type="rmsnorm", norm_eps=1e-5,
        activation="silu", gated_mlp=True, position_embedding="rope",
        rope_theta=theta, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, sliding_window=window,
    )


# --- GPT-2 family (reference default model, inference.html:22) ---
register(_gpt2("gpt2", 768, 12, 12))
register(_gpt2("gpt2-medium", 1024, 24, 16))
register(_gpt2("gpt2-large", 1280, 36, 20))
register(_gpt2("gpt2-xl", 1600, 48, 25))

# --- OPT family (reference: facebook/opt-350m hint, inference.html:23) ---
register(_opt("opt-125m", 768, 3072, 12, 12))
register(_opt("opt-350m", 1024, 4096, 24, 16).replace(
    embed_proj_dim=512, post_norm=True))
register(_opt("opt-1.3b", 2048, 8192, 24, 32))

# --- Llama 3 family (BASELINE.md configs 2 & 5) ---
register(_llama("llama-3-8b", 4096, 14336, 32, 32, 8))
register(_llama("llama-3-70b", 8192, 28672, 80, 64, 8))

# --- Mistral (BASELINE.md config 3): llama arch + sliding window ---
register(_llama("mistral-7b", 4096, 14336, 32, 32, 8, vocab=32000,
                ctx=32768, theta=10000.0, window=4096))

# --- Mixtral (BASELINE.md config 4): Mistral + 8-expert MoE ---
register(_llama("mixtral-8x7b", 4096, 14336, 32, 32, 8, vocab=32000,
                ctx=32768, theta=1000000.0).replace(
                    name="mixtral-8x7b", num_experts=8, num_experts_per_tok=2))

# --- Qwen2: llama layout + bias on q/k/v only (models/convert.py) ---
register(_llama("qwen2-7b", 3584, 18944, 28, 28, 4, vocab=152064,
                ctx=32768, theta=1000000.0).replace(
                    name="qwen2-7b", attn_bias=True, o_bias=False))
register(_llama("qwen2-0.5b", 896, 4864, 24, 14, 2, vocab=151936,
                ctx=32768, theta=1000000.0).replace(
                    name="qwen2-0.5b", attn_bias=True, o_bias=False,
                    tie_word_embeddings=True))

# --- Gemma: llama layout + tanh-gelu, sqrt(D) embed normalizer, wide
# head_dim (256 > hidden/heads), tied 256k-vocab head ---
register(_llama("gemma-7b", 3072, 24576, 28, 16, 16, vocab=256000,
                ctx=8192, theta=10000.0).replace(
                    name="gemma-7b", head_dim=256, activation="gelu",
                    tie_word_embeddings=True, embed_scale=3072 ** 0.5,
                    norm_eps=1e-6, norm_offset=True))
register(_llama("gemma-2b", 2048, 16384, 18, 8, 1, vocab=256000,
                ctx=8192, theta=10000.0).replace(
                    name="gemma-2b", head_dim=256, activation="gelu",
                    tie_word_embeddings=True, embed_scale=2048 ** 0.5,
                    norm_eps=1e-6, norm_offset=True))

# --- MoE proxy (BASELINE.md config 4's measurable stand-in): Mixtral
# itself cannot fit one v5e chip even int4, so this 8-expert ~2.6B-total
# (~0.8B active) llama-layout MoE makes the dense-vs-capacity dispatch
# trade measurable on the real chip (bench.py moe_* keys). ---
register(_llama("moe-proxy-8e", 1536, 4096, 16, 12, 4, vocab=32000,
                ctx=4096, theta=10000.0).replace(
                    name="moe-proxy-8e", num_experts=8,
                    num_experts_per_tok=2))

# --- DeepSeek proxy: V3's mechanisms (MLA latent attention + sigmoid
# group-limited routing + shared experts) at a scale one chip serves —
# the real 671B is a multi-pod deployment. Dims follow V3's ratios
# (kv_lora_rank ≈ D/14, rope head = nope/2, v = nope). ---
register(ModelConfig(
    name="deepseek-proxy", family="deepseek", vocab_size=32000,
    hidden_size=1024, intermediate_size=2048, num_layers=12, num_heads=16,
    num_kv_heads=16, head_dim=32, qk_nope_head_dim=64,
    qk_rope_head_dim=32, v_head_dim=64, q_lora_rank=384, kv_lora_rank=128,
    max_position_embeddings=4096, norm_type="rmsnorm", activation="silu",
    gated_mlp=True, position_embedding="rope", rope_theta=10000.0,
    rope_interleaved=True, attn_bias=False, mlp_bias=False,
    tie_word_embeddings=False, num_experts=8, num_experts_per_tok=2,
    moe_router="deepseek_v3", moe_n_group=4, moe_topk_group=2,
    moe_routed_scale=2.5, moe_shared_experts=1,
    dense_prefix_layers=1, moe_intermediate_size=512))

# --- Kanana-2 30B-A3B (kakaocorp/kanana-2-30b-a3b-instruct-2601,
# model_type deepseek_v3): MLA with a full-rank q (no q bottleneck), one
# routing group, 128 sigmoid-routed experts top-6 and two shared experts
# (one always-on SwiGLU of width 2 x 768), one leading dense layer. Every
# size as the source's config.json has it; the benchmark's cell runs 7 of
# the 48 layers (benchmarks/chip/configs/kanana-2-30b-a3b-l7.json). ---
register(ModelConfig(
    name="kanana-2-30b-a3b", family="deepseek", vocab_size=128256,
    hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768,
    num_layers=48, num_heads=32, num_kv_heads=32, head_dim=64,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    q_lora_rank=None, kv_lora_rank=512, max_position_embeddings=32768,
    norm_type="rmsnorm", norm_eps=1e-6, activation="silu", gated_mlp=True,
    position_embedding="rope", rope_theta=1000000.0, rope_interleaved=True,
    attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
    num_experts=128, num_experts_per_tok=6, moe_router="deepseek_v3",
    moe_n_group=1, moe_topk_group=1, moe_routed_scale=2.448,
    moe_norm_topk=True, moe_shared_experts=2, dense_prefix_layers=1))

# --- GPT-NeoX / Pythia: parallel residual, partial rotary, exact gelu ---
register(ModelConfig(
    name="pythia-6.9b", family="gpt-neox", vocab_size=50432,
    hidden_size=4096, intermediate_size=16384, num_layers=32, num_heads=32,
    num_kv_heads=32, head_dim=128, max_position_embeddings=2048,
    norm_type="layernorm", activation="gelu_exact", gated_mlp=False,
    position_embedding="rope", rope_theta=10000.0, rope_pct=0.25,
    attn_bias=True, mlp_bias=True, tie_word_embeddings=False,
    parallel_residual=True))
register(ModelConfig(
    name="pythia-1.4b", family="gpt-neox", vocab_size=50304,
    hidden_size=2048, intermediate_size=8192, num_layers=24, num_heads=16,
    num_kv_heads=16, head_dim=128, max_position_embeddings=2048,
    norm_type="layernorm", activation="gelu_exact", gated_mlp=False,
    position_embedding="rope", rope_theta=10000.0, rope_pct=0.25,
    attn_bias=True, mlp_bias=True, tie_word_embeddings=False,
    parallel_residual=True))

# --- Phi-2: parallel residual + single shared norm, biased lm_head ---
register(ModelConfig(
    name="phi-2", family="phi", vocab_size=51200, hidden_size=2560,
    intermediate_size=10240, num_layers=32, num_heads=32, num_kv_heads=32,
    head_dim=80, max_position_embeddings=2048, norm_type="layernorm",
    activation="gelu", gated_mlp=False, position_embedding="rope",
    rope_theta=10000.0, rope_pct=0.4, attn_bias=True, mlp_bias=True,
    lm_head_bias=True, tie_word_embeddings=False, parallel_residual=True,
    shared_attn_mlp_norm=True))

# --- Falcon-7B: MQA fused QKV, parallel residual + shared norm ---
register(ModelConfig(
    name="falcon-7b", family="falcon", vocab_size=65024, hidden_size=4544,
    intermediate_size=18176, num_layers=32, num_heads=71, num_kv_heads=1,
    head_dim=64, max_position_embeddings=2048, norm_type="layernorm",
    activation="gelu_exact", gated_mlp=False, position_embedding="rope",
    rope_theta=10000.0, attn_bias=False, mlp_bias=False,
    tie_word_embeddings=True, parallel_residual=True,
    shared_attn_mlp_norm=True))

# --- BLOOM: ALiBi positions, layernormed embedding, tied 250k head ---
register(ModelConfig(
    name="bloom-7b1", family="bloom", vocab_size=250880, hidden_size=4096,
    intermediate_size=16384, num_layers=30, num_heads=32, num_kv_heads=32,
    head_dim=128, max_position_embeddings=2048, norm_type="layernorm",
    activation="gelu", gated_mlp=False, position_embedding="alibi",
    embed_norm=True, attn_bias=True, mlp_bias=True,
    tie_word_embeddings=True))

# --- Falcon-RW-1B: ALiBi + sequential residual (the RW layout) ---
register(ModelConfig(
    name="falcon-rw-1b", family="falcon", vocab_size=50304,
    hidden_size=2048, intermediate_size=8192, num_layers=24, num_heads=32,
    num_kv_heads=32, head_dim=64, max_position_embeddings=2048,
    norm_type="layernorm", activation="gelu_exact", gated_mlp=False,
    position_embedding="alibi", alibi_scale=64 ** -0.5,
    attn_bias=True, mlp_bias=True, tie_word_embeddings=True))

# --- MPT-7B: ALiBi, bias-free straight-concat fused QKV, tied head ---
register(ModelConfig(
    name="mpt-7b", family="mpt", vocab_size=50432, hidden_size=4096,
    intermediate_size=16384, num_layers=32, num_heads=32, num_kv_heads=32,
    head_dim=128, max_position_embeddings=2048, norm_type="layernorm",
    activation="gelu_exact", gated_mlp=False, position_embedding="alibi",
    attn_bias=False, mlp_bias=False, tie_word_embeddings=True))

# --- GPT-J-6B: interleaved partial rotary, shared-norm parallel block ---
register(ModelConfig(
    name="gpt-j-6b", family="gptj", vocab_size=50400, hidden_size=4096,
    intermediate_size=16384, num_layers=28, num_heads=16, num_kv_heads=16,
    head_dim=256, max_position_embeddings=2048, norm_type="layernorm",
    activation="gelu", gated_mlp=False, position_embedding="rope",
    rope_theta=10000.0, rope_pct=0.25, rope_interleaved=True,
    attn_bias=False, o_bias=False, mlp_bias=True, lm_head_bias=True,
    tie_word_embeddings=False, parallel_residual=True,
    shared_attn_mlp_norm=True))

# --- Trinity (afmoe): three windowed rotary layers to one full layer
# without rotation, a sigmoid gate on the attention output, q/k RMSNorm,
# four norms a layer, a sqrt(D) embedding multiplier, sigmoid-routed
# experts + one shared behind leading dense layers ---
def _afmoe(name, layer_types, window, **kw):
    """``layer_types`` as the source lists them: windowed layers rotate,
    full ones do not (models/reference/afmoe_ref.py has the equations)."""
    sliding = [t == "sliding_attention" for t in layer_types]
    return ModelConfig(
        name=name, family="afmoe", num_layers=len(layer_types),
        norm_type="rmsnorm", norm_eps=1e-5, activation="silu",
        gated_mlp=True, position_embedding="rope", rope_theta=10000.0,
        attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
        qk_norm="rms_head", post_block_norms=True, attn_gate=True,
        sliding_window=window,
        attn_windows=tuple(window if s else None for s in sliding),
        rope_layers=tuple(int(s) for s in sliding),
        moe_router="deepseek_v3", moe_n_group=1, moe_topk_group=1,
        moe_norm_topk=True, moe_shared_experts=1, **kw)


register(_afmoe(
    "trinity-mini", (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    2048, vocab_size=200192, hidden_size=2048, intermediate_size=6144,
    moe_intermediate_size=1024, num_heads=32, num_kv_heads=4, head_dim=128,
    max_position_embeddings=131072, embed_scale=2048 ** 0.5,
    num_experts=128, num_experts_per_tok=8, moe_routed_scale=2.826,
    dense_prefix_layers=2))

# --- Ouro (ByteDance LoopLM): a llama-shaped layer under sandwich norms,
# and the whole stack run loop_steps times a token over one set of
# weights, the final norm between passes; each (step, layer) pair has
# its own K and V plane (models/reference/ouro_ref.py has the equations) ---
def _ouro(name, **kw):
    return ModelConfig(
        name=name, family="ouro", norm_type="rmsnorm", norm_eps=1e-6,
        activation="silu", gated_mlp=True, position_embedding="rope",
        rope_theta=1000000.0, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, post_block_norms=True, **kw)


register(_ouro(
    "ouro-2.6b", vocab_size=49152, hidden_size=2048, intermediate_size=5632,
    num_layers=48, loop_steps=4, num_heads=16, num_kv_heads=16, head_dim=128,
    max_position_embeddings=65536))

# --- Falcon-H1 (tiiuae): every block runs a Mamba-2 state-space mixer
# beside its attention heads, both on the block's normed input, joined
# before the one residual add; muP multipliers on every path
# (models/reference/falcon_h1_ref.py has the equations, ops/ssm.py the
# served forms) ---
def _falcon_h1(name, **kw):
    return ModelConfig(
        name=name, family="falcon_h1", norm_type="rmsnorm", norm_eps=1e-5,
        activation="silu", gated_mlp=True, position_embedding="rope",
        attn_bias=False, mlp_bias=False, tie_word_embeddings=False, **kw)


register(_falcon_h1(
    "falcon-h1-34b", vocab_size=261120, hidden_size=5120,
    intermediate_size=21504, num_layers=72, num_heads=20, num_kv_heads=4,
    head_dim=128, max_position_embeddings=262144, rope_theta=1e11,
    embed_scale=5.656854249492381, logit_scale=0.0078125,
    ssm=SSMConfig(
        d_ssm=4096, n_heads=32, d_head=128, d_state=256, n_groups=2,
        d_conv=4, chunk_size=128, conv_bias=True,
        in_multiplier=0.25, out_multiplier=0.08838834764831845,
        multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
        attn_in_multiplier=1.0, attn_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804,
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284))))

# --- MiMo-V2 (XiaomiMiMo): windowed layers (8 K/V heads, a sink a query
# head, rotary base 1e4) and full layers (4 K/V heads, no sink, base 1e7)
# in one stack, q and k heads 192 wide with the first 64 columns rotated,
# v heads 128 wide and scaled, a leading dense layer, then sigmoid-routed
# experts with no shared one (models/reference/mimo_v2_ref.py has the
# equations). The language model alone: the multi-token-prediction
# layers and the vision and audio towers are not built. ---
def _mimo_v2(name, pattern, swa_kv_heads, swa_rope_theta, **kw):
    return ModelConfig(
        name=name, family="mimo_v2", norm_type="rmsnorm", norm_eps=1e-5,
        activation="silu", gated_mlp=True, position_embedding="rope",
        attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
        swa=SWAConfig(pattern=tuple(pattern), num_kv_heads=swa_kv_heads,
                      rope_theta=swa_rope_theta, sinks=True),
        moe_router="deepseek_v3", moe_n_group=1, moe_topk_group=1,
        moe_norm_topk=True, moe_routed_scale=1.0, dense_prefix_layers=1,
        **kw)


register(_mimo_v2(
    "mimo-v2.5", [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0], 8, 1e4,
    vocab_size=152576, hidden_size=4096, intermediate_size=16384,
    moe_intermediate_size=2048, num_layers=48, num_heads=64, num_kv_heads=4,
    head_dim=192, v_head_dim=128, attn_value_scale=0.707, rope_pct=0.334,
    rope_theta=1e7, sliding_window=128, max_position_embeddings=1048576,
    num_experts=256, num_experts_per_tok=8))

# --- Tiny configs for tests/dryrun (not real checkpoints) ---
register(ModelConfig(
    name="tiny-gpt2", family="gpt2", vocab_size=256, hidden_size=64,
    intermediate_size=256, num_layers=4, num_heads=4, num_kv_heads=4,
    head_dim=16, max_position_embeddings=128, norm_type="layernorm",
    activation="gelu", gated_mlp=False, position_embedding="learned",
    attn_bias=True, mlp_bias=True, tie_word_embeddings=True))
register(ModelConfig(
    name="tiny-llama", family="llama", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=4, num_heads=8, num_kv_heads=4,
    head_dim=8, max_position_embeddings=128, norm_type="rmsnorm",
    activation="silu", gated_mlp=True, position_embedding="rope",
    attn_bias=False, mlp_bias=False, tie_word_embeddings=False))
register(ModelConfig(
    # tiny-llama with a 1k context: the disaggregation bench's workload
    # model (bench.py --scenario disagg) — long prompts need prefill
    # that costs real compute relative to a decode step, which the
    # 128-token tiny-llama cannot express
    name="tiny-llama-long", family="llama", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=4, num_heads=8, num_kv_heads=4,
    head_dim=8, max_position_embeddings=1024, norm_type="rmsnorm",
    activation="silu", gated_mlp=True, position_embedding="rope",
    attn_bias=False, mlp_bias=False, tie_word_embeddings=False))
register(ModelConfig(
    name="tiny-mixtral", family="llama", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=8, num_kv_heads=4,
    head_dim=8, max_position_embeddings=128, norm_type="rmsnorm",
    activation="silu", gated_mlp=True, position_embedding="rope",
    attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
    num_experts=4, num_experts_per_tok=2))
register(ModelConfig(
    name="tiny-deepseek", family="deepseek", vocab_size=256,
    hidden_size=64, intermediate_size=48, num_layers=3, num_heads=8,
    num_kv_heads=8, head_dim=8, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, q_lora_rank=32, kv_lora_rank=16,
    max_position_embeddings=128, norm_type="rmsnorm", activation="silu",
    gated_mlp=True, position_embedding="rope", rope_interleaved=True,
    attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
    num_experts=4, num_experts_per_tok=2, moe_router="deepseek_v3",
    moe_n_group=2, moe_topk_group=1, moe_routed_scale=2.5,
    moe_shared_experts=1,
    # the shipped first_k_dense_replace layout: one dense-MLP layer
    # ahead of the MoE tail (its own stacked segment, layers_dense)
    dense_prefix_layers=1, moe_intermediate_size=32))
register(ModelConfig(
    # kanana-2-30b-a3b's switches at toy widths: full-rank q, one routing
    # group, two shared experts, one dense layer ahead of three MoE
    # layers (tiny-deepseek has a q bottleneck, two groups, one shared)
    name="tiny-kanana", family="deepseek", vocab_size=256,
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_layers=4, num_heads=4, num_kv_heads=4, head_dim=8,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    q_lora_rank=None, kv_lora_rank=32, max_position_embeddings=256,
    norm_type="rmsnorm", norm_eps=1e-6, activation="silu", gated_mlp=True,
    position_embedding="rope", rope_theta=1000000.0, rope_interleaved=True,
    attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
    num_experts=16, num_experts_per_tok=3, moe_router="deepseek_v3",
    moe_n_group=1, moe_topk_group=1, moe_routed_scale=2.448,
    moe_norm_topk=True, moe_shared_experts=2, dense_prefix_layers=1))
register(_afmoe(
    # trinity-mini's switches at toy widths: one leading dense layer and
    # one period [windowed x 3, full], as the benchmark's cut has it
    "tiny-afmoe", ["sliding_attention"] * 4 + ["full_attention"], 8,
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_heads=4, num_kv_heads=2, head_dim=16,
    max_position_embeddings=256, embed_scale=8.0, num_experts=16,
    num_experts_per_tok=4, moe_routed_scale=2.826, dense_prefix_layers=1))

register(_ouro(
    # ouro-2.6b's switches at toy widths: 3 layers run 3 times, 9 planes
    "tiny-ouro", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=3, loop_steps=3, num_heads=4, num_kv_heads=4, head_dim=16,
    max_position_embeddings=256))

register(_falcon_h1(
    # falcon-h1's switches at toy widths: head_dim != hidden / heads, 2
    # groups, a scan chunk of 8, every multiplier away from 1
    "tiny-falcon-h1", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=24,
    max_position_embeddings=256, rope_theta=1e6, embed_scale=2.5,
    logit_scale=0.4,
    ssm=SSMConfig(
        d_ssm=64, n_heads=4, d_head=16, d_state=16, n_groups=2, d_conv=4,
        chunk_size=8, conv_bias=True, in_multiplier=1.8,
        out_multiplier=0.7, multipliers=(0.7, 1.6, 0.5, 1.5, 2.5),
        attn_in_multiplier=1.1, attn_out_multiplier=0.5,
        key_multiplier=0.6, mlp_multipliers=(0.7, 0.45))))

register(_mimo_v2(
    # mimo-v2.5's switches at toy widths: the benchmark's cut of one
    # leading dense full layer and one period [windowed x 5, full], K/V
    # heads 4 against 2, value heads narrower than the q and k heads, 8
    # of 24 columns rotated, the two rotary bases apart
    "tiny-mimo-v2", [0, 1, 1, 1, 1, 1, 0], 4, 1e2,
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_layers=7, num_heads=8, num_kv_heads=2,
    head_dim=24, v_head_dim=16, attn_value_scale=0.707, rope_pct=0.334,
    rope_theta=1e4, sliding_window=8, max_position_embeddings=256,
    num_experts=32, num_experts_per_tok=4))
