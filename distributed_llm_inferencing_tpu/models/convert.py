"""HF checkpoint -> JAX pytree conversion.

The TPU-native replacement for the reference's model ingestion
(reference: worker/app.py:117-121 ``AutoModelForCausalLM.from_pretrained``
and the shard_model CLI's layer copying, shard_model.py:71-91): we read an
HF checkpoint ONCE into the stacked-layer pytree of models/transformer.py.
Sharding is a PartitionSpec assignment at load time (parallel/sharding.py),
not a file rewrite — no full-size "shards" with random out-of-range weights
(the reference's flaw, SURVEY.md §2.4).

Entry points:
- ``config_from_hf(hf_config)`` — map a transformers config to ModelConfig
- ``convert_state_dict(cfg, state_dict)`` — torch/numpy state dict -> pytree
- ``load_hf_model(path_or_model)`` — local checkpoint dir or in-memory HF
  model -> (ModelConfig, params). Works fully offline.
"""

from __future__ import annotations

import os

import numpy as np
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models.config import ModelConfig


def _np(t):
    """torch tensor | np array -> float32 numpy."""
    if hasattr(t, "detach"):
        t = t.detach().cpu().float().numpy()
    return np.asarray(t, dtype=np.float32)


def _qwen2_window(hf_config):
    """Qwen2 windows only layers >= max_window_layers (HF
    configuration_qwen2.py) — a per-layer mix our global
    cfg.sliding_window cannot represent, so accept only the two shapes
    that map exactly and refuse the rest loudly (silently windowing the
    full-attention layers would corrupt long-prompt logits)."""
    if not getattr(hf_config, "use_sliding_window", False):
        return None
    mwl = getattr(hf_config, "max_window_layers", 0) or 0
    if mwl > 0 and mwl < hf_config.num_hidden_layers:
        raise NotImplementedError(
            f"qwen2 with use_sliding_window and 0 < max_window_layers="
            f"{mwl} < num_layers={hf_config.num_hidden_layers}: mixed "
            "full/windowed layers are not supported")
    if mwl >= hf_config.num_hidden_layers:
        return None                       # every layer is full-attention
    return hf_config.sliding_window       # every layer is windowed


def _yarn_params(rs: dict, dim: int, base: float, max_pos: int):
    """Yarn NTK-by-part rope scaling (HF modeling_rope_utils.py
    _compute_yarn_parameters, arXiv:2309.00071): interpolated and
    extrapolated frequency ladders blended by a per-dim linear ramp
    between the beta_fast/beta_slow correction bounds. Returns
    (inv_freq tuple [dim/2], attention_factor, mscale_all_dim_scale) —
    the last is HF deepseek's separate uniform score multiplier
    (modeling_deepseek_v3.py:372-377), squared there; we fold its square
    into the q weights at conversion."""
    import math
    factor = float(rs["factor"])
    beta_fast = float(rs.get("beta_fast") or 32)
    beta_slow = float(rs.get("beta_slow") or 1)
    orig = int(rs.get("original_max_position_embeddings") or max_pos)
    mscale = rs.get("mscale")
    mscale_all = rs.get("mscale_all_dim")

    def get_mscale(scale, m=1.0):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    attn_factor = rs.get("attention_factor")
    if attn_factor is None:
        if mscale and mscale_all:
            attn_factor = get_mscale(factor, mscale) / get_mscale(
                factor, mscale_all)
        else:
            attn_factor = get_mscale(factor)

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                ) / (2 * math.log(base))
    low, high = corr_dim(beta_fast), corr_dim(beta_slow)
    if rs.get("truncate", True):   # HF floor/ceils unless truncate:false
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv_freq = (1.0 / (factor * pos_freqs)) * ramp \
        + (1.0 / pos_freqs) * (1.0 - ramp)
    score_scale = get_mscale(factor, float(mscale_all or 0.0)) \
        if mscale_all else 1.0
    return tuple(float(f) for f in inv_freq), float(attn_factor), \
        float(score_scale)


def _rope_scaling_params(hf_config, dim: int, what: str):
    """Map an HF ``rope_scaling`` dict to (inv_freq tuple | None,
    attention_factor, score_scale) for cfg.rope_inv_freq /
    cfg.rope_attn_factor (ops/rope.apply_rope). Covers the schemes whose
    effect is a static frequency-ladder rewrite — "yarn" (+ deepseek's
    mscale), "llama3" (Llama 3.1+ NTK-by-part smoothing, HF
    modeling_rope_utils._compute_llama3_parameters), "linear"
    (position-interpolation: uniform /factor), "longrope" (Phi-3.5
    factor sets, static regime pick), "default" — and refuses
    the rest loudly (silently ignoring rope_scaling would corrupt
    long-context logits for every scaled checkpoint)."""
    import math
    rs = getattr(hf_config, "rope_scaling", None)
    if not rs:
        return None, 1.0, 1.0
    kind = rs.get("rope_type", rs.get("type"))
    base = float(getattr(hf_config, "rope_theta", 10000.0))
    if kind == "yarn":
        return _yarn_params(rs, dim, base,
                            hf_config.max_position_embeddings)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv_freq = 1.0 / pos_freqs
    if kind in (None, "default"):
        return None, 1.0, 1.0
    if kind == "linear":
        return tuple(float(f) for f in inv_freq / float(rs["factor"])), \
            1.0, 1.0
    if kind == "longrope":
        # Phi-3-style longrope carries TWO per-dim factor sets that HF
        # switches per forward at original_max_position_embeddings. A
        # static conversion must pick ONE regime: we convert for the
        # window the checkpoint ADVERTISES — long factors (plus the
        # attention factor) when max_position_embeddings was extended
        # past the original, short factors otherwise. Exact HF parity
        # within the chosen regime; sequences in the other regime see
        # the divergence HF itself acknowledges when the cache crosses
        # the boundary mid-generation.
        # HF reads original_max_position_embeddings from the CONFIG
        # attribute only (never the rope_scaling dict), deriving the
        # attention-factor base from max/original when present and from
        # rs["factor"] otherwise (modeling_rope_utils.py
        # _compute_longrope_parameters)
        orig = getattr(hf_config, "original_max_position_embeddings",
                       None)
        if orig:
            factor = hf_config.max_position_embeddings / orig
            extended = hf_config.max_position_embeddings > orig
        else:
            orig = hf_config.max_position_embeddings
            factor = float(rs.get("factor") or 1.0)
            extended = False   # no original => HF stays on short factors
        ext = np.asarray(rs["long_factor" if extended else "short_factor"],
                         np.float64)
        if ext.shape != (dim // 2,):
            raise NotImplementedError(
                f"longrope factor set has {ext.shape[0]} entries for "
                f"rotary dim {dim}")
        attn_factor = rs.get("attention_factor")
        if attn_factor is None:
            attn_factor = (1.0 if factor <= 1.0
                           else math.sqrt(1 + math.log(factor)
                                          / math.log(orig)))
        return tuple(float(v) for v in 1.0 / (ext * pos_freqs)), \
            float(attn_factor), 1.0
    if kind == "llama3":
        factor = float(rs["factor"])
        lo_f = float(rs["low_freq_factor"])
        hi_f = float(rs["high_freq_factor"])
        old = float(rs.get("original_max_position_embeddings")
                    or hf_config.max_position_embeddings)
        wavelen = 2 * math.pi / inv_freq
        scaled = np.where(wavelen > old / lo_f, inv_freq / factor, inv_freq)
        smooth = (old / wavelen - lo_f) / (hi_f - lo_f)
        smoothed = (1 - smooth) * scaled / factor + smooth * scaled
        medium = ~(wavelen < old / hi_f) & ~(wavelen > old / lo_f)
        out = np.where(medium, smoothed, scaled)
        return tuple(float(f) for f in out), 1.0, 1.0
    raise NotImplementedError(
        f"{what} rope_scaling type {kind!r} — yarn, llama3, linear and "
        "longrope convert")


def _layer_windows_from_hf(hf_config, require_use_flag: bool = False):
    """Per-layer windows from an HF ``layer_types`` list: returns
    (sliding_window, attn_windows, kinds) ready for the ModelConfig
    kwargs — the uniform case keeps the static sliding_window (pallas
    flash kernels stay eligible), the mixed case emits the per-layer
    tuple. ``require_use_flag``: gate on use_sliding_window (smollm3)
    instead of sliding_window's presence alone."""
    kinds = list(getattr(hf_config, "layer_types", None) or [])
    win = getattr(hf_config, "sliding_window", None)
    enabled = (bool(getattr(hf_config, "use_sliding_window", win))
               if require_use_flag else win is not None)
    wins = tuple(win if (enabled and t == "sliding_attention") else None
                 for t in kinds)
    windowed = any(w is not None for w in wins)
    uniform = not windowed or len(set(wins)) == 1
    return ((wins[0] if windowed and uniform else None),
            (None if uniform else wins), kinds)


# HF hidden_act -> our activation kinds (models/transformer.py _act).
# "gelu" is the erf form; gelu_new/gelu_pytorch_tanh are the tanh approx.
_HF_ACT = {"gelu": "gelu_exact", "gelu_new": "gelu",
           "gelu_pytorch_tanh": "gelu", "silu": "silu", "relu": "relu",
           "relu2": "relu2"}


def _act_from_hf(name: str) -> str:
    if name not in _HF_ACT:
        raise NotImplementedError(f"unsupported hidden_act {name!r}")
    return _HF_ACT[name]


SUPPORTED_MODEL_TYPES = ("gpt2", "opt", "llama", "mistral", "mixtral",
                         "qwen2", "gemma", "gpt_neox", "phi", "falcon",
                         "bloom", "gptj", "mpt", "gpt_bigcode", "stablelm",
                         "codegen", "starcoder2", "olmo", "phi3",
                         "gpt_neo", "gemma2", "cohere", "qwen3",
                         "qwen3_moe", "granite", "olmo2", "glm", "glm4",
                         "nemotron", "deepseek_v3", "ernie4_5", "smollm3",
                         "hunyuan_v1_dense", "exaone4", "dbrx", "glm4_moe",
                         "ernie4_5_moe", "gpt_oss", "hunyuan_v1_moe",
                         "afmoe", "ouro", "falcon_h1", "mimo_v2")


def config_from_hf(hf_config) -> ModelConfig:
    mt = hf_config.model_type
    if mt == "gpt2":
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", "gpt2") or "gpt2",
            family="gpt2", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
            num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
            num_kv_heads=hf_config.n_head,
            head_dim=hf_config.n_embd // hf_config.n_head,
            max_position_embeddings=hf_config.n_positions,
            norm_type="layernorm", norm_eps=hf_config.layer_norm_epsilon,
            activation="gelu", gated_mlp=False, position_embedding="learned",
            attn_bias=True, mlp_bias=True, tie_word_embeddings=True)
    if mt == "opt":
        proj = getattr(hf_config, "word_embed_proj_dim", hf_config.hidden_size)
        return ModelConfig(
            embed_proj_dim=proj if proj != hf_config.hidden_size else None,
            post_norm=not getattr(hf_config, "do_layer_norm_before", True),
            name=getattr(hf_config, "name_or_path", "opt") or "opt",
            family="opt", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.ffn_dim,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_attention_heads,
            head_dim=hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="layernorm", activation="relu", gated_mlp=False,
            position_embedding="learned", attn_bias=True, mlp_bias=True,
            tie_word_embeddings=True)
    if mt in ("llama", "mistral", "mixtral", "qwen2", "gemma"):
        # All share the llama layer layout (model.layers.N.self_attn.*,
        # mlp gate/up/down, input/post_attention layernorms), so one
        # conversion family covers them; the deltas are config switches.
        num_experts = getattr(hf_config, "num_local_experts", 0) if mt == "mixtral" else 0
        inv_freq, attn_factor, _ = _rope_scaling_params(
            hf_config, getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads, mt)
        return ModelConfig(
            rope_inv_freq=inv_freq, rope_attn_factor=attn_factor,
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads",
                                 hf_config.num_attention_heads),
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            # gemma: gelu_pytorch_tanh == our default tanh-gelu
            activation="gelu" if mt == "gemma" else "silu",
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            # qwen2: bias on q/k/v only (baked into the HF module, not a
            # config attr), o_proj bias-free
            attn_bias=(True if mt == "qwen2"
                       else getattr(hf_config, "attention_bias", False)),
            o_bias=False if mt == "qwen2" else None,
            mlp_bias=getattr(hf_config, "mlp_bias", False),
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        mt == "gemma"),
            # qwen2 carries sliding_window=4096 in its config but only
            # APPLIES it when use_sliding_window is set (HF default off)
            sliding_window=_qwen2_window(hf_config) if mt == "qwen2"
            else getattr(hf_config, "sliding_window", None),
            num_experts=num_experts,
            num_experts_per_tok=getattr(hf_config, "num_experts_per_tok", 2),
            # gemma: sqrt(D) embedding normalizer + (1+w) norm convention
            embed_scale=(hf_config.hidden_size ** 0.5 if mt == "gemma"
                         else None),
            norm_offset=mt == "gemma")
    if mt == "gpt_neox":
        # GPT-NeoX / Pythia: parallel-residual blocks (two norms), fused
        # per-head-interleaved QKV, partial rotary (rotary_pct), exact
        # (erf) gelu, untied embed_out head.
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="gpt-neox", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_attention_heads,
            head_dim=hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="layernorm", norm_eps=hf_config.layer_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=False, position_embedding="rope",
            rope_theta=getattr(hf_config, "rotary_emb_base", None)
            or getattr(hf_config, "rope_theta", 10000.0),
            rope_pct=getattr(hf_config, "rotary_pct", 1.0),
            attn_bias=getattr(hf_config, "attention_bias", True),
            mlp_bias=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False),
            parallel_residual=getattr(hf_config, "use_parallel_residual",
                                      True))
    if mt == "phi":
        # Phi-1/1.5/2: parallel residual with a SINGLE shared layernorm,
        # partial rotary, biases everywhere incl. the untied lm_head.
        if getattr(hf_config, "qk_layernorm", False):
            raise NotImplementedError("phi with qk_layernorm")
        heads = hf_config.num_attention_heads
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="phi", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers, num_heads=heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or heads,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="layernorm", norm_eps=hf_config.layer_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=False, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_pct=getattr(hf_config, "partial_rotary_factor", 0.5),
            attn_bias=True, mlp_bias=True, lm_head_bias=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False),
            parallel_residual=True, shared_attn_mlp_norm=True)
    if mt == "falcon":
        # Falcon: fused grouped/MQA QKV, exact gelu, no biases. Three
        # shapes map: the 7B layout (multi_query, parallel residual,
        # single shared norm), the new decoder architecture (grouped-KV,
        # ln_attn + ln_mlp parallel norms), and the RW layout (per-head
        # fused QKV, sequential residual, ALiBi positions).
        new_arch = getattr(hf_config, "new_decoder_architecture", False)
        parallel = getattr(hf_config, "parallel_attn", True)
        alibi = getattr(hf_config, "alibi", False)
        if new_arch and getattr(hf_config, "num_ln_in_parallel_attn",
                                None) == 1:
            raise NotImplementedError("falcon new-arch with a single "
                                      "parallel layernorm")
        heads = hf_config.num_attention_heads
        if new_arch:
            kv = getattr(hf_config, "num_kv_heads", None) or heads
        else:
            kv = 1 if getattr(hf_config, "multi_query", True) else heads
        bias = getattr(hf_config, "bias", False)
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="falcon", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=getattr(hf_config, "ffn_hidden_size", None)
            or 4 * hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers, num_heads=heads,
            num_kv_heads=kv,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=getattr(
                hf_config, "max_position_embeddings", 2048),
            norm_type="layernorm",
            norm_eps=getattr(hf_config, "layer_norm_epsilon", 1e-5),
            activation=_act_from_hf(getattr(hf_config, "activation",
                                            "gelu")),
            gated_mlp=False,
            position_embedding="alibi" if alibi else "rope",
            # falcon scales (scores + alibi) by 1/sqrt(hd) together
            alibi_scale=(hf_config.hidden_size // heads) ** -0.5
            if alibi else 1.0,
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=bias, mlp_bias=bias,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True),
            parallel_residual=parallel,
            shared_attn_mlp_norm=parallel and not new_arch)
    if mt == "bloom":
        # BLOOM: ALiBi positions, layernormed embedding output, per-head
        # interleaved fused QKV, tanh-gelu, tied 250k-vocab head.
        heads = hf_config.n_head
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="bloom", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=4 * hf_config.hidden_size,
            num_layers=hf_config.n_layer, num_heads=heads,
            num_kv_heads=heads,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=getattr(hf_config, "seq_length", None)
            or 2048,
            norm_type="layernorm",
            norm_eps=hf_config.layer_norm_epsilon,
            activation="gelu", gated_mlp=False,
            position_embedding="alibi", embed_norm=True,
            attn_bias=True, mlp_bias=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True))
    if mt == "gptj":
        # GPT-J: parallel residual with ONE shared layernorm, partial
        # INTERLEAVED rotary (rotate_every_two over rotary_dim dims),
        # bias-free attention, biased MLP and untied biased lm_head.
        heads = hf_config.n_head
        hd = hf_config.n_embd // heads
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="gptj", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            intermediate_size=getattr(hf_config, "n_inner", None)
            or 4 * hf_config.n_embd,
            num_layers=hf_config.n_layer, num_heads=heads,
            num_kv_heads=heads, head_dim=hd,
            max_position_embeddings=hf_config.n_positions,
            norm_type="layernorm",
            norm_eps=hf_config.layer_norm_epsilon,
            activation=_act_from_hf(hf_config.activation_function),
            gated_mlp=False, position_embedding="rope",
            rope_theta=10000.0,
            rope_pct=(getattr(hf_config, "rotary_dim", None) or hd) / hd,
            rope_interleaved=True,
            attn_bias=False, o_bias=False, mlp_bias=True,
            lm_head_bias=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False),
            parallel_residual=True, shared_attn_mlp_norm=True)
    if mt == "mpt":
        # MPT: ALiBi (BLOOM-convention slopes for power-of-two heads),
        # straight-concat fused QKV (optionally grouped KV), bias-free
        # layout by default, exact gelu, tied head.
        ac = hf_config.attn_config

        def acget(key, default=None):
            return (ac.get(key, default) if isinstance(ac, dict)
                    else getattr(ac, key, default))
        if not acget("alibi", True):
            raise NotImplementedError("mpt without alibi positions")
        if acget("clip_qkv") or acget("qk_ln", False):
            raise NotImplementedError("mpt with clip_qkv/qk_ln")
        if acget("softmax_scale") is not None:
            raise NotImplementedError(
                "mpt with a custom attn softmax_scale (the runtime always "
                "uses 1/sqrt(head_dim))")
        if acget("alibi_bias_max", 8) != 8:
            raise NotImplementedError("mpt with alibi_bias_max != 8")
        heads = hf_config.n_heads
        if heads & (heads - 1):
            raise NotImplementedError(
                "mpt with non-power-of-two heads: its alibi slope "
                "interpolation differs from the BLOOM convention")
        D = hf_config.d_model
        bias = not getattr(hf_config, "no_bias", True)
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="mpt", vocab_size=hf_config.vocab_size,
            hidden_size=D,
            intermediate_size=int(hf_config.expansion_ratio * D),
            num_layers=hf_config.n_layers, num_heads=heads,
            num_kv_heads=acget("kv_n_heads", None) or heads,
            head_dim=D // heads,
            max_position_embeddings=hf_config.max_seq_len,
            norm_type="layernorm", norm_eps=1e-5,
            activation="gelu_exact", gated_mlp=False,
            position_embedding="alibi",
            attn_bias=bias, mlp_bias=bias,
            tie_word_embeddings=True)
    if mt == "gpt_bigcode":
        # StarCoder / SantaCoder: GPT-2 block layout but nn.Linear (not
        # Conv1D) weights, multi-query attention (1 kv head) by default,
        # tanh-gelu, learned positions, tied head.
        heads = hf_config.n_head
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="gpt_bigcode", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            intermediate_size=getattr(hf_config, "n_inner", None)
            or 4 * hf_config.n_embd,
            num_layers=hf_config.n_layer, num_heads=heads,
            num_kv_heads=1 if getattr(hf_config, "multi_query", True)
            else heads,
            head_dim=hf_config.n_embd // heads,
            max_position_embeddings=hf_config.n_positions,
            norm_type="layernorm",
            norm_eps=hf_config.layer_norm_epsilon,
            activation=_act_from_hf(getattr(hf_config,
                                            "activation_function",
                                            "gelu_pytorch_tanh")),
            gated_mlp=False, position_embedding="learned",
            attn_bias=True, mlp_bias=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True))
    if mt == "stablelm":
        # StableLM / StableLM-2: llama layer layout with LAYERNORMS
        # (biased) instead of rmsnorm, partial rotary, optional qkv-only
        # bias, untied head.
        if getattr(hf_config, "use_parallel_residual", False):
            raise NotImplementedError("stablelm with use_parallel_residual")
        if getattr(hf_config, "qk_layernorm", False):
            raise NotImplementedError("stablelm with qk_layernorm")
        heads = hf_config.num_attention_heads
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="stablelm", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers, num_heads=heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or heads,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="layernorm",
            norm_eps=getattr(hf_config, "layer_norm_eps", 1e-5),
            activation=_act_from_hf(getattr(hf_config, "hidden_act",
                                            "silu")),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_pct=getattr(hf_config, "partial_rotary_factor", 0.25),
            attn_bias=getattr(hf_config, "use_qkv_bias", False),
            o_bias=False, mlp_bias=False,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "codegen":
        # CodeGen (Salesforce): GPT-J topology — parallel residual with a
        # single shared ln_1, partial INTERLEAVED rotary over rotary_dim,
        # bias-free attention, biased MLP + untied biased lm_head. Only
        # the fused-QKV weight layout differs (mp_num blocks, q|v|k
        # order — see convert_state_dict).
        heads = hf_config.n_head
        hd = hf_config.n_embd // heads
        if heads % 4:
            raise NotImplementedError(
                "codegen with n_head not divisible by mp_num=4 (HF "
                "CodeGenAttention hard-codes 4 TP blocks in the fused "
                "QKV layout)")
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="codegen", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            intermediate_size=getattr(hf_config, "n_inner", None)
            or 4 * hf_config.n_embd,
            num_layers=hf_config.n_layer, num_heads=heads,
            num_kv_heads=heads, head_dim=hd,
            max_position_embeddings=hf_config.n_positions,
            norm_type="layernorm",
            norm_eps=hf_config.layer_norm_epsilon,
            activation=_act_from_hf(hf_config.activation_function),
            gated_mlp=False, position_embedding="rope",
            rope_theta=10000.0,
            rope_pct=(getattr(hf_config, "rotary_dim", None) or hd) / hd,
            rope_interleaved=True,
            attn_bias=False, o_bias=False, mlp_bias=True,
            lm_head_bias=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False),
            parallel_residual=True, shared_attn_mlp_norm=True)
    if mt == "starcoder2":
        # StarCoder2: llama layer layout/names but biased LAYERNORMS, a
        # plain (non-gated) tanh-gelu MLP named c_fc/c_proj, biased
        # linears (use_bias), full rotary, optional sliding window.
        heads = hf_config.num_attention_heads
        bias = getattr(hf_config, "use_bias", True)
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="starcoder2", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers, num_heads=heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or heads,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="layernorm",
            norm_eps=getattr(hf_config, "norm_epsilon", 1e-5),
            activation=_act_from_hf(getattr(hf_config, "hidden_act",
                                            "gelu_pytorch_tanh")),
            gated_mlp=False, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=bias, mlp_bias=bias,
            sliding_window=getattr(hf_config, "sliding_window", None),
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True))
    if mt == "olmo":
        # OLMo: llama layout with NON-PARAMETRIC layernorms (no scale or
        # bias — converted as unit-scale/zero-bias leaves so the runtime
        # norm stays uniform), SwiGLU, bias-free linears, full rotary.
        if getattr(hf_config, "clip_qkv", None):
            raise NotImplementedError(
                "olmo with clip_qkv (the runtime applies no QKV "
                "activation clamp)")
        heads = hf_config.num_attention_heads
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="olmo", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers, num_heads=heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or heads,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            # HF OlmoLayerNorm: F.layer_norm with no affine, eps 1e-5
            norm_type="layernorm", norm_eps=1e-5,
            activation=_act_from_hf(getattr(hf_config, "hidden_act",
                                            "silu")),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=getattr(hf_config, "attention_bias", False),
            mlp_bias=False,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "phi3":
        # Phi-3: llama semantics (rmsnorm, SwiGLU, full rotary, GQA,
        # bias-free, untied head) with FUSED qkv_proj ([q|k|v] rows) and
        # gate_up_proj ([gate|up] rows) — split in convert_state_dict.
        # Longrope (Phi-3.5's 128k extension) converts via the static
        # regime pick in _rope_scaling_params.
        heads = hf_config.num_attention_heads
        p3_inv_freq, p3_attn_factor, _ = _rope_scaling_params(
            hf_config,
            int((hf_config.hidden_size // heads)
                * getattr(hf_config, "partial_rotary_factor", 1.0)), mt)
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="phi3", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers, num_heads=heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or heads,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(getattr(hf_config, "hidden_act",
                                            "silu")),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_inv_freq=p3_inv_freq, rope_attn_factor=p3_attn_factor,
            # phi-4-mini ships partial rotary; the scaled ladder above is
            # already sized to the partial dim, and rope_pct keeps
            # apply_rope's rotated slice to the same width
            rope_pct=float(getattr(hf_config, "partial_rotary_factor",
                                   1.0)),
            attn_bias=False, mlp_bias=False,
            sliding_window=getattr(hf_config, "sliding_window", None),
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "gpt_neo":
        # GPT-Neo: gpt2 topology (learned positions, sequential pre-LN,
        # plain gelu MLP) with two quirks: attention scores are UNSCALED
        # (no 1/sqrt(hd) — folded into the q weights at conversion, the
        # same absorb-at-conversion idiom as gemma's norm offset), and
        # layers alternate global / local-window attention
        # (attention_types) — the per-layer window rides the param tree
        # (config.py attn_windows).
        kinds = list(hf_config.attention_layers)
        if not all(t in ("global", "local") for t in kinds):
            raise NotImplementedError(
                f"gpt_neo attention_types {sorted(set(kinds))!r} — only "
                "global/local convert")
        win = int(getattr(hf_config, "window_size", 256))
        wins = tuple(None if t == "global" else win for t in kinds)
        uniform = len(set(wins)) == 1   # all-global OR all-local: the
        # static uniform path keeps the pallas flash kernels eligible
        heads = hf_config.num_heads
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="gpt_neo", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=getattr(hf_config, "intermediate_size", None)
            or 4 * hf_config.hidden_size,
            num_layers=hf_config.num_layers, num_heads=heads,
            num_kv_heads=heads,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="layernorm",
            norm_eps=hf_config.layer_norm_epsilon,
            activation=_act_from_hf(getattr(hf_config,
                                            "activation_function",
                                            "gelu_new")),
            gated_mlp=False, position_embedding="learned",
            attn_bias=False, o_bias=True, mlp_bias=True,
            sliding_window=wins[0] if uniform else None,
            attn_windows=None if uniform else wins,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True))
    if mt == "gemma2":
        # Gemma-2: gemma's rmsnorm/(1+w)/embed-scale conventions plus
        # FOUR norms per block (sandwich, post_block_norms), attention +
        # final logit softcapping, query_pre_attn_scalar replacing the
        # 1/sqrt(hd) score scale (the ratio folds into q at conversion),
        # and alternating sliding/full layers (attn_windows).
        heads = hf_config.num_attention_heads
        kinds = list(getattr(hf_config, "layer_types", None)
                     or ["sliding_attention" if i % 2 == 0
                         else "full_attention"
                         for i in range(hf_config.num_hidden_layers)])
        if not all(t in ("sliding_attention", "full_attention")
                   for t in kinds):
            raise NotImplementedError(
                f"gemma2 layer_types {sorted(set(kinds))!r}")
        win = getattr(hf_config, "sliding_window", None)
        wins = tuple(win if t == "sliding_attention" else None
                     for t in kinds)
        uniform2 = win is None or len(set(wins)) == 1
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="gemma2", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers, num_heads=heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(getattr(hf_config, "hidden_activation",
                                            "gelu_pytorch_tanh")),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=getattr(hf_config, "attention_bias", False),
            mlp_bias=False,
            sliding_window=(wins[0] if uniform2 else None),
            attn_windows=None if uniform2 else wins,
            attn_softcap=getattr(hf_config, "attn_logit_softcapping",
                                 None),
            logit_softcap=getattr(hf_config, "final_logit_softcapping",
                                  None),
            post_block_norms=True,
            query_pre_attn_scalar=float(
                getattr(hf_config, "query_pre_attn_scalar", None)
                or (getattr(hf_config, "head_dim", None)
                    or hf_config.hidden_size // heads)),
            embed_scale=hf_config.hidden_size ** 0.5,
            norm_offset=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True))
    if mt == "cohere":
        # Cohere (Command-R): parallel residual with ONE shared bias-free
        # layernorm, INTERLEAVED full rotary, tied head with a constant
        # logit scale.
        heads = hf_config.num_attention_heads
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="cohere", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers, num_heads=heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or heads,
            head_dim=hf_config.hidden_size // heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="layernorm",
            norm_eps=getattr(hf_config, "layer_norm_eps", 1e-5),
            activation=_act_from_hf(getattr(hf_config, "hidden_act",
                                            "silu")),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_interleaved=True,
            attn_bias=getattr(hf_config, "attention_bias", False),
            mlp_bias=False,
            logit_scale=getattr(hf_config, "logit_scale", None),
            # Command-R+: bias-free per-head layernorm on q/k with
            # distinct per-head scales
            qk_norm=("ln_head" if getattr(hf_config, "use_qk_norm", False)
                     else None),
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True),
            parallel_residual=True, shared_attn_mlp_norm=True)
    if mt in ("qwen3", "qwen3_moe"):
        # Qwen3 (+ MoE): llama layer layout plus per-head RMS q/k norms
        # (ONE [head_dim] scale shared across heads) and an explicit
        # head_dim decoupled from hidden_size/num_heads. The MoE variant
        # is mixtral-shaped (softmax -> top-k, with norm_topk_prob
        # driving the renormalize — cfg.moe_norm_topk).
        sw, aw, _ = _layer_windows_from_hf(hf_config)
        q3_inv_freq, q3_attn_factor, _ = _rope_scaling_params(
            hf_config, getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads, mt)
        num_experts = 0
        if mt == "qwen3_moe":
            num_experts = hf_config.num_experts
            if list(getattr(hf_config, "mlp_only_layers", []) or []):
                raise NotImplementedError("qwen3_moe with mlp_only_layers")
            if getattr(hf_config, "decoder_sparse_step", 1) != 1:
                raise NotImplementedError(
                    "qwen3_moe with decoder_sparse_step != 1")
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=(hf_config.moe_intermediate_size
                               if num_experts
                               else hf_config.intermediate_size),
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_inv_freq=q3_inv_freq, rope_attn_factor=q3_attn_factor,
            attn_bias=getattr(hf_config, "attention_bias", False),
            mlp_bias=False, qk_norm="rms_head",
            sliding_window=sw, attn_windows=aw,
            num_experts=num_experts,
            num_experts_per_tok=getattr(hf_config, "num_experts_per_tok",
                                        2),
            moe_norm_topk=bool(getattr(hf_config, "norm_topk_prob", True)),
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "ernie4_5":
        # ERNIE 4.5 (dense): llama layout with ONE use_bias switch on
        # every linear (attention, o and MLP alike) and an explicit
        # head_dim decoupled from hidden/heads.
        b = bool(getattr(hf_config, "use_bias", False))
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=b, o_bias=b, mlp_bias=b,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True))
    if mt == "smollm3":
        # SmolLM3: llama layout with per-layer NoPE (no_rope_layers: 1 =
        # rotate, 0 = position-free — config.py rope_layers) and
        # optional per-layer sliding windows via layer_types.
        sw, aw, _ = _layer_windows_from_hf(hf_config, require_use_flag=True)
        nope = tuple(int(v) for v in
                     getattr(hf_config, "no_rope_layers", None) or [])
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=bool(getattr(hf_config, "attention_bias", False)),
            mlp_bias=bool(getattr(hf_config, "mlp_bias", False)),
            sliding_window=sw, attn_windows=aw,
            rope_layers=(nope if nope and not all(nope) else None),
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True))
    if mt == "hunyuan_v1_dense":
        # HunYuan-Dense: llama layout + shared [head_dim] q/k RMS norms
        # applied AFTER RoPE (qk_norm_after_rope — qwen3/exaone norm
        # before rotating).
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=bool(getattr(hf_config, "attention_bias", False)),
            mlp_bias=False, qk_norm="rms_head", qk_norm_after_rope=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "afmoe":
        # Arcee Trinity (modeling_afmoe.py): gated grouped-query
        # attention with shared [head_dim] q/k RMS norms, sliding layers
        # that rotate and full layers that do not (layer_types), four
        # norms a layer, a sqrt(hidden) embedding multiplier
        # (mup_enabled), and a sigmoid token-choice router with a
        # selection-only expert_bias over num_dense_layers dense layers
        # and one group — deepseek_v3's routing at n_group 1.
        if getattr(hf_config, "score_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(
                f"afmoe score_func {hf_config.score_func!r} — only "
                "sigmoid converts")
        kinds = list(hf_config.layer_types)
        win = hf_config.sliding_window
        sliding = [t == "sliding_attention" for t in kinds]
        L, nd = hf_config.num_hidden_layers, hf_config.num_dense_layers
        E = hf_config.num_experts if nd < L else 0
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="afmoe", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            moe_intermediate_size=(hf_config.moe_intermediate_size if E
                                   else None),
            num_layers=L, num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_key_value_heads,
            head_dim=hf_config.head_dim,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=False, mlp_bias=False, qk_norm="rms_head",
            post_block_norms=True, attn_gate=True,
            embed_scale=(hf_config.hidden_size ** 0.5
                         if getattr(hf_config, "mup_enabled", False)
                         else None),
            sliding_window=win if any(sliding) else None,
            attn_windows=(tuple(win if s_ else None for s_ in sliding)
                          if any(sliding) else None),
            rope_layers=tuple(int(s_) for s_ in sliding),
            num_experts=E,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            moe_router="deepseek_v3" if E else "softmax",
            moe_n_group=1, moe_topk_group=1,
            moe_routed_scale=float(getattr(hf_config, "route_scale", 1.0)),
            moe_norm_topk=bool(getattr(hf_config, "route_norm", True)),
            moe_shared_experts=(getattr(hf_config, "num_shared_experts", 0)
                                or 0) if E else 0,
            dense_prefix_layers=nd if 0 < nd < L else 0,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "mimo_v2":
        # XiaomiMiMo MiMo-V2 (config.json, model_type mimo_v2): windowed
        # and full layers of different shapes in one stack
        # (hybrid_layer_pattern; models/config.py SWAConfig), q and k
        # heads of head_dim with the first int(head_dim *
        # partial_rotary_factor) columns rotated, value heads of
        # v_head_dim scaled by attention_value_scale, leading dense
        # layers (moe_layer_freq) and deepseek_v3's routing at one group
        # with no shared expert. The language model alone: the
        # multi-token-prediction layers and the vision and audio towers
        # are not built. Not yet checked against a real checkpoint
        # (nothing is downloaded): tests/test_mimo_v2.py converts a
        # synthetic state dict under the names assumed below.
        from distributed_llm_inferencing_tpu.models.config import SWAConfig
        g = lambda key, d=None: getattr(hf_config, key, d)   # noqa: E731
        for key, want in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"),
                          ("swa_head_dim", g("head_dim")),
                          ("swa_v_head_dim", g("v_head_dim")),
                          ("swa_num_attention_heads",
                           g("num_attention_heads"))):
            if g(key, want) != want:
                raise NotImplementedError(
                    f"mimo_v2 {key}={g(key)!r} — only {want!r} converts")
        if (g("rope_scaling") or {}).get("rope_type", "default") != "default":
            raise NotImplementedError("mimo_v2 rope_scaling")
        if g("n_shared_experts"):
            raise NotImplementedError("mimo_v2 n_shared_experts")
        L = hf_config.num_hidden_layers
        freq = list(g("moe_layer_freq") or [1] * L)
        nd = freq.index(1) if 1 in freq else L
        if any(f != 1 for f in freq[nd:]):
            raise NotImplementedError(
                "mimo_v2 moe_layer_freq: dense layers behind the first "
                "expert layer")
        E = hf_config.n_routed_experts if nd < L else 0
        return ModelConfig(
            name=g("name_or_path", mt) or mt, family="mimo_v2",
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            moe_intermediate_size=(hf_config.moe_intermediate_size if E
                                   else None),
            num_layers=L, num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_key_value_heads,
            head_dim=hf_config.head_dim, v_head_dim=hf_config.v_head_dim,
            attn_value_scale=g("attention_value_scale"),
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=g("layernorm_epsilon", 1e-5),
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=float(g("rope_theta", 10000.0)),
            rope_pct=float(g("partial_rotary_factor", 1.0)),
            attn_bias=bool(g("attention_bias", False)), mlp_bias=False,
            attn_sinks=bool(g("add_full_attention_sink_bias", False)),
            sliding_window=g("sliding_window"),
            swa=SWAConfig(
                pattern=tuple(hf_config.hybrid_layer_pattern),
                num_kv_heads=hf_config.swa_num_key_value_heads,
                rope_theta=float(g("swa_rope_theta", 10000.0)),
                sinks=bool(g("add_swa_attention_sink_bias", False))),
            num_experts=E,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            moe_router="deepseek_v3" if E else "softmax",
            moe_n_group=g("n_group", 1) or 1,
            moe_topk_group=g("topk_group", 1) or 1,
            moe_routed_scale=float(g("routed_scaling_factor") or 1.0),
            moe_norm_topk=bool(g("norm_topk_prob", True)),
            dense_prefix_layers=nd if 0 < nd < L else 0,
            tie_word_embeddings=g("tie_word_embeddings", False))
    if mt == "ouro":
        # ByteDance Ouro (modeling_ouro.py, LoopLM): a llama-shaped layer
        # under sandwich norms (input_layernorm_2 and
        # post_attention_layernorm_2 on the sublayers' outputs), the
        # whole stack run total_ut_steps times over one set of weights
        # with model.norm after every pass, and an exit gate on each
        # pass's result. The served path runs every step
        # (early_exit_threshold 1, the published value): a lower
        # threshold is refused, not served without its exits.
        if float(getattr(hf_config, "early_exit_threshold", 1.0)) < 1.0:
            raise NotImplementedError(
                f"ouro early_exit_threshold "
                f"{hf_config.early_exit_threshold!r} — only 1 (every "
                "token runs every step) converts")
        if getattr(hf_config, "use_sliding_window", False):
            raise NotImplementedError("ouro use_sliding_window")
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="ouro", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            loop_steps=int(hf_config.total_ut_steps),
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_key_value_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
            attn_bias=bool(getattr(hf_config, "attention_bias", False)),
            o_bias=(False if getattr(hf_config, "attention_bias", False)
                    else None), mlp_bias=False, post_block_norms=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "falcon_h1":
        # tiiuae Falcon-H1 (modeling_falcon_h1.py): every block runs a
        # Mamba-2 mixer beside its attention heads (attn_layer_indices
        # null: every block is the same), muP multipliers on every path.
        # NOT YET CHECKED AGAINST A REAL CHECKPOINT: no published
        # weights are in the repository; tests/test_falcon_h1.py runs a
        # synthetic state dict under these names (and, where the
        # installed transformers has the family, a random tiny
        # FalconH1ForCausalLM's own logits).
        from distributed_llm_inferencing_tpu.models.config import SSMConfig
        g = lambda k, d=None: getattr(hf_config, k, d)   # noqa: E731
        for key, want in (("mamba_rms_norm", True),
                          ("mamba_norm_before_gate", False),
                          ("mamba_proj_bias", False),
                          ("projectors_bias", False),
                          ("attention_bias", False), ("mlp_bias", False)):
            if bool(g(key, want)) != want:
                raise NotImplementedError(
                    f"falcon_h1 {key}={g(key)!r} — only {want} (the "
                    "published value) converts")
        if g("attn_layer_indices") is not None:
            raise NotImplementedError("falcon_h1 attn_layer_indices")
        if g("rope_scaling") is not None:
            raise NotImplementedError("falcon_h1 rope_scaling")
        H = g("mamba_n_heads")
        d_ssm = g("mamba_d_ssm") or int(g("mamba_expand", 2)
                                        * hf_config.hidden_size)
        return ModelConfig(
            name=g("name_or_path", mt) or mt, family="falcon_h1",
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_key_value_heads,
            head_dim=g("head_dim") or (hf_config.hidden_size
                                       // hf_config.num_attention_heads),
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=float(g("rope_theta", 10000.0)),
            attn_bias=False, mlp_bias=False,
            embed_scale=float(g("embedding_multiplier", 1.0)),
            logit_scale=float(g("lm_head_multiplier", 1.0)),
            tie_word_embeddings=bool(g("tie_word_embeddings", False)),
            ssm=SSMConfig(
                d_ssm=d_ssm, n_heads=H,
                d_head=g("mamba_d_head") or d_ssm // H,
                d_state=g("mamba_d_state"), n_groups=g("mamba_n_groups"),
                d_conv=g("mamba_d_conv"),
                chunk_size=g("mamba_chunk_size", 128),
                conv_bias=bool(g("mamba_conv_bias", True)),
                in_multiplier=float(g("ssm_in_multiplier", 1.0)),
                out_multiplier=float(g("ssm_out_multiplier", 1.0)),
                multipliers=tuple(g("ssm_multipliers", (1.0,) * 5)),
                attn_in_multiplier=float(g("attention_in_multiplier", 1.0)),
                attn_out_multiplier=float(g("attention_out_multiplier",
                                            1.0)),
                key_multiplier=float(g("key_multiplier", 1.0)),
                mlp_multipliers=tuple(g("mlp_multipliers", (1.0, 1.0)))))
    if mt == "exaone4":
        # EXAONE 4.0: the olmo2 sublayer-postnorm topology (x +
        # norm(f(x)), norms named post_attention/post_feedforward) with
        # shared [head_dim] q/k RMS norms, hybrid attention — sliding
        # layers rotate, full-attention layers are NoPE (rope_layers) —
        # and per-layer windows from layer_types.
        sw, aw, kinds = _layer_windows_from_hf(hf_config)
        windowed = sw is not None or aw is not None
        rope_on = (tuple(1 if t == "sliding_attention" else 0
                         for t in kinds) if windowed else None)
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="olmo2", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=False, mlp_bias=False, qk_norm="rms_head",
            sublayer_postnorm_only=True,
            sliding_window=sw, attn_windows=aw, rope_layers=rope_on,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "gpt_oss":
        # gpt-oss: llama-shaped attention (GQA, biases, yarn rope,
        # alternating sliding/full layers) plus two mechanisms of its
        # own — learned per-head attention SINKS (a virtual softmax
        # column, config.py attn_sinks / ops/attention.attend) and a
        # clamped-swish expert GLU with per-expert biases
        # (moe_swiglu_limit/alpha, transformer._glu_h) under a
        # top-k-then-softmax router whose bias is part of the linear
        # (moe_router="topk_softmax"). HF modeling_gpt_oss.py.
        hd = (getattr(hf_config, "head_dim", None)
              or hf_config.hidden_size // hf_config.num_attention_heads)
        go_inv_freq, go_attn_factor, _ = _rope_scaling_params(
            hf_config, hd, mt)
        sw, aw, _ = _layer_windows_from_hf(hf_config)
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="gpt_oss", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=hd,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation="silu",   # unused by the clamped GLU, kept sane
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 150000.0),
            rope_inv_freq=go_inv_freq, rope_attn_factor=go_attn_factor,
            attn_bias=bool(getattr(hf_config, "attention_bias", True)),
            mlp_bias=True,   # per-expert biases ride the expert leaves
            sliding_window=sw, attn_windows=aw,
            attn_sinks=True,
            num_experts=hf_config.num_local_experts,
            num_experts_per_tok=getattr(hf_config, "num_experts_per_tok",
                                        4),
            moe_router="topk_softmax",
            moe_swiglu_limit=float(getattr(hf_config, "swiglu_limit",
                                           7.0)),
            moe_swiglu_alpha=1.702,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "hunyuan_v1_moe":
        # HunYuan-MoE: the hunyuan dense layout (post-RoPE per-head q/k
        # RMS norms) with mixtral-convention routing (softmax -> top-k
        # -> renormalize) and an always-active shared MLP of the same
        # intermediate width.
        ne = hf_config.num_experts
        tk = getattr(hf_config, "moe_topk", 1)
        if not isinstance(ne, int) or not isinstance(tk, int):
            raise NotImplementedError(
                "hunyuan_v1_moe with per-layer num_experts/moe_topk "
                "lists")
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=bool(getattr(hf_config, "attention_bias", False)),
            mlp_bias=False, qk_norm="rms_head", qk_norm_after_rope=True,
            num_experts=ne, num_experts_per_tok=tk,
            moe_shared_experts=1,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "ernie4_5_moe":
        # ERNIE 4.5 MoE: the dense ernie4_5 layout with softmax routing
        # under deepseek-style bias-corrected SELECTION (moe_statics.
        # e_score_correction_bias, moe_router="ernie"), shared experts,
        # and a dense prefix (moe_layer_start_index). Every-Nth-layer
        # MoE interleaving (moe_layer_interval > 1) and early MoE end
        # are refused — the segment machinery models prefix+tail only.
        L = hf_config.num_hidden_layers
        if getattr(hf_config, "moe_layer_interval", 1) != 1:
            raise NotImplementedError(
                "ernie4_5_moe with moe_layer_interval != 1")
        if getattr(hf_config, "moe_layer_end_index", L - 1) not in (
                -1, L - 1):
            raise NotImplementedError(
                "ernie4_5_moe with moe_layer_end_index before the last "
                "layer")
        fk = getattr(hf_config, "moe_layer_start_index", 0) or 0
        mixed = 0 < fk < L
        b = bool(getattr(hf_config, "use_bias", False))
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            moe_intermediate_size=hf_config.moe_intermediate_size,
            num_layers=L, num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=b, o_bias=b, mlp_bias=b,
            num_experts=hf_config.moe_num_experts,
            num_experts_per_tok=getattr(hf_config, "moe_k", 2),
            moe_router="ernie",
            moe_shared_experts=(getattr(hf_config,
                                        "moe_num_shared_experts", 0)
                                or 0),
            dense_prefix_layers=fk if mixed else 0,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        True))
    if mt == "glm4_moe":
        # GLM-4.5 (MoE): llama block topology with optional per-head
        # q/k RMS norms (pre-rope, qwen3-style), partial half-split
        # rotary, and DeepSeek-V3's exact routing — sigmoid scores,
        # e_score_correction_bias group-limited top-k, shared experts —
        # over a first_k_dense_replace mixed dense/MoE stack (HF
        # modeling_glm4_moe.py Glm4MoeTopkRouter is byte-for-byte
        # deepseek's).
        L = hf_config.num_hidden_layers
        fk = getattr(hf_config, "first_k_dense_replace", 0) or 0
        all_dense = fk >= L
        E = 0 if all_dense else hf_config.n_routed_experts
        mixed = 0 < fk < L
        hd = (getattr(hf_config, "head_dim", None)
              or hf_config.hidden_size // hf_config.num_attention_heads)
        pct = float(getattr(hf_config, "partial_rotary_factor", 1.0))
        gm_inv_freq, gm_attn_factor, _ = _rope_scaling_params(
            hf_config, int(hd * pct), mt)
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            moe_intermediate_size=(hf_config.moe_intermediate_size if E
                                   else None),
            num_layers=L, num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=hd,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_pct=pct,
            rope_inv_freq=gm_inv_freq, rope_attn_factor=gm_attn_factor,
            attn_bias=bool(getattr(hf_config, "attention_bias", False)),
            o_bias=False, mlp_bias=False,
            qk_norm=("rms_head" if getattr(hf_config, "use_qk_norm",
                                           False) else None),
            num_experts=E,
            num_experts_per_tok=getattr(hf_config, "num_experts_per_tok",
                                        8),
            moe_router="deepseek_v3" if E else "softmax",
            moe_n_group=getattr(hf_config, "n_group", 1) or 1,
            moe_topk_group=getattr(hf_config, "topk_group", 1) or 1,
            moe_routed_scale=float(getattr(hf_config,
                                           "routed_scaling_factor", 1.0)),
            moe_norm_topk=bool(getattr(hf_config, "norm_topk_prob", True)),
            moe_shared_experts=(getattr(hf_config, "n_shared_experts", 0)
                                or 0) if E else 0,
            dense_prefix_layers=fk if mixed else 0,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "dbrx":
        # DBRX: the standard pre-LN sequential block under unusual
        # naming (norm_attn_norm.norm_1/norm_2 ≡ attn/mlp pre-norms,
        # bias-free LayerNorms), fused Wqkv with the clip_qkv activation
        # clamp (config.py qkv_clip), and a 16-expert GLU MoE whose
        # router renormalizes top-k weights by their p-norm —
        # p=1 over softmax weights == our renorm; None == no renorm
        # (moe_norm_topk); other p values are refused.
        ac, fc = hf_config.attn_config, hf_config.ffn_config
        p = getattr(fc, "moe_normalize_expert_weights", 1.0)
        if p is not None and float(p) != 1.0:
            raise NotImplementedError(
                f"dbrx moe_normalize_expert_weights={p} — only 1.0 "
                "(L1 over positive softmax weights == renormalize) or "
                "None convert")
        act = getattr(fc, "ffn_act_fn", None) or {}
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="dbrx", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.d_model,
            intermediate_size=fc.ffn_hidden_size,
            num_layers=hf_config.n_layers, num_heads=hf_config.n_heads,
            num_kv_heads=ac.kv_n_heads,
            head_dim=hf_config.d_model // hf_config.n_heads,
            max_position_embeddings=hf_config.max_seq_len,
            norm_type="layernorm",
            activation=_act_from_hf(act.get("name", "silu")),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(ac, "rope_theta", 10000.0),
            attn_bias=False, mlp_bias=False,
            qkv_clip=(float(ac.clip_qkv) if getattr(ac, "clip_qkv", None)
                      else None),
            num_experts=fc.moe_num_experts,
            num_experts_per_tok=fc.moe_top_k,
            moe_norm_topk=p is not None,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "deepseek_v3":
        # DeepSeek-V3: llama residual topology with multi-head latent
        # attention (low-rank q/kv bottlenecks with mid-stack RMSNorms,
        # decoupled shared-rope head — config.py kv_lora_rank and
        # transformer._mla_qkv) and sigmoid/group-limited MoE routing
        # with always-active shared experts (transformer._moe_route
        # "deepseek_v3"). HF: modeling_deepseek_v3.py.
        nd = hf_config.qk_nope_head_dim
        rd = hf_config.qk_rope_head_dim
        # the rope ladder spans only the decoupled rope head (dim=rd —
        # HF's DeepseekV3Config sets head_dim accordingly)
        inv_freq, attn_factor, score_scale = _rope_scaling_params(
            hf_config, rd, mt)
        # yarn's mscale_all_dim multiplier scales SCORES uniformly by
        # score_scale**2 (HF modeling_deepseek_v3.py:372-377); fold it
        # into the q weights via the query_pre_attn_scalar absorption
        # (conversion scales q by sqrt(hd/qpas) — pick qpas so that
        # equals score_scale**2)
        qpas = None
        if score_scale != 1.0:
            qpas = (nd + rd) / score_scale ** 4
        L = hf_config.num_hidden_layers
        fk = getattr(hf_config, "first_k_dense_replace", 0) or 0
        # fk >= L: every layer dense (num_experts=0). 0 < fk < L: the
        # shipped V3/V2 layout — a dense prefix segment ahead of the MoE
        # tail (config.py dense_prefix_layers; the layer scans run the
        # two stacked segments back to back, transformer.layer_segments)
        all_dense = fk >= L
        E = 0 if all_dense else hf_config.n_routed_experts
        mixed = 0 < fk < L
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="deepseek", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            moe_intermediate_size=(hf_config.moe_intermediate_size if E
                                   else None),
            num_layers=L, num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_attention_heads,
            head_dim=rd,   # the source's meaning: the rope head
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_interleaved=bool(getattr(hf_config, "rope_interleave",
                                          True)),
            rope_inv_freq=inv_freq, rope_attn_factor=attn_factor,
            query_pre_attn_scalar=qpas,
            attn_bias=bool(getattr(hf_config, "attention_bias", False)),
            mlp_bias=False,
            q_lora_rank=getattr(hf_config, "q_lora_rank", None),
            kv_lora_rank=hf_config.kv_lora_rank,
            qk_nope_head_dim=nd, qk_rope_head_dim=rd,
            v_head_dim=hf_config.v_head_dim,
            num_experts=E,
            num_experts_per_tok=getattr(hf_config, "num_experts_per_tok",
                                        8),
            moe_router="deepseek_v3" if E else "softmax",
            moe_n_group=getattr(hf_config, "n_group", 1) or 1,
            moe_topk_group=getattr(hf_config, "topk_group", 1) or 1,
            moe_routed_scale=float(getattr(hf_config,
                                           "routed_scaling_factor", 1.0)),
            moe_norm_topk=bool(getattr(hf_config, "norm_topk_prob", True)),
            moe_shared_experts=(getattr(hf_config, "n_shared_experts", 0)
                                or 0) if E else 0,
            dense_prefix_layers=fk if mixed else 0,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "granite":
        # Granite 3.x: llama layout with four scalar multipliers, all
        # absorbed into existing mechanisms — embedding_multiplier ->
        # embed_scale, attention_multiplier -> query_pre_attn_scalar
        # (HF scales scores by am == qpas**-0.5, so qpas = am**-2; the
        # ratio folds into the q weights at conversion),
        # residual_multiplier -> residual_scale, and 1/logits_scaling ->
        # logit_scale.
        am = float(getattr(hf_config, "attention_multiplier", 1.0))
        ls = float(getattr(hf_config, "logits_scaling", 1.0))
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="llama", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=hf_config.hidden_size
            // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=getattr(hf_config, "attention_bias", False),
            mlp_bias=getattr(hf_config, "mlp_bias", False),
            embed_scale=float(getattr(hf_config, "embedding_multiplier",
                                      1.0)),
            query_pre_attn_scalar=am ** -2,
            residual_scale=float(getattr(hf_config,
                                         "residual_multiplier", 1.0)),
            logit_scale=1.0 / ls,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "olmo2":
        # OLMo-2: llama dims, but norms move to the sublayer OUTPUTS
        # (x + norm(f(x)), no pre-norms) and full-width RMS q/k norms on
        # the projections.
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="olmo2", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=hf_config.hidden_size
            // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            attn_bias=getattr(hf_config, "attention_bias", False),
            mlp_bias=False, qk_norm="rms_full",
            sublayer_postnorm_only=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt in ("glm", "glm4"):
        # GLM-4 lineage: llama dims with a fused gate_up MLP (split at
        # conversion), INTERLEAVED rotary over the first
        # partial_rotary_factor of head_dim (GPT-J pairing — HF glm's
        # local rotate_half is the 0::2/1::2 stack), q/k/v bias without
        # o bias, explicit head_dim. glm4 additionally sandwiches each
        # sublayer with post norms (post_self_attn/post_mlp_layernorm ->
        # post_block_norms).
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="glm", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="rmsnorm", norm_eps=hf_config.rms_norm_eps,
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=True, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_pct=float(getattr(hf_config, "partial_rotary_factor",
                                   0.5)),
            rope_interleaved=True,
            attn_bias=bool(getattr(hf_config, "attention_bias", True)),
            o_bias=False, mlp_bias=False,
            post_block_norms=(mt == "glm4"),
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    if mt == "nemotron":
        # Nemotron: ungated squared-ReLU MLP, LayerNorm1P ((1+w) scale,
        # absorbed at conversion like gemma's rmsnorm offset), partial
        # non-interleaved rotary, untied head, no biases.
        return ModelConfig(
            name=getattr(hf_config, "name_or_path", mt) or mt,
            family="nemotron", vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            head_dim=getattr(hf_config, "head_dim", None)
            or hf_config.hidden_size // hf_config.num_attention_heads,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_type="layernorm",
            norm_eps=getattr(hf_config, "norm_eps", 1e-5),
            activation=_act_from_hf(hf_config.hidden_act),
            gated_mlp=False, position_embedding="rope",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rope_pct=float(getattr(hf_config, "partial_rotary_factor",
                                   0.5)),
            attn_bias=bool(getattr(hf_config, "attention_bias", False)),
            mlp_bias=bool(getattr(hf_config, "mlp_bias", False)),
            norm_offset=True,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings",
                                        False))
    raise NotImplementedError(
        f"unsupported HF model_type {mt!r}; supported: "
        f"{', '.join(SUPPORTED_MODEL_TYPES)}")


def _stack(dicts):
    """list of {leaf: np [..]} -> {leaf: np [L, ..]} recursively."""
    out = {}
    for k in dicts[0]:
        if isinstance(dicts[0][k], dict):
            out[k] = _stack([d[k] for d in dicts])
        else:
            out[k] = np.stack([d[k] for d in dicts])
    return out


def convert_state_dict(cfg: ModelConfig, sd, dtype=None):
    """HF state dict (name -> torch tensor/np array) -> our param pytree."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    fam = cfg.family
    D = cfg.hidden_size

    def get(name):
        return _np(sd[name])

    if fam == "gpt2":
        def layer(i):
            p = f"transformer.h.{i}."
            cattn_w = get(p + "attn.c_attn.weight")  # [D, 3D] (Conv1D: in,out)
            cattn_b = get(p + "attn.c_attn.bias")
            return {
                "attn_norm": {"scale": get(p + "ln_1.weight"),
                              "bias": get(p + "ln_1.bias")},
                "q": {"w": cattn_w[:, :D], "b": cattn_b[:D]},
                "k": {"w": cattn_w[:, D:2 * D], "b": cattn_b[D:2 * D]},
                "v": {"w": cattn_w[:, 2 * D:], "b": cattn_b[2 * D:]},
                "o": {"w": get(p + "attn.c_proj.weight"),
                      "b": get(p + "attn.c_proj.bias")},
                "mlp_norm": {"scale": get(p + "ln_2.weight"),
                             "bias": get(p + "ln_2.bias")},
                "up": {"w": get(p + "mlp.c_fc.weight"),
                       "b": get(p + "mlp.c_fc.bias")},
                "down": {"w": get(p + "mlp.c_proj.weight"),
                         "b": get(p + "mlp.c_proj.bias")},
            }
        params = {
            "embed": {"tokens": get("transformer.wte.weight"),
                      "positions": get("transformer.wpe.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("transformer.ln_f.weight"),
                           "bias": get("transformer.ln_f.bias")},
        }
    elif fam == "opt":
        def layer(i):
            p = f"model.decoder.layers.{i}."
            def lin(n):  # torch Linear stores [out, in] -> transpose
                return {"w": get(p + n + ".weight").T, "b": get(p + n + ".bias")}
            return {
                "attn_norm": {"scale": get(p + "self_attn_layer_norm.weight"),
                              "bias": get(p + "self_attn_layer_norm.bias")},
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.out_proj"),
                "mlp_norm": {"scale": get(p + "final_layer_norm.weight"),
                             "bias": get(p + "final_layer_norm.bias")},
                "up": lin("fc1"),
                "down": lin("fc2"),
            }
        params = {
            "embed": {
                "tokens": get("model.decoder.embed_tokens.weight"),
                # OPT's learned positions are offset by 2 internally
                # (transformers OPTLearnedPositionalEmbedding); slice here so
                # position p indexes row p.
                "positions": get("model.decoder.embed_positions.weight")[2:],
            },
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
        }
        if not cfg.post_norm:   # opt-350m (post-LN) has no final norm
            params["final_norm"] = {
                "scale": get("model.decoder.final_layer_norm.weight"),
                "bias": get("model.decoder.final_layer_norm.bias")}
        if cfg.embed_proj_dim:
            params["embed"]["project_in"] = {
                "w": get("model.decoder.project_in.weight").T}
            params["embed"]["project_out"] = {
                "w": get("model.decoder.project_out.weight").T}
    elif fam == "llama":
        # gemma stores rmsnorm weights in the (1 + w) convention; absorb
        # the offset here so the runtime norm stays plain (config.py
        # norm_offset)
        off = 1.0 if cfg.norm_offset else 0.0
        # granite: attention_multiplier replaces the 1/sqrt(hd) score
        # scale via query_pre_attn_scalar — fold the ratio into q (same
        # absorption as the gemma2 branch)
        qs = (cfg.head_dim / (cfg.query_pre_attn_scalar
                              or cfg.head_dim)) ** 0.5

        def layer(i, moe):
            p = f"model.layers.{i}."
            def lin(n, scale=1.0):
                out = {"w": get(p + n + ".weight").T * scale}
                if p + n + ".bias" in sd:  # attention_bias / mlp_bias variants
                    out["b"] = get(p + n + ".bias") * scale
                return out
            lp = {
                "attn_norm": {"scale": get(p + "input_layernorm.weight") + off},
                # under qk_norm the q RMS-normalize erases any weight
                # scale, so the qs fold moves to the q_norm scale below
                "q": lin("self_attn.q_proj", 1.0 if cfg.qk_norm else qs),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "mlp_norm": {"scale": get(p + "post_attention_layernorm.weight") + off},
            }
            if cfg.qk_norm:   # shared [head_dim] rms scales — qwen3
                # names them q_norm/k_norm, hunyuan query_layernorm/
                # key_layernorm
                qn = ("self_attn.q_norm.weight"
                      if p + "self_attn.q_norm.weight" in sd
                      else "self_attn.query_layernorm.weight")
                kn = ("self_attn.k_norm.weight"
                      if p + "self_attn.k_norm.weight" in sd
                      else "self_attn.key_layernorm.weight")
                lp["q_norm"] = {"scale": get(p + qn) * qs}
                lp["k_norm"] = {"scale": get(p + kn)}
            rn = next((c for c in ("mlp.gate.weight", "mlp.gate.wg.weight")
                       if p + c in sd), None)
            if moe and rn:
                # qwen3_moe / glm4_moe name the router mlp.gate,
                # hunyuan_v1_moe wraps it as mlp.gate.wg; experts are
                # mlp.experts.N.{gate,up,down}_proj either way
                lp["router"] = {"w": get(p + rn).T}
                if cfg.moe_router in ("deepseek_v3", "ernie"):
                    # glm4_moe names the bias under the gate; ernie
                    # under moe_statics (shape [1, E] — squeeze)
                    bn = p + "mlp.gate.e_score_correction_bias"
                    if bn in sd:
                        lp["router"]["bias"] = get(bn)
                    else:
                        lp["router"]["bias"] = get(
                            p + "mlp.moe_statics.e_score_correction_bias"
                        ).reshape(-1)
                ex = [f"mlp.experts.{e}." for e in range(cfg.num_experts)]
                lp["experts"] = {
                    "gate": {"w": np.stack([get(p + e + "gate_proj.weight").T for e in ex])},
                    "up": {"w": np.stack([get(p + e + "up_proj.weight").T for e in ex])},
                    "down": {"w": np.stack([get(p + e + "down_proj.weight").T for e in ex])},
                }
                if p + ex[0] + "gate_proj.bias" in sd:
                    # ernie4_5_moe use_bias=True: per-expert biases
                    for nm, pj in (("gate", "gate_proj"), ("up", "up_proj"),
                                   ("down", "down_proj")):
                        lp["experts"][nm]["b"] = np.stack(
                            [get(p + e + f"{pj}.bias") for e in ex])
                if cfg.moe_shared_experts:
                    s = ("mlp.shared_experts."
                         if p + "mlp.shared_experts.gate_proj.weight" in sd
                         else "mlp.shared_mlp.")   # hunyuan_v1_moe
                    lp["shared_gate"] = lin(s + "gate_proj")
                    lp["shared_up"] = lin(s + "up_proj")
                    lp["shared_down"] = lin(s + "down_proj")
            elif moe:
                lp["router"] = {"w": get(p + "block_sparse_moe.gate.weight").T}
                ex = [f"block_sparse_moe.experts.{e}." for e in range(cfg.num_experts)]
                lp["experts"] = {
                    "gate": {"w": np.stack([get(p + e + "w1.weight").T for e in ex])},
                    "down": {"w": np.stack([get(p + e + "w2.weight").T for e in ex])},
                    "up": {"w": np.stack([get(p + e + "w3.weight").T for e in ex])},
                }
            else:
                lp["gate"] = lin("mlp.gate_proj")
                lp["up"] = lin("mlp.up_proj")
                lp["down"] = lin("mlp.down_proj")
            return lp
        pref = cfg.dense_prefix_layers
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i, cfg.is_moe)
                              for i in range(pref, cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight") + off},
        }
        if pref:   # glm4_moe first_k_dense_replace: dense prefix segment
            params["layers_dense"] = _stack(
                [layer(i, False) for i in range(pref)])
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "afmoe":
        # model.layers.N.{input_layernorm, post_attention_layernorm (on
        # the attention OUTPUT), pre_mlp_layernorm, post_mlp_layernorm},
        # self_attn.{q,k,v,o,gate}_proj + q_norm/k_norm, and mlp.{gate,
        # up,down}_proj (dense) or mlp.{router.gate, expert_bias,
        # experts.E.*, shared_experts.*} (modeling_afmoe.py)
        def layer(i, moe):
            p = f"model.layers.{i}."

            def lin(n):
                return {"w": get(p + n + ".weight").T}

            def scale(n):
                return {"scale": get(p + n + ".weight")}
            lp = {
                "attn_norm": scale("input_layernorm"),
                "attn_post_norm": scale("post_attention_layernorm"),
                "mlp_norm": scale("pre_mlp_layernorm"),
                "mlp_post_norm": scale("post_mlp_layernorm"),
                "q": lin("self_attn.q_proj"), "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"), "o": lin("self_attn.o_proj"),
                "attn_gate": lin("self_attn.gate_proj"),
                "q_norm": scale("self_attn.q_norm"),
                "k_norm": scale("self_attn.k_norm"),
            }
            if not moe:
                lp.update(gate=lin("mlp.gate_proj"), up=lin("mlp.up_proj"),
                          down=lin("mlp.down_proj"))
                return lp
            lp["router"] = {"w": get(p + "mlp.router.gate.weight").T,
                            "bias": get(p + "mlp.expert_bias")}
            ex = [f"mlp.experts.{e}." for e in range(cfg.num_experts)]
            lp["experts"] = {
                nm: {"w": np.stack([get(p + e + f"{nm}_proj.weight").T
                                    for e in ex])}
                for nm in ("gate", "up", "down")}
            if cfg.moe_shared_experts:
                for nm in ("gate", "up", "down"):
                    lp[f"shared_{nm}"] = lin(f"mlp.shared_experts.{nm}_proj")
            return lp
        pref = cfg.dense_prefix_layers
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i, cfg.is_moe)
                              for i in range(pref, cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight")},
        }
        if pref:
            params["layers_dense"] = _stack(
                [layer(i, False) for i in range(pref)])
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "ouro":
        # model.layers.N.{input_layernorm, input_layernorm_2 (on the
        # attention OUTPUT), post_attention_layernorm (before the MLP),
        # post_attention_layernorm_2 (on the MLP's output)},
        # self_attn.{q,k,v,o}_proj, mlp.{gate,up,down}_proj; model.norm
        # (after every pass) and model.early_exit_gate, a Linear(D, 1)
        # (modeling_ouro.py)
        def layer(i):
            p = f"model.layers.{i}."

            def lin(n, bias=False):
                out = {"w": get(p + n + ".weight").T}
                if bias:
                    out["b"] = get(p + n + ".bias")
                return out

            def scale(n):
                return {"scale": get(p + n + ".weight")}
            return {
                "attn_norm": scale("input_layernorm"),
                "attn_post_norm": scale("input_layernorm_2"),
                "mlp_norm": scale("post_attention_layernorm"),
                "mlp_post_norm": scale("post_attention_layernorm_2"),
                "q": lin("self_attn.q_proj", cfg.attn_bias),
                "k": lin("self_attn.k_proj", cfg.attn_bias),
                "v": lin("self_attn.v_proj", cfg.attn_bias),
                "o": lin("self_attn.o_proj"),
                "gate": lin("mlp.gate_proj"), "up": lin("mlp.up_proj"),
                "down": lin("mlp.down_proj"),
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight")},
            "exit_gate": {"w": get("model.early_exit_gate.weight").T,
                          "b": get("model.early_exit_gate.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "falcon_h1":
        # model.layers.N.{input_layernorm, pre_ff_layernorm,
        # self_attn.{q,k,v,o}_proj, feed_forward.{gate,up,down}_proj,
        # mamba.{in_proj, conv1d (weight [C, 1, K] + bias), A_log, D,
        # dt_bias, norm, out_proj}}; model.final_layernorm; lm_head
        # (modeling_falcon_h1.py). Not yet checked against a real
        # checkpoint (see config_from_hf). No multiplier is folded into
        # a weight: each is applied at run time where the source does.
        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                return {"w": get(p + n + ".weight").T}
            lp = {
                "attn_norm": {"scale": get(p + "input_layernorm.weight")},
                "mlp_norm": {"scale": get(p + "pre_ff_layernorm.weight")},
                "q": lin("self_attn.q_proj"), "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"), "o": lin("self_attn.o_proj"),
                "gate": lin("feed_forward.gate_proj"),
                "up": lin("feed_forward.up_proj"),
                "down": lin("feed_forward.down_proj"),
                "in_proj": lin("mamba.in_proj"),
                # torch's depthwise filter [C, 1, K] -> taps [K, C]
                "conv": {"w": get(p + "mamba.conv1d.weight")[:, 0, :].T},
                "dt_bias": get(p + "mamba.dt_bias"),
                "A_log": get(p + "mamba.A_log"),
                "D": get(p + "mamba.D"),
                "ssm_norm": {"scale": get(p + "mamba.norm.weight")},
                "out_proj": lin("mamba.out_proj"),
            }
            if cfg.ssm.conv_bias:
                lp["conv"]["b"] = get(p + "mamba.conv1d.bias")
            return lp
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.final_layernorm.weight")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "mimo_v2":
        # model.layers.N.{input_layernorm, post_attention_layernorm,
        # self_attn.{qkv_proj (fused rows [q | k | v], cut by the
        # layer's kind: 4 or 8 K/V heads), o_proj, attention_sink_bias
        # (windowed layers)}, mlp.{gate,up,down}_proj (dense) or
        # mlp.{gate.weight, gate.e_score_correction_bias, experts.E.*}};
        # model.norm; lm_head. NOT yet checked against a real checkpoint:
        # the names are deepseek_v3's with a fused qkv_proj
        # (attention_projection_layout: fused_qkv) and may need a
        # correction when the files are at hand. The value scale is not
        # folded into Wv: the block applies it at run time.
        kinds, pref = cfg.swa.kinds(), cfg.dense_prefix_layers

        def layer(i, moe):
            p = f"model.layers.{i}."
            kc = cfg.kind_cfg(kinds[i], 1)

            def lin(n):
                return {"w": get(p + n + ".weight").T}
            qkv = get(p + "self_attn.qkv_proj.weight").T
            assert qkv.shape[1] == kc.q_dim + kc.kv_dim + kc.v_dim, (
                i, kinds[i], qkv.shape)
            lp = {
                "attn_norm": {"scale": get(p + "input_layernorm.weight")},
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight")},
                "q": {"w": qkv[:, :kc.q_dim]},
                "k": {"w": qkv[:, kc.q_dim:kc.q_dim + kc.kv_dim]},
                "v": {"w": qkv[:, kc.q_dim + kc.kv_dim:]},
                "o": lin("self_attn.o_proj"),
            }
            if kc.attn_sinks:
                lp["sinks"] = get(p + "self_attn.attention_sink_bias")
            if not moe:
                lp.update(gate=lin("mlp.gate_proj"), up=lin("mlp.up_proj"),
                          down=lin("mlp.down_proj"))
                return lp
            lp["router"] = {
                "w": get(p + "mlp.gate.weight").T,
                "bias": get(p + "mlp.gate.e_score_correction_bias")}
            first, count = cfg.experts_held or (0, cfg.num_experts)
            ex = [f"mlp.experts.{e}." for e in range(first, first + count)]
            lp["experts"] = {
                nm: {"w": np.stack([get(p + e + f"{nm}_proj.weight").T
                                    for e in ex])}
                for nm in ("gate", "up", "down")}
            return lp
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "final_norm": {"scale": get("model.norm.weight")},
        }
        for name, kind in (("layers", "swa"), ("layers_full", "full")):
            mine = [i for i in range(pref, cfg.num_layers)
                    if kinds[i] == kind]
            if mine:
                params[name] = _stack([layer(i, True) for i in mine])
        if pref:
            params["layers_dense"] = _stack(
                [layer(i, False) for i in range(pref)])
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "dbrx":
        # transformer.blocks.N.norm_attn_norm.{norm_1, attn.Wqkv,
        # attn.out_proj, norm_2} + ffn.{router.layer, experts.mlp.
        # {w1,v1,w2}}; LayerNorms are bias-free (zero bias is the exact
        # parametric equivalent), experts are FUSED [E*I, D] stacks —
        # w1/v1 contract transposed (gate/up), w2 contracts as stored
        # (down, HF DbrxExpertGLU.forward).
        D = cfg.hidden_size
        E, I = cfg.num_experts, cfg.intermediate_size
        kvd = cfg.num_kv_heads * cfg.head_dim
        zb = np.zeros((D,), np.float32)

        def layer(i):
            p = f"transformer.blocks.{i}."
            qkv = get(p + "norm_attn_norm.attn.Wqkv.weight").T  # [D,D+2kvd]
            w1 = get(p + "ffn.experts.mlp.w1").reshape(E, I, D)
            v1 = get(p + "ffn.experts.mlp.v1").reshape(E, I, D)
            w2 = get(p + "ffn.experts.mlp.w2").reshape(E, I, D)
            return {
                "attn_norm": {
                    "scale": get(p + "norm_attn_norm.norm_1.weight"),
                    "bias": zb},
                "q": {"w": qkv[:, :D]},
                "k": {"w": qkv[:, D:D + kvd]},
                "v": {"w": qkv[:, D + kvd:]},
                "o": {"w": get(p + "norm_attn_norm.attn.out_proj.weight").T},
                "mlp_norm": {
                    "scale": get(p + "norm_attn_norm.norm_2.weight"),
                    "bias": zb},
                "router": {"w": get(p + "ffn.router.layer.weight").T},
                "experts": {
                    "gate": {"w": np.swapaxes(w1, 1, 2)},   # [E, D, I]
                    "up": {"w": np.swapaxes(v1, 1, 2)},
                    "down": {"w": w2},                      # [E, I, D]
                },
            }
        params = {
            "embed": {"tokens": get("transformer.wte.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("transformer.norm_f.weight"),
                           "bias": zb},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "gpt_oss":
        # llama projection names with biases + self_attn.sinks per
        # layer; fused-interleaved expert stacks: gate_up_proj
        # [E, D, 2I] with gate at even and up at odd columns (HF
        # GptOssExperts gate_up[..., ::2]/[..., 1::2]); down_proj
        # [E, I, D] contracts as stored; router is mlp.router (a real
        # linear with bias).
        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:
                    out["b"] = get(p + n + ".bias")
                return out
            gu = get(p + "mlp.experts.gate_up_proj")        # [E, D, 2I]
            gub = get(p + "mlp.experts.gate_up_proj_bias")  # [E, 2I]
            return {
                "attn_norm": {"scale": get(p + "input_layernorm.weight")},
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "sinks": get(p + "self_attn.sinks"),
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight")},
                "router": {"w": get(p + "mlp.router.weight").T,
                           "bias": get(p + "mlp.router.bias")},
                "experts": {
                    "gate": {"w": gu[..., 0::2], "b": gub[..., 0::2]},
                    "up": {"w": gu[..., 1::2], "b": gub[..., 1::2]},
                    "down": {"w": get(p + "mlp.experts.down_proj"),
                             "b": get(p + "mlp.experts.down_proj_bias")},
                },
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "deepseek":
        # MLA projections (HF modeling_deepseek_v3.py:327-446). Our
        # runtime orders per-head q/k dims [rope | nope] (HF: [nope |
        # rope]) so the rope slice is contiguous where apply_rope
        # rotates — a score-invariant permutation applied here to the q
        # projection columns (k is assembled in that order at runtime:
        # kv_a's rope slice + kv_b's nope columns, transformer._mla_qkv).
        H, hd = cfg.num_heads, cfg.qk_head_dim
        nd, rd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        vd = cfg.v_head_dim_effective
        # yarn mscale_all_dim: HF multiplies scores by score_scale**2
        # uniformly; config_from_hf encoded score_scale**2 as the
        # query_pre_attn_scalar absorption (qs == sqrt(hd/qpas)) — the
        # scalar commutes with the projection AND the rope rotation, so
        # scaling q here is exact
        qs = (hd / (cfg.query_pre_attn_scalar or hd)) ** 0.5

        def q_permute(w):
            """[din, H*hd] with per-head [nope|rope] -> [rope|nope]."""
            w = w.reshape(-1, H, hd)
            return np.concatenate([w[..., nd:], w[..., :nd]],
                                  axis=-1).reshape(-1, H * hd) * qs

        def layer(i, moe):
            p = f"model.layers.{i}."

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:   # attention_bias variants
                    out["b"] = get(p + n + ".bias")
                return out
            kv_b = get(p + "self_attn.kv_b_proj.weight").T  # [r, H*(nd+vd)]
            kv_b = kv_b.reshape(-1, H, nd + vd)
            lp = {
                "attn_norm": {"scale": get(p + "input_layernorm.weight")},
                "kv_a": lin("self_attn.kv_a_proj_with_mqa"),
                "kv_a_norm": {
                    "scale": get(p + "self_attn.kv_a_layernorm.weight")},
                "kv_b_k": {"w": kv_b[..., :nd].reshape(-1, H * nd)},
                "kv_b_v": {"w": kv_b[..., nd:].reshape(-1, H * vd)},
                "o": lin("self_attn.o_proj"),
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight")},
            }
            if cfg.q_lora_rank:
                lp["q_a"] = lin("self_attn.q_a_proj")
                lp["q_a_norm"] = {
                    "scale": get(p + "self_attn.q_a_layernorm.weight")}
                lp["q_b"] = {
                    "w": q_permute(get(p + "self_attn.q_b_proj.weight").T)}
            else:
                lp["q"] = {
                    "w": q_permute(get(p + "self_attn.q_proj.weight").T)}
            if moe:
                lp["router"] = {
                    "w": get(p + "mlp.gate.weight").T,
                    "bias": get(p + "mlp.gate.e_score_correction_bias"),
                }
                ex = [f"mlp.experts.{e}." for e in range(cfg.num_experts)]
                lp["experts"] = {
                    "gate": {"w": np.stack(
                        [get(p + e + "gate_proj.weight").T for e in ex])},
                    "up": {"w": np.stack(
                        [get(p + e + "up_proj.weight").T for e in ex])},
                    "down": {"w": np.stack(
                        [get(p + e + "down_proj.weight").T for e in ex])},
                }
                if cfg.moe_shared_experts:
                    s = "mlp.shared_experts."
                    lp["shared_gate"] = lin(s + "gate_proj")
                    lp["shared_up"] = lin(s + "up_proj")
                    lp["shared_down"] = lin(s + "down_proj")
            else:
                lp["gate"] = lin("mlp.gate_proj")
                lp["up"] = lin("mlp.up_proj")
                lp["down"] = lin("mlp.down_proj")
            return lp
        pref = cfg.dense_prefix_layers
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i, cfg.is_moe)
                              for i in range(pref, cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight")},
        }
        if pref:   # first_k_dense_replace: dense-MLP prefix segment
            params["layers_dense"] = _stack(
                [layer(i, False) for i in range(pref)])
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "gpt-neox":
        H, hd = cfg.num_heads, cfg.head_dim

        def layer(i):
            p = f"gpt_neox.layers.{i}."
            # fused QKV, per-head interleaved: out-row h*3*hd + j*hd + d
            # holds head h, kind j (q,k,v), dim d (HF GPTNeoXAttention
            # views [.., heads, 3*head_size] then splits the last axis)
            qkv_w = get(p + "attention.query_key_value.weight")  # [3Hhd, D]
            qkv_b = get(p + "attention.query_key_value.bias")
            w3 = qkv_w.reshape(H, 3, hd, D)
            b3 = qkv_b.reshape(H, 3, hd)

            def proj(j):
                return {"w": w3[:, j].reshape(H * hd, D).T,
                        "b": b3[:, j].reshape(H * hd)}
            return {
                "attn_norm": {"scale": get(p + "input_layernorm.weight"),
                              "bias": get(p + "input_layernorm.bias")},
                "q": proj(0), "k": proj(1), "v": proj(2),
                "o": {"w": get(p + "attention.dense.weight").T,
                      "b": get(p + "attention.dense.bias")},
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight"),
                    "bias": get(p + "post_attention_layernorm.bias")},
                "up": {"w": get(p + "mlp.dense_h_to_4h.weight").T,
                       "b": get(p + "mlp.dense_h_to_4h.bias")},
                "down": {"w": get(p + "mlp.dense_4h_to_h.weight").T,
                         "b": get(p + "mlp.dense_4h_to_h.bias")},
            }
        params = {
            "embed": {"tokens": get("gpt_neox.embed_in.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {
                "scale": get("gpt_neox.final_layer_norm.weight"),
                "bias": get("gpt_neox.final_layer_norm.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("embed_out.weight").T}
    elif fam == "phi":
        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                return {"w": get(p + n + ".weight").T,
                        "b": get(p + n + ".bias")}
            # single shared layernorm (cfg.shared_attn_mlp_norm): no
            # mlp_norm leaf
            return {
                "attn_norm": {"scale": get(p + "input_layernorm.weight"),
                              "bias": get(p + "input_layernorm.bias")},
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.dense"),
                "up": lin("mlp.fc1"),
                "down": lin("mlp.fc2"),
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.final_layernorm.weight"),
                           "bias": get("model.final_layernorm.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T,
                                 "b": get("lm_head.bias")}
    elif fam == "falcon":
        H, hd, KV = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
        g = H // KV
        two_norms = not cfg.shared_attn_mlp_norm   # new decoder arch

        def layer(i):
            p = f"transformer.h.{i}."
            # fused QKV, grouped per kv head: [KV, g + 2, hd] out rows —
            # g query heads then k then v per group (HF Falcon
            # _split_heads; the 7B MQA layout is the KV == 1 case)
            qkv_w = get(p + "self_attention.query_key_value.weight")
            wg = qkv_w.reshape(KV, g + 2, hd, D)
            bg = (get(p + "self_attention.query_key_value.bias"
                      ).reshape(KV, g + 2, hd) if cfg.attn_bias else None)

            def proj(sel, rows):
                out = {"w": wg[:, sel].reshape(rows * hd, D).T}
                if bg is not None:
                    out["b"] = bg[:, sel].reshape(rows * hd)
                return out

            def lin(n, bias):
                out = {"w": get(p + n + ".weight").T}
                if bias:
                    out["b"] = get(p + n + ".bias")
                return out
            lp = {
                "q": proj(slice(0, g), H),
                "k": proj(slice(g, g + 1), KV),
                "v": proj(slice(g + 1, g + 2), KV),
                "o": lin("self_attention.dense", cfg.o_bias_effective),
                "up": lin("mlp.dense_h_to_4h", cfg.mlp_bias),
                "down": lin("mlp.dense_4h_to_h", cfg.mlp_bias),
            }
            if two_norms:
                # new decoder arch names them ln_attn/ln_mlp; the RW
                # sequential layout reuses the llama-style pair
                if p + "ln_attn.weight" in sd:
                    attn_n, mlp_n = "ln_attn", "ln_mlp"
                else:
                    attn_n, mlp_n = ("input_layernorm",
                                     "post_attention_layernorm")
                lp["attn_norm"] = {"scale": get(p + attn_n + ".weight"),
                                   "bias": get(p + attn_n + ".bias")}
                lp["mlp_norm"] = {"scale": get(p + mlp_n + ".weight"),
                                  "bias": get(p + mlp_n + ".bias")}
            else:
                lp["attn_norm"] = {
                    "scale": get(p + "input_layernorm.weight"),
                    "bias": get(p + "input_layernorm.bias")}
            return lp
        params = {
            "embed": {"tokens": get("transformer.word_embeddings.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("transformer.ln_f.weight"),
                           "bias": get("transformer.ln_f.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "bloom":
        H, hd = cfg.num_heads, cfg.head_dim

        def layer(i):
            p = f"transformer.h.{i}."
            # fused QKV, per-head interleaved [H, 3, hd] (HF
            # BloomAttention._reshape)
            w3 = get(p + "self_attention.query_key_value.weight"
                     ).reshape(H, 3, hd, D)
            b3 = get(p + "self_attention.query_key_value.bias"
                     ).reshape(H, 3, hd)

            def proj(j):
                return {"w": w3[:, j].reshape(H * hd, D).T,
                        "b": b3[:, j].reshape(H * hd)}

            def lin(n):
                return {"w": get(p + n + ".weight").T,
                        "b": get(p + n + ".bias")}
            return {
                "attn_norm": {"scale": get(p + "input_layernorm.weight"),
                              "bias": get(p + "input_layernorm.bias")},
                "q": proj(0), "k": proj(1), "v": proj(2),
                "o": lin("self_attention.dense"),
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight"),
                    "bias": get(p + "post_attention_layernorm.bias")},
                "up": lin("mlp.dense_h_to_4h"),
                "down": lin("mlp.dense_4h_to_h"),
            }
        params = {
            "embed": {
                "tokens": get("transformer.word_embeddings.weight"),
                "norm": {
                    "scale": get(
                        "transformer.word_embeddings_layernorm.weight"),
                    "bias": get(
                        "transformer.word_embeddings_layernorm.bias")},
            },
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("transformer.ln_f.weight"),
                           "bias": get("transformer.ln_f.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "gptj":
        def layer(i):
            p = f"transformer.h.{i}."

            def lin(n, bias):
                out = {"w": get(p + n + ".weight").T}
                if bias:
                    out["b"] = get(p + n + ".bias")
                return out
            # single shared ln_1 (cfg.shared_attn_mlp_norm): no mlp_norm
            return {
                "attn_norm": {"scale": get(p + "ln_1.weight"),
                              "bias": get(p + "ln_1.bias")},
                "q": lin("attn.q_proj", False),
                "k": lin("attn.k_proj", False),
                "v": lin("attn.v_proj", False),
                "o": lin("attn.out_proj", False),
                "up": lin("mlp.fc_in", True),
                "down": lin("mlp.fc_out", True),
            }
        params = {
            "embed": {"tokens": get("transformer.wte.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("transformer.ln_f.weight"),
                           "bias": get("transformer.ln_f.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T,
                                 "b": get("lm_head.bias")}
    elif fam == "mpt":
        qd, kvd = cfg.q_dim, cfg.kv_dim

        def layer(i):
            p = f"transformer.blocks.{i}."

            def norm_leaf(n):
                # no_bias MPT norms carry weight only; a zero bias is the
                # exact equivalent of HF's bias=None layer_norm
                return {"scale": get(p + n + ".weight"),
                        "bias": get(p + n + ".bias")
                        if p + n + ".bias" in sd
                        else np.zeros((D,), np.float32)}

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:
                    out["b"] = get(p + n + ".bias")
                return out
            # straight-concat fused QKV: rows [q | k | v]
            wqkv = get(p + "attn.Wqkv.weight")          # [qd+2*kvd, D]
            lp = {
                "attn_norm": norm_leaf("norm_1"),
                "q": {"w": wqkv[:qd].T},
                "k": {"w": wqkv[qd:qd + kvd].T},
                "v": {"w": wqkv[qd + kvd:].T},
                "o": lin("attn.out_proj"),
                "mlp_norm": norm_leaf("norm_2"),
                "up": lin("ffn.up_proj"),
                "down": lin("ffn.down_proj"),
            }
            if p + "attn.Wqkv.bias" in sd:
                bqkv = get(p + "attn.Wqkv.bias")
                lp["q"]["b"] = bqkv[:qd]
                lp["k"]["b"] = bqkv[qd:qd + kvd]
                lp["v"]["b"] = bqkv[qd + kvd:]
            return lp
        params = {
            "embed": {"tokens": get("transformer.wte.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {
                "scale": get("transformer.norm_f.weight"),
                "bias": get("transformer.norm_f.bias")
                if "transformer.norm_f.bias" in sd
                else np.zeros((D,), np.float32)},
        }
    elif fam == "gpt_bigcode":
        # StarCoder: gpt2 block layout, nn.Linear (out-major) weights.
        # Fused c_attn rows: MQA stores [q (D) | k (hd) | v (hd)]
        # straight; the MHA variant is PER-HEAD interleaved
        # [q_h | k_h | v_h] per head (HF GPTBigCodeAttention views
        # [heads, 3*head_dim] before splitting).
        H, hd = cfg.num_heads, cfg.head_dim
        mqa = cfg.num_kv_heads == 1

        def layer(i):
            p = f"transformer.h.{i}."
            ca_w = get(p + "attn.c_attn.weight")
            ca_b = get(p + "attn.c_attn.bias")
            if mqa:
                qw, kw, vw = (ca_w[:D], ca_w[D:D + hd], ca_w[D + hd:])
                qb, kb, vb = (ca_b[:D], ca_b[D:D + hd], ca_b[D + hd:])
            else:
                w3 = ca_w.reshape(H, 3, hd, D)
                b3 = ca_b.reshape(H, 3, hd)
                qw, kw, vw = (w3[:, j].reshape(H * hd, D)
                              for j in range(3))
                qb, kb, vb = (b3[:, j].reshape(H * hd) for j in range(3))

            def lin(n):
                return {"w": get(p + n + ".weight").T,
                        "b": get(p + n + ".bias")}
            return {
                "attn_norm": {"scale": get(p + "ln_1.weight"),
                              "bias": get(p + "ln_1.bias")},
                "q": {"w": qw.T, "b": qb},
                "k": {"w": kw.T, "b": kb},
                "v": {"w": vw.T, "b": vb},
                "o": lin("attn.c_proj"),
                "mlp_norm": {"scale": get(p + "ln_2.weight"),
                             "bias": get(p + "ln_2.bias")},
                "up": lin("mlp.c_fc"),
                "down": lin("mlp.c_proj"),
            }
        params = {
            "embed": {"tokens": get("transformer.wte.weight"),
                      "positions": get("transformer.wpe.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("transformer.ln_f.weight"),
                           "bias": get("transformer.ln_f.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "stablelm":
        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:   # use_qkv_bias variants
                    out["b"] = get(p + n + ".bias")
                return out
            return {
                "attn_norm": {"scale": get(p + "input_layernorm.weight"),
                              "bias": get(p + "input_layernorm.bias")},
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight"),
                    "bias": get(p + "post_attention_layernorm.bias")},
                "gate": lin("mlp.gate_proj"),
                "up": lin("mlp.up_proj"),
                "down": lin("mlp.down_proj"),
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight"),
                           "bias": get("model.norm.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "codegen":
        # Fused QKV in mp_num=4 TP blocks; within each block the order is
        # q | v | k (HF CodeGenAttention splits query, value, key), and
        # block m holds global heads [m*H/4, (m+1)*H/4) — so kind j's
        # rows, concatenated across blocks, are already in global head
        # order.
        mp = 4
        local = 3 * D // mp  # block width: q+v+k for H/4 heads

        def layer(i):
            p = f"transformer.h.{i}."

            def lin(n, bias):
                out = {"w": get(p + n + ".weight").T}
                if bias:
                    out["b"] = get(p + n + ".bias")
                return out
            wb = get(p + "attn.qkv_proj.weight").reshape(mp, local, D)

            def proj(j):  # j: 0=q, 1=v, 2=k
                third = local // 3
                return {"w": wb[:, j * third:(j + 1) * third]
                        .reshape(D, D).T}
            return {
                "attn_norm": {"scale": get(p + "ln_1.weight"),
                              "bias": get(p + "ln_1.bias")},
                "q": proj(0), "v": proj(1), "k": proj(2),
                "o": lin("attn.out_proj", False),
                "up": lin("mlp.fc_in", True),
                "down": lin("mlp.fc_out", True),
            }
        params = {
            "embed": {"tokens": get("transformer.wte.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("transformer.ln_f.weight"),
                           "bias": get("transformer.ln_f.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T,
                                 "b": get("lm_head.bias")}
    elif fam == "starcoder2":
        def layer(i):
            p = f"model.layers.{i}."

            def lin(n, bias):
                out = {"w": get(p + n + ".weight").T}
                if bias:
                    out["b"] = get(p + n + ".bias")
                return out
            return {
                "attn_norm": {"scale": get(p + "input_layernorm.weight"),
                              "bias": get(p + "input_layernorm.bias")},
                "q": lin("self_attn.q_proj", cfg.attn_bias),
                "k": lin("self_attn.k_proj", cfg.attn_bias),
                "v": lin("self_attn.v_proj", cfg.attn_bias),
                "o": lin("self_attn.o_proj", cfg.o_bias_effective),
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight"),
                    "bias": get(p + "post_attention_layernorm.bias")},
                "up": lin("mlp.c_fc", cfg.mlp_bias),
                "down": lin("mlp.c_proj", cfg.mlp_bias),
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight"),
                           "bias": get("model.norm.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "olmo":
        # Non-parametric norms: HF OlmoLayerNorm has no weights at all —
        # unit scale / zero bias is its exact parametric equivalent.
        unit_norm = {"scale": np.ones((D,), np.float32),
                     "bias": np.zeros((D,), np.float32)}

        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:
                    out["b"] = get(p + n + ".bias")
                return out
            return {
                "attn_norm": dict(unit_norm),
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "mlp_norm": dict(unit_norm),
                "gate": lin("mlp.gate_proj"),
                "up": lin("mlp.up_proj"),
                "down": lin("mlp.down_proj"),
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": dict(unit_norm),
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "phi3":
        qd = cfg.num_heads * cfg.head_dim
        kvd = cfg.num_kv_heads * cfg.head_dim
        I = cfg.intermediate_size

        def layer(i):
            p = f"model.layers.{i}."
            wqkv = get(p + "self_attn.qkv_proj.weight")     # [q|k|v, D]
            wgu = get(p + "mlp.gate_up_proj.weight")        # [gate|up, D]
            return {
                "attn_norm": {"scale": get(p + "input_layernorm.weight")},
                "q": {"w": wqkv[:qd].T},
                "k": {"w": wqkv[qd:qd + kvd].T},
                "v": {"w": wqkv[qd + kvd:].T},
                "o": {"w": get(p + "self_attn.o_proj.weight").T},
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight")},
                "gate": {"w": wgu[:I].T},
                "up": {"w": wgu[I:].T},
                "down": {"w": get(p + "mlp.down_proj.weight").T},
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "gpt_neo":
        # HF GPTNeo computes UNSCALED attention scores; our attend always
        # multiplies by 1/sqrt(hd), so scale q by sqrt(hd) here — exact
        # (the scalar commutes with the projection).
        qs = float(cfg.head_dim) ** 0.5

        def layer(i):
            p = f"transformer.h.{i}."

            def lin(n, bias):
                out = {"w": get(p + n + ".weight").T}
                if bias:
                    out["b"] = get(p + n + ".bias")
                return out
            lp = {
                "attn_norm": {"scale": get(p + "ln_1.weight"),
                              "bias": get(p + "ln_1.bias")},
                "q": {"w": get(p + "attn.attention.q_proj.weight").T * qs},
                "k": lin("attn.attention.k_proj", False),
                "v": lin("attn.attention.v_proj", False),
                "o": lin("attn.attention.out_proj", True),
                "mlp_norm": {"scale": get(p + "ln_2.weight"),
                             "bias": get(p + "ln_2.bias")},
                "up": lin("mlp.c_fc", True),
                "down": lin("mlp.c_proj", True),
            }
            return lp
        params = {
            "embed": {"tokens": get("transformer.wte.weight"),
                      "positions": get("transformer.wpe.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("transformer.ln_f.weight"),
                           "bias": get("transformer.ln_f.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "gemma2":
        # (1 + w) rmsnorm convention absorbed on ALL five norm kinds;
        # query_pre_attn_scalar**-0.5 replaces attend's 1/sqrt(hd) score
        # scale, so fold the ratio sqrt(hd / qpas) into q here — exact,
        # the scalar commutes with the projection (q_proj is bias-free).
        hd = cfg.head_dim
        qs = (hd / (cfg.query_pre_attn_scalar or hd)) ** 0.5

        def layer(i):
            p = f"model.layers.{i}."

            def nrm(n):
                return {"scale": get(p + n + ".weight") + 1.0}

            def lin(n, scale=1.0):
                out = {"w": get(p + n + ".weight").T * scale}
                if p + n + ".bias" in sd:   # attention_bias variants —
                    # the q fold scales bias with weight (commutes)
                    out["b"] = get(p + n + ".bias") * scale
                return out
            return {
                "attn_norm": nrm("input_layernorm"),
                "q": lin("self_attn.q_proj", qs),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "attn_post_norm": nrm("post_attention_layernorm"),
                "mlp_norm": nrm("pre_feedforward_layernorm"),
                "gate": lin("mlp.gate_proj"),
                "up": lin("mlp.up_proj"),
                "down": lin("mlp.down_proj"),
                "mlp_post_norm": nrm("post_feedforward_layernorm"),
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight") + 1.0},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "cohere":
        # CohereLayerNorm has no bias — zero bias is its exact parametric
        # equivalent under our layer_norm.
        zb = np.zeros((D,), np.float32)

        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:   # attention_bias variants
                    out["b"] = get(p + n + ".bias")
                return out
            lp = {
                "attn_norm": {"scale": get(p + "input_layernorm.weight"),
                              "bias": zb},
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "gate": lin("mlp.gate_proj"),
                "up": lin("mlp.up_proj"),
                "down": lin("mlp.down_proj"),
            }
            if cfg.qk_norm:   # use_qk_norm: [H, hd] per-head scales,
                # stored flat (params.py layers["q_norm"])
                lp["q_norm"] = {"scale": get(
                    p + "self_attn.q_norm.weight").reshape(-1)}
                lp["k_norm"] = {"scale": get(
                    p + "self_attn.k_norm.weight").reshape(-1)}
            return lp
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight"), "bias": zb},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "olmo2":
        # llama tensor names for the projections, but the two block
        # norms are the post-sublayer norms (sublayer_postnorm_only) and
        # q/k carry full-projection-width rms norms.
        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:
                    out["b"] = get(p + n + ".bias")
                return out
            return {
                "attn_norm": {
                    "scale": get(p + "post_attention_layernorm.weight")},
                "mlp_norm": {
                    "scale": get(p + "post_feedforward_layernorm.weight")},
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "q_norm": {"scale": get(p + "self_attn.q_norm.weight")},
                "k_norm": {"scale": get(p + "self_attn.k_norm.weight")},
                "gate": lin("mlp.gate_proj"),
                "up": lin("mlp.up_proj"),
                "down": lin("mlp.down_proj"),
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "glm":
        # Fused gate_up like phi3 ([gate|up, D], split here); glm4's two
        # extra per-block norms map onto the gemma2 sandwich leaves.
        I = cfg.intermediate_size

        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:   # q/k/v bias, o bias-free
                    out["b"] = get(p + n + ".bias")
                return out
            wgu = get(p + "mlp.gate_up_proj.weight")        # [gate|up, D]
            lp = {
                "attn_norm": {"scale": get(p + "input_layernorm.weight")},
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight")},
                "gate": {"w": wgu[:I].T},
                "up": {"w": wgu[I:].T},
                "down": {"w": get(p + "mlp.down_proj.weight").T},
            }
            if cfg.post_block_norms:   # glm4
                lp["attn_post_norm"] = {
                    "scale": get(p + "post_self_attn_layernorm.weight")}
                lp["mlp_post_norm"] = {
                    "scale": get(p + "post_mlp_layernorm.weight")}
            return lp
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    elif fam == "nemotron":
        # LayerNorm1P: (1 + w) * x̂ + b — absorb the +1 into the stored
        # scale (norm_offset), biases kept as-is.
        def layer(i):
            p = f"model.layers.{i}."

            def lin(n):
                out = {"w": get(p + n + ".weight").T}
                if p + n + ".bias" in sd:
                    out["b"] = get(p + n + ".bias")
                return out
            return {
                "attn_norm": {
                    "scale": get(p + "input_layernorm.weight") + 1.0,
                    "bias": get(p + "input_layernorm.bias")},
                "q": lin("self_attn.q_proj"),
                "k": lin("self_attn.k_proj"),
                "v": lin("self_attn.v_proj"),
                "o": lin("self_attn.o_proj"),
                "mlp_norm": {
                    "scale": get(p + "post_attention_layernorm.weight")
                    + 1.0,
                    "bias": get(p + "post_attention_layernorm.bias")},
                "up": lin("mlp.up_proj"),
                "down": lin("mlp.down_proj"),
            }
        params = {
            "embed": {"tokens": get("model.embed_tokens.weight")},
            "layers": _stack([layer(i) for i in range(cfg.num_layers)]),
            "final_norm": {"scale": get("model.norm.weight") + 1.0,
                           "bias": get("model.norm.bias")},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"w": get("lm_head.weight").T}
    else:
        raise NotImplementedError(fam)

    # Per-layer attention windows ride the param tree (transformer.
    # _layer_window) — emitted HERE, once, for every family whose config
    # carries them (gpt_neo's alternating global/local, gemma2, qwen3's
    # mixed layer_types through the shared llama branch, ...); no family
    # branch emits its own copy. sharding.param_specs expects the leaf
    # whenever cfg.attn_windows is set.
    # A dense prefix is a segment of its own (layers_dense) and takes its
    # layers' entries.
    pref = cfg.dense_prefix_layers if "layers_dense" in params else 0
    for leaf, per_layer in (
            ("attn_window", None if cfg.attn_windows is None else
             [-1 if w is None else w for w in cfg.attn_windows]),
            ("rope_on", cfg.rope_layers)):   # per-layer NoPE
        if per_layer is not None:            # (smollm3/exaone4/afmoe)
            per_layer = np.asarray(per_layer, np.int32)
            params["layers"][leaf] = per_layer[pref:]
            if pref:
                params["layers_dense"][leaf] = per_layer[:pref]

    return _to_jax(params, dtype)


def _to_jax(tree, dtype):
    if isinstance(tree, dict):
        return {k: (jnp.asarray(v, jnp.int32)
                    if k in ("attn_window", "rope_on")
                    else _to_jax(v, dtype))
                for k, v in tree.items()}
    return jnp.asarray(tree, dtype)


def allow_download() -> bool:
    """Hub downloads are opt-in: offline-by-default is the safe serving
    posture (a worker must not silently reach the internet), but the
    reference's download-any-model-by-name capability (worker/app.py:117-121,
    cache dir worker/app.py:19-20) is available behind DLI_ALLOW_DOWNLOAD=1."""
    return os.environ.get("DLI_ALLOW_DOWNLOAD", "") == "1"


def hub_cache_dir() -> str:
    """Where opted-in downloads land (≙ reference MODEL_CACHE_DIR,
    worker/app.py:19-20). Shared across workers via a mounted volume the
    same way the reference's compose file did (docker-compose.yml:12)."""
    return os.environ.get(
        "DLI_MODEL_CACHE", os.path.join(os.path.expanduser("~"),
                                        ".cache", "dli_models"))


def load_hf_model(path_or_model, dtype=None):
    """Load a local HF checkpoint directory, a hub id (opt-in), or an
    in-memory HF model.

    Returns (ModelConfig, params). Offline by default: paths must exist
    locally (the reference relied on HF-hub downloads per worker,
    worker/app.py:117-121; here checkpoint distribution is explicit).
    With ``DLI_ALLOW_DOWNLOAD=1`` a non-local name is fetched from the
    hub into ``hub_cache_dir()`` once and reused thereafter.
    """
    if isinstance(path_or_model, str):
        import transformers
        local_only = not allow_download() or os.path.isdir(path_or_model)
        # redirect the cache only when an actual download is permitted —
        # offline hub-id loads must keep resolving against the standard
        # HF cache a user may already have populated
        kw = ({"cache_dir": hub_cache_dir()}
              if not local_only and not os.path.isdir(path_or_model) else {})
        model = transformers.AutoModelForCausalLM.from_pretrained(
            path_or_model, local_files_only=local_only, **kw)
    else:
        model = path_or_model
    cfg = config_from_hf(model.config)
    params = convert_state_dict(cfg, dict(model.state_dict()), dtype=dtype)
    return cfg, params
