"""Model configuration.

One dataclass covers every supported family (GPT-2, OPT, Llama/Mistral,
Mixtral); the fields are the union of what those architectures need. The
reference framework had no config object at all — architecture handling was
an attribute sniff on the HF module tree (reference: shard_model.py:40-50);
here the config is the single source of truth for shapes, partitioning and
weight conversion.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """A Mamba-2 state-space mixer beside the attention heads of every
    block (Falcon-H1, modeling_falcon_h1.py), and the multipliers that
    family scales its block's paths by. The mixer's equations are in
    models/reference/falcon_h1_ref.py; the served forms in ops/ssm.py.

    Per request the mixer keeps a recurrent state [n_heads, d_head,
    d_state] (float32) and the last d_conv - 1 inputs of its causal
    depthwise convolution, [conv_dim] each: a fixed size whatever the
    context, overwritten on every token (ops/paged_kvcache.py keeps a
    row a serving slot of each beside the block pool)."""
    d_ssm: int = 4096          # mamba_d_ssm = n_heads * d_head
    n_heads: int = 32          # mamba_n_heads
    d_head: int = 128          # mamba_d_head
    d_state: int = 256         # mamba_d_state (N)
    n_groups: int = 2          # mamba_n_groups: B and C are a group's
    d_conv: int = 4            # mamba_d_conv
    chunk_size: int = 128      # mamba_chunk_size: prefill's scan chunk
    conv_bias: bool = True     # mamba_conv_bias
    # multipliers, each applied where the source applies it:
    in_multiplier: float = 1.0    # ssm_in_multiplier: on in_proj's input
    out_multiplier: float = 1.0   # ssm_out_multiplier: on out_proj's output
    # ssm_multipliers: in_proj's output by part, (z, x, B, C, dt)
    multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    attn_in_multiplier: float = 1.0    # on q/k/v projections' input
    attn_out_multiplier: float = 1.0   # on o_proj's output
    key_multiplier: float = 1.0        # on k_proj's output, before RoPE
    # mlp_multipliers: (on gate_proj's output, on down_proj's output)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)

    def __post_init__(self):
        for name in ("multipliers", "mlp_multipliers"):
            object.__setattr__(self, name,
                               tuple(float(v) for v in getattr(self, name)))
        assert len(self.multipliers) == 5 and len(self.mlp_multipliers) == 2
        assert self.d_ssm == self.n_heads * self.d_head, (
            f"d_ssm={self.d_ssm} != n_heads * d_head")
        assert self.n_heads % self.n_groups == 0
        assert self.d_ssm % self.n_groups == 0 and self.d_conv >= 2

    @property
    def conv_dim(self) -> int:
        """Channels of the depthwise convolution: [x | B | C]."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def proj_dim(self) -> int:
        """in_proj's output: [z | x | B | C | dt]."""
        return self.d_ssm + self.conv_dim + self.n_heads

    @property
    def state_elems(self) -> int:
        return self.n_heads * self.d_head * self.d_state

    @property
    def conv_elems(self) -> int:
        return (self.d_conv - 1) * self.conv_dim


@dataclasses.dataclass(frozen=True)
class SWAConfig:
    """Layers of two kinds in one stack (MiMo-V2, modeling_mimo_v2.py):
    ``pattern[l]`` 1 makes layer l a *windowed* layer, 0 a *full* one
    (the source's hybrid_layer_pattern). A full layer's attention is the
    model's own (num_kv_heads, rope_theta, attn_sinks, no window); a
    windowed layer's has the K/V head count, rotary base and sink given
    here and sees ModelConfig.sliding_window positions. Head and value
    widths are the model's in both kinds. The kinds differ in the shape
    of their K, V and sink leaves, so each kind is a stack of its own
    (``layers`` the windowed, ``layers_full`` the full ones;
    transformer.layer_segments runs them in the pattern's order), and in
    their caches: the batcher's block pool holds the full layers' K and
    V alone, the windowed layers' live in a ring a serving slot
    (ops/paged_kvcache.py). The equations are in
    models/reference/mimo_v2_ref.py."""
    pattern: Tuple[int, ...] = ()
    num_kv_heads: int = 8          # swa_num_key_value_heads
    rope_theta: float = 10000.0    # swa_rope_theta
    sinks: bool = True             # add_swa_attention_sink_bias

    def __post_init__(self):
        object.__setattr__(self, "pattern",
                           tuple(int(v) for v in self.pattern))
        assert set(self.pattern) <= {0, 1}, self.pattern

    def kinds(self) -> Tuple[str, ...]:
        return tuple("swa" if v else "full" for v in self.pattern)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # Identity
    name: str = "gpt2"
    family: str = "gpt2"  # gpt2 | opt | llama | mixtral

    # Core dimensions
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 12  # < num_heads => GQA
    head_dim: int = 64
    max_position_embeddings: int = 1024
    # Looped models (Ouro: total_ut_steps): the whole layer stack runs
    # this many times a token over ONE set of weights, the final norm
    # between passes, and each (step, layer) pair keeps K and V of its
    # own: cache plane step * num_layers + layer (cache_planes). 1 is
    # every other family; num_layers stays the weights' depth.
    loop_steps: int = 1
    # State layers (Falcon-H1): every block runs a Mamba-2 mixer beside
    # its attention heads, both reading the block's normed input, their
    # outputs joined before the one residual add. None is every other
    # family. (A dict, as a checkpoint's config.json gives it back, is
    # taken for its fields.)
    ssm: Optional[SSMConfig] = None
    # Windowed and full layers of different shapes in one stack
    # (MiMo-V2): see SWAConfig. None is every other family; beside it
    # ``sliding_window`` is the windowed kind's width. (A dict is taken
    # for its fields, as ssm's is.)
    swa: Optional[SWAConfig] = None
    # swa | full on the config of ONE kind's layers (kind_cfg), whose
    # other fields are then that kind's own and whose ``swa`` is None;
    # never set by a caller.
    attn_kind: Optional[str] = None

    # Architecture switches
    norm_type: str = "layernorm"  # layernorm | rmsnorm
    norm_eps: float = 1e-5
    # gelu (tanh approx) | gelu_exact | silu | relu | relu2 (squared
    # ReLU, Nemotron)
    activation: str = "gelu"
    gated_mlp: bool = False  # llama-style SwiGLU (gate+up) vs plain fc
    # learned | rope | alibi (BLOOM/Falcon-RW: linear attention bias,
    # position-free K/V — the cache layout matches the RoPE families')
    position_embedding: str = "learned"
    # Multiplier on the ALiBi slopes: BLOOM adds the bias to the SCALED
    # scores (1.0); Falcon-RW scales (scores + bias) together, i.e. the
    # bias carries an extra 1/sqrt(head_dim).
    alibi_scale: float = 1.0
    rope_theta: float = 10000.0
    # Partial rotary (GPT-NeoX rotary_pct / Phi partial_rotary_factor):
    # only the first rope_pct * head_dim dims rotate, the rest pass
    # through position-free.
    rope_pct: float = 1.0
    # GPT-J rotate_every_two convention: frequency i rotates dims
    # (2i, 2i+1) instead of HF-llama's (i, i + rot/2) halves.
    rope_interleaved: bool = False
    # Context-extension override of the rope frequency ladder ([rot/2]
    # floats, e.g. yarn's NTK-by-part interpolation) — computed ONCE at
    # conversion (models/convert.py _yarn_inv_freq) and carried here so
    # checkpoints roundtrip it through config.json. None => the plain
    # theta ladder.
    rope_inv_freq: Optional[Tuple[float, ...]] = None
    # yarn attention_factor: multiplies cos/sin (ops/rope.apply_rope),
    # i.e. scores scale by its square over the rotated dims. The
    # separate mscale_all_dim score multiplier (uniform over ALL dims)
    # is folded into the q weights at conversion via
    # query_pre_attn_scalar instead.
    rope_attn_factor: float = 1.0
    # BLOOM: layernorm applied to the embedding output.
    embed_norm: bool = False
    attn_bias: bool = True
    # Qwen2-style asymmetric attention bias: q/k/v carry bias, the output
    # projection does not. None => o follows attn_bias.
    o_bias: Optional[bool] = None
    mlp_bias: bool = True
    # Phi-style bias on the untied lm_head projection.
    lm_head_bias: bool = False
    tie_word_embeddings: bool = True
    # GPT-NeoX / Phi / Falcon block topology: attention and MLP both read
    # (norms of) the SAME block input and share one residual add —
    # x + attn(norm1(x)) + mlp(norm2(x)) — instead of the sequential
    # two-residual layout.
    parallel_residual: bool = False
    # Phi / Falcon-7B: ONE layernorm feeds both attention and MLP (layer
    # params then carry no mlp_norm). Only meaningful with
    # parallel_residual.
    shared_attn_mlp_norm: bool = False
    sliding_window: Optional[int] = None  # Mistral-style local attention
    # Per-LAYER attention windows (GPT-Neo alternating global/local-256):
    # a full per-layer tuple, entries None => global. ``sliding_window``
    # beside it only names the width every windowed layer has, as a
    # source's ``sliding_window`` key does beside ``layer_types``
    # (Trinity/afmoe); the tuple decides. Threaded through the runtime
    # as an int32 leaf ``attn_window`` ([L], -1 == global) in the layer
    # param tree (models/params.py, convert.py), so every scan / unroll /
    # pipeline-stage / sharding path carries it without special cases;
    # attention reads it as a traced scalar (ops/attention.py). Forces
    # the XLA attention formulation — the pallas flash kernels take
    # static windows only (models/transformer.py).
    attn_windows: Optional[Tuple[Optional[int], ...]] = None
    # Trinity (afmoe) gated attention: a per-layer ``attn_gate`` linear
    # [D, H*hd] beside q/k/v; the attention output is multiplied by
    # sigmoid(attn_gate(h)) elementwise, before the o projection.
    attn_gate: bool = False
    # Gemma-2 logit softcapping: scores/logits squashed to
    # cap * tanh(x / cap). ``attn_softcap`` applies to attention scores
    # (pre-mask; forces the XLA attention formulation — the flash
    # kernels' online softmax has no tanh hook); ``logit_softcap`` to
    # the final vocab logits.
    attn_softcap: Optional[float] = None
    logit_softcap: Optional[float] = None
    # Cohere: tied-head logits multiplied by a constant scale.
    logit_scale: Optional[float] = None
    # Gemma-2 block topology: sandwich norms — attention/MLP outputs are
    # normed BEFORE their residual add (attn_post_norm/mlp_post_norm
    # leaves), in addition to the usual pre-norms.
    post_block_norms: bool = False
    # Gemma-2 query_pre_attn_scalar: HF scales scores by qpas**-0.5
    # instead of head_dim**-0.5. Conversion absorbs the ratio
    # sqrt(head_dim / qpas) into the q weights (models/convert.py) so
    # the runtime score scale stays uniform; like norm_offset, this
    # field only drives that conversion step.
    query_pre_attn_scalar: Optional[float] = None
    # Gemma-style sqrt(hidden_size) embedding normalizer, applied to the
    # embedding OUTPUT only (the tied head reads the raw table).
    embed_scale: Optional[float] = None
    # Gemma's RMSNorm convention is (1 + w) * x̂. Conversion absorbs the
    # +1 into the stored scale (models/convert.py) so the runtime norm
    # stays plain; this flag only drives that conversion step (and
    # random-init's ones() is already the absorbed identity).
    norm_offset: bool = False
    # Q/K normalization applied to the projected q and k BEFORE RoPE:
    # None | "rms_head" (RMSNorm over head_dim, per head — Qwen3 /
    # Qwen3-MoE) | "rms_full" (RMSNorm over the full projection width —
    # OLMo2) | "ln_head" (bias-free LayerNorm over head_dim — Cohere
    # use_qk_norm). Adds q_norm/k_norm scale leaves to the layer tree.
    qk_norm: Optional[str] = None
    # OLMo2 block topology: NO pre-norms; the attn/mlp norm leaves apply
    # to the sublayer OUTPUT before its residual add — x + norm(f(x)).
    # (Distinct from post_norm, which norms after the add, and from
    # post_block_norms, which sandwiches pre- AND post-norms.)
    sublayer_postnorm_only: bool = False
    # HunYuan-Dense: the q/k norms apply AFTER RoPE (Qwen3/Exaone norm
    # then rotate; HunYuan rotates then norms). Only meaningful with
    # qk_norm.
    qk_norm_after_rope: bool = False
    # DBRX clip_qkv: the fused qkv projection output is clamped to
    # ±this before heads split — a runtime nonlinearity on activations
    # (clamping after our separate q/k/v projections is identical).
    qkv_clip: Optional[float] = None
    # Per-LAYER rope on/off (SmolLM3 no_rope_layers: every Nth layer is
    # NoPE; Exaone4 hybrid: full-attention layers skip rope while
    # sliding layers rotate). A full per-layer tuple of 1/0; None => all
    # layers rotate. Rides the layer param tree as an int32 ``rope_on``
    # leaf ([L]) like attn_windows, so every scan/unroll/pipeline path
    # carries it; the block computes the rotation and selects per layer.
    rope_layers: Optional[Tuple[int, ...]] = None
    # Granite residual_multiplier: sublayer outputs scaled by this before
    # their residual add. (Granite's other multipliers map onto existing
    # fields: embedding_multiplier -> embed_scale, attention_multiplier
    # -> query_pre_attn_scalar absorption, 1/logits_scaling ->
    # logit_scale.)
    residual_scale: Optional[float] = None
    # OPT-350m specifics (reference's second arch family, shard_model.py:46):
    # token embeds live in a smaller space with linear project_in/out...
    embed_proj_dim: Optional[int] = None
    # ...and blocks normalize AFTER the residual add (do_layer_norm_before
    # = False), with no final norm before the head.
    post_norm: bool = False

    # DeepSeek-V3 multi-head latent attention (MLA, HF
    # modeling_deepseek_v3.py DeepseekV3Attention): q and kv project
    # through low-rank bottlenecks with an RMSNorm at each bottleneck
    # (which is why MLA cannot be folded into plain q/k/v weights at
    # conversion), per-head q/k dims split into a position-free "nope"
    # part and a RoPE'd part whose k side is computed ONCE and shared
    # across heads. kv_lora_rank non-None switches the block to MLA;
    # head_dim is the rope head's width (qk_rope_head_dim), as the source
    # configs have it: a q or k head is qk_head_dim = qk_nope_head_dim +
    # qk_rope_head_dim wide. num_kv_heads == num_heads (per-head k/v exist
    # where a prefill materializes them; the caches hold the latent).
    q_lora_rank: Optional[int] = None     # None => full-rank q projection
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    # MLA value head width; v is zero-padded to head_dim inside the block
    # so every cache/attention path keeps a single head_dim, and the
    # attention output is sliced back before the o projection. None =>
    # head_dim (all other families but MiMo-V2, whose v projection,
    # caches and o projection are this wide beside q and k heads of
    # head_dim: nothing is padded there).
    v_head_dim: Optional[int] = None
    # MiMo-V2 attention_value_scale: v is multiplied by it as projected
    # (the caches hold the scaled rows). None => no scale.
    attn_value_scale: Optional[float] = None
    # MLA's actual point: cache ONE shared latent row per token —
    # [k_rot (qk_rope_head_dim, post-RoPE) | c (kv_lora_rank, normed)] —
    # instead of materialized per-head K/V, and decode via the absorbed
    # formulation (scores q_nope·(W_uk c) == (W_uk^T q_nope)·c; outputs
    # W_uv (Σ w c)), i.e. MQA over the latent with per-head up/down
    # projections folded around the attention (transformer.
    # _mla_absorbed). Cuts cache bytes by
    # 2·H·qk_head_dim / (kv_lora_rank + qk_rope_head_dim) (19x on the
    # deepseek-proxy, 21x on kanana-2-30b-a3b). The engine enables it on
    # eligible meshes (no sp/pp, no kv_quant; DLI_MLA_LATENT=0 opts out
    # there); the paged batcher always serves an MLA model from a latent
    # pool and refuses what that pool cannot take (runtime/batcher.py).
    mla_latent_cache: bool = False

    # Mixture-of-experts (Mixtral). num_experts == 0 => dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Router convention: "softmax" (Mixtral/Qwen3-MoE: softmax -> top-k
    # -> renormalize) | "topk_softmax" (gpt-oss: select by raw biased
    # logits, weights = softmax over the selected k logits) | "ernie" (ERNIE-4.5-MoE: softmax scores under the
    # deepseek-style bias-corrected SELECTION, unbiased weights) |
    # "deepseek_v3" (sigmoid scores; selection by
    # scores + e_score_correction_bias under group-limited top-k —
    # moe_n_group groups scored by their top-2 sum, top moe_topk_group
    # groups kept; weights are the UNbiased scores, renormalized when
    # moe_norm_topk, then scaled by moe_routed_scale).
    moe_router: str = "softmax"
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scale: float = 1.0
    moe_norm_topk: bool = True
    # DeepSeek shared experts: a dense SwiGLU MLP of width
    # moe_shared_experts * (per-expert intermediate), always active,
    # added to the routed output (layer tree leaves shared_gate/up/down).
    moe_shared_experts: int = 0
    # gpt-oss expert GLU: gate clamped to (-inf, limit], up to ±limit,
    # glu = gate * sigmoid(alpha * gate), output (up + 1) * glu — with
    # per-expert BIASES on gate/up/down (leaves carry "b"). None =>
    # the standard act(gate) * up.
    moe_swiglu_limit: Optional[float] = None
    moe_swiglu_alpha: float = 1.702
    # gpt-oss attention sinks: one learned logit per head ([H] ``sinks``
    # leaf in the layer tree) appended to every softmax as a virtual
    # column and dropped after normalization — the sink only inflates
    # the denominator (ops/attention.attend).
    attn_sinks: bool = False
    # DeepSeek first_k_dense_replace: the first k layers run a plain
    # dense MLP (width intermediate_size) instead of the MoE. The
    # param tree then carries a second stacked segment ``layers_dense``
    # ([k, ...]) ahead of the MoE ``layers`` ([L-k, ...]) — the layer
    # scans run the two segments back to back
    # (models/transformer.py layer_segments). Attention/cache layout is
    # identical across segments, so the KV cache stays one [L, ...]
    # stack.
    dense_prefix_layers: int = 0
    # Per-expert MLP width where it differs from intermediate_size (the
    # source configs' moe_intermediate_size): routed experts are this
    # wide and the shared experts moe_shared_experts times it, while
    # intermediate_size stays what the source calls it, the dense MLP's
    # width (the dense prefix of a mixed stack). None => experts are
    # intermediate_size wide (Mixtral).
    moe_intermediate_size: Optional[int] = None
    # The share of each MoE layer's experts this program holds, (first,
    # count): expert parallelism's cut as one chip sees it. The router
    # stays num_experts wide and a token's weights are normalised over
    # all its chosen experts; the expert leaves are [count, ...] and
    # _moe sums the chosen experts in [first, first + count) alone (a
    # choice that falls elsewhere costs no read and adds nothing). The
    # partial sum goes on to the next layer: nothing stands in for the
    # other shares or their exchange. None => all of them.
    experts_held: Optional[Tuple[int, int]] = None
    # Numerics
    dtype: str = "bfloat16"  # activation/weight dtype on device
    # Weight-only quantization (ops/quant.py): None | "int8" | "int4".
    # int8 halves the HBM weight traffic of decode and doubles
    # fit-per-chip at negligible accuracy cost; int4 (nibble-packed)
    # halves it again — the throughput mode, measurably lossier.
    quant: Optional[str] = None
    # Token-embedding-table quantization: None | "int8" (per-row scales,
    # ops/quant.py quantize_embed). The tied-head lever: gpt2-family
    # unembed streams the whole [V, D] table per decode step, and
    # llama's table is ~1 GB bf16 of footprint. Opt-in separately from
    # ``quant`` because embeddings are the most accuracy-sensitive table.
    embed_quant: Optional[str] = None
    # KV-cache quantization: None | "int8" (per-token-per-head symmetric
    # scales, ops/kvcache.py quant_kv). Halves cache traffic/footprint —
    # the long-context decode lever on top of weight int8. Attention
    # dequantizes at read; XLA fuses the int8->bf16 convert+scale into the
    # attention matmuls so the HBM read stays int8.
    kv_quant: Optional[str] = None

    # The dense cache's attention backend (the single-stream engine's
    # forward passes): auto | xla | pallas | pallas_interpret
    # (trace-time static; see ops/attention.py resolve_backend). The
    # batcher pins "xla" and its paged programs read it nowhere.
    attn_backend: str = "auto"

    # Pinned by the engine at init (like attn_backend's resolution): True
    # when the enclosing GSPMD program shards linear weights over tp, so
    # row-parallel (din-sharded: o/down) int4 leaves keep the XLA unpack
    # instead of the pallas kernel, whose partitioning rule shards only
    # the output axis (ops/pallas/quant_matmul.py supported()). Local-
    # view (shard_map) callers keep False: their weights arrive pre-
    # sliced and the kernel is a plain local matmul.
    tp_row_sharded: bool = False
    # Pinned by the batcher at init: the form of the experts' grouped
    # matmuls where a call holds few rows an expert
    # (models/transformer.py _expert_stream). "xla"
    # (lax.ragged_dot everywhere) | "pallas" (a one-device TPU program:
    # ops/pallas/grouped_matmul.py at decode size) | "pallas_interpret"
    # (tests). Not a serving option: the batcher overwrites it.
    expert_matmul: str = "xla"
    # Pinned by the batcher beside it, by the same rule: the form of a
    # decode chunk's read of the paged pool (models/transformer.py
    # _pool_kernel). "xla" (the in-loop gather as far as _pool_ladder's
    # rung, everywhere) | "pallas" (a one-device TPU program:
    # ops/pallas/paged_attention.py where the pool's shape is one it
    # reads as it lies: K and V planes whose heads of whole lanes fill
    # a tile's 8 sublanes or divide them, a latent pool's one plane, the
    # flat rows of a model with layer kinds' full layers;
    # scanned layers or layers held one by one) | "pallas_interpret" (tests). Not a serving
    # option: the batcher overwrites it. A model with state layers takes
    # its decode chunk's one-step state update by the same pin
    # (transformer._ssm_kernel, ops/pallas/ssm_step.py).
    pool_kernel: str = "xla"

    def __post_init__(self):
        assert self.loop_steps >= 1, f"loop_steps={self.loop_steps}"
        assert self.loop_steps == 1 or not self.post_norm, (
            "a looped stack takes the final norm between passes; a "
            "post_norm model has none")
        assert self.num_heads % self.num_kv_heads == 0, (
            f"num_heads={self.num_heads} must be divisible by "
            f"num_kv_heads={self.num_kv_heads}"
        )
        if isinstance(self.ssm, dict):
            object.__setattr__(self, "ssm", SSMConfig(**self.ssm))
        if self.ssm is not None:
            assert (not self.mla and not self.is_moe and self.gated_mlp
                    and self.loop_steps == 1 and not self.post_norm
                    and not self.parallel_residual
                    and not self.post_block_norms
                    and not self.sublayer_postnorm_only), (
                "a state-space mixer rides the plain pre-norm block with "
                "a gated MLP (Falcon-H1's)")
        if isinstance(self.swa, dict):
            object.__setattr__(self, "swa", SWAConfig(**self.swa))
        if self.swa is not None:
            assert len(self.swa.pattern) == self.num_layers, (
                f"swa.pattern has {len(self.swa.pattern)} entries for "
                f"{self.num_layers} layers")
            assert self.num_heads % self.swa.num_kv_heads == 0
            assert (self.sliding_window and not self.mla
                    and self.ssm is None and self.loop_steps == 1
                    and self.attn_windows is None
                    and self.rope_layers is None
                    and self.position_embedding == "rope"), (
                "layer kinds (cfg.swa) ride the plain rope block; "
                "sliding_window is the windowed kind's width")
            k = self.dense_prefix_layers
            assert len(set(self.swa.pattern[:k])) <= 1, (
                "the dense prefix is one stack: its layers share a kind")
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(v) for v in self.experts_held))
            first, count = self.experts_held
            # (a dense prefix's own config keeps the field and has no
            # experts: its layers count the same MOE_STATS as the rest)
            assert not self.num_experts or (
                0 <= first and count >= 1
                and first + count <= self.num_experts), self.experts_held
        if self.rope_inv_freq is not None:
            # normalize (checkpoint config.json roundtrips tuple -> list)
            object.__setattr__(self, "rope_inv_freq",
                               tuple(float(f) for f in self.rope_inv_freq))
        if self.attn_windows is not None:
            # normalize (checkpoint config.json roundtrips tuple -> list)
            object.__setattr__(self, "attn_windows",
                               tuple(self.attn_windows))
            assert len(self.attn_windows) == self.num_layers, (
                f"attn_windows has {len(self.attn_windows)} entries for "
                f"{self.num_layers} layers")
            assert self.sliding_window is None or all(
                w in (None, self.sliding_window)
                for w in self.attn_windows), (
                "sliding_window beside attn_windows names the width of "
                "the windowed layers: every entry is None or equal to it")
        if self.rope_layers is not None:
            object.__setattr__(self, "rope_layers",
                               tuple(int(v) for v in self.rope_layers))
            assert len(self.rope_layers) == self.num_layers, (
                f"rope_layers has {len(self.rope_layers)} entries for "
                f"{self.num_layers} layers")
            assert self.position_embedding == "rope", (
                "rope_layers only makes sense with rope positions")
        assert not (self.post_block_norms
                    and (self.parallel_residual or self.post_norm)), (
            "post_block_norms (sandwich) excludes parallel_residual and "
            "post_norm topologies")
        assert not (self.parallel_residual and self.post_norm), (
            "parallel_residual and post_norm are mutually exclusive")
        assert not (self.sublayer_postnorm_only
                    and (self.parallel_residual or self.post_norm
                         or self.post_block_norms)), (
            "sublayer_postnorm_only (olmo2) excludes parallel_residual, "
            "post_norm and post_block_norms topologies")
        assert self.qk_norm in (None, "rms_head", "rms_full", "ln_head"), (
            f"unknown qk_norm {self.qk_norm!r}")
        assert not (self.shared_attn_mlp_norm
                    and not self.parallel_residual), (
            "shared_attn_mlp_norm requires parallel_residual")
        if self.kv_lora_rank is not None:
            assert self.head_dim == self.qk_rope_head_dim, (
                "MLA: head_dim is the rope head's width, as the source "
                "configs have it (q and k heads are qk_head_dim = "
                "qk_nope_head_dim + qk_rope_head_dim wide)")
            assert self.num_kv_heads == self.num_heads, (
                "MLA materializes k/v per head: num_kv_heads == num_heads")
            assert self.position_embedding == "rope" and self.qk_norm is None
            assert not self.attn_gate, "MLA has no attn_gate path"
        if self.mla_latent_cache:
            assert self.mla, "mla_latent_cache requires an MLA config"
            assert self.kv_quant is None, (
                "mla_latent_cache and kv_quant are mutually exclusive "
                "(the latent row is already the compressed representation)")
            assert (self.sliding_window is None
                    and self.attn_windows is None
                    and self.attn_softcap is None), (
                "mla_latent_cache's absorbed attention does not thread "
                "sliding windows or score softcapping (no MLA "
                "architecture uses them); serve such a config with the "
                "materialized layout (DLI_MLA_LATENT=0)")
        assert self.moe_router in ("softmax", "deepseek_v3", "ernie",
                                   "topk_softmax"), (
            f"unknown moe_router {self.moe_router!r}")
        if self.dense_prefix_layers:
            assert 0 < self.dense_prefix_layers < self.num_layers, (
                f"dense_prefix_layers={self.dense_prefix_layers} must be "
                f"in (0, num_layers={self.num_layers}); an all-dense "
                "model is just num_experts=0")
            assert self.num_experts > 0, (
                "dense_prefix_layers describes a dense prefix AHEAD of "
                "MoE layers; set num_experts")
            assert self.moe_intermediate_size, (
                "dense_prefix_layers needs moe_intermediate_size (the "
                "per-expert width differs from the prefix MLP's "
                "intermediate_size)")
        if self.moe_router in ("deepseek_v3", "ernie") and self.num_experts:
            E, G = self.num_experts, self.moe_n_group
            assert G >= 1 and E % G == 0, (
                f"deepseek routing: num_experts={E} must divide into "
                f"moe_n_group={G} groups")
            assert E // G >= 2, (
                f"deepseek routing scores each group by its top-2 sum: "
                f"need >= 2 experts per group, got {E // G}")
            assert 1 <= self.moe_topk_group <= G, (
                f"moe_topk_group={self.moe_topk_group} must be in "
                f"[1, moe_n_group={G}]")
            assert self.moe_topk_group * (E // G) >= self.num_experts_per_tok, (
                f"top-{self.num_experts_per_tok} routing needs at least "
                f"that many eligible experts, but moe_topk_group="
                f"{self.moe_topk_group} groups expose only "
                f"{self.moe_topk_group * (E // G)}")

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank is not None

    def dense_segment_cfg(self, num_layers: Optional[int] = None
                          ) -> "ModelConfig":
        """The per-segment config of the dense-MLP prefix of a mixed
        stack: MoE fields cleared, MLP width = intermediate_size.
        The ONE derivation shared by execution
        (transformer.layer_segments), init (params.init_params) and
        sharding (param_specs) — a field zeroed here is zeroed
        everywhere."""
        n = self.dense_prefix_layers if num_layers is None else num_layers
        return self.replace(
            num_experts=0, moe_shared_experts=0, moe_router="softmax",
            dense_prefix_layers=0, moe_intermediate_size=None,
            num_layers=n, **self._per_layer(0, n))

    def moe_segment_cfg(self) -> "ModelConfig":
        """The MoE tail of a mixed stack as a stack of its own (init and
        sharding build the two segments apart): the per-layer tuples cut
        to the layers behind the dense prefix."""
        k = self.dense_prefix_layers
        return self.replace(dense_prefix_layers=0,
                            num_layers=self.num_layers - k,
                            **self._per_layer(k, self.num_layers))

    def _per_layer(self, start: int, stop: int) -> dict:
        """attn_windows / rope_layers / swa.pattern of layers
        [start, stop)."""
        out = {name: (None if getattr(self, name) is None
                      else getattr(self, name)[start:stop])
               for name in ("attn_windows", "rope_layers")}
        if self.swa is not None:
            out["swa"] = dataclasses.replace(
                self.swa, pattern=self.swa.pattern[start:stop])
        return out

    def kind_cfg(self, kind: str, num_layers: int) -> "ModelConfig":
        """The config of ``num_layers`` layers of one kind of a model
        with layer kinds (cfg.swa): a homogeneous stack, its attention
        fields that kind's own. The ONE derivation shared by execution
        (transformer.layer_segments), init and sharding."""
        kw = dict(swa=None, attn_kind=kind, num_layers=num_layers,
                  dense_prefix_layers=0)
        if kind == "swa":
            kw.update(num_kv_heads=self.swa.num_kv_heads,
                      rope_theta=self.swa.rope_theta,
                      attn_sinks=self.swa.sinks)
        else:
            kw.update(sliding_window=None)
        return self.replace(**kw)

    def kind_stacks(self):
        """(param-tree key, that stack's config) of each stack a model
        with layer kinds has, the empty ones left out: ``layers`` the
        windowed MoE (or only) layers, ``layers_full`` the full ones,
        ``layers_dense`` the leading dense layers (one kind). Shared by
        init and sharding; transformer.layer_segments runs their runs in
        the pattern's order."""
        kinds, k = self.swa.kinds(), self.dense_prefix_layers
        stacks = [("layers", self, "swa", kinds[k:].count("swa")),
                  ("layers_full", self, "full", kinds[k:].count("full"))]
        if k:
            stacks.append(("layers_dense", self.dense_segment_cfg(),
                           kinds[0], k))
        return [(name, base.kind_cfg(kind, n))
                for name, base, kind, n in stacks if n]

    def kind_layers(self, kind: str) -> Tuple[int, ...]:
        """Indices, in the whole stack, of the layers of ``kind``."""
        return tuple(i for i, k in enumerate(self.swa.kinds()) if k == kind)

    @property
    def cache_index(self) -> Tuple[int, ...]:
        """Per layer, its plane in its own kind's cache (the full
        layers' block pool, the windowed layers' ring): how many layers
        of its kind lie before it."""
        seen = {"swa": 0, "full": 0}
        out = []
        for k in self.swa.kinds():
            out.append(seen[k])
            seen[k] += 1
        return tuple(out)

    @property
    def slot_cache(self) -> bool:
        """Whether a serving slot holds a cache of its own beside its
        blocks (state layers' planes, windowed layers' ring): the
        batcher then matches and inserts no prefix, a chunked prompt
        keeps its slot, a preempted request is prefilled again."""
        return self.ssm is not None or self.swa is not None

    @property
    def qk_head_dim(self) -> int:
        """Width of one q or k head: head_dim, except under MLA, where
        head_dim is the rope slice alone (the source's meaning)."""
        if self.mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def expert_intermediate_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def v_head_dim_effective(self) -> int:
        return (self.qk_head_dim if self.v_head_dim is None
                else self.v_head_dim)

    # Dense-cache plane shapes (ops/kvcache.init_cache, sharding.
    # cache_specs): the latent layout stores ONE shared
    # [k_rot | c] row per token in the k plane and nothing in the v
    # plane (attention reads v as a slice of k — the c part).
    @property
    def cache_planes(self) -> int:
        """Leading axis of every cache and pool plane: one K and V plane
        a (loop step, layer) pair, step-major."""
        return self.loop_steps * self.num_layers

    @property
    def cache_kv_heads(self) -> int:
        if self.swa is not None:   # the dense cache: the wider kind's
            return max(self.num_kv_heads, self.swa.num_kv_heads)
        return 1 if self.mla_latent_cache else self.num_kv_heads

    @property
    def cache_head_dim(self) -> int:
        if self.mla_latent_cache:
            return self.qk_rope_head_dim + self.kv_lora_rank
        return self.qk_head_dim

    @property
    def cache_v_head_dim(self) -> int:
        if self.mla:   # materialized: v rides zero-padded to qk_head_dim
            return 0 if self.mla_latent_cache else self.qk_head_dim
        return self.v_head_dim_effective

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.qk_head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.qk_head_dim

    @property
    def v_dim(self) -> int:
        """Width of the v projection (kv_dim but for MiMo-V2)."""
        return self.num_kv_heads * self.v_head_dim_effective

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def o_bias_effective(self) -> bool:
        return self.attn_bias if self.o_bias is None else self.o_bias

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)
