"""Golden-parity tests: our JAX forward vs HF transformers (torch, CPU).

This is the property the reference conspicuously never verified (SURVEY.md
§4): that the framework's compute matches the source checkpoints. We build
tiny random HF models from configs (fully offline) and require logits to
agree to float32 tolerance.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models import convert, transformer
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from conftest import jitted


def _logits_ours(cfg, params, tokens):
    B, S = tokens.shape
    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    lengths = jnp.full((B,), S, jnp.int32)
    logits, _ = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(tokens), lengths, cache)
    return np.asarray(logits)


def _check_model(hf_model, tokens, atol=2e-3):
    import torch
    cfg, params = convert.load_hf_model(hf_model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.float().numpy()
    ours = _logits_ours(cfg, params, tokens)
    np.testing.assert_allclose(ours, ref, atol=atol, rtol=1e-3)


def test_gpt2_matches_hf():
    import transformers
    torch_cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=3, n_head=4)
    import torch
    torch.manual_seed(0)
    model = transformers.GPT2LMHeadModel(torch_cfg).eval()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 128, size=(2, 12), dtype=np.int64)
    _check_model(model, tokens)


def test_llama_gqa_matches_hf():
    import transformers
    torch_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0,
        tie_word_embeddings=False, attention_bias=False)
    import torch
    torch.manual_seed(1)
    model = transformers.LlamaForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_mistral_sliding_window_matches_hf():
    import transformers
    torch_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=4,
        tie_word_embeddings=False)
    import torch
    torch.manual_seed(2)
    model = transformers.MistralForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 128, size=(1, 16), dtype=np.int64)
    _check_model(model, tokens)


def test_opt_matches_hf():
    import transformers
    torch_cfg = transformers.OPTConfig(
        vocab_size=128, hidden_size=32, ffn_dim=64, num_hidden_layers=3,
        num_attention_heads=4, max_position_embeddings=64,
        word_embed_proj_dim=32, do_layer_norm_before=True)
    import torch
    torch.manual_seed(3)
    model = transformers.OPTForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, 128, size=(2, 8), dtype=np.int64)
    _check_model(model, tokens)


def test_opt_350m_arch_matches_hf():
    """The opt-350m shape: word_embed_proj_dim < hidden (project_in/out)
    plus post-LN blocks and no final norm (reference supported this arch
    via shard_model.py:46-50; the TPU build must serve the real
    checkpoint)."""
    import transformers
    torch_cfg = transformers.OPTConfig(
        vocab_size=128, hidden_size=32, ffn_dim=64, num_hidden_layers=3,
        num_attention_heads=4, max_position_embeddings=64,
        word_embed_proj_dim=16, do_layer_norm_before=False)
    import torch
    torch.manual_seed(6)
    model = transformers.OPTForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.embed_proj_dim == 16 and cfg.post_norm
    rng = np.random.default_rng(6)
    tokens = rng.integers(0, 128, size=(2, 8), dtype=np.int64)
    _check_model(model, tokens)


def test_opt_350m_decode_matches_hf_generate():
    """Greedy decode through the dense cache ≡ HF generate for the
    post-LN + projected-embedding arch (exercises decode_step, not just
    prefill)."""
    import torch
    import transformers
    torch_cfg = transformers.OPTConfig(
        vocab_size=128, hidden_size=32, ffn_dim=64, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        word_embed_proj_dim=16, do_layer_norm_before=False)
    torch.manual_seed(7)
    model = transformers.OPTForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    rng = np.random.default_rng(7)
    prompt = rng.integers(4, 128, size=(1, 6), dtype=np.int64)
    with torch.no_grad():
        want = model.generate(
            torch.tensor(prompt), max_new_tokens=8, do_sample=False,
            pad_token_id=0)[0, 6:].tolist()

    cache = init_cache(cfg, 1, 32, dtype=jnp.float32)
    logits, cache = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(prompt.astype(np.int32)),
        jnp.asarray([6], jnp.int32), cache)
    cur = int(np.argmax(np.asarray(logits)[0, 5]))
    got = [cur]
    for _ in range(7):
        logits, cache = jitted(transformer.decode_step)(
            params, cfg, jnp.asarray([[cur]], jnp.int32), cache)
        cur = int(np.argmax(np.asarray(logits)[0, 0]))
        got.append(cur)
    assert got == want


def test_mixtral_matches_hf():
    import transformers
    torch_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4,
        num_experts_per_tok=2, tie_word_embeddings=False,
        sliding_window=None)
    import torch
    torch.manual_seed(4)
    model = transformers.MixtralForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, 128, size=(1, 8), dtype=np.int64)
    _check_model(model, tokens)


def test_ragged_prefill_matches_unpadded():
    """Right-padded batched prefill must give the same logits (at valid
    positions) as running each sequence alone."""
    import transformers, torch
    torch_cfg = transformers.GPT2Config(
        vocab_size=64, n_positions=32, n_embd=16, n_layer=2, n_head=2)
    torch.manual_seed(5)
    model = transformers.GPT2LMHeadModel(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    rng = np.random.default_rng(5)
    a = rng.integers(0, 64, size=(1, 9), dtype=np.int64)
    b = rng.integers(0, 64, size=(1, 5), dtype=np.int64)
    padded = np.zeros((2, 9), dtype=np.int64)
    padded[0] = a[0]
    padded[1, :5] = b[0]

    cache = init_cache(cfg, 2, 16, dtype=jnp.float32)
    logits, _ = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(padded), jnp.asarray([9, 5], jnp.int32), cache)
    sole_a = _logits_ours(cfg, params, a)
    sole_b = _logits_ours(cfg, params, b)
    np.testing.assert_allclose(np.asarray(logits)[0, :9], sole_a[0], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(logits)[1, :5], sole_b[0], atol=1e-4, rtol=1e-4)


def test_qwen2_matches_hf():
    """Qwen2: llama layout + bias on q/k/v only (o_proj bias-free)."""
    import transformers
    torch_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0,
        tie_word_embeddings=False, use_sliding_window=False)
    import torch
    torch.manual_seed(8)
    model = transformers.Qwen2ForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.attn_bias and cfg.o_bias is False
    assert cfg.sliding_window is None   # declared but not applied by HF
    rng = np.random.default_rng(8)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_gemma_matches_hf():
    """Gemma: (1+w) rmsnorm (absorbed at conversion), sqrt(D) embedding
    normalizer, tanh-gelu gated MLP, head_dim > hidden/heads, tied head."""
    import transformers
    torch_cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, max_position_embeddings=64, rope_theta=10000.0,
        hidden_activation="gelu_pytorch_tanh")
    import torch
    torch.manual_seed(9)
    model = transformers.GemmaForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.tie_word_embeddings and cfg.norm_offset
    assert cfg.head_dim == 16 and cfg.embed_scale == 32 ** 0.5
    rng = np.random.default_rng(9)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_gemma_decode_matches_hf_generate():
    """Greedy decode parity for the gemma deltas (embed scale must apply
    on the decode path too, and the MQA cache must round-trip)."""
    import torch
    import transformers
    torch_cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, max_position_embeddings=64,
        hidden_activation="gelu_pytorch_tanh")
    torch.manual_seed(10)
    model = transformers.GemmaForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    rng = np.random.default_rng(10)
    prompt = rng.integers(4, 128, size=(1, 6), dtype=np.int64)
    with torch.no_grad():
        want = model.generate(
            torch.tensor(prompt), max_new_tokens=8, do_sample=False,
            pad_token_id=0)[0, 6:].tolist()

    cache = init_cache(cfg, 1, 32, dtype=jnp.float32)
    logits, cache = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(prompt.astype(np.int32)),
        jnp.asarray([6], jnp.int32), cache)
    cur = int(np.argmax(np.asarray(logits)[0, 5]))
    got = [cur]
    for _ in range(7):
        logits, cache = jitted(transformer.decode_step)(
            params, cfg, jnp.asarray([[cur]], jnp.int32), cache)
        cur = int(np.argmax(np.asarray(logits)[0, 0]))
        got.append(cur)
    assert got == want


def test_gpt_neox_matches_hf():
    """GPT-NeoX/Pythia: parallel-residual blocks, per-head-interleaved
    fused QKV, partial rotary (rotary_pct), exact (erf) gelu, untied
    embed_out head."""
    import transformers
    torch_cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.25,
        use_parallel_residual=True, tie_word_embeddings=False)
    import torch
    torch.manual_seed(11)
    model = transformers.GPTNeoXForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.parallel_residual and cfg.rope_pct == 0.25
    assert cfg.activation == "gelu_exact"
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_gpt_neox_sequential_residual_matches_hf():
    """use_parallel_residual=False NeoX variants run the sequential
    two-residual block — the conversion must carry the flag through."""
    import transformers
    torch_cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.5,
        use_parallel_residual=False, tie_word_embeddings=False)
    import torch
    torch.manual_seed(12)
    model = transformers.GPTNeoXForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert not cfg.parallel_residual
    rng = np.random.default_rng(12)
    tokens = rng.integers(0, 128, size=(1, 9), dtype=np.int64)
    _check_model(model, tokens)


def test_phi_matches_hf():
    """Phi: parallel residual with ONE shared layernorm per block,
    partial rotary, biases everywhere including the untied lm_head."""
    import transformers
    torch_cfg = transformers.PhiConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=64, partial_rotary_factor=0.5,
        tie_word_embeddings=False)
    import torch
    torch.manual_seed(13)
    model = transformers.PhiForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.parallel_residual and cfg.shared_attn_mlp_norm
    assert cfg.lm_head_bias and "b" in params["lm_head"]
    assert "mlp_norm" not in params["layers"]
    rng = np.random.default_rng(13)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_falcon_mqa_matches_hf():
    """Falcon-7B layout: multi-query fused QKV (H query heads + 1 k +
    1 v), parallel residual, single shared norm, no biases, tied head."""
    import transformers
    torch_cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, multi_query=True,
        new_decoder_architecture=False, parallel_attn=True, bias=False,
        alibi=False, max_position_embeddings=64)
    import torch
    torch.manual_seed(14)
    model = transformers.FalconForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.num_kv_heads == 1 and cfg.parallel_residual
    assert cfg.shared_attn_mlp_norm
    rng = np.random.default_rng(14)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_falcon_new_arch_matches_hf():
    """Falcon new decoder architecture (40B/180B layout): grouped-KV
    fused QKV with ln_attn + ln_mlp parallel norms."""
    import transformers
    torch_cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_kv_heads=2,
        new_decoder_architecture=True, parallel_attn=True, bias=False,
        alibi=False, max_position_embeddings=64)
    import torch
    torch.manual_seed(15)
    model = transformers.FalconForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.num_kv_heads == 2 and not cfg.shared_attn_mlp_norm
    assert "mlp_norm" in params["layers"]
    rng = np.random.default_rng(15)
    tokens = rng.integers(0, 128, size=(1, 8), dtype=np.int64)
    _check_model(model, tokens)


def test_mpt_matches_hf():
    """MPT: ALiBi, straight-concat bias-free fused QKV, zero-bias
    layernorms, exact gelu, tied head."""
    import transformers
    torch_cfg = transformers.MptConfig(
        vocab_size=128, d_model=32, n_heads=4, n_layers=3, max_seq_len=64)
    import torch
    torch.manual_seed(24)
    model = transformers.MptForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.position_embedding == "alibi" and not cfg.attn_bias
    assert cfg.tie_word_embeddings
    rng = np.random.default_rng(24)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_mpt_unsupported_attn_options_rejected():
    import transformers
    torch_cfg = transformers.MptConfig(
        vocab_size=128, d_model=32, n_heads=4, n_layers=2,
        attn_config=dict(qk_ln=True))
    with pytest.raises(NotImplementedError, match="qk_ln"):
        convert.config_from_hf(torch_cfg)
    torch_cfg = transformers.MptConfig(
        vocab_size=128, d_model=36, n_heads=6, n_layers=2)
    with pytest.raises(NotImplementedError, match="power-of-two"):
        convert.config_from_hf(torch_cfg)


def test_unsupported_model_type_names_supported_families():
    """The unsupported-architecture error must enumerate what converts."""
    class FakeCfg:
        model_type = "mamba"
    with pytest.raises(NotImplementedError, match="gpt_neox"):
        convert.config_from_hf(FakeCfg())


def test_phi_decode_matches_hf_generate():
    """Greedy decode parity for the phi deltas (shared-norm parallel
    block + partial rotary on the decode path, biased head)."""
    import torch
    import transformers
    torch_cfg = transformers.PhiConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, partial_rotary_factor=0.5,
        tie_word_embeddings=False)
    torch.manual_seed(16)
    model = transformers.PhiForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    rng = np.random.default_rng(16)
    prompt = rng.integers(4, 128, size=(1, 6), dtype=np.int64)
    with torch.no_grad():
        want = model.generate(
            torch.tensor(prompt), max_new_tokens=8, do_sample=False,
            pad_token_id=0)[0, 6:].tolist()

    cache = init_cache(cfg, 1, 32, dtype=jnp.float32)
    logits, cache = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(prompt.astype(np.int32)),
        jnp.asarray([6], jnp.int32), cache)
    cur = int(np.argmax(np.asarray(logits)[0, 5]))
    got = [cur]
    for _ in range(7):
        logits, cache = jitted(transformer.decode_step)(
            params, cfg, jnp.asarray([[cur]], jnp.int32), cache)
        cur = int(np.argmax(np.asarray(logits)[0, 0]))
        got.append(cur)
    assert got == want


def test_bloom_matches_hf():
    """BLOOM: ALiBi position bias, layernormed embedding output, per-head
    interleaved fused QKV, tied head."""
    import transformers
    torch_cfg = transformers.BloomConfig(
        vocab_size=128, hidden_size=32, n_layer=3, n_head=4,
        layer_norm_epsilon=1e-5)
    import torch
    torch.manual_seed(17)
    model = transformers.BloomForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.position_embedding == "alibi" and cfg.embed_norm
    assert "norm" in params["embed"]
    rng = np.random.default_rng(17)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_bloom_nonpow2_heads_matches_hf():
    """ALiBi slope interpolation for non-power-of-two head counts must
    match HF's build_alibi_tensor exactly."""
    import transformers
    torch_cfg = transformers.BloomConfig(
        vocab_size=128, hidden_size=36, n_layer=2, n_head=6)
    import torch
    torch.manual_seed(18)
    model = transformers.BloomForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(18)
    tokens = rng.integers(0, 128, size=(1, 9), dtype=np.int64)
    _check_model(model, tokens)


def test_bloom_decode_matches_hf_generate():
    """Greedy decode parity for ALiBi: the bias must track the query's
    absolute position on the cached decode path too."""
    import torch
    import transformers
    torch_cfg = transformers.BloomConfig(
        vocab_size=128, hidden_size=32, n_layer=2, n_head=4)
    torch.manual_seed(19)
    model = transformers.BloomForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    rng = np.random.default_rng(19)
    prompt = rng.integers(4, 128, size=(1, 6), dtype=np.int64)
    with torch.no_grad():
        want = model.generate(
            torch.tensor(prompt), max_new_tokens=8, do_sample=False,
            pad_token_id=0)[0, 6:].tolist()

    cache = init_cache(cfg, 1, 32, dtype=jnp.float32)
    logits, cache = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(prompt.astype(np.int32)),
        jnp.asarray([6], jnp.int32), cache)
    cur = int(np.argmax(np.asarray(logits)[0, 5]))
    got = [cur]
    for _ in range(7):
        logits, cache = jitted(transformer.decode_step)(
            params, cfg, jnp.asarray([[cur]], jnp.int32), cache)
        cur = int(np.argmax(np.asarray(logits)[0, 0]))
        got.append(cur)
    assert got == want


def test_gptj_matches_hf():
    """GPT-J: interleaved (rotate_every_two) partial rotary, parallel
    residual with one shared norm, biased MLP + untied biased head."""
    import transformers
    torch_cfg = transformers.GPTJConfig(
        vocab_size=128, n_embd=32, n_layer=3, n_head=4, rotary_dim=4,
        n_positions=64, tie_word_embeddings=False)
    import torch
    torch.manual_seed(20)
    model = transformers.GPTJForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.rope_interleaved and cfg.rope_pct == 0.5  # 4 of 8 dims
    assert cfg.parallel_residual and cfg.shared_attn_mlp_norm
    assert "b" in params["lm_head"]
    rng = np.random.default_rng(20)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_falcon_rw_alibi_matches_hf():
    """The Falcon-RW layout: per-head fused QKV, SEQUENTIAL residual
    (parallel_attn=False), ALiBi positions."""
    import transformers
    torch_cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, multi_query=False,
        new_decoder_architecture=False, parallel_attn=False, bias=True,
        alibi=True, max_position_embeddings=64)
    import torch
    torch.manual_seed(22)
    model = transformers.FalconForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.position_embedding == "alibi"
    assert not cfg.parallel_residual and "mlp_norm" in params["layers"]
    rng = np.random.default_rng(22)
    tokens = rng.integers(0, 128, size=(1, 9), dtype=np.int64)
    _check_model(model, tokens)


def test_alibi_paged_serving_matches_engine():
    """ALiBi through the SERVING path: the continuous batcher's paged
    prefill + chunked decode must reproduce the engine's greedy tokens
    (the bias rides q/kv positions, so block-table indirection must not
    disturb it)."""
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)
    torch_cfg = transformers.BloomConfig(
        vocab_size=128, hidden_size=32, n_layer=2, n_head=4)
    torch.manual_seed(23)
    model = transformers.BloomForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32", attn_backend="xla")
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, 128, size=11).tolist()

    eng = InferenceEngine(cfg, params, max_seq=64)
    want = eng.generate([prompt], max_new_tokens=10,
                        sampling=SamplingParams.greedy()).tokens[0]

    b = ContinuousBatcher(cfg, params, num_blocks=32, block_size=8,
                          slots=2, max_seq=64, seed=0)
    r = b.submit(prompt, max_new_tokens=10,
                 sampling=SamplingParams.greedy())
    for _ in range(40):
        b.step()
        if r.done.is_set():
            break
    assert r.wait() == want, (r.tokens, want)


def test_qwen2_mixed_window_rejected():
    """Qwen2's layer-indexed sliding window (full attention below
    max_window_layers) is not representable by the global
    cfg.sliding_window — conversion must refuse, not silently window
    every layer."""
    import transformers
    torch_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        use_sliding_window=True, sliding_window=8, max_window_layers=2)
    with pytest.raises(NotImplementedError, match="max_window_layers"):
        convert.config_from_hf(torch_cfg)
    # ...but the two exactly-representable shapes convert
    all_win = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        use_sliding_window=True, sliding_window=8, max_window_layers=0)
    assert convert.config_from_hf(all_win).sliding_window == 8
    none_win = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        use_sliding_window=True, sliding_window=8, max_window_layers=4)
    assert convert.config_from_hf(none_win).sliding_window is None


def test_gpt_bigcode_mqa_matches_hf():
    """StarCoder layout: MQA (1 kv head) + learned positions + fused
    nn.Linear c_attn — paths the other 14 families don't combine."""
    import torch
    import transformers
    torch_cfg = transformers.GPTBigCodeConfig(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=3, n_head=4,
        multi_query=True, activation_function="gelu_pytorch_tanh")
    torch.manual_seed(11)
    model = transformers.GPTBigCodeForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, 128, size=(2, 12), dtype=np.int64)
    _check_model(model, tokens)


def test_gpt_bigcode_mha_matches_hf():
    import torch
    import transformers
    torch_cfg = transformers.GPTBigCodeConfig(
        vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        multi_query=False)
    torch.manual_seed(12)
    model = transformers.GPTBigCodeForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(12)
    tokens = rng.integers(0, 96, size=(1, 9), dtype=np.int64)
    _check_model(model, tokens)


def test_stablelm_matches_hf():
    """StableLM: llama layout with biased layernorms + partial rotary +
    qkv-only bias."""
    import torch
    import transformers
    torch_cfg = transformers.StableLmConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, partial_rotary_factor=0.5,
        use_qkv_bias=True, tie_word_embeddings=False)
    torch.manual_seed(13)
    model = transformers.StableLmForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(13)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_stablelm_unsupported_options_rejected():
    import transformers
    import pytest as _pytest
    cfg = transformers.StableLmConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=4,
        use_parallel_residual=True)
    with _pytest.raises(NotImplementedError, match="parallel_residual"):
        convert.config_from_hf(cfg)
    cfg2 = transformers.StableLmConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=4, qk_layernorm=True)
    with _pytest.raises(NotImplementedError, match="qk_layernorm"):
        convert.config_from_hf(cfg2)


def test_codegen_matches_hf():
    """CodeGen: GPT-J topology via a DIFFERENT fused-QKV layout (mp_num=4
    TP blocks, q|v|k order within each block) + partial interleaved
    rotary."""
    import torch
    import transformers
    torch_cfg = transformers.CodeGenConfig(
        vocab_size=128, n_positions=64, n_ctx=64, n_embd=32, n_layer=3,
        n_head=4, rotary_dim=4, activation_function="gelu_new",
        tie_word_embeddings=False)
    torch.manual_seed(14)
    model = transformers.CodeGenForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(14)
    tokens = rng.integers(0, 128, size=(2, 11), dtype=np.int64)
    _check_model(model, tokens)


def test_codegen_head_divisibility_rejected():
    import transformers
    import pytest as _pytest
    cfg = transformers.CodeGenConfig(
        vocab_size=64, n_positions=64, n_embd=30, n_layer=1, n_head=6,
        rotary_dim=4)
    with _pytest.raises(NotImplementedError, match="mp_num"):
        convert.config_from_hf(cfg)


def test_starcoder2_matches_hf():
    """StarCoder2: llama layer names with biased layernorms, biased
    linears and a plain (non-gated) tanh-gelu c_fc/c_proj MLP."""
    import torch
    import transformers
    torch_cfg = transformers.Starcoder2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, use_bias=True, sliding_window=None,
        tie_word_embeddings=True)
    torch.manual_seed(15)
    model = transformers.Starcoder2ForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(15)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_olmo_matches_hf():
    """OLMo: llama layout with NON-PARAMETRIC layernorms (converted to
    unit-scale/zero-bias leaves)."""
    import torch
    import transformers
    torch_cfg = transformers.OlmoConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, clip_qkv=None,
        tie_word_embeddings=False)
    torch.manual_seed(16)
    model = transformers.OlmoForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(16)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_olmo_clip_qkv_rejected():
    import transformers
    import pytest as _pytest
    cfg = transformers.OlmoConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=4, clip_qkv=8.0)
    with _pytest.raises(NotImplementedError, match="clip_qkv"):
        convert.config_from_hf(cfg)


def test_phi3_matches_hf():
    """Phi-3: llama semantics with fused qkv_proj and gate_up_proj rows
    split at conversion."""
    import torch
    import transformers
    torch_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=None,
        pad_token_id=0, tie_word_embeddings=False)
    torch.manual_seed(17)
    model = transformers.Phi3ForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(17)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_phi3_longrope_matches_hf():
    """Phi-3.5 longrope (previously refused): the static conversion
    picks the LONG factor set + attention factor when the checkpoint
    advertises an extended window — exact HF parity for sequences past
    original_max_position_embeddings (where HF also uses the long set).
    Sequence length 24 > original 16 here."""
    import torch
    import transformers
    torch_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, original_max_position_embeddings=16,
        rope_scaling={"type": "longrope",
                      "short_factor": [1.0, 1.1, 1.2, 1.3],
                      "long_factor": [1.5, 2.0, 3.0, 4.0]},
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(56)
    model = transformers.Phi3ForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.rope_inv_freq is not None and len(cfg.rope_inv_freq) == 4
    assert cfg.rope_attn_factor > 1.0
    rng = np.random.default_rng(56)
    tokens = rng.integers(0, 128, size=(1, 24), dtype=np.int64)
    _check_model(model, tokens)


def test_gpt_neo_matches_hf():
    """GPT-Neo: UNSCALED attention (sqrt(hd) folded into q at conversion)
    + alternating global/local-window layers via the per-layer traced
    ``attn_window`` leaf. window_size=8 < seq so the local mask binds."""
    import torch
    import transformers
    torch_cfg = transformers.GPTNeoConfig(
        vocab_size=128, max_position_embeddings=64, hidden_size=32,
        num_layers=4, attention_types=[[["global", "local"], 2]],
        num_heads=4, window_size=8)
    torch.manual_seed(18)
    model = transformers.GPTNeoForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(18)
    tokens = rng.integers(0, 128, size=(2, 14), dtype=np.int64)
    _check_model(model, tokens)


def test_gpt_neo_all_global_matches_hf():
    """All-global GPT-Neo converts WITHOUT attn_windows (uniform path)."""
    import torch
    import transformers
    torch_cfg = transformers.GPTNeoConfig(
        vocab_size=96, max_position_embeddings=64, hidden_size=32,
        num_layers=2, attention_types=[[["global"], 2]], num_heads=4)
    cfg = convert.config_from_hf(torch_cfg)
    assert cfg.attn_windows is None
    torch.manual_seed(19)
    model = transformers.GPTNeoForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(19)
    tokens = rng.integers(0, 96, size=(1, 9), dtype=np.int64)
    _check_model(model, tokens)


def test_gpt_neo_decode_matches_hf_generate():
    """Greedy decode through the engine (cached attend_decode with the
    traced per-layer window) vs HF generate."""
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)
    torch_cfg = transformers.GPTNeoConfig(
        vocab_size=128, max_position_embeddings=64, hidden_size=32,
        num_layers=4, attention_types=[[["global", "local"], 2]],
        num_heads=4, window_size=8)
    torch.manual_seed(20)
    model = transformers.GPTNeoForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")
    rng = np.random.default_rng(20)
    prompt = rng.integers(0, 128, size=12).tolist()
    eng = InferenceEngine(cfg, params, max_seq=40)
    ours = eng.generate([prompt], max_new_tokens=16,
                        sampling=SamplingParams.greedy()).tokens[0]
    with torch.no_grad():
        ref = model.generate(torch.tensor([prompt]), max_new_tokens=16,
                             do_sample=False)
    assert ours == ref[0, len(prompt):].tolist()


def test_gpt_neo_paged_serving_matches_engine():
    """Per-layer windows through the SERVING path: paged prefill +
    chunked decode reproduce the engine's greedy tokens (the window mask
    rides q/kv positions, so block-table indirection must not disturb
    it — and decode must keep attending far-back pool blocks on GLOBAL
    layers while masking them on local ones)."""
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)
    torch_cfg = transformers.GPTNeoConfig(
        vocab_size=128, max_position_embeddings=64, hidden_size=32,
        num_layers=4, attention_types=[[["global", "local"], 2]],
        num_heads=4, window_size=8)
    torch.manual_seed(21)
    model = transformers.GPTNeoForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32", attn_backend="xla")
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, 128, size=11).tolist()

    eng = InferenceEngine(cfg, params, max_seq=64)
    want = eng.generate([prompt], max_new_tokens=12,
                        sampling=SamplingParams.greedy()).tokens[0]

    b = ContinuousBatcher(cfg, params, num_blocks=32, block_size=8,
                          slots=2, max_seq=64, seed=0)
    r = b.submit(prompt, max_new_tokens=12,
                 sampling=SamplingParams.greedy())
    for _ in range(40):
        b.step()
        if r.done.is_set():
            break
    assert r.wait() == want, (r.tokens, want)


def test_gemma2_matches_hf():
    """Gemma-2: sandwich norms (post_block_norms), attention + final
    logit softcapping, query_pre_attn_scalar folded into q, alternating
    sliding/full layers, explicit head_dim != hidden/heads, (1+w) norm
    absorb, sqrt(D) embed scale. Window 8 < seq so the sliding mask
    binds; qpas=32 != head_dim=16 so the scale fold binds."""
    import torch
    import transformers
    torch_cfg = transformers.Gemma2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, sliding_window=8,
        query_pre_attn_scalar=32, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0, pad_token_id=0,
        tie_word_embeddings=True)
    torch.manual_seed(24)
    model = transformers.Gemma2ForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(24)
    tokens = rng.integers(0, 128, size=(2, 14), dtype=np.int64)
    _check_model(model, tokens)


def test_gemma2_decode_matches_hf_stepwise():
    """Greedy decode through the engine: softcaps + alternating windows
    through the cached path. Compared against HF run FULL-CONTEXT each
    step (not HF generate: its HybridCache decode reorders fp ops and the
    final softcap squashes logits into +-cap, so exact-tie flips between
    HF's own cached and uncached paths are expected — observed 8e-3 logit
    gaps flipping argmax; our full-context logits match HF's to 0.0)."""
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)
    torch_cfg = transformers.Gemma2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, sliding_window=8,
        query_pre_attn_scalar=32, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0, pad_token_id=0,
        tie_word_embeddings=True)
    torch.manual_seed(25)
    model = transformers.Gemma2ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")
    rng = np.random.default_rng(25)
    prompt = rng.integers(0, 128, size=12).tolist()
    eng = InferenceEngine(cfg, params, max_seq=40)
    ours = eng.generate([prompt], max_new_tokens=14,
                        sampling=SamplingParams.greedy()).tokens[0]
    seq = list(prompt)
    for got in ours:
        with torch.no_grad():
            hl = model(torch.tensor([seq])).logits[0, -1].float().numpy()
        want = int(hl.argmax())
        # accept either side of an exact near-tie (the cached engine path
        # reorders fp like HF's cache does); anything beyond tie range is
        # a real bug
        assert got == want or hl[want] - hl[got] < 2e-2, (
            seq, got, want, hl[want] - hl[got])
        seq.append(got)


def test_cohere_matches_hf():
    """Cohere: shared bias-free layernorm parallel residual, INTERLEAVED
    rotary, tied head with constant logit scale."""
    import torch
    import transformers
    torch_cfg = transformers.CohereConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, logit_scale=0.25, pad_token_id=0,
        tie_word_embeddings=True)
    torch.manual_seed(26)
    model = transformers.CohereForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(26)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_cohere_qk_norm_matches_hf():
    """Command-R+ use_qk_norm: bias-free per-head layernorm on q/k with
    DISTINCT [H, hd] scales (qk_norm="ln_head")."""
    import torch
    import transformers
    torch_cfg = transformers.CohereConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, logit_scale=0.25, use_qk_norm=True,
        pad_token_id=0, tie_word_embeddings=True)
    torch.manual_seed(27)
    model = transformers.CohereForCausalLM(torch_cfg).eval()
    # random-init layernorm scales are all-ones — perturb them so the
    # test distinguishes per-head scales from a shared one
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.self_attn.q_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.q_norm.weight) + 0.5)
            lyr.self_attn.k_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.k_norm.weight) + 0.5)
    rng = np.random.default_rng(27)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_qwen3_matches_hf():
    """Qwen3: llama layout + per-head RMS q/k norms (shared [head_dim]
    scale) + head_dim decoupled from hidden//heads."""
    import torch
    import transformers
    torch_cfg = transformers.Qwen3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        tie_word_embeddings=False)
    torch.manual_seed(28)
    model = transformers.Qwen3ForCausalLM(torch_cfg).eval()
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.self_attn.q_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.q_norm.weight) + 0.5)
            lyr.self_attn.k_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.k_norm.weight) + 0.5)
    rng = np.random.default_rng(28)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_qwen3_mixed_sliding_windows_match_hf():
    """Qwen3 with MIXED sliding/full layer_types (use_sliding_window +
    max_window_layers < num_layers): the per-layer windows must ride the
    param tree as the stacked attn_window leaf — the qwen3 config branch
    reuses the llama state-dict path, which emits no per-layer leaves of
    its own, so a missing generic emission silently ran every layer
    global (seq > window here, so that bug shifts logits by ~0.17)."""
    import torch
    import transformers
    torch_cfg = transformers.Qwen3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, tie_word_embeddings=False,
        use_sliding_window=True, sliding_window=4, max_window_layers=1)
    assert len(set(torch_cfg.layer_types)) == 2  # genuinely mixed
    torch.manual_seed(29)
    model = transformers.Qwen3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.attn_windows is not None and cfg.sliding_window is None
    assert "attn_window" in params["layers"]
    rng = np.random.default_rng(29)
    tokens = rng.integers(0, 128, size=(2, 12), dtype=np.int64)  # 12 > 4
    _check_model(model, tokens)


def test_qwen3_moe_matches_hf():
    """Qwen3-MoE: qwen3 attention + mixtral-convention router
    (softmax -> top-k -> renormalize; norm_topk_prob=True)."""
    import torch
    import transformers
    torch_cfg = transformers.Qwen3MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=48, num_experts=4, num_experts_per_tok=2,
        norm_topk_prob=True, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, max_position_embeddings=64,
        mlp_only_layers=[], decoder_sparse_step=1,
        tie_word_embeddings=False)
    torch.manual_seed(29)
    model = transformers.Qwen3MoeForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(29)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens, atol=4e-3)


def test_granite_matches_hf():
    """Granite: llama layout + the four scalar multipliers (embedding,
    attention, residual, logits_scaling) absorbed into existing fields."""
    import torch
    import transformers
    torch_cfg = transformers.GraniteConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, embedding_multiplier=6.0,
        attention_multiplier=0.31, residual_multiplier=0.22,
        logits_scaling=4.0, tie_word_embeddings=False)
    torch.manual_seed(30)
    model = transformers.GraniteForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(30)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_olmo2_matches_hf():
    """OLMo-2: post-sublayer norms only (x + norm(f(x))) and full-width
    RMS q/k norms on the projections."""
    import torch
    import transformers
    torch_cfg = transformers.Olmo2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False)
    torch.manual_seed(31)
    model = transformers.Olmo2ForCausalLM(torch_cfg).eval()
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.self_attn.q_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.q_norm.weight) + 0.5)
            lyr.self_attn.k_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.k_norm.weight) + 0.5)
    rng = np.random.default_rng(31)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_glm_matches_hf():
    """GLM: interleaved PARTIAL rotary (gpt-j pairing over the first
    half of head_dim), fused gate_up split, qkv bias without o bias."""
    import torch
    import transformers
    torch_cfg = transformers.GlmConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, partial_rotary_factor=0.5, attention_bias=True,
        max_position_embeddings=64, pad_token_id=0,
        tie_word_embeddings=False)
    torch.manual_seed(32)
    model = transformers.GlmForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(32)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_glm4_matches_hf():
    """GLM-4: glm plus sandwich post norms (post_self_attn/post_mlp ->
    post_block_norms)."""
    import torch
    import transformers
    torch_cfg = transformers.Glm4Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, partial_rotary_factor=0.5, attention_bias=True,
        max_position_embeddings=64, pad_token_id=0,
        tie_word_embeddings=False)
    torch.manual_seed(33)
    model = transformers.Glm4ForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(33)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_nemotron_matches_hf():
    """Nemotron: LayerNorm1P ((1+w) absorbed), squared-ReLU ungated MLP,
    partial non-interleaved rotary."""
    import torch
    import transformers
    torch_cfg = transformers.NemotronConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        partial_rotary_factor=0.5, max_position_embeddings=64,
        tie_word_embeddings=False)
    torch.manual_seed(34)
    model = transformers.NemotronForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(34)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def _deepseek_cfg(**kw):
    import transformers
    base = dict(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, head_dim=8,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        n_group=4, topk_group=2, routed_scaling_factor=2.5,
        norm_topk_prob=True, first_k_dense_replace=0,
        max_position_embeddings=64, rope_scaling=None,
        tie_word_embeddings=False, pad_token_id=0)
    base.update(kw)
    return transformers.DeepseekV3Config(**base)


def test_deepseek_v3_dense_mla_matches_hf():
    """DeepSeek-V3 multi-head latent attention, all-dense MLP layers
    (first_k_dense_replace >= num_layers). Exercises the low-rank q/kv
    bottlenecks with mid-stack RMSNorms, the [rope|nope] head-dim
    permutation, the shared (MQA-style) rope head, interleaved rope, and
    the v_head_dim < qk_head_dim zero-padding."""
    import torch
    import transformers
    torch_cfg = _deepseek_cfg(first_k_dense_replace=3)
    torch.manual_seed(40)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.mla and cfg.num_experts == 0
    assert cfg.qk_head_dim == 24 and cfg.head_dim == 8   # the rope head
    assert cfg.v_head_dim == 12
    rng = np.random.default_rng(40)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_deepseek_v3_no_q_lora_matches_hf():
    """q_lora_rank=None: full-rank q projection path."""
    import torch
    import transformers
    torch_cfg = _deepseek_cfg(first_k_dense_replace=3, q_lora_rank=None)
    torch.manual_seed(41)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    rng = np.random.default_rng(41)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_deepseek_v3_moe_matches_hf():
    """All-MoE layers: sigmoid scores, e_score_correction_bias-ranked
    group-limited top-k (selection bias only — weights are the unbiased
    scores), renormalized, routed_scaling_factor, plus the always-active
    shared-experts MLP."""
    import torch
    import transformers
    torch_cfg = _deepseek_cfg()
    torch.manual_seed(42)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    # non-zero correction bias so the selection-vs-weight distinction is
    # actually exercised (the buffer inits to zeros)
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.mlp.gate.e_score_correction_bias.uniform_(0.0, 0.2)
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.moe_router == "deepseek_v3" and cfg.moe_shared_experts == 1
    rng = np.random.default_rng(42)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_deepseek_v3_mixed_stack_refuses_pp():
    """The GPipe stage split assumes one uniformly-stacked layer tree;
    a mixed stack under pp is refused at plan time with a named error
    (parallel/mesh.validate_spec)."""
    import transformers
    from distributed_llm_inferencing_tpu.parallel.mesh import (
        MeshSpec, validate_spec)
    cfg = convert.config_from_hf(_deepseek_cfg(
        first_k_dense_replace=1, num_hidden_layers=4))
    with pytest.raises(NotImplementedError, match="mixed dense/MoE"):
        validate_spec(MeshSpec(pp=2), cfg)
    validate_spec(MeshSpec(tp=2, ep=2), cfg)   # tp/ep compose fine


def test_deepseek_v3_decode_and_batcher_match_hf_generate():
    """MLA through the REAL serving paths: greedy decode via the engine's
    dense cache AND via the paged continuous batcher ≡ HF generate.
    Exercises cached k (with the shared rope head materialized per head),
    the zero-padded v riding the caches, and the deepseek MoE router
    under single-token decode shapes."""
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)

    torch_cfg = _deepseek_cfg()
    torch.manual_seed(43)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    rng = np.random.default_rng(43)
    prompt = rng.integers(0, 128, 8).tolist()
    with torch.no_grad():
        want = model.generate(
            torch.tensor([prompt]), max_new_tokens=10, do_sample=False,
            pad_token_id=0)[0, 8:].tolist()

    eng = InferenceEngine(cfg, max_seq=32, seed=0, params=params)
    got = eng.generate([prompt], max_new_tokens=10,
                       sampling=SamplingParams.greedy()).tokens[0]
    assert got == want

    b = ContinuousBatcher(cfg, num_blocks=16, block_size=8, slots=2,
                          max_seq=32, seed=0, params=params)
    r = b.submit(prompt, max_new_tokens=10,
                 sampling=SamplingParams.greedy())
    while b.step():
        pass
    assert r.error is None and r.tokens == want


def test_deepseek_v3_yarn_rope_scaling_matches_hf():
    """Yarn context extension: NTK-by-part interpolated rope ladder
    (cfg.rope_inv_freq), the attention_factor on cos/sin, AND the
    separate mscale_all_dim uniform score multiplier (folded into the q
    weights via the query_pre_attn_scalar absorption). mscale !=
    mscale_all_dim so both mechanisms are exercised; seq length runs
    past original_max_position_embeddings so the extension bites."""
    import torch
    import transformers
    torch_cfg = _deepseek_cfg(
        first_k_dense_replace=3,
        rope_scaling={"rope_type": "yarn", "factor": 4.0,
                      "original_max_position_embeddings": 16,
                      "beta_fast": 32, "beta_slow": 1,
                      "mscale": 0.8, "mscale_all_dim": 1.2})
    torch.manual_seed(44)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.rope_inv_freq is not None and len(cfg.rope_inv_freq) == 4
    assert cfg.rope_attn_factor != 1.0
    assert cfg.query_pre_attn_scalar is not None
    rng = np.random.default_rng(44)
    tokens = rng.integers(0, 128, size=(1, 24), dtype=np.int64)
    _check_model(model, tokens)


def test_deepseek_v3_mixed_dense_moe_matches_hf():
    """The SHIPPED DeepSeek layout: first_k_dense_replace dense-MLP
    layers ahead of the MoE tail. The param tree carries the prefix as
    its own stacked segment (layers_dense) and the layer scans run the
    two segments back to back (transformer.layer_segments)."""
    import torch
    import transformers
    torch_cfg = _deepseek_cfg(first_k_dense_replace=1)
    torch.manual_seed(45)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.dense_prefix_layers == 1 and cfg.num_experts == 8
    assert cfg.intermediate_size == 64 and cfg.moe_intermediate_size == 16
    assert "layers_dense" in params
    assert params["layers_dense"]["up"]["w"].shape == (1, 32, 64)
    assert params["layers"]["experts"]["up"]["w"].shape == (2, 8, 32, 16)
    rng = np.random.default_rng(45)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_deepseek_v3_mixed_decode_and_batcher_match_hf_generate():
    """Mixed stack through the real serving paths: greedy decode via the
    engine (dense cache) and via the
    paged continuous batcher, both ≡ HF generate."""
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)

    torch_cfg = _deepseek_cfg(first_k_dense_replace=1)
    torch.manual_seed(46)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    rng = np.random.default_rng(46)
    prompt = rng.integers(0, 128, 8).tolist()
    with torch.no_grad():
        want = model.generate(
            torch.tensor([prompt]), max_new_tokens=10, do_sample=False,
            pad_token_id=0)[0, 8:].tolist()

    eng = InferenceEngine(cfg, max_seq=32, seed=0, params=params)
    got = eng.generate([prompt], max_new_tokens=10,
                       sampling=SamplingParams.greedy()).tokens[0]
    assert got == want

    b = ContinuousBatcher(cfg, num_blocks=16, block_size=8, slots=2,
                          max_seq=32, seed=0, params=params)
    r = b.submit(prompt, max_new_tokens=10,
                 sampling=SamplingParams.greedy())
    while b.step():
        pass
    assert r.error is None and r.tokens == want


def test_llama31_rope_scaling_matches_hf():
    """Llama 3.1+ ships rope_scaling rope_type="llama3" (NTK-by-part
    smoothing); before cfg.rope_inv_freq existed this was silently
    IGNORED, corrupting every position past the unscaled ladder's
    wavelengths. Parity at sequence lengths where the smoothing bites."""
    import torch
    import transformers
    torch_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 16},
        tie_word_embeddings=False, attention_bias=False)
    torch.manual_seed(47)
    model = transformers.LlamaForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.rope_inv_freq is not None and len(cfg.rope_inv_freq) == 4
    rng = np.random.default_rng(47)
    tokens = rng.integers(0, 128, size=(1, 40), dtype=np.int64)
    _check_model(model, tokens)


def test_qwen2_linear_rope_scaling_matches_hf():
    """Position-interpolation ("linear") scaling: uniform /factor."""
    import torch
    import transformers
    torch_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
        rope_scaling={"rope_type": "linear", "factor": 4.0},
        tie_word_embeddings=False)
    torch.manual_seed(48)
    model = transformers.Qwen2ForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.rope_inv_freq is not None
    rng = np.random.default_rng(48)
    tokens = rng.integers(0, 128, size=(1, 24), dtype=np.int64)
    _check_model(model, tokens)


def test_unknown_rope_scaling_refused():
    import transformers
    torch_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        rope_scaling={"rope_type": "dynamic", "factor": 2.0})
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        convert.config_from_hf(torch_cfg)


def test_deepseek_v3_mixed_stack_with_yarn_matches_hf():
    """The shipped 671B combination: mixed dense-prefix/MoE-tail stack
    WITH yarn (the q-weight mscale fold must land in BOTH segments'
    q projections, and the scaled rope ladder rides every layer)."""
    import torch
    import transformers
    torch_cfg = _deepseek_cfg(
        first_k_dense_replace=1,
        rope_scaling={"rope_type": "yarn", "factor": 4.0,
                      "original_max_position_embeddings": 16,
                      "mscale": 1.0, "mscale_all_dim": 1.0})
    torch.manual_seed(49)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.dense_prefix_layers == 1 and cfg.rope_inv_freq is not None
    assert cfg.query_pre_attn_scalar is not None  # mscale fold active
    rng = np.random.default_rng(49)
    tokens = rng.integers(0, 128, size=(1, 24), dtype=np.int64)
    _check_model(model, tokens)


def test_ernie45_matches_hf():
    """ERNIE 4.5 dense: llama layout, one use_bias switch on every
    linear, explicit head_dim decoupled from hidden/heads."""
    import torch
    import transformers
    torch_cfg = transformers.Ernie4_5Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, use_bias=True, max_position_embeddings=64,
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(50)
    model = transformers.Ernie4_5ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.attn_bias and cfg.mlp_bias and "b" in params["layers"]["o"]
    rng = np.random.default_rng(50)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_smollm3_nope_layers_match_hf():
    """SmolLM3: per-layer NoPE (no_rope_layers) — the rope_on leaf must
    disable rotation exactly on the flagged layers."""
    import torch
    import transformers
    torch_cfg = transformers.SmolLM3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        no_rope_layers=[1, 1, 1, 0], no_rope_layer_interval=4,
        max_position_embeddings=64, tie_word_embeddings=False,
        pad_token_id=0)
    torch.manual_seed(51)
    model = transformers.SmolLM3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.rope_layers == (1, 1, 1, 0)
    assert "rope_on" in params["layers"]
    rng = np.random.default_rng(51)
    tokens = rng.integers(0, 128, size=(2, 12), dtype=np.int64)
    _check_model(model, tokens)


def test_hunyuan_dense_post_rope_qk_norm_matches_hf():
    """HunYuan-Dense: shared [head_dim] q/k RMS norms applied AFTER
    RoPE (query_layernorm/key_layernorm; qwen3 norms before)."""
    import torch
    import transformers
    torch_cfg = transformers.HunYuanDenseV1Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=64, tie_word_embeddings=False,
        pad_token_id=0)
    torch.manual_seed(52)
    model = transformers.HunYuanDenseV1ForCausalLM(torch_cfg).eval()
    with torch.no_grad():   # distinguish the norms from identity
        for lyr in model.model.layers:
            lyr.self_attn.query_layernorm.weight.mul_(
                torch.rand_like(lyr.self_attn.query_layernorm.weight) + 0.5)
            lyr.self_attn.key_layernorm.weight.mul_(
                torch.rand_like(lyr.self_attn.key_layernorm.weight) + 0.5)
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.qk_norm == "rms_head" and cfg.qk_norm_after_rope
    rng = np.random.default_rng(52)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_exaone4_hybrid_matches_hf():
    """EXAONE 4.0: sublayer-postnorm topology (x + norm(f(x))), shared
    [head_dim] q/k norms, hybrid attention — sliding layers rotate,
    full-attention layers are NoPE — with per-layer windows. Sequence
    longer than the window so both mechanisms bite."""
    import torch
    import transformers
    torch_cfg = transformers.Exaone4Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=4, sliding_window_pattern=4,
        max_position_embeddings=64, tie_word_embeddings=False,
        pad_token_id=0)
    torch.manual_seed(53)
    model = transformers.Exaone4ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.sublayer_postnorm_only and cfg.qk_norm == "rms_head"
    assert cfg.rope_layers is not None and 0 in cfg.rope_layers
    assert cfg.attn_windows is not None
    rng = np.random.default_rng(53)
    tokens = rng.integers(0, 128, size=(1, 12), dtype=np.int64)
    _check_model(model, tokens)


def test_dbrx_matches_hf():
    """DBRX: fused-Wqkv pre-LN block with the clip_qkv activation clamp,
    bias-free LayerNorms, and a fused-GLU MoE whose router renormalizes
    top-k softmax weights by L1 (p=1). top_k=2 of 4 experts here."""
    import torch
    import transformers
    torch_cfg = transformers.DbrxConfig(
        vocab_size=128, d_model=32, n_heads=4, n_layers=3, max_seq_len=64,
        attn_config={"kv_n_heads": 2, "clip_qkv": 0.5,
                     "rope_theta": 10000.0},
        ffn_config={"ffn_hidden_size": 16, "moe_num_experts": 4,
                    "moe_top_k": 2, "moe_normalize_expert_weights": 1.0},
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(54)
    model = transformers.DbrxForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.qkv_clip == 0.5 and cfg.num_experts == 4
    assert cfg.moe_norm_topk
    rng = np.random.default_rng(54)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_qwen3_moe_no_renorm_matches_hf():
    """qwen3_moe with norm_topk_prob=False (previously refused): the
    top-k softmax weights apply UNnormalized (cfg.moe_norm_topk)."""
    import torch
    import transformers
    torch_cfg = transformers.Qwen3MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        norm_topk_prob=False, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, max_position_embeddings=64,
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(55)
    model = transformers.Qwen3MoeForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert not cfg.moe_norm_topk
    rng = np.random.default_rng(55)
    tokens = rng.integers(0, 128, size=(2, 8), dtype=np.int64)
    _check_model(model, tokens)


def test_phi3_partial_rotary_longrope_matches_hf():
    """Phi-4-mini shape: partial_rotary_factor < 1 WITH longrope — the
    scaled ladder sizes to the partial dim and rope_pct keeps the
    rotated slice to the same width (full-width rotation would
    shape-mismatch the 6-entry ladder against 8-dim halves)."""
    import torch
    import transformers
    torch_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        partial_rotary_factor=0.75,
        max_position_embeddings=64, original_max_position_embeddings=16,
        rope_scaling={"type": "longrope",
                      "short_factor": [1.0] * 6,
                      "long_factor": [1.5, 2.0, 2.5, 3.0, 3.5, 4.0]},
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(57)
    model = transformers.Phi3ForCausalLM(torch_cfg).eval()
    cfg, _ = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.rope_pct == 0.75 and len(cfg.rope_inv_freq) == 6
    rng = np.random.default_rng(57)
    tokens = rng.integers(0, 128, size=(1, 24), dtype=np.int64)
    _check_model(model, tokens)


def test_longrope_without_original_attr_uses_short_and_rs_factor():
    """HF reads original_max_position_embeddings from the CONFIG
    attribute only; without it the short factors apply and the
    attention factor derives from rope_scaling['factor'] (mirrors
    modeling_rope_utils._compute_longrope_parameters)."""
    import math
    from types import SimpleNamespace
    hf = SimpleNamespace(
        rope_theta=10000.0, max_position_embeddings=64,
        rope_scaling={"type": "longrope", "factor": 4.0,
                      "short_factor": [1.0, 1.1, 1.2, 1.3],
                      "long_factor": [9.0] * 4})
    inv, attn, _ = convert._rope_scaling_params(hf, 8, "test")
    base = 10000.0 ** (np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(
        inv, 1.0 / (np.array([1.0, 1.1, 1.2, 1.3]) * base), rtol=1e-12)
    assert attn == pytest.approx(
        math.sqrt(1 + math.log(4.0) / math.log(64)))


def test_glm45_moe_matches_hf():
    """GLM-4.5 (glm4_moe): llama block + per-head q/k norms + partial
    half-split rotary + DeepSeek-V3's exact sigmoid group-limited
    routing with shared experts over a first_k_dense_replace mixed
    stack — every mechanism shared with existing families, composed."""
    from conftest import tiny_glm45_moe_model
    model = tiny_glm45_moe_model(seed=58)
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.moe_router == "deepseek_v3" and cfg.dense_prefix_layers == 1
    assert cfg.qk_norm == "rms_head" and cfg.rope_pct == 0.5
    assert "layers_dense" in params
    assert "bias" in params["layers"]["router"]
    rng = np.random.default_rng(58)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_ernie45_moe_matches_hf():
    """ERNIE 4.5 MoE: softmax routing with bias-corrected SELECTION
    (moe_statics.e_score_correction_bias — weights stay unbiased),
    shared experts, and a dense prefix (moe_layer_start_index) through
    the mixed-stack machinery."""
    import torch
    import transformers
    torch_cfg = transformers.Ernie4_5_MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, moe_num_experts=4, moe_k=2,
        moe_num_shared_experts=1, moe_layer_start_index=1,
        moe_layer_interval=1, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False,
        use_bias=True, pad_token_id=0)   # biases on EVERY linear incl.
    # the per-expert and shared-expert MLPs
    torch.manual_seed(59)
    model = transformers.Ernie4_5_MoeForCausalLM(torch_cfg).eval()
    with torch.no_grad():   # non-zero selection bias
        for lyr in model.model.layers:
            if hasattr(lyr.mlp, "moe_statics"):
                lyr.mlp.moe_statics.e_score_correction_bias.uniform_(
                    0.0, 0.3)
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.moe_router == "ernie" and cfg.dense_prefix_layers == 1
    assert cfg.moe_shared_experts == 1
    assert "bias" in params["layers"]["router"]
    rng = np.random.default_rng(59)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)


def test_gpt_oss_matches_hf():
    """gpt-oss: learned per-head attention sinks (virtual softmax
    column), clamped-swish expert GLU with per-expert biases,
    top-k-then-softmax routing, alternating sliding/full layers, and
    yarn rope with truncate=false. Sequence longer than the window and
    past the original rope window so everything bites."""
    import torch
    import transformers
    torch_cfg = transformers.GptOssConfig(
        vocab_size=128, hidden_size=32, intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, num_local_experts=4, num_experts_per_tok=2,
        sliding_window=4, layer_types=["sliding_attention",
                                       "full_attention"],
        max_position_embeddings=64,
        rope_scaling={"rope_type": "yarn", "factor": 4.0,
                      "beta_fast": 32.0, "beta_slow": 1.0,
                      "truncate": False,
                      "original_max_position_embeddings": 16},
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(60)
    model = transformers.GptOssForCausalLM(torch_cfg).eval()
    with torch.no_grad():   # non-trivial sinks (init may be empty/zeros)
        for lyr in model.model.layers:
            lyr.self_attn.sinks.normal_(0.0, 1.0)
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.attn_sinks and cfg.moe_router == "topk_softmax"
    assert cfg.moe_swiglu_limit == 7.0
    assert "sinks" in params["layers"]
    assert "b" in params["layers"]["experts"]["gate"]
    rng = np.random.default_rng(60)
    tokens = rng.integers(0, 128, size=(2, 20), dtype=np.int64)
    _check_model(model, tokens)


def test_gpt_oss_decode_and_batcher_match_hf_generate():
    """gpt-oss through the REAL serving paths: the sinks column must
    ride cached decode (dense engine) and the paged batcher's chunk and
    prefix formulations identically — greedy ≡ HF generate."""
    import torch
    from conftest import tiny_gpt_oss_model
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)
    model = tiny_gpt_oss_model(seed=61)
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    prompt = np.random.default_rng(61).integers(0, 128, 9).tolist()
    with torch.no_grad():
        want = model.generate(
            torch.tensor([prompt]), max_new_tokens=10, do_sample=False,
            pad_token_id=0)[0, 9:].tolist()

    eng = InferenceEngine(cfg, max_seq=32, seed=0, params=params)
    got = eng.generate([prompt], max_new_tokens=10,
                       sampling=SamplingParams.greedy()).tokens[0]
    assert got == want

    b = ContinuousBatcher(cfg, num_blocks=16, block_size=8, slots=2,
                          max_seq=32, seed=0, params=params)
    r = b.submit(prompt, max_new_tokens=10,
                 sampling=SamplingParams.greedy())
    while b.step():
        pass
    assert r.error is None and r.tokens == want


def test_hunyuan_moe_matches_hf():
    """HunYuan-MoE: post-RoPE q/k norms + mixtral-convention routing +
    an always-active shared MLP of the same intermediate width (router
    named mlp.gate.wg, shared weights under mlp.shared_mlp)."""
    import torch
    import transformers
    torch_cfg = transformers.HunYuanMoEV1Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_experts=4, moe_topk=2, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=64,
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(62)
    model = transformers.HunYuanMoEV1ForCausalLM(torch_cfg).eval()
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.self_attn.query_layernorm.weight.mul_(
                torch.rand_like(lyr.self_attn.query_layernorm.weight) + 0.5)
            lyr.self_attn.key_layernorm.weight.mul_(
                torch.rand_like(lyr.self_attn.key_layernorm.weight) + 0.5)
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    assert cfg.num_experts == 4 and cfg.moe_norm_topk
    assert cfg.moe_shared_experts == 1 and cfg.qk_norm_after_rope
    assert "shared_gate" in params["layers"]
    rng = np.random.default_rng(62)
    tokens = rng.integers(0, 128, size=(2, 10), dtype=np.int64)
    _check_model(model, tokens)
