"""MLA latent-cache serving (cfg.mla_latent_cache).

The latent formulation caches ONE shared [k_rot | c] row per token
(kv_lora_rank + qk_rope_head_dim wide) instead of materialized per-head
K/V, and decodes via the absorbed reassociation (scores q_nope·(W_uk c)
== (W_uk^T q_nope)·c; outputs W_uv (Σ w c)) — mathematically the same
attention, so these tests pin numerical equivalence against the
materialized path, HF greedy parity through the engine (which
auto-enables the latent layout on eligible meshes), and the cache-size
claim itself.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
from conftest import jitted


def _decode_logits(cfg, params, prompt, steps=6):
    """Prefill + greedy decode loop; returns stacked per-step logits."""
    B, S = prompt.shape
    cache = init_cache(cfg, B, 32, dtype=jnp.float32)
    logits, cache = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(prompt), jnp.full((B,), S, jnp.int32),
        cache)
    outs = [np.asarray(logits)[:, S - 1]]
    cur = jnp.argmax(logits[:, S - 1], axis=-1).astype(jnp.int32)
    for _ in range(steps):
        logits, cache = jitted(transformer.decode_step)(
            params, cfg, cur[:, None], cache)
        outs.append(np.asarray(logits)[:, 0])
        cur = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
    return np.stack(outs)


def test_latent_decode_matches_materialized():
    base = get_config("tiny-deepseek").replace(dtype="float32",
                                               attn_backend="xla")
    params = init_params(base, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = np.random.default_rng(0).integers(
        0, base.vocab_size, (2, 9)).astype(np.int32)
    dense = _decode_logits(base, params, prompt)
    latent = _decode_logits(base.replace(mla_latent_cache=True), params,
                            prompt)
    np.testing.assert_allclose(latent, dense, atol=2e-4, rtol=2e-4)


def test_latent_cache_is_smaller_by_the_claimed_ratio():
    cfg = get_config("deepseek-proxy").replace(dtype="float32")
    lat = cfg.replace(mla_latent_cache=True)
    dense_bytes = 2 * cfg.num_kv_heads * cfg.qk_head_dim
    latent_bytes = (lat.cache_head_dim + lat.cache_v_head_dim)
    assert lat.cache_kv_heads == 1 and lat.cache_v_head_dim == 0
    # deepseek-proxy: 2*16*96 / (128+32) = 19.2x
    assert dense_bytes / latent_bytes == pytest.approx(19.2)
    ck = init_cache(lat, 1, 64, dtype=jnp.float32)
    cd = init_cache(cfg, 1, 64, dtype=jnp.float32)
    ratio = (cd.k.size + cd.v.size) / (ck.k.size + ck.v.size)
    assert ratio == pytest.approx(19.2)


def test_engine_auto_enables_latent_and_matches_hf_generate():
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.models import convert
    torch_cfg = transformers.DeepseekV3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, head_dim=8, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, n_group=4,
        topk_group=2, routed_scaling_factor=2.5, first_k_dense_replace=1,
        max_position_embeddings=64, rope_scaling=None,
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(70)
    model = transformers.DeepseekV3ForCausalLM(torch_cfg).eval()
    cfg, params = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32")

    prompt = np.random.default_rng(70).integers(0, 128, 8).tolist()
    with torch.no_grad():
        want = model.generate(
            torch.tensor([prompt]), max_new_tokens=10, do_sample=False,
            pad_token_id=0)[0, 8:].tolist()

    eng = InferenceEngine(cfg, max_seq=32, seed=0, params=params)
    assert eng.cfg.mla_latent_cache   # auto-enabled on this mesh
    got = eng.generate([prompt], max_new_tokens=10,
                       sampling=SamplingParams.greedy()).tokens[0]
    assert got == want


def test_latent_int8_weights_compose():
    """int8 weight-only quantization with the latent cache: kv_b_k/v
    dequantize inside the absorbed einsums (_wfull)."""
    base = get_config("tiny-deepseek").replace(dtype="float32",
                                               attn_backend="xla")
    from distributed_llm_inferencing_tpu.ops.quant import maybe_quantize
    params = init_params(base, jax.random.PRNGKey(1), dtype=jnp.float32)
    qcfg = base.replace(quant="int8")
    qparams = maybe_quantize(params, qcfg)
    prompt = np.random.default_rng(1).integers(
        0, base.vocab_size, (1, 7)).astype(np.int32)
    dense = _decode_logits(qcfg, qparams, prompt, steps=4)
    latent = _decode_logits(qcfg.replace(mla_latent_cache=True), qparams,
                            prompt, steps=4)
    np.testing.assert_allclose(latent, dense, atol=2e-4, rtol=2e-4)


def test_latent_excludes_kv_quant():
    base = get_config("tiny-deepseek")
    with pytest.raises(AssertionError, match="mutually exclusive"):
        base.replace(mla_latent_cache=True, kv_quant="int8")


def test_latent_speculative_verify_matches_plain_greedy():
    """Multi-token speculative VERIFY over the latent cache: the verify
    step runs forward with s = gamma+1 fresh tokens and per-token
    q_positions — each draft must be causally masked at its own position
    (a lengths-1 default would let drafts attend their own future).
    Greedy + ngram speculation must emit exactly plain greedy's tokens."""
    base = get_config("tiny-deepseek").replace(dtype="float32",
                                               attn_backend="xla")
    params = init_params(base, jax.random.PRNGKey(2), dtype=jnp.float32)
    # repetitive prompt: the workload prompt-lookup drafting accepts on
    rng = np.random.default_rng(2)
    piece = rng.integers(0, base.vocab_size, 4).tolist()
    prompt = (piece * 5)[:18]

    eng = InferenceEngine(base, params, max_seq=64)
    assert eng.cfg.mla_latent_cache
    plain = eng.generate([prompt], max_new_tokens=12,
                         sampling=SamplingParams.greedy()).tokens[0]
    spec = eng.generate([prompt], max_new_tokens=12,
                        sampling=SamplingParams.greedy(),
                        speculative="ngram").tokens[0]
    assert spec == plain


@pytest.mark.parametrize("kw,env,named", [
    (dict(cfg=dict(kv_quant="int8")), {}, "kv_quant"),
    (dict(mesh=dict(pp=2)), {}, "pp > 1"),
    (dict(batcher=dict(speculative="ngram")), {}, "speculative"),
    (dict(cfg=dict(sliding_window=16)), {}, "sliding windows"),
])
def test_batcher_refuses_what_the_latent_pool_cannot_take(
        kw, env, named, monkeypatch):
    """An MLA model's paged pool is latent; an option with no latent path
    is refused at construction, by name, never served from a
    materialized pool without a word."""
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    cfg = get_config("tiny-deepseek").replace(
        dtype="float32", **{"attn_backend": "xla", **kw.get("cfg", {})})
    with pytest.raises(ValueError, match="latent paged pool") as e:
        ContinuousBatcher(cfg, num_blocks=16, block_size=8, slots=2,
                          max_seq=32, seed=0,
                          mesh_spec=MeshSpec(**kw.get("mesh", {})),
                          **kw.get("batcher", {}))
    assert named in str(e.value)


@pytest.mark.parametrize("asked,env", [
    ("auto", {"DLI_ATTENTION": "pallas"}),
    ("pallas", {}),
    ("pallas_interpret", {}),
])
def test_batcher_serves_the_latent_pool_whatever_attention_is_asked(
        asked, env, monkeypatch):
    from conftest import served_as_under_auto
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    b = served_as_under_auto(lambda attn_backend: ContinuousBatcher(
        get_config("tiny-deepseek").replace(
            dtype="float32", attn_backend=attn_backend),
        num_blocks=16, block_size=8, slots=2, max_seq=32, seed=0),
        asked, env, monkeypatch)
    assert b.stats()["interpreted_kernels"] == []


def test_deepseek_tp_ep_batcher_matches_engine():
    """MLA + deepseek MoE through the tp x ep sharded continuous batcher
    (latent pool, replicated over tp) must emit the same greedy tokens as the
    single-device engine (which auto-enables the latent cache) — the two
    layouts and the sharding are all numerically the same attention."""
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    base = get_config("tiny-deepseek").replace(dtype="float32",
                                               attn_backend="xla")
    params = init_params(base, jax.random.PRNGKey(4), dtype=jnp.float32)
    prompt = np.random.default_rng(4).integers(
        0, base.vocab_size, 11).tolist()

    spec = MeshSpec(tp=2, ep=2)
    b = ContinuousBatcher(base, params, num_blocks=16, block_size=8,
                          slots=2, max_seq=32, mesh_spec=spec)
    r = b.submit(prompt, max_new_tokens=8,
                 sampling=SamplingParams.greedy())
    while b.step():
        pass
    assert r.error is None

    eng = InferenceEngine(base, params, max_seq=32)
    want = eng.generate([prompt], max_new_tokens=8,
                        sampling=SamplingParams.greedy()).tokens[0]
    assert r.tokens == want
