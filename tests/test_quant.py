"""Weight-only int8 quantization (ops/quant.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.params import (
    init_params, param_bytes)
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.quant import (
    dequantize_weight, maybe_quantize, quantize_weight)
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
from conftest import jitted


def test_quantize_roundtrip_error():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    q = quantize_weight(w)
    assert q["q"].dtype == jnp.int8 and q["scale"].shape == (32,)
    err = np.abs(np.asarray(dequantize_weight(q)) - np.asarray(w))
    # per-channel symmetric int8: max error is scale/2 per channel
    assert np.all(err <= np.asarray(q["scale"]) / 2 + 1e-7)


@pytest.mark.parametrize("model", ["tiny-gpt2", "tiny-llama",
                                   "tiny-mixtral", "tiny-deepseek"])
def test_quantized_logits_close(model):
    cfg = get_config(model).replace(dtype="float32", attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qcfg = cfg.replace(quant="int8")
    qparams = maybe_quantize(params, qcfg)
    # big matmul weights are int8 now (deepseek MLA: the q bottleneck)
    ql = qparams["layers"]["q_a" if cfg.mla and cfg.q_lora_rank else "q"]
    assert ql["q"].dtype == jnp.int8
    assert param_bytes(qparams) < 0.75 * param_bytes(params)

    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12)),
        jnp.int32)
    lens = jnp.full((2,), 12, jnp.int32)

    def fwd(cfg_, p):
        cache = init_cache(cfg_, 2, 16, dtype=jnp.float32)
        logits, _ = jitted(transformer.prefill)(p, cfg_, toks, lens, cache)
        return np.asarray(logits)

    full = fwd(cfg, params)
    quant = fwd(qcfg, qparams)
    # weight-only int8 should track full precision closely on random nets
    rel = np.abs(quant - full) / (np.abs(full).mean() + 1e-6)
    assert rel.mean() < 0.05, rel.mean()


def test_engine_generate_int8_and_sharded():
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla", quant="int8")
    params = init_params(get_config("tiny-llama").replace(dtype="float32"),
                         jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = np.random.default_rng(1).integers(0, 256, 9).tolist()

    eng = InferenceEngine(cfg, params, max_seq=64)
    r1 = eng.generate([prompt], max_new_tokens=8,
                      sampling=SamplingParams.greedy())
    assert len(r1.tokens[0]) == 8

    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    eng2 = InferenceEngine(cfg, params, mesh_spec=MeshSpec(tp=2), max_seq=64)
    r2 = eng2.generate([prompt], max_new_tokens=8,
                       sampling=SamplingParams.greedy())
    # same quantized weights; tp=2 reduction order may flip argmax ties on
    # random nets, so compare trajectories only up to first divergence
    assert r2.tokens[0][0] == r1.tokens[0][0]


def test_batcher_int8():
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla", quant="int8")
    b = ContinuousBatcher(cfg, num_blocks=32, block_size=8, slots=2,
                          max_seq=64)
    r = b.submit([1, 2, 3, 4], max_new_tokens=6,
                 sampling=SamplingParams.greedy())
    for _ in range(20):
        b.step()
        if r.done.is_set():
            break
    assert r.wait() and len(r.tokens) == 6


def test_plan_accounts_int8_bytes():
    from distributed_llm_inferencing_tpu.parallel.plan import make_plan
    full = make_plan("llama-3-8b", {"tp": 1})
    q = make_plan(get_config("llama-3-8b").replace(quant="int8"), {"tp": 1})
    # weights dominate an 8B model: int8 plan must be close to half
    assert q["param_bytes_total"] < 0.62 * full["param_bytes_total"]


def test_quantized_checkpoint_roundtrip(tmp_path):
    from distributed_llm_inferencing_tpu.models import checkpoint
    cfg = get_config("tiny-llama").replace(dtype="float32", quant="int8")
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    checkpoint.save_checkpoint(str(tmp_path / "q"), cfg, params)
    cfg2, params2 = checkpoint.load_checkpoint(str(tmp_path / "q"))
    assert cfg2.quant == "int8"
    assert params2["layers"]["up"]["q"].dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(params["layers"]["up"]["q"]),
                                  np.asarray(params2["layers"]["up"]["q"]))


def test_random_init_emits_int8_directly():
    """cfg.quant='int8' random init produces quantized leaves WITHOUT ever
    materializing the float tree (the 8B flagship would not fit one chip's
    HBM through an init-bf16-then-quantize path)."""
    cfg = get_config("tiny-llama").replace(dtype="float32", quant="int8")
    p = init_params(cfg, jax.random.PRNGKey(0))
    for leaf in ("q", "k", "v", "o", "up", "gate", "down"):
        assert "w" not in p["layers"][leaf]
        assert p["layers"][leaf]["q"].dtype == jnp.int8
        assert p["layers"][leaf]["scale"].dtype == jnp.float32
    # norms/embeddings stay float (ops/quant.py policy)
    assert p["embed"]["tokens"].dtype == jnp.float32
    # the engine runs it end to end
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    eng = InferenceEngine(cfg, p, max_seq=64)
    out = eng.generate([[3, 5, 7, 11]], max_new_tokens=6,
                       sampling=SamplingParams.greedy())
    assert len(out.tokens[0]) == 6


def test_random_init_int8_moe_experts():
    cfg = get_config("tiny-mixtral").replace(dtype="float32", quant="int8")
    p = init_params(cfg, jax.random.PRNGKey(1))
    for k in ("gate", "up", "down"):
        assert p["layers"]["experts"][k]["q"].dtype == jnp.int8
    assert "w" in p["layers"]["router"]   # router kept float: routing-critical


# ---------------- int4 (nibble-packed) weight-only ----------------

def test_int4_pack_roundtrip_exact():
    from distributed_llm_inferencing_tpu.ops.quant import (
        pack_int4, unpack_int4)
    # every nibble value through pack->unpack, odd leading dims included
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-8, 8, (3, 10, 7)), jnp.int8)
    np.testing.assert_array_equal(np.asarray(unpack_int4(pack_int4(q))),
                                  np.asarray(q))


def test_int4_quantize_roundtrip_error():
    from distributed_llm_inferencing_tpu.ops.quant import quantize_weight_int4
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    p = quantize_weight_int4(w)
    assert p["p4"].dtype == jnp.uint8 and p["p4"].shape == (32, 32)
    assert p["scale"].shape == (32,)
    err = np.abs(np.asarray(dequantize_weight(p)) - np.asarray(w))
    # per-channel symmetric int4: max error is scale/2 per channel
    assert np.all(err <= np.asarray(p["scale"]) / 2 + 1e-7)


@pytest.mark.parametrize("model", ["tiny-gpt2", "tiny-llama", "tiny-mixtral"])
def test_int4_forward_matches_dequantized_weights(model):
    """The packed-int4 compute path (unpack fused into the matmul,
    models/transformer.py _qw) must equal an ordinary float forward over
    the *dequantized* weights — this isolates the pack/unpack/scale
    plumbing from the (intentional) int4 rounding loss."""
    from distributed_llm_inferencing_tpu.ops.quant import is_quantized
    cfg = get_config(model).replace(dtype="float32", attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qcfg = cfg.replace(quant="int4")
    qparams = maybe_quantize(params, qcfg)
    assert qparams["layers"]["q"]["p4"].dtype == jnp.uint8
    assert param_bytes(qparams) < 0.45 * param_bytes(params)

    def deq_tree(p):
        if isinstance(p, dict):
            # NB the layers dict itself has a key named "q" (the query
            # projection), so require an array leaf before dequantizing
            if is_quantized(p) and not isinstance(p.get("q", p.get("p4")),
                                                  dict):
                out = {k: v for k, v in p.items() if k not in ("p4", "q",
                                                               "scale")}
                out["w"] = dequantize_weight(p).astype(jnp.float32)
                return out
            return {k: deq_tree(v) for k, v in p.items()}
        return p

    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12)),
        jnp.int32)
    lens = jnp.full((2,), 12, jnp.int32)

    def fwd(cfg_, p):
        cache = init_cache(cfg_, 2, 16, dtype=jnp.float32)
        logits, _ = jitted(transformer.prefill)(p, cfg_, toks, lens, cache)
        return np.asarray(logits)

    quant = fwd(qcfg, qparams)
    ref = fwd(cfg, deq_tree(qparams))
    np.testing.assert_allclose(quant, ref, rtol=2e-3, atol=2e-3)


def test_random_init_emits_int4_directly():
    cfg = get_config("tiny-llama").replace(dtype="float32", quant="int4")
    p = init_params(cfg, jax.random.PRNGKey(0))
    for leaf in ("q", "k", "v", "o", "up", "gate", "down"):
        assert "w" not in p["layers"][leaf]
        assert p["layers"][leaf]["p4"].dtype == jnp.uint8
        # packed along din: half the rows of the float weight
    assert p["layers"]["up"]["p4"].shape[-2] == cfg.hidden_size // 2
    eng = InferenceEngine(cfg, p, max_seq=64)
    out = eng.generate([[3, 5, 7, 11]], max_new_tokens=6,
                       sampling=SamplingParams.greedy())
    assert len(out.tokens[0]) == 6


def test_engine_generate_int4_sharded():
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla", quant="int4")
    params = init_params(get_config("tiny-llama").replace(dtype="float32"),
                         jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = InferenceEngine(cfg, params, max_seq=64)
    prompt = np.random.default_rng(1).integers(0, 256, 9).tolist()
    r1 = eng.generate([prompt], max_new_tokens=8,
                      sampling=SamplingParams.greedy())
    assert len(r1.tokens[0]) == 8
    eng2 = InferenceEngine(cfg, params, mesh_spec=MeshSpec(tp=2), max_seq=64)
    r2 = eng2.generate([prompt], max_new_tokens=8,
                       sampling=SamplingParams.greedy())
    assert r2.tokens[0][0] == r1.tokens[0][0]


def test_batcher_int4():
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla", quant="int4")
    b = ContinuousBatcher(cfg, num_blocks=32, block_size=8, slots=2,
                          max_seq=64)
    r = b.submit([1, 2, 3, 4], max_new_tokens=6,
                 sampling=SamplingParams.greedy())
    for _ in range(20):
        b.step()
        if r.done.is_set():
            break
    assert r.wait() and len(r.tokens) == 6


def test_plan_accounts_int4_bytes():
    from distributed_llm_inferencing_tpu.parallel.plan import make_plan
    full = make_plan("llama-3-8b", {"tp": 1})
    q = make_plan(get_config("llama-3-8b").replace(quant="int4"), {"tp": 1})
    # int4 packs two weights per byte: ~0.25x + embeddings/norms float
    assert q["param_bytes_total"] < 0.45 * full["param_bytes_total"]


def test_int4_checkpoint_roundtrip(tmp_path):
    from distributed_llm_inferencing_tpu.models import checkpoint
    cfg = get_config("tiny-llama").replace(dtype="float32", quant="int4")
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    checkpoint.save_checkpoint(str(tmp_path / "q4"), cfg, params)
    cfg2, params2 = checkpoint.load_checkpoint(str(tmp_path / "q4"))
    assert cfg2.quant == "int4"
    np.testing.assert_array_equal(np.asarray(params["layers"]["up"]["p4"]),
                                  np.asarray(params2["layers"]["up"]["p4"]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_q4_matmul_kernel_matches_reference(dtype):
    """The pallas int4 kernel (interpret mode here — the real thing needs
    a TPU) against the dequantized-weight reference, both nibble planes
    and the bias-correction path exercised."""
    from distributed_llm_inferencing_tpu.ops.pallas.quant_matmul import (
        q4_matmul)
    from distributed_llm_inferencing_tpu.ops.quant import (
        quantize_weight_int4)
    rng = np.random.default_rng(0)
    din, dout, b = 256, 384, 3        # b deliberately off the sublane tile
    w = jnp.asarray(rng.standard_normal((din, dout)) * 0.1, jnp.float32)
    p = quantize_weight_int4(w)
    x = jnp.asarray(rng.standard_normal((b, din)), jnp.dtype(dtype))
    ref = jnp.einsum("bd,df->bf", x.astype(jnp.float32),
                     dequantize_weight(p))
    out = q4_matmul(x, p["p4"], p["scale"], interpret=True)
    assert out.dtype == x.dtype and out.shape == (b, dout)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref),
        rtol=0.05 if dtype == "bfloat16" else 2e-3,
        atol=0.05 if dtype == "bfloat16" else 2e-3)


# ---------------- int8 embedding table (cfg.embed_quant) ----------------

def test_embed_quantize_roundtrip_error():
    from distributed_llm_inferencing_tpu.ops.quant import (
        dequantize_embed, quantize_embed)
    rng = np.random.default_rng(0)
    emb = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    p = quantize_embed(emb)
    assert p["q8"].dtype == jnp.int8 and p["rscale"].shape == (64,)
    err = np.abs(np.asarray(dequantize_embed(p)) - np.asarray(emb))
    assert np.all(err <= np.asarray(p["rscale"])[:, None] / 2 + 1e-7)


@pytest.mark.parametrize("model", ["tiny-gpt2", "tiny-llama"])
def test_embed_quant_forward_matches_dequantized_table(model):
    """int8-table forward (gather dequant + tied-head commuted scale) vs a
    float forward over the dequantized table — isolates the plumbing from
    the rounding loss. Covers a tied (gpt2) and an untied (llama) family."""
    from distributed_llm_inferencing_tpu.ops.quant import (
        dequantize_embed, maybe_quantize_embed)
    cfg = get_config(model).replace(dtype="float32", attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qcfg = cfg.replace(embed_quant="int8")
    qparams = maybe_quantize_embed(params, qcfg)
    assert qparams["layers"] is params["layers"]   # only the table changes

    ref_params = dict(qparams)
    ref_params["embed"] = dict(qparams["embed"])
    ref_params["embed"]["tokens"] = dequantize_embed(
        qparams["embed"]["tokens"]).astype(jnp.float32)

    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12)),
        jnp.int32)
    lens = jnp.full((2,), 12, jnp.int32)

    def fwd(cfg_, p):
        cache = init_cache(cfg_, 2, 16, dtype=jnp.float32)
        logits, _ = jitted(transformer.prefill)(p, cfg_, toks, lens, cache)
        return np.asarray(logits)

    np.testing.assert_allclose(fwd(qcfg, qparams), fwd(cfg, ref_params),
                               rtol=2e-3, atol=2e-3)


def test_random_init_emits_embed_int8_directly():
    cfg = get_config("tiny-gpt2").replace(dtype="float32",
                                          embed_quant="int8")
    p = init_params(cfg, jax.random.PRNGKey(0))
    assert p["embed"]["tokens"]["q8"].dtype == jnp.int8
    eng = InferenceEngine(cfg, p, max_seq=64)
    out = eng.generate([[3, 5, 7, 11]], max_new_tokens=6,
                       sampling=SamplingParams.greedy())
    assert len(out.tokens[0]) == 6


def test_embed_quant_sharded_and_stacked_with_int4():
    """embed int8 + weights int4 together, tp=2: specs cover the dict
    table leaf and the engine still decodes."""
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    cfg = get_config("tiny-gpt2").replace(
        dtype="float32", attn_backend="xla", quant="int4",
        embed_quant="int8")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(1).integers(0, 256, 9).tolist()
    eng = InferenceEngine(cfg, params, max_seq=64)
    r1 = eng.generate([prompt], max_new_tokens=8,
                      sampling=SamplingParams.greedy())
    eng2 = InferenceEngine(cfg, params, mesh_spec=MeshSpec(tp=2), max_seq=64)
    r2 = eng2.generate([prompt], max_new_tokens=8,
                       sampling=SamplingParams.greedy())
    assert r2.tokens[0][0] == r1.tokens[0][0]


def test_int4_pallas_multidevice_mesh_construction_allowed(monkeypatch):
    """The kernel now carries a GSPMD/shardy partitioning rule
    (ops/pallas/quant_matmul.py), so int4 on a multi-device mesh is no
    longer refused at construction — with any DLI_INT4_PALLAS mode —
    and the tp=2 engine still decodes correctly (column-parallel leaves
    per-shard, row-parallel on the XLA unpack; equivalence pinned in
    tests/test_quant_partition.py)."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    monkeypatch.setenv("DLI_INT4_PALLAS", "always")
    cfg = get_config("tiny-llama").replace(dtype="float32", quant="int4")
    eng = InferenceEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                          mesh_spec=MeshSpec(tp=2), max_seq=64)
    monkeypatch.delenv("DLI_INT4_PALLAS")
    ref = InferenceEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                          max_seq=64)
    g = SamplingParams.greedy()
    a = eng.generate([[3, 1, 4, 1]], max_new_tokens=6, sampling=g).tokens[0]
    b = ref.generate([[3, 1, 4, 1]], max_new_tokens=6, sampling=g).tokens[0]
    assert a == b


def test_embed_quant_untied_int4_full_stack():
    """The llama-family full quant story (bench llama_3_8b_int4_eq8):
    int4 matmuls INCLUDING the untied lm_head + int8 embedding table.
    Greedy decode must match the same stack with a dequantized table at
    relaxed tolerance, and the engine must serve it tp-sharded."""
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    cfg = get_config("tiny-llama").replace(
        dtype="float32", attn_backend="xla", quant="int4",
        embed_quant="int8")
    params = init_params(cfg, jax.random.PRNGKey(3))
    assert "p4" in params["lm_head"]            # untied head is int4
    assert "q8" in params["embed"]["tokens"]    # table is int8
    prompt = np.random.default_rng(2).integers(0, 256, 9).tolist()
    eng = InferenceEngine(cfg, params, max_seq=64)
    r1 = eng.generate([prompt], max_new_tokens=8,
                      sampling=SamplingParams.greedy())
    assert len(r1.tokens[0]) == 8

    # same stack, table dequantized to float: first greedy tokens agree
    # (rounding-loss tolerance: compare the first token only, the rest
    # can legitimately diverge after an argmax flip)
    from distributed_llm_inferencing_tpu.ops.quant import dequantize_embed
    ref = {k: v for k, v in params.items()}
    ref["embed"] = dict(params["embed"])
    ref["embed"]["tokens"] = dequantize_embed(
        params["embed"]["tokens"]).astype(jnp.float32)
    eng_ref = InferenceEngine(cfg.replace(embed_quant=None), ref, max_seq=64)
    r2 = eng_ref.generate([prompt], max_new_tokens=8,
                          sampling=SamplingParams.greedy())
    assert r1.tokens[0][0] == r2.tokens[0][0]

    eng_tp = InferenceEngine(cfg, params, mesh_spec=MeshSpec(tp=2),
                             max_seq=64)
    r3 = eng_tp.generate([prompt], max_new_tokens=8,
                         sampling=SamplingParams.greedy())
    assert r3.tokens[0][0] == r1.tokens[0][0]


def test_embed_quant_checkpoint_roundtrip(tmp_path):
    from distributed_llm_inferencing_tpu.models import checkpoint
    cfg = get_config("tiny-gpt2").replace(dtype="float32",
                                          embed_quant="int8")
    params = init_params(cfg, jax.random.PRNGKey(2))
    checkpoint.save_checkpoint(str(tmp_path / "eq"), cfg, params)
    cfg2, params2 = checkpoint.load_checkpoint(str(tmp_path / "eq"))
    assert cfg2.embed_quant == "int8"
    np.testing.assert_array_equal(
        np.asarray(params["embed"]["tokens"]["q8"]),
        np.asarray(params2["embed"]["tokens"]["q8"]))


def test_plan_accounts_embed_int8_bytes():
    from distributed_llm_inferencing_tpu.parallel.plan import make_plan
    full = make_plan("gpt2-xl", {"tp": 1})
    q = make_plan(get_config("gpt2-xl").replace(embed_quant="int8"),
                  {"tp": 1})
    # gpt2-xl's [50257, 1600] table is ~5% of the model in bf16; int8
    # saves half of it
    assert q["param_bytes_total"] < 0.98 * full["param_bytes_total"]


def test_cli_quant_modes_in_sync():
    """__main__ keeps a literal copy of MODES so jax-free subcommands
    never import jax to build the parser."""
    from distributed_llm_inferencing_tpu import __main__ as cli
    from distributed_llm_inferencing_tpu.ops.quant import MODES
    assert tuple(cli.quant_modes) == tuple(MODES)


def test_engine_applies_embed_quant_to_float_params():
    """Caller-supplied float params + cfg.embed_quant: the engine must
    quantize the table itself (the specs already expect the dict leaf)."""
    cfg = get_config("tiny-gpt2").replace(dtype="float32",
                                          embed_quant="int8")
    fparams = init_params(get_config("tiny-gpt2").replace(dtype="float32"),
                          jax.random.PRNGKey(0), dtype=jnp.float32)
    assert not isinstance(fparams["embed"]["tokens"], dict)
    eng = InferenceEngine(cfg, fparams, max_seq=64)
    assert isinstance(eng.params["embed"]["tokens"], dict)
    out = eng.generate([[3, 5, 7]], max_new_tokens=4,
                       sampling=SamplingParams.greedy())
    assert len(out.tokens[0]) == 4
