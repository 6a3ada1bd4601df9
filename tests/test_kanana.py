"""kanana-2-30b-a3b (model_type deepseek_v3: MLA with a full-rank q, 128
sigmoid-routed experts + 2 shared, one leading dense layer) on the normal
serving path, at `tiny-kanana`'s toy widths: the latent paged pool, the
absorbed decode and the grouped expert dispatch against the plain float32
reference (models/reference/deepseek_v3_ref.py), on logits.
"""

import json
import types
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models import convert, transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.reference import (
    deepseek_v3_ref as ref)
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    init_paged_cache)
from distributed_llm_inferencing_tpu.ops.quant import maybe_quantize
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime.batcher import (
    ContinuousBatcher)
from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
from conftest import jitted

ROOT = Path(__file__).resolve().parents[1]
BS, NB = 8, 64                      # block size, pool blocks (0 = dummy)
# float32 everywhere: the system and the reference then differ only by
# the order of summation (absorbed against materialized attention, grouped
# against per-expert sums), a few float32 roundings through four layers
F32_TOL = 2e-4


def _cfg(dtype="float32"):
    return get_config("tiny-kanana").replace(
        dtype=dtype, attn_backend="xla", mla_latent_cache=True)


def _setup(dtype="float32", seed=0):
    cfg = _cfg(dtype)
    params = init_params(cfg, jax.random.PRNGKey(seed),
                         dtype=jnp.dtype(dtype))
    return cfg, params, ref.arch_of(cfg)


CFG = _cfg()
ARCH = ref.arch_of(CFG)
PARAMS = None       # float32 weights, drawn by the first case that runs


@pytest.fixture(scope="module", autouse=True)
def _params():
    """Not at import: every worker imports every file to collect it, and
    drawing 128 experts' weights took each 6 s before its first case."""
    global PARAMS
    _, PARAMS, _ = _setup()


# One trace and one compile a (function, configuration, shapes) for the
# whole file (conftest.jitted says why)
_prefill_tail = jitted(transformer.paged_prefill_tail)
_decode_step = jitted(transformer.paged_decode_step)
_dense_prefill = jitted(transformer.prefill)
_dense_decode_step = jitted(transformer.decode_step)
_decode_chunk = jax.jit(transformer.paged_decode_chunk, static_argnums=(1, 2),
                        static_argnames=("dummy_block",))


def _err(got, want):
    """Largest logit error of a position over the spread of that
    position's reference logits."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want).max(-1) / want.std(-1)))


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, CFG.vocab_size, n).tolist() for n in lengths]


def _prefill(cfg, params, paged, rows):
    """One admit-shaped call of paged_prefill_tail. rows: (tail tokens,
    their blocks, cached prefix blocks, cached prefix length)."""
    t = max(-(-len(r[0]) // BS) for r in rows) * BS
    pb = max(len(r[2]) for r in rows)
    toks = np.zeros((len(rows), t), np.int32)
    tb = np.zeros((len(rows), t // BS), np.int32)
    pfb = np.zeros((len(rows), pb), np.int32)
    for i, (tail, blocks, pblocks, _) in enumerate(rows):
        toks[i, :len(tail)] = tail
        tb[i, :len(blocks)] = blocks
        pfb[i, :len(pblocks)] = pblocks
    return _prefill_tail(
        params, cfg, jnp.asarray(toks),
        jnp.asarray([len(r[0]) for r in rows], jnp.int32), jnp.asarray(tb),
        jnp.asarray(pfb), jnp.asarray([r[3] for r in rows], jnp.int32),
        paged)


def _blocks(start, n_tokens):
    n = -(-n_tokens // BS)
    return list(range(start, start + n))


def test_prefill_into_the_latent_pool_matches_reference():
    p = _prompts(1, (13, 29))
    paged = init_paged_cache(CFG, NB, BS)
    assert paged.v is None and paged.k.shape[3:] == (1, 128)   # 8 + 32
    logits, paged = _prefill(CFG, PARAMS, paged, [
        (p[0], _blocks(1, 13), [], 0), (p[1], _blocks(10, 29), [], 0)])
    for i in range(2):
        want = ref.forward(PARAMS, ARCH, p[i], rows=[len(p[i]) - 1])
        assert _err(logits[i:i + 1], want) < F32_TOL


@pytest.mark.parametrize("chunks", [(16, 13), (16, 16, 13), (8, 32, 5)])
def test_tail_over_cached_latent_prefix_matches_reference(chunks):
    """A radix hit and the chunks of a chunked prefill are the same
    program: a tail attending latent rows it did not compute, beside a
    row that has no prefix."""
    total = sum(chunks)
    p, other = _prompts(2, (total, 11))
    paged = init_paged_cache(CFG, NB, BS)
    blocks, done = _blocks(1, total), 0
    for n in chunks:
        logits, paged = _prefill(CFG, PARAMS, paged, [
            (p[done:done + n], blocks[done // BS:-(-(done + n) // BS)],
             blocks[:done // BS], done),
            (other, _blocks(40, 11), [], 0)])
        done += n
    assert _err(logits[:1], ref.forward(PARAMS, ARCH, p, rows=[total - 1])) \
        < F32_TOL
    assert _err(logits[1:], ref.forward(PARAMS, ARCH, other, rows=[10])) \
        < F32_TOL


def test_forty_decode_steps_through_the_latent_pool_match_reference():
    """Two slots of different lengths, 40 absorbed decode steps
    (paged_decode_step, logits each step), and the chunked program
    (paged_decode_chunk, 5 x 8) over the same pool: same tokens."""
    p = _prompts(3, (21, 6))
    mb = 16
    tables = np.zeros((2, mb), np.int32)
    tables[0, :8], tables[1, :8] = range(1, 9), range(20, 28)
    paged = init_paged_cache(CFG, NB, BS)
    logits, paged = _prefill(CFG, PARAMS, paged, [
        (p[0], tables[0, :3].tolist(), [], 0),
        (p[1], tables[1, :1].tolist(), [], 0)])
    first = np.asarray(jnp.argmax(logits, -1), np.int32)
    cl0 = np.asarray([21, 6], np.int32)
    def step(t, pg, cl):
        return _decode_step(PARAMS, CFG, t, pg, jnp.asarray(tables), cl)
    cur, cl, seqs, got, pg = first, cl0, [list(x) for x in p], [], paged
    for _ in range(40):
        for i in range(2):
            seqs[i].append(int(cur[i]))
        lg, pg = step(jnp.asarray(cur), pg, jnp.asarray(cl))
        got.append(np.asarray(lg))
        cur, cl = np.asarray(jnp.argmax(lg, -1), np.int32), cl + 1
    got = np.stack(got, 1)                               # [2, 40, V]
    for i in range(2):
        n0 = len(p[i])
        want = ref.forward(PARAMS, ARCH, seqs[i], rows=range(n0, n0 + 40))
        assert _err(got[i], want) < F32_TOL

    r = 2
    zeros, ones = np.zeros((r,), np.int32), np.ones((r,), np.float32)
    toks_all, cur, cl, pg, moe_sum = [], first, cl0, paged, 0
    for c in range(5):
        toks, emits, moe, _, _, pg = _decode_chunk(
            PARAMS, CFG, 8, jnp.asarray(cur), pg, jnp.asarray(tables),
            jnp.asarray(cl), jnp.asarray(zeros), jnp.asarray(zeros + 8 * c),
            jnp.asarray(ones), jnp.asarray(zeros), jnp.asarray(ones),
            jnp.zeros((r,), bool), jnp.full((r,), 8, jnp.int32),
            jnp.full((r,), -1, jnp.int32), dummy_block=0)
        assert np.asarray(emits).all()
        toks_all.append(np.asarray(toks))
        cur, cl, moe_sum = np.asarray(toks)[-1], cl + 8, moe_sum + moe
    toks_all = np.concatenate(toks_all, 0).T             # [2, 40]
    for i in range(2):
        assert toks_all[i].tolist() == seqs[i][len(p[i]) + 1:] + [
            int(np.argmax(got[i, -1]))]
    moe_sum = dict(zip(transformer.MOE_STATS, np.asarray(moe_sum)))
    assert moe_sum["layer_passes"] == 40 * 3             # three MoE layers
    assert moe_sum["rows"] == 40 * 3 * 2 * CFG.num_experts_per_tok
    assert moe_sum["idle_rows"] == 0
    assert 3 <= moe_sum["experts_hit"] / moe_sum["layer_passes"] <= 6


def test_batcher_over_32_tokens_a_program_emits_the_engines_tokens():
    """Through ContinuousBatcher, admit programs of more than 32 tokens
    (the old dense/capacity switch) and 4-slot decode: greedy tokens
    equal the single-stream engine's."""
    prompts = _prompts(4, (45, 37, 50, 9))
    b = ContinuousBatcher(CFG, PARAMS, slots=4, num_blocks=64, block_size=BS,
                          max_seq=128, prefill_chunk=None)
    assert b.paged.v is None and b.cfg.mla_latent_cache
    reqs = [b.submit(p, max_new_tokens=20, sampling=SamplingParams.greedy(),
                     seed=0) for p in prompts]
    while b.step():
        pass
    eng = InferenceEngine(CFG, PARAMS, max_seq=128)
    want = eng.generate(prompts, max_new_tokens=20,
                        sampling=SamplingParams.greedy()).tokens
    assert [r.tokens for r in reqs] == [list(w) for w in want]
    counters = b.metrics.snapshot()["counters"]
    assert counters["batcher_moe_layer_passes"] > 0
    assert counters["batcher_moe_idle_rows"] == 0 or \
        counters["batcher_moe_rows"] > counters["batcher_moe_idle_rows"]
    # MoE layers are held one by one, so the pool ladder is the full
    # extent alone (transformer._pool_ladder) and the counter says so
    assert counters["batcher_decode_pool_positions"] \
        == 128 * counters["batcher_weight_passes"]


def test_batcher_chunked_prefill_and_radix_hit_keep_the_tokens():
    """The same prompt served cold in one piece, in chunks of 16, and
    again (a radix hit on its own blocks): one token stream."""
    prompt = _prompts(5, (61,))[0]
    outs = []
    for chunk in (None, 2):
        b = ContinuousBatcher(CFG, PARAMS, slots=2, num_blocks=64,
                              block_size=BS, max_seq=128,
                              prefill_chunk=chunk)
        for _ in range(2):
            r = b.submit(prompt, max_new_tokens=12,
                         sampling=SamplingParams.greedy(), seed=0)
            while b.inflight():
                b.step()
            assert r.error is None and len(r.tokens) == 12
            outs.append(r.tokens)
        assert b._chunked_admissions == (3 if chunk else 0)   # 61 = 3x16+13
        assert b.pool.stats()["prefix_hits"] > 0  # the second serve's
    assert all(o == outs[0] for o in outs)


def test_latent_pool_matches_the_materialized_formulation():
    """Latent rows + absorbed decode against per-head K and V in a dense
    cache (transformer.prefill / decode_step, mla_latent_cache off):
    float32, so only the order of summation differs; and the pool takes
    L x (r + rd) x itemsize bytes a token, not 2 x H x 24 x L."""
    p = _prompts(6, (19,))[0]
    mat = CFG.replace(mla_latent_cache=False)
    cache = init_cache(mat, 1, 64, dtype=jnp.float32)
    lg, cache = _dense_prefill(PARAMS, mat, jnp.asarray([p]),
                               jnp.asarray([19], jnp.int32), cache)
    want, cur = [np.asarray(lg)[0, 18]], int(jnp.argmax(lg[0, 18]))
    paged = init_paged_cache(CFG, NB, BS)
    tables = np.zeros((1, 8), np.int32)
    tables[0, :5] = range(1, 6)
    lg_p, paged = _prefill(CFG, PARAMS, paged,
                           [(p, tables[0, :3].tolist(), [], 0)])
    got = [np.asarray(lg_p)[0]]
    for i in range(12):
        lg, cache = _dense_decode_step(
            PARAMS, mat, jnp.asarray([[cur]], jnp.int32), cache)
        lg_p, paged = _decode_step(
            PARAMS, CFG, jnp.asarray([cur], jnp.int32), paged,
            jnp.asarray(tables), jnp.asarray([19 + i], jnp.int32))
        want.append(np.asarray(lg)[0, 0])
        got.append(np.asarray(lg_p)[0])
        cur = int(np.argmax(want[-1]))
    assert _err(np.stack(got), np.stack(want)) < F32_TOL
    # a row is stored whole 128-lane tiles wide (lane_width): the toy's
    # rd + r in one tile, the cell's 576 in 640, zeros after the row
    L, r, rd = CFG.num_layers, CFG.kv_lora_rank, CFG.qk_rope_head_dim
    assert r + rd < 128 and paged.bytes_per_token == L * 128 * 4
    assert not np.asarray(paged.k[..., r + rd:]).any()
    bf16 = init_paged_cache(CFG.replace(dtype="bfloat16"), NB, BS)
    assert bf16.bytes_per_token == L * 128 * 2
    full = get_config("kanana-2-30b-a3b").replace(num_layers=7,
                                                  mla_latent_cache=True)
    shape = jax.eval_shape(lambda: init_paged_cache(full, 4, 16))
    assert shape.k.shape == (7, 4, 16, 1, 640) and shape.v is None
    assert full.cache_head_dim == 576 and 7 * 640 * 2 == 8960
    assert 2 * 32 * 192 * 2 * 7 == 172032      # the materialized pool's


def _err_rms(got, want):
    """Largest root-mean-square logit error of a position over the spread
    of that position's reference logits."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.sqrt(np.mean((got - want) ** 2, -1))
                        / want.std(-1)))


# bf16 weights and activations against the float32 reference at these toy
# widths, seeds 0-3: 0.0066-0.0076 (root-mean-square logit error over the
# logits' spread: nine positions, bf16 roundings through four layers);
# the same with every linear weight int8: 0.012-0.073. The limit sits
# between the two readings. (int8 experts alone do not show at these
# widths: 0.0065-0.0081. The chip's comparison, at published widths, is
# benchmarks/chip/compare_reference.py.)
BF16_TOL = 0.0095


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_bf16_passes_the_reference_and_int8_weights_fail_it(seed):
    """The tolerance has teeth: the configuration's precision (bf16)
    passes it, the next precision down (int8 weights) does not."""
    cfg, params, arch = _setup("bfloat16", seed)
    p = np.random.default_rng(seed).integers(3, cfg.vocab_size, 48).tolist()

    def serve(c, prm):
        paged = init_paged_cache(c, NB, BS)
        tables = np.zeros((1, 16), np.int32)
        tables[0, :10] = range(1, 11)
        lg, paged = _prefill(c, prm, paged, [(p[:40], _blocks(1, 40), [], 0)])
        out = [np.asarray(lg)[0]]
        for i in range(40, 48):
            lg, paged = _decode_step(
                prm, c, jnp.asarray([p[i]], jnp.int32), paged,
                jnp.asarray(tables), jnp.asarray([i], jnp.int32))
            out.append(np.asarray(lg)[0])
        return np.stack(out)
    want = ref.forward(params, arch, p, rows=range(39, 48))
    assert _err_rms(serve(cfg, params), want) < BF16_TOL
    q = cfg.replace(quant="int8")
    assert _err_rms(serve(q, maybe_quantize(params, q)), want) > BF16_TOL


def test_registry_entry_is_the_source_config():
    """config_from_hf on the catalog row's config gives the registry
    entry; the benchmark's configuration file states the same numbers,
    at the top level and verbatim under source_config."""
    with open(ROOT / "benchmarks/chip/configs/kanana-2-30b-a3b-l7.json") as f:
        conf = json.load(f)
    src = conf["source_config"]
    hf = types.SimpleNamespace(**src, name_or_path="kanana-2-30b-a3b")
    entry = get_config("kanana-2-30b-a3b")
    assert convert.config_from_hf(hf) == entry
    for key, value in src.items():      # the file's own top level
        if key != "num_hidden_layers":
            assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["num_hidden_layers"] == 7 and src["num_hidden_layers"] == 48
    # every key of the source against the field that carries it
    fields = {
        "hidden_size": entry.hidden_size, "head_dim": entry.head_dim,
        "intermediate_size": entry.intermediate_size,
        "moe_intermediate_size": entry.moe_intermediate_size,
        "kv_lora_rank": entry.kv_lora_rank, "q_lora_rank": entry.q_lora_rank,
        "qk_head_dim": entry.qk_head_dim,
        "qk_nope_head_dim": entry.qk_nope_head_dim,
        "qk_rope_head_dim": entry.qk_rope_head_dim,
        "v_head_dim": entry.v_head_dim, "vocab_size": entry.vocab_size,
        "num_attention_heads": entry.num_heads,
        "num_key_value_heads": entry.num_kv_heads,
        "num_hidden_layers": entry.num_layers,
        "n_routed_experts": entry.num_experts,
        "n_shared_experts": entry.moe_shared_experts,
        "num_experts_per_tok": entry.num_experts_per_tok,
        "n_group": entry.moe_n_group, "topk_group": entry.moe_topk_group,
        "norm_topk_prob": entry.moe_norm_topk,
        "routed_scaling_factor": entry.moe_routed_scale,
        "first_k_dense_replace": entry.dense_prefix_layers,
        "rms_norm_eps": entry.norm_eps, "rope_theta": entry.rope_theta,
        "rope_interleave": entry.rope_interleaved,
        "attention_bias": entry.attn_bias,
        "tie_word_embeddings": entry.tie_word_embeddings,
        "max_position_embeddings": entry.max_position_embeddings,
        "hidden_act": entry.activation,
        "scoring_func": {"deepseek_v3": "sigmoid"}[entry.moe_router],
        "model_type": {"deepseek": "deepseek_v3"}[entry.family],
        "rope_scaling": entry.rope_inv_freq, "moe_layer_freq": 1,
        "topk_method": "noaux_tc",
    }
    assert set(fields) >= set(src)
    for key, value in src.items():
        assert fields[key] == value, key
    run = get_config(conf["registry"]).replace(**conf["overrides"])
    assert run.num_layers == 7 and run.dense_prefix_layers == 1
