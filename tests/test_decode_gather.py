"""The decode chunk's two ways to the pool agree.

``paged_decode_chunk`` and ``paged_speculative_chunk`` gather the pool
once a chunk while the gathered bytes stay under
``transformer._PREGATHER_MAX_BYTES``, and per layer inside the loop above
it. Every tier-1 model is toy-width and sits under the cap; every cell of
the benchmark sits above it (mistral-7b: 4.0 GiB, kanana-2-30b-a3b:
1.23 GiB), so the chip runs the in-loop form. These cases run the same
inputs through both (the cap patched to 0 for the in-loop one) and hold
them equal: tokens and ``emits`` exactly, the pool at the tolerance
``tests/test_paged.py`` holds paged against dense to.
"""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    PagedKVCache, init_paged_cache)
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams

R, MB, BS = 4, 6, 8          # slots, blocks a slot, block size
DUMMY = 0
# slot 0 is dead from the start (no budget); slot 2 dies of eos
CONTEXT = np.asarray([0, 13, 21, 30], np.int32)
GAMMA = 3


def _llama(**kw):
    return get_config("tiny-llama").replace(
        dtype="bfloat16", attn_backend="xla", **kw)


def _sinks(params, cfg):
    h = params["layers"]["sinks"].shape
    params["layers"]["sinks"] = jax.random.normal(
        jax.random.PRNGKey(7), h, jnp.float32)
    return params


def _lora(params, cfg):
    """A stacked device pack of three adapter slots (0 = base, all zero)
    on q and down, the shape models/lora.py builds."""
    L, s, rank = cfg.num_layers, 3, 4
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 4))
    pack = {}
    for name in ("q", "down"):
        din, dout = params["layers"][name]["w"].shape[1:]
        a = 0.3 * jax.random.normal(next(keys), (L, s, din, rank))
        b = 0.3 * jax.random.normal(next(keys), (L, s, rank, dout))
        pack[name] = {"a": a.at[:, 0].set(0).astype(jnp.bfloat16),
                      "b": b.at[:, 0].set(0).astype(jnp.bfloat16)}
    params["layers"] = dict(params["layers"], lora=pack)
    return params


# name -> (config, what to do to its freshly drawn params, lora_ids)
CASES = {
    "gqa-bf16": lambda: (_llama(), None, None),
    "gqa-int8-pool": lambda: (_llama(kv_quant="int8"), None, None),
    "layer-windows": lambda: (
        _llama(attn_windows=(None, 8, None, 16)), None, None),
    "alibi-softcap": lambda: (
        _llama(position_embedding="alibi", attn_softcap=30.0), None, None),
    "sinks": lambda: (_llama(attn_sinks=True), _sinks, None),
    "moe": lambda: (
        get_config("tiny-mixtral").replace(
            dtype="bfloat16", attn_backend="xla"), None, None),
    "mla-latent": lambda: (
        get_config("tiny-kanana").replace(
            dtype="bfloat16", attn_backend="xla", mla_latent_cache=True),
        None, None),
    "lora": lambda: (_llama(), _lora, np.asarray([0, 1, 2, 0], np.int32)),
}


def _random_pool(cfg):
    """A pool whose every block holds random rows (int8 levels and
    positive scales where it is quantized): what the two forms read."""
    paged = init_paged_cache(cfg, 1 + R * MB, BS)
    keys = jax.random.split(jax.random.PRNGKey(3), len(paged.planes()))

    def fill(plane, key):
        if plane.dtype == jnp.int8:
            return jax.random.randint(key, plane.shape, -127, 128,
                                      jnp.int32).astype(jnp.int8)
        x = jax.random.normal(key, plane.shape, jnp.float32)
        if plane.ndim == 4:                        # a scale plane
            x = 0.01 + 0.01 * jnp.abs(x)
        return x.astype(plane.dtype)

    return PagedKVCache(*(fill(p, k) for p, k in zip(paged.planes(), keys)))


@functools.lru_cache(maxsize=None)
def _setup(case):
    cfg, edit, lora_ids = CASES[case]()
    params = init_params(cfg, jax.random.PRNGKey(0))
    if edit is not None:
        params = edit(params, cfg)
    if cfg.is_moe:
        # the batcher's layout for MoE layers: a list, run unrolled
        from distributed_llm_inferencing_tpu.runtime.batcher import (
            _unstack_layers)
        params["layers"] = _unstack_layers(params.pop("layers"))
    bt = 1 + np.arange(R * MB, dtype=np.int32).reshape(R, MB)
    return cfg, params, _random_pool(cfg), bt, lora_ids


def _sampling_rows(sampled, top_k=0):
    """The cells' sampling (temperature 0.7, top-p 0.9) or greedy, a row
    a slot."""
    sp = (SamplingParams(temperature=0.7, top_k=top_k, top_p=0.9)
          if sampled else SamplingParams.greedy())
    return (np.full((R,), sp.temperature, np.float32),
            np.full((R,), sp.top_k, np.int32),
            np.full((R,), sp.top_p, np.float32),
            np.full((R,), sp.do_sample, bool))


NO_EOS = np.full((R,), -1, np.int32)
SEEDS = np.asarray([1, 2, 3, 4], np.int32)
STEPS0 = np.asarray([0, 3, 1, 7], np.int32)


def _decode_chunk(case, k):
    cfg, params, pool, bt, lora_ids = _setup(case)
    tokens = np.asarray([0, 5, 9, 17], np.int32)
    # slot 3 runs out of budget inside an 8-pass chunk
    budget = np.minimum(k, np.asarray([0, 8, 8, 5])).astype(np.int32)

    def chunk(eos_ids, temps, tks, tps, ds):
        return transformer.paged_decode_chunk(
            params, cfg, k, tokens, pool, bt, CONTEXT, SEEDS, STEPS0,
            temps, tks, tps, ds, budget, eos_ids, DUMMY, lora_ids=lora_ids)
    return chunk, budget


def _spec_chunk(case, k):
    cfg, params, pool, bt, _ = _setup(case)
    rng = np.random.default_rng(5)
    # a repeating history, so that prompt lookup has something to draft
    hist = np.zeros((R, MB * BS + 1), np.int32)
    for r in range(R):
        base = rng.integers(0, cfg.vocab_size, 4)
        hist[r, :CONTEXT[r] + 1] = np.resize(base, CONTEXT[r] + 1)
    tokens = hist[np.arange(R), CONTEXT]
    budget = np.asarray([0, 12, 12, 5], np.int32)
    gammas = np.asarray([GAMMA, GAMMA, 1, 0], np.int32)   # a width mix

    def chunk(eos_ids, temps, tks, tps, ds):
        return transformer.paged_speculative_chunk(
            params, cfg, k, GAMMA, tokens, hist, pool, bt, CONTEXT, SEEDS,
            STEPS0, temps, tks, tps, ds, budget, eos_ids, DUMMY,
            gammas=gammas)
    return chunk, budget


@functools.lru_cache(maxsize=None)
def _forms(make, case, k):
    """One chunk program as toy widths trace it (pre-gathered) and with
    the cap patched to 0 (the in-loop gather): each a jit of a function
    of its own (jit keys its traces on the function, so two jits of one
    function would share the first trace), traced here by a first call.
    eos ids and sampling rows are arguments, so the greedy and the
    sampled case of one (case, k) share the two compiles."""
    chunk, budget = make(case, k)
    rows0 = (NO_EOS,) + _sampling_rows(False)
    with mock.patch.object(transformer, "_layer_gather",
                           side_effect=transformer._layer_gather) as spy:
        pre_fn = jax.jit(lambda *a: chunk(*a))
        pre_fn(*rows0)
        assert spy.call_count == 0, \
            "toy widths were expected under the pre-gather cap"
        with mock.patch.object(transformer, "_PREGATHER_MAX_BYTES", 0):
            loop_fn = jax.jit(lambda *a: chunk(*a))
            loop_fn(*rows0)
        assert spy.call_count > 0, \
            "the patched cap did not select the in-loop gather"
    return pre_fn, loop_fn, budget


def _run_both(make, case, k, rows, eos_of):
    """Both forms on the same inputs, held equal: everything but the
    pool exactly, the pool at tests/test_paged.py's tolerance. Slot 2's
    eos is ``eos_of(tokens)`` of a run without any: a token it really
    emits. Returns the pre-gathered form's outputs and the budgets."""
    pre_fn, loop_fn, budget = _forms(make, case, k)
    probe = jax.device_get(pre_fn(NO_EOS, *rows))
    eos = np.asarray([-1, -1, eos_of(probe[0]), -1], np.int32)
    (*pre, pool_a), (*loop, pool_b) = jax.device_get(
        (pre_fn(eos, *rows), loop_fn(eos, *rows)))
    for x, y in zip(pre, loop):
        np.testing.assert_array_equal(x, y)
    for pa, pb in zip(pool_a.planes(), pool_b.planes()):
        np.testing.assert_allclose(
            np.asarray(pa, np.float32), np.asarray(pb, np.float32),
            rtol=2e-4, atol=2e-4)
    return pre, budget


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "top-p"])
@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("case", list(CASES))
def test_decode_chunk_in_loop_gather_equals_pregathered(case, k, sampled):
    (toks, emits, moe), budget = _run_both(
        _decode_chunk, case, k, _sampling_rows(sampled),
        lambda toks: toks[k // 2, 2])     # dies mid-chunk
    assert not emits[:, 0].any()                      # dead from the start
    assert emits[:, 1].sum() == budget[1]
    assert emits[:, 2].sum() < budget[2]              # died of eos
    assert emits[:, 3].sum() == budget[3]


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "top-k"])
@pytest.mark.parametrize("case", ["gqa-bf16", "gqa-int8-pool"])
def test_speculative_chunk_in_loop_gather_equals_pregathered(case, sampled):
    # accept_rejection_batch covers sampled rows whose top_k lies inside
    # the prefix tier; the cells' top-p-only rows draw one token a pass
    (toks, keeps, eos_seen), _ = _run_both(
        _spec_chunk, case, 3, _sampling_rows(sampled, top_k=20),
        lambda toks: toks[1, 2, 0])
    assert not keeps[:, 0].any()
    assert eos_seen[-1, 2] and not eos_seen[-1, 1]


def test_batcher_on_the_in_loop_gather_emits_the_engines_tokens(monkeypatch):
    """tests/test_batcher.py::test_single_request_matches_engine with the
    cap at 0: the scheduler's own decode programs, traced in the form the
    chip runs, against the dense-cache engine."""
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 13).tolist()
    want = InferenceEngine(cfg, params, max_seq=128).generate(
        [prompt], max_new_tokens=20,
        sampling=SamplingParams.greedy()).tokens[0]

    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    spy = mock.Mock(side_effect=transformer._layer_gather)
    monkeypatch.setattr(transformer, "_layer_gather", spy)
    b = ContinuousBatcher(cfg, params, num_blocks=64, block_size=8,
                          slots=4, max_seq=128)
    r = b.submit(prompt, max_new_tokens=20,
                 sampling=SamplingParams.greedy())
    for _ in range(100):
        b.step()
        if r.done.is_set():
            break
    assert r.wait() == want
    assert spy.call_count > 0
