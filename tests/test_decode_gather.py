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

Both forms stop the pool at the rung of ``transformer._pool_ladder`` that
holds the longest live context (``_pool_rung``, a ``lax.switch`` inside
the program). The rung cases run each rung, forced by the contexts,
against the same program with the ladder patched to ``(mb,)``, the full
extent: the same tokens, the same ``emits``, the same pool. Layers held
one by one (the batcher's MoE layout) have the full extent alone.

The chunk in the in-loop form is also held, on the logits of every live
pass, to ``paged_decode_step`` fed the chunk's own tokens: the one other
formulation of a decode pass, which writes the pool and gathers all of
it every step.
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
    # 4 K/V heads of 128: a one-device pool stores a position's heads in
    # one row of 512 (ops/paged_kvcache.heads_in_rows): the chunk reads
    # the rows as they lie (q zero-expanded), the step form by heads
    "gqa-flat-rows": lambda: (_llama(head_dim=128), None, None),
}
# the same configuration with its layers left stacked, under lax.scan as
# the engine runs them: the pool ladder engages only there, and the
# in-loop gather goes by (layer, block) from the stacked planes. A dense
# layer ahead of the MoE layers makes two scanned segments: the second's
# layer index starts where the first ended
CASES["mla-latent-scanned"] = CASES["mla-latent"]
# two segments again, K and V planes with an int8 pool's two scale planes
# (indexed by layer like the rest), traced per-layer windows
CASES["afmoe-int8-scanned"] = lambda: (
    get_config("tiny-afmoe").replace(
        dtype="bfloat16", attn_backend="xla", kv_quant="int8"), None, None)


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
    if cfg.is_moe and not case.endswith("-scanned"):
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
TOKENS = np.asarray([0, 5, 9, 17], np.int32)   # the plain chunk's input


def _budget(k):
    """Slot 0 is dead from the start; slot 3 runs out of budget inside
    an 8-pass chunk."""
    return np.minimum(k, np.asarray([0, 8, 8, 5])).astype(np.int32)


def _decode_chunk(case, k):
    """The plain chunk as a function of (inputs, eos ids, sampling rows),
    and what makes its inputs from the slots' contexts and budgets."""
    cfg, params, pool, bt, lora_ids = _setup(case)
    budget = _budget(k)

    def inputs(context):
        return np.asarray(context, np.int32), budget

    def chunk(inp, eos_ids, temps, tks, tps, ds):
        context, budget = inp
        return transformer.paged_decode_chunk(
            params, cfg, k, TOKENS, pool, bt, context, SEEDS, STEPS0,
            temps, tks, tps, ds, budget, eos_ids, DUMMY, lora_ids=lora_ids)
    return chunk, inputs, budget


def _spec_chunk(case, k):
    cfg, params, pool, bt, _ = _setup(case)
    budget = np.asarray([0, 12, 12, 5], np.int32)
    gammas = np.asarray([GAMMA, GAMMA, 1, 0], np.int32)   # a width mix

    def inputs(context):
        rng = np.random.default_rng(5)
        # a repeating history, so that prompt lookup has something to draft
        hist = np.zeros((R, MB * BS + 1), np.int32)
        for r in range(R):
            base = rng.integers(0, cfg.vocab_size, 4)
            hist[r, :context[r] + 1] = np.resize(base, context[r] + 1)
        return (np.asarray(context, np.int32), budget,
                hist[np.arange(R), context], hist)

    def chunk(inp, eos_ids, temps, tks, tps, ds):
        context, budget, tokens, hist = inp
        return transformer.paged_speculative_chunk(
            params, cfg, k, GAMMA, tokens, hist, pool, bt, context, SEEDS,
            STEPS0, temps, tks, tps, ds, budget, eos_ids, DUMMY,
            gammas=gammas)
    return chunk, inputs, budget


@functools.lru_cache(maxsize=None)
def _forms(make, case, k, full_extent=False):
    """One chunk program as toy widths trace it (pre-gathered) and with
    the cap patched to 0 (the in-loop gather): each a jit of a function
    of its own (jit keys its traces on the function, so two jits of one
    function would share the first trace), traced here by a first call.
    Contexts, budgets, eos ids and sampling rows are arguments, so every
    rung and the greedy and the sampled case of one (case, k) share the
    two compiles. ``full_extent`` traces both with the ladder patched to
    its top rung alone: the program that reads the whole block table."""
    chunk, inputs, budget = make(case, k)
    args0 = (inputs(CONTEXT), NO_EOS) + _sampling_rows(False)
    ladder = ((lambda mb, scanned=True: (mb,)) if full_extent
              else transformer._pool_ladder)
    with mock.patch.object(transformer, "_layer_gather",
                           side_effect=transformer._layer_gather) as spy, \
            mock.patch.object(transformer, "_pool_ladder", ladder):
        pre_fn = jax.jit(lambda *a: chunk(*a))
        pre_fn(*args0)
        assert spy.call_count == 0, \
            "toy widths were expected under the pre-gather cap"
        with mock.patch.object(transformer, "_PREGATHER_MAX_BYTES", 0):
            loop_fn = jax.jit(lambda *a: chunk(*a))
            loop_fn(*args0)
        assert spy.call_count > 0, \
            "the patched cap did not select the in-loop gather"
    return pre_fn, loop_fn, inputs, budget


def _assert_pools_close(pool_a, pool_b, skip_dummy=False):
    """tests/test_paged.py's tolerance; ``skip_dummy`` leaves out the
    reserved block, where the rows of slots that are not alive land."""
    first = 1 if skip_dummy else 0
    for pa, pb in zip(pool_a.planes(), pool_b.planes()):
        np.testing.assert_allclose(
            np.asarray(pa[:, first:], np.float32),
            np.asarray(pb[:, first:], np.float32), rtol=2e-4, atol=2e-4)


def _run_both(make, case, k, rows, eos_of):
    """Both forms on the same inputs, held equal: everything but the
    pool exactly, the pool at tests/test_paged.py's tolerance. Slot 2's
    eos is ``eos_of(tokens)`` of a run without any: a token it really
    emits. Returns the pre-gathered form's outputs and the budgets."""
    pre_fn, loop_fn, inputs, budget = _forms(make, case, k)
    inp = inputs(CONTEXT)
    probe = jax.device_get(pre_fn(inp, NO_EOS, *rows))
    eos = np.asarray([-1, -1, eos_of(probe[0]), -1], np.int32)
    (*pre, pool_a), (*loop, pool_b) = jax.device_get(
        (pre_fn(inp, eos, *rows), loop_fn(inp, eos, *rows)))
    for x, y in zip(pre, loop):
        np.testing.assert_array_equal(x, y)
    _assert_pools_close(pool_a, pool_b)
    return pre, budget


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "top-p"])
@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("case", list(CASES))
def test_decode_chunk_in_loop_gather_equals_pregathered(case, k, sampled):
    (toks, emits, moe, positions, _), budget = _run_both(
        _decode_chunk, case, k, _sampling_rows(sampled),
        lambda toks: toks[k // 2, 2])     # dies mid-chunk
    # the rung that holds CONTEXT's 30; layers held one by one read it all
    unrolled = isinstance(_setup(case)[1]["layers"], list)
    assert positions == (MB * BS if unrolled else 40)
    assert not emits[:, 0].any()                      # dead from the start
    assert emits[:, 1].sum() == budget[1]
    assert emits[:, 2].sum() < budget[2]              # died of eos
    assert emits[:, 3].sum() == budget[3]


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "top-k"])
@pytest.mark.parametrize("case", ["gqa-bf16", "gqa-int8-pool",
                                  "afmoe-int8-scanned", "gqa-flat-rows"])
def test_speculative_chunk_in_loop_gather_equals_pregathered(case, sampled):
    # accept_rejection_batch covers sampled rows whose top_k lies inside
    # the prefix tier; the cells' top-p-only rows draw one token a pass
    (toks, keeps, eos_seen), _ = _run_both(
        _spec_chunk, case, 3, _sampling_rows(sampled, top_k=20),
        lambda toks: toks[1, 2, 0])
    assert not keeps[:, 0].any()
    assert eos_seen[-1, 2] and not eos_seen[-1, 1]


# -- the chunk against single steps ----------------------------------------

# largest logit error of a live (pass, slot) over the spread of the step
# form's logits there: tests/test_kanana.py's measure and its tolerance
F32_TOL = 2e-4


@functools.lru_cache(maxsize=None)
def _setup_f32(case):
    """_setup's configuration, weights and block tables in float32, a
    float32 (or int8) pool of random rows, and paged_decode_step jitted
    for them. Over an int8 pool the step form would quantize each fresh
    row as it writes it, where the chunk reads its own rows as computed
    until it ends (the side buffers): its steps run over the same
    pool's rows dequantized, in float32, so that both read one set of
    values."""
    cfg, params, _, bt, lora_ids = _setup(case)
    cfg = cfg.replace(dtype="float32")
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)
    pool = step_pool = _random_pool(cfg)
    if pool.quantized:
        from distributed_llm_inferencing_tpu.ops.kvcache import dequant_kv
        step_pool = PagedKVCache(
            k=dequant_kv(pool.k, pool.k_scale, jnp.float32),
            v=dequant_kv(pool.v, pool.v_scale, jnp.float32))
    step_cfg = cfg.replace(kv_quant=None)
    step = jax.jit(lambda *a: transformer.paged_decode_step(
        params, step_cfg, *a, lora_ids=lora_ids))
    return cfg, params, pool, bt, lora_ids, step, step_pool


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("case", list(CASES))
def test_decode_chunk_logits_equal_single_steps(case, k):
    """The chunk in the form the chip's cells take without the kernel
    (the in-loop gather, pool and side rows in one softmax, the pool
    written once at the end) against paged_decode_step, the one other
    formulation of a decode pass (the pool written and gathered whole
    every step; what benchmarks/chip/compare_reference*.py hold to the
    float32 references), fed the chunk's own tokens: every live pass's
    logits, and the pool the passes leave."""
    cfg, params, pool, bt, lora_ids, step, pool_b = _setup_f32(case)
    budget = _budget(k)
    with mock.patch.object(transformer, "_PREGATHER_MAX_BYTES", 0), \
            mock.patch.object(transformer, "_layer_gather",
                              side_effect=transformer._layer_gather) as spy:
        toks, emits, *_, pool_a, logits = jax.device_get(jax.jit(
            lambda pool: transformer.decode_chunk_with_logits(
                params, cfg, k, TOKENS, pool, bt, CONTEXT, SEEDS, STEPS0,
                *_sampling_rows(False), budget, NO_EOS, DUMMY,
                lora_ids=lora_ids))(pool))
    assert spy.call_count > 0, "not the in-loop gather"
    cur = TOKENS
    for t in range(k):
        alive = t < budget
        assert (emits[t] == alive).all()
        want, pool_b = step(cur, pool_b, np.where(alive[:, None], bt, DUMMY),
                            np.where(alive, CONTEXT + t, 0))
        want = np.asarray(want)[alive]
        err = np.abs(logits[t][alive] - want).max(-1) / want.std(-1)
        assert err.max() < F32_TOL, (t, err)
        assert (toks[t][alive] == logits[t][alive].argmax(-1)).all()
        cur = toks[t]
    if not pool.quantized:      # (the chunk's rows go in as int8 levels)
        _assert_pools_close(pool_a, jax.device_get(pool_b), skip_dummy=True)


def test_the_chunk_traces_one_program_whatever_attention_is_asked(
        monkeypatch):
    """``attn_backend`` / ``DLI_ATTENTION`` choose the dense cache's
    flash kernels (ops/attention.resolve_backend); the decode chunk
    reads neither, and how it reads the pool is _pool_kernel's choice."""
    cfg, params, pool, bt, _ = _setup("gqa-bf16")        # tiny-llama

    def jaxpr(attn_backend):
        return str(jax.make_jaxpr(
            lambda pool: transformer.paged_decode_chunk(
                params, cfg.replace(attn_backend=attn_backend), 2, TOKENS,
                pool, bt, CONTEXT, SEEDS, STEPS0, *_sampling_rows(False),
                _budget(2), NO_EOS, DUMMY))(pool))
    want = jaxpr("auto")
    monkeypatch.setenv("DLI_ATTENTION", "pallas")
    assert jaxpr("auto") == want
    monkeypatch.delenv("DLI_ATTENTION")
    assert jaxpr("pallas") == want


# -- the rungs -----------------------------------------------------------

@pytest.mark.parametrize("mb, want", [
    (128, (16, 32, 48, 64, 96, 128)),   # mistral-7b's cells: 256..2048
    (160, (20, 40, 60, 80, 120, 160)),  # kanana's width, were it scanned
    (MB, (1, 2, 3, 5, 6)), (5, (1, 2, 3, 4, 5)), (3, (1, 2, 3)),
    (2, (1, 2)), (1, (1,)),
])
def test_pool_ladder(mb, want):
    assert transformer._pool_ladder(mb) == want
    # layers held one by one: the full extent alone, whatever its size
    assert transformer._pool_ladder(mb, False) == (mb,)


def test_layers_scanned_is_false_where_layers_are_held_one_by_one():
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert transformer._layers_scanned(params, cfg)
    held = dict(params, layers=[
        jax.tree.map(lambda a: a[i], params["layers"])
        for i in range(cfg.num_layers)])
    assert not transformer._layers_scanned(held, cfg)


@pytest.mark.parametrize("need, want", [
    (0, 8), (1, 8), (8, 8),       # a context at a rung's edge stays on it
    (9, 16), (16, 16),
    (17, 24), (24, 24),
    (25, 40), (40, 40),
    (41, 48), (48, 48),
])
def test_pool_rung_is_the_smallest_that_holds_the_longest_live_context(
        need, want):
    ladder = transformer._pool_ladder(MB)
    # the slot with no budget carries a stale, longer context
    context = np.asarray([47, need, 3, 0], np.int32)
    live = np.asarray([False, True, True, True])
    rung, positions = jax.jit(
        lambda c, a: transformer._pool_rung(ladder, BS, c, a))(context, live)
    assert int(positions) == want == ladder[int(rung)] * BS


# positions a rung holds -> contexts that force it (slot 0 has no budget;
# the longest sits at the rung's edge, or one past the rung below)
RUNG_CONTEXTS = {
    8: [0, 5, 8, 3],
    16: [0, 13, 16, 5],
    24: [0, 13, 17, 5],
    40: [0, 13, 25, 30],
    48: [0, 13, 25, 41],      # slot 3's budget of 5 still fits its table
}
RUNG_CASES = ["gqa-bf16", "sinks", "gqa-int8-pool", "mla-latent-scanned",
              "afmoe-int8-scanned"]
# (case, passes a chunk); a chunk of one pass writes its side buffer once
RUNG_CHUNKS = [(c, 4) for c in RUNG_CASES] + [
    ("gqa-int8-pool", 1), ("mla-latent-scanned", 1)]


def _run_rung(make, case, k, in_loop, context, rows, eos_of):
    """One form of the program on one set of contexts, traced with the
    ladder and with the full extent alone: (outputs, the full extent's
    outputs, pool, the full extent's pool)."""
    fns = _forms(make, case, k)
    full = _forms(make, case, k, full_extent=True)
    inp = fns[2](context)
    probe = jax.device_get(full[in_loop](inp, NO_EOS, *rows))
    eos = np.asarray([-1, -1, eos_of(probe[0]), -1], np.int32)
    (*got, pool_a), (*want, pool_b) = jax.device_get(
        (fns[in_loop](inp, eos, *rows), full[in_loop](inp, eos, *rows)))
    return got, want, pool_a, pool_b


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "top-p"])
@pytest.mark.parametrize("form", ["pregathered", "in-loop"])
@pytest.mark.parametrize("positions", list(RUNG_CONTEXTS))
@pytest.mark.parametrize("case, k", RUNG_CHUNKS,
                         ids=[f"{c}-k{k}" for c, k in RUNG_CHUNKS])
def test_decode_chunk_rung_equals_full_extent(case, k, positions, form,
                                              sampled):
    got, want, pool_a, pool_b = _run_rung(
        _decode_chunk, case, k, form == "in-loop",
        RUNG_CONTEXTS[positions], _sampling_rows(sampled),
        lambda toks: toks[k // 2, 2])
    assert got[3] == positions and want[3] == MB * BS
    for x, y in zip(got[1:3], want[1:3]):             # emits, moe
        np.testing.assert_array_equal(x, y)
    # toks: what a slot computes after its eos is nobody's (``emits``
    # masks it) and tiny-afmoe computes something else there from a
    # shorter pool (slot 2's last pass; the parent's program did too):
    # there, hold the passes a slot was alive in, elsewhere every token
    emits = got[1].astype(bool)
    dead_differ = case == "afmoe-int8-scanned"
    alive = (np.vstack([emits[:1] | True, emits[:-1]]) if dead_differ
             else np.ones_like(emits))
    np.testing.assert_array_equal(np.where(alive, got[0], 0),
                                  np.where(alive, want[0], 0))
    assert got[1][:, 1].all() and not got[1][:, 2].all()   # eos took slot 2
    # a dead slot's rows land in the reserved block
    _assert_pools_close(pool_a, pool_b, skip_dummy=dead_differ)


@pytest.mark.parametrize("form", ["pregathered", "in-loop"])
def test_layers_held_one_by_one_read_the_full_extent(form):
    """The batcher's layout for MoE layers (a list, run unrolled): the
    ladder is the full extent alone, so the program is the one with no
    switch and says so, whatever the contexts."""
    k = 4
    got, want, pool_a, pool_b = _run_rung(
        _decode_chunk, "mla-latent", k, form == "in-loop", RUNG_CONTEXTS[8],
        _sampling_rows(False), lambda toks: toks[k // 2, 2])
    assert got[3] == want[3] == MB * BS
    for x, y in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(x, y)
    for a, b in zip(pool_a.planes(), pool_b.planes()):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "top-k"])
@pytest.mark.parametrize("form", ["pregathered", "in-loop"])
@pytest.mark.parametrize("positions", list(RUNG_CONTEXTS))
@pytest.mark.parametrize("case", RUNG_CASES[:3])   # it carries no latent pool
def test_speculative_chunk_rung_equals_full_extent(case, positions, form,
                                                   sampled):
    got, want, pool_a, pool_b = _run_rung(
        _spec_chunk, case, 3, form == "in-loop", RUNG_CONTEXTS[positions],
        _sampling_rows(sampled, top_k=20), lambda toks: toks[1, 2, 0])
    for x, y in zip(got, want):                       # toks, keeps, eos_seen
        np.testing.assert_array_equal(x, y)
    assert got[1][:, 1].any() and got[2][-1, 2]
    _assert_pools_close(pool_a, pool_b)


@pytest.mark.parametrize("form", ["pregathered", "in-loop"])
@pytest.mark.parametrize("make", [_decode_chunk, _spec_chunk],
                         ids=["plain", "speculative"])
def test_a_slot_without_budget_does_not_raise_the_rung(make, form):
    """Slot 0 has no budget and a stale context of 40, three rungs past
    the live slots' 16. The program takes the rung of 16, and every live
    slot reads as with the full extent."""
    plain = make is _decode_chunk
    k = 4 if plain else 3
    got, want, pool_a, pool_b = _run_rung(
        make, "gqa-bf16", k, form == "in-loop", [40, 13, 16, 5],
        _sampling_rows(True, top_k=20),
        lambda toks: toks[k // 2, 2] if plain else toks[1, 2, 0])
    if plain:
        assert got[3] == 16
    live = got[1].astype(bool)            # emits [K, R], or keeps > 0
    assert not live[:, 0].any() and live[:, 1:].any(axis=0).all()
    np.testing.assert_array_equal(got[1], want[1])
    mask = live if plain else live[..., None]     # toks [K, R(, G+1)]
    np.testing.assert_array_equal(np.where(mask, got[0], 0),
                                  np.where(mask, want[0], 0))
    _assert_pools_close(pool_a, pool_b, skip_dummy=True)


def _cond_operand_shapes(jaxpr):
    """The shape of every operand of every ``cond`` (lax.switch) of a
    jaxpr, those of nested jaxprs (the scans' bodies) included."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            shapes += [tuple(v.aval.shape) for v in eqn.invars[1:]]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            shapes += _cond_operand_shapes(sub)
    return shapes


@pytest.mark.parametrize("make, case", [
    (_decode_chunk, "gqa-bf16"), (_decode_chunk, "gqa-int8-pool"),
    (_decode_chunk, "mla-latent-scanned"),
    (_spec_chunk, "gqa-bf16"), (_spec_chunk, "gqa-int8-pool"),
], ids=["plain-bf16", "plain-int8-pool", "plain-latent-two-segments",
        "speculative-bf16", "speculative-int8-pool"])
def test_no_operand_of_the_switch_is_one_layers_pool(make, case):
    """A conditional takes its operands as buffers: a layer's slice of
    the pool handed to the rung's lax.switch is copied out of the stack
    on every pass of every layer (3 ms of mistral-7b's 17.9 ms pass on a
    v5e: PERF.md section 6, PR 36). Under a layer scan the in-loop
    gather's branches take the stacked planes as they lie and the layer's
    index."""
    chunk, inputs, _ = make(case, 3)
    args = (inputs(CONTEXT), NO_EOS) + _sampling_rows(True, top_k=20)
    with mock.patch.object(transformer, "_PREGATHER_MAX_BYTES", 0):
        shapes = set(_cond_operand_shapes(jax.make_jaxpr(chunk)(*args).jaxpr))
    planes = _setup(case)[2].planes()
    assert {tuple(p.shape) for p in planes} <= shapes
    assert () in shapes                               # the layer's index
    assert not {tuple(p.shape[1:]) for p in planes} & shapes


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


# -- the admit program's two ways to the pool ----------------------------
# transformer.paged_prefill_tail gathers a layer's prefix from the
# stacked pool by (layer, block) and writes every layer's tail once after
# the stack; conftest.paged_prefill_tail_per_layer_write is the plain
# form it replaced (each layer writes its slice and attends from it, the
# slices come back re-stacked). Same pool, same waves: the first-token
# logits and every plane are equal bit for bit, and no block but a
# wave's tail blocks and the reserved one changes.

T = 2 * BS            # a tail bucket of two blocks
PB = 4                # prefix columns: 24 cached positions and a dummy pad

# wave -> rows of (tail_len, tail blocks, prefix blocks, prefix_len); bt
# row r is slot r's MB blocks. "fresh": two prompts without a prefix, one
# ending inside its second block, and a padding row (tail_len 1, every
# block the reserved one). "cached": a tail over a cached prefix of three
# random blocks (24 positions: longer than layer-windows' 8 and 16), a
# prompt's second chunk over the chunk the fresh wave wrote, and a
# padding row between them.
WAVES = {
    "fresh": lambda bt: [
        (T, bt[0, :2], [], 0), (11, bt[2, :2], [], 0), (1, [], [], 0)],
    "cached": lambda bt: [
        (T, bt[1, 3:5], bt[1, :3], 3 * BS), (1, [], [], 0),
        (9, bt[0, 2:4], bt[0, :2], T)],
}


def _wave_inputs(cfg, rows):
    rng = np.random.default_rng(17)
    toks = rng.integers(0, cfg.vocab_size, (len(rows), T)).astype(np.int32)
    tail_blocks = np.full((len(rows), T // BS), DUMMY, np.int32)
    prefix_blocks = np.full((len(rows), PB), DUMMY, np.int32)
    for i, (_, tb, pfb, _) in enumerate(rows):
        tail_blocks[i, :len(tb)] = tb
        prefix_blocks[i, :len(pfb)] = pfb
    return (toks, np.asarray([r[0] for r in rows], np.int32), tail_blocks,
            prefix_blocks, np.asarray([r[3] for r in rows], np.int32))


@functools.lru_cache(maxsize=None)
def _tail_forms(case):
    from conftest import paged_prefill_tail_per_layer_write
    cfg, params, _, _, lora_ids = _setup(case)
    ids = None if lora_ids is None else lora_ids[:3]
    return tuple(
        jax.jit(lambda *a, f=f: f(params, cfg, *a, lora_ids=ids))
        for f in (transformer.paged_prefill_tail,
                  paged_prefill_tail_per_layer_write))


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_tail_equals_the_per_layer_write(case):
    cfg, _, pool, bt, _ = _setup(case)
    new_fn, plain_fn = _tail_forms(case)
    new_pool = plain_pool = pool
    for wave in ("fresh", "cached"):
        rows = WAVES[wave](bt)
        inputs = _wave_inputs(cfg, rows)
        before = new_pool
        (new_logits, new_pool), (plain_logits, plain_pool) = jax.device_get(
            (new_fn(*inputs, new_pool), plain_fn(*inputs, plain_pool)))
        # (a flat pool's tail attends the rows as they lie, q expanded
        # to a row, where the plain form views them by heads: bf16's
        # rounding apart, not bit for bit)
        same = (np.testing.assert_array_equal if case != "gqa-flat-rows"
                else functools.partial(np.testing.assert_allclose,
                                       rtol=2e-2, atol=2e-2))
        same(new_logits, plain_logits)
        assert np.isfinite(new_logits).all()
        written = sorted({int(b) for r in rows for b in r[1]})
        kept = [b for b in range(1, 1 + R * MB) if b not in written]
        for got, want, was in zip(new_pool.planes(), plain_pool.planes(),
                                  jax.device_get(before).planes()):
            # every block but the reserved one, where padding rows land
            same(np.asarray(got[:, 1:], np.float32),
                 np.asarray(want[:, 1:], np.float32))
            np.testing.assert_array_equal(got[:, kept], was[:, kept])
            assert (got[:, written] != was[:, written]).any()


def _made(jaxpr):
    """(primitive, shape) of every output of every equation of a jaxpr,
    nested jaxprs (scans' and branches' bodies, jitted helpers) included."""
    out = []
    for eqn in jaxpr.eqns:
        out += [(eqn.primitive.name, tuple(v.aval.shape))
                for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _made(sub)
    return out


def _assert_pool_is_read_where_it_lies(jaxpr, planes):
    """No equation yields one layer's plane, and a whole plane comes only
    out of the scatter that writes it (and the loops and jitted helpers
    that hand it on)."""
    made = _made(jaxpr)
    layer = {tuple(p.shape[1:]) for p in planes}
    whole = {tuple(p.shape) for p in planes}
    assert not [m for m in made if m[1] in layer]
    makers = {name for name, shape in made if shape in whole}
    assert "scatter" in makers
    assert makers <= {"scatter", "pjit", "scan", "while", "cond"}, makers


@pytest.mark.parametrize("case", ["moe", "mla-latent"])
def test_layers_held_one_by_one_take_no_slice_of_the_pool(case):
    """Where layers are held one by one (the batcher's MoE layers) the
    decode chunk took static slices of the stacked pool, which XLA
    hoisted out of the token loop and re-laid out, the whole pool once a
    chunk (kanana: 19 ms a chunk of 8 passes; PERF.md section 6, PR 38).
    Now a layer takes its index, as under a scan."""
    chunk, inputs, _ = _decode_chunk(case, 3)
    args = (inputs(CONTEXT), NO_EOS) + _sampling_rows(True)
    with mock.patch.object(transformer, "_PREGATHER_MAX_BYTES", 0):
        jaxpr = jax.make_jaxpr(chunk)(*args).jaxpr
    _assert_pool_is_read_where_it_lies(jaxpr, _setup(case)[2].planes())


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_tail_takes_no_layers_plane_in_or_out(case):
    """The admit program's layer stack neither takes the pool's planes
    layer by layer nor gives them back re-stacked: a donated pool that
    went in as slices and came out of a stack could not be written in
    place, and every wave copied it whole (mistral: `copy.123`,
    `copy.124` and a slice and an update a layer)."""
    cfg, params, pool, bt, lora_ids = _setup(case)
    ids = None if lora_ids is None else lora_ids[:3]
    jaxpr = jax.make_jaxpr(
        lambda *a: transformer.paged_prefill_tail(
            params, cfg, *a, lora_ids=ids))(
        *_wave_inputs(cfg, WAVES["cached"](bt)), pool).jaxpr
    _assert_pool_is_read_where_it_lies(jaxpr, pool.planes())
