"""Trinity (afmoe) at toy widths, `tiny-afmoe`: a leading dense layer and
one period of three windowed rotary layers and one full layer without
rotation, gated attention, q/k norms, four norms a layer, sigmoid-routed
experts + one shared. The float32 reference is
models/reference/afmoe_ref.py, which imports nothing from the package."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import convert, transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.reference import afmoe_ref
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops import paged_kvcache
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    init_paged_cache)
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime import batcher as batcher_mod
from distributed_llm_inferencing_tpu.runtime.batcher import (
    ContinuousBatcher, _unstack_layers)
from distributed_llm_inferencing_tpu.utils import trace

WINDOW = 8           # tiny-afmoe's; contexts below are 1, 3 and 6 of them
BS = 4


def cfg32():
    return get_config("tiny-afmoe").replace(dtype="float32",
                                            attn_backend="xla")


@pytest.fixture(scope="module")
def params():
    return init_params(cfg32(), jax.random.PRNGKey(0), dtype=jnp.float32)


def held_one_by_one(params):
    """The tree as the batcher holds it: MoE layers a list."""
    out = jax.tree.map(lambda a: a, params)
    out["layers"] = _unstack_layers(out["layers"])
    return out


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, n).astype(np.int32)


def spread(ref):
    return float(np.asarray(ref).std())


def ref_logits(cfg, params, toks):
    """The reference's logits at every position, one jit a call: run op
    by op it compiles a program for every layer's loop on every call."""
    arch = afmoe_ref.arch_of(cfg)
    return np.asarray(jax.jit(
        lambda p, t: afmoe_ref.forward(p, arch, t))(params, jnp.asarray(toks)))


def dense_logits(cfg, params, toks):
    cache = init_cache(cfg, 1, 64, dtype=jnp.dtype(cfg.dtype))
    logits, _ = transformer.prefill(params, cfg, jnp.asarray(toks[None]),
                                    jnp.asarray([len(toks)]), cache)
    return np.asarray(logits[0], np.float32)


# ---- (a) forward and the paged serving path against the reference -------

@pytest.mark.parametrize("windows", [1, 3, 6])
def test_forward_matches_the_reference(params, windows):
    cfg = cfg32()
    toks = tokens(windows * WINDOW, seed=windows)
    ref = ref_logits(cfg, params, toks)
    got = dense_logits(cfg, params, toks)
    assert np.abs(got - ref).max() < 1e-4 * spread(ref)
    # the tolerance has teeth: the same weights and tokens in bf16 fail it
    bf = cfg.replace(dtype="bfloat16")
    low = dense_logits(bf, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 else a, params), toks)
    assert np.abs(low - ref).max() > 1e-4 * spread(ref)


@pytest.mark.parametrize("windows", [1, 3, 6])
def test_paged_logits_match_the_reference(params, windows, monkeypatch):
    """A tail over a cached prefix (paged_prefill_tail twice) and decode
    steps behind it (paged_decode_step), on logits."""
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    cfg = cfg32()
    held = held_one_by_one(params)
    n_pre, n_tail, steps = windows * WINDOW, 6, 3
    seq = tokens(n_pre + n_tail + steps, seed=10 + windows)
    ref = ref_logits(cfg, params, seq)
    prefill = jax.jit(lambda *a: transformer.paged_prefill_tail(
        held, cfg, *a))
    step = jax.jit(lambda *a: transformer.paged_decode_step(held, cfg, *a))
    paged = init_paged_cache(cfg, 64, BS, dtype=jnp.float32)
    pre_blocks = np.arange(1, 1 + n_pre // BS)
    tail_blocks = np.arange(20, 22)
    _, paged = prefill(
        jnp.asarray(seq[None, :n_pre]), jnp.asarray([n_pre]),
        jnp.asarray(pre_blocks[None]), jnp.zeros((1, 1), jnp.int32),
        jnp.asarray([0]), paged)
    tail = np.zeros((1, 8), np.int32)
    tail[0, :n_tail] = seq[n_pre:n_pre + n_tail]
    pfb = np.zeros((1, 16), np.int32)
    pfb[0, :len(pre_blocks)] = pre_blocks
    logits, paged = prefill(
        jnp.asarray(tail), jnp.asarray([n_tail]),
        jnp.asarray(tail_blocks[None]), jnp.asarray(pfb),
        jnp.asarray([n_pre]), paged)
    n = n_pre + n_tail
    assert np.abs(np.asarray(logits[0]) - ref[n - 1]).max() \
        < 1e-4 * spread(ref)
    table = np.zeros((1, 16), np.int32)
    table[0, :len(pre_blocks)] = pre_blocks
    table[0, len(pre_blocks):len(pre_blocks) + 2] = tail_blocks
    table[0, len(pre_blocks) + 2] = 30
    for t in range(steps):
        logits, paged = step(
            jnp.asarray(seq[n + t:n + t + 1]), paged,
            jnp.asarray(table), jnp.asarray([n + t]))
        assert np.abs(np.asarray(logits[0]) - ref[n + t]).max() \
            < 1e-4 * spread(ref)


def serve(cfg, prompts, new=12, slots=4, cap=8, **kw):
    b = ContinuousBatcher(cfg, None, seed=0, slots=slots, num_blocks=128,
                          block_size=BS, max_seq=128, prefill_chunk=4,
                          decode_chunk_cap=cap, kv_host_mb=0, **kw)
    greedy = SamplingParams.greedy()
    reqs = [b.submit(p, max_new_tokens=new, sampling=greedy, seed=0)
            for p in prompts]
    order = []
    while b.inflight():
        before = [r.first_token_at is not None for r in reqs]
        b.step()
        order += [i for i, r in enumerate(reqs)
                  if r.first_token_at is not None and not before[i]]
    return b, reqs, order


@pytest.mark.parametrize("cap", [1, 8])
def test_the_batcher_serves_what_the_reference_computes(cap, monkeypatch):
    """submit -> chunked admission of a shared prefix of 6 windows, a tail
    over the cached prefix (the second and third prompts hit it in the
    radix cache), decode chunks of `cap` with the windowed read: greedy
    tokens are the reference's argmax at every position."""
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    cfg = cfg32()
    shared = tokens(6 * WINDOW, seed=3).tolist()
    prompts = [shared + tokens(n, seed=n).tolist() for n in (9, 5, 7)]
    b, reqs, _ = serve(cfg, prompts, cap=cap)
    for r in reqs:
        assert r.error is None and len(r.tokens) == 12
        seq = r.prompt + r.tokens
        ref = ref_logits(cfg, b.params, seq[:-1])
        assert np.argmax(ref[len(r.prompt) - 1:], -1).tolist() == r.tokens
    c = b.metrics.snapshot()["counters"]
    assert c["prefill_cached_tokens"] == 2 * len(shared)
    # a windowed layer read ceil(8 / 4) + 1 columns of the 32 a slot has
    assert c["batcher_decode_window_positions"] * 128 \
        == c["batcher_decode_pool_positions"] * 12
    chunk = [s for s in trace.get_tracer().spans()
             if s.name == "batcher.decode_chunk"][-1]
    assert (chunk.attrs["pool_positions"],
            chunk.attrs["window_positions"]) == (128, 12)


# ---- (b) the windowed read is the masked full read ---------------------
# Every position the bounded read leaves out has weight exactly zero in
# the masked read, so the two sum the same terms; XLA sums a row of
# another length in another order, and the results differ in the last
# bit of a float32 (6e-8 on logits of 0.25). ROUNDING holds them to that.
ROUNDING = 1e-6


def same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max()) <= ROUNDING * max(
        1.0, float(np.abs(b).max()))


SPARE = 70           # first of two blocks filled_pool leaves unused


def filled_pool(cfg, params, contexts, mb):
    """A pool holding `contexts[r]` positions of slot r (prefilled a block
    at a time through paged_prefill_tail), its tables and last tokens."""
    paged = init_paged_cache(cfg, SPARE + 2, BS, dtype=jnp.float32)
    assert 1 + len(contexts) * mb <= SPARE
    tables = np.zeros((len(contexts), mb), np.int32)
    prefill = jax.jit(lambda *a: transformer.paged_prefill_tail(
        params, cfg, *a))
    for r, n in enumerate(contexts):
        tables[r] = 1 + r * mb + np.arange(mb)
        seq = tokens(n, seed=100 + r)
        for c in range(0, n, BS):
            row = np.zeros((1, BS), np.int32)
            real = min(BS, n - c)
            row[0, :real] = seq[c:c + real]
            pfb = tables[r:r + 1, :max(c // BS, 1)]
            _, paged = prefill(
                jnp.asarray(row), jnp.asarray([real]),
                jnp.asarray(tables[r:r + 1, c // BS:c // BS + 1]),
                jnp.asarray(pfb), jnp.asarray([c]), paged)
    return paged, tables


def decode_chunk(cfg, params, paged, tables, contexts, k):
    """One chunk under a jit of its own (traced anew at every call, so
    that a patched window_read takes)."""
    r = len(contexts)
    z = jnp.zeros((r,), jnp.int32)
    return jax.jit(lambda *a: transformer.paged_decode_chunk(
        params, cfg, k, *a, 0))(
        jnp.asarray(tokens(r, seed=7)), paged,
        jnp.asarray(tables), jnp.asarray(contexts, jnp.int32), z, z,
        jnp.ones((r,), jnp.float32), z, jnp.ones((r,), jnp.float32),
        jnp.zeros((r,), bool), z + k, z - 1)


def test_decode_chunk_windowed_read_is_the_masked_read(params, monkeypatch):
    """Slots shorter than the window, inside their second block, one
    position short of a block's end (so the chunk crosses it) and at 6
    windows; the bounded read against the masked read of the whole
    table: the same tokens, and every layer's written K and V to a
    float32's rounding."""
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    cfg, held = cfg32(), held_one_by_one(params)
    contexts, mb, k = [5, 6, 23, 48], 16, 8
    paged, tables = filled_pool(cfg, held, contexts, mb)
    toks, emits, _, pool_pos, win_pos, got = decode_chunk(
        cfg, held, paged, tables, contexts, k)
    assert (int(pool_pos), int(win_pos)) == (mb * BS, 3 * BS)
    monkeypatch.setattr(paged_kvcache, "window_read",
                        lambda *a, **kw: None)
    toks_f, emits_f, _, _, win_f, want = decode_chunk(
        cfg, held, paged, tables, contexts, k)
    assert int(win_f) == mb * BS        # nothing was bounded
    assert np.array_equal(toks, toks_f) and np.asarray(emits).all()
    for a, b_ in zip(got.planes(), want.planes()):
        assert same(a, b_)


@pytest.mark.parametrize("prefix_len", [3, 16, 45])
def test_tail_prefill_windowed_prefix_read_is_the_masked_read(
        params, prefix_len, monkeypatch):
    """A tail over a cached prefix shorter than the window, of two
    windows and of nearly six: logits and pool to a float32's rounding
    (bit for bit where the prefix bucket is read whole)."""
    cfg, held = cfg32(), held_one_by_one(params)
    mb = 16
    paged, tables = filled_pool(cfg, held, [prefix_len], mb)
    cached = prefix_len // BS * BS      # the radix cache holds whole blocks
    tail = np.zeros((2, 8), np.int32)
    tail[0, :7] = tokens(7, seed=9)
    pfb = np.zeros((2, mb), np.int32)
    pfb[0, :cached // BS] = tables[0, :cached // BS]

    def run():      # a jit of its own: a patched window_read takes
        return jax.jit(lambda *a: transformer.paged_prefill_tail(
            held, cfg, *a))(
            jnp.asarray(tail), jnp.asarray([7, 1]),
            jnp.asarray([[SPARE, SPARE + 1], [0, 0]]), jnp.asarray(pfb),
            jnp.asarray([cached, 0]), paged)
    logits, got = run()
    monkeypatch.setattr(paged_kvcache, "window_read",
                        lambda *a, **kw: None)
    logits_f, want = run()
    assert same(logits[0], logits_f[0])
    for a, b_ in zip(got.planes(), want.planes()):
        assert same(a[:, SPARE:], b_[:, SPARE:])


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["f32", "int8"])
@pytest.mark.parametrize("prefix_len", [3, 16, 45])
def test_tail_prefill_equals_the_per_layer_write(params, prefix_len,
                                                 kv_quant):
    """paged_prefill_tail (the prefix gathered from the stacked pool by
    (layer, block), a windowed layer's bounded columns likewise, one
    write after the stack) against the plain form it replaced
    (conftest.paged_prefill_tail_per_layer_write): layers held one by
    one, the dense layer ahead of them, a prefix shorter than the
    window, of two windows and of nearly six, a padding row beside the
    real one, a float32 pool and an int8 one with its scale planes.
    First-token logits and every plane bit for bit, the reserved block
    aside."""
    from conftest import paged_prefill_tail_per_layer_write
    cfg, held = cfg32().replace(kv_quant=kv_quant), held_one_by_one(params)
    mb = 16
    paged, tables = filled_pool(cfg, held, [prefix_len], mb)
    cached = prefix_len // BS * BS
    tail = np.zeros((2, 8), np.int32)
    tail[0, :7] = tokens(7, seed=9)
    pfb = np.zeros((2, mb), np.int32)
    pfb[0, :cached // BS] = tables[0, :cached // BS]
    (logits, got), (logits_p, want) = (
        jax.jit(lambda *a, f=f: f(held, cfg, *a))(
            jnp.asarray(tail), jnp.asarray([7, 1]),
            jnp.asarray([[SPARE, SPARE + 1], [0, 0]]), jnp.asarray(pfb),
            jnp.asarray([cached, 0]), paged)
        for f in (transformer.paged_prefill_tail,
                  paged_prefill_tail_per_layer_write))
    assert len(got.planes()) == (4 if kv_quant else 2)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_p))
    for a, b_, was in zip(got.planes(), want.planes(), paged.planes()):
        np.testing.assert_array_equal(np.asarray(a[:, 1:]),
                                      np.asarray(b_[:, 1:]))
        np.testing.assert_array_equal(np.asarray(a[:, 1:SPARE]),
                                      np.asarray(was[:, 1:SPARE]))
        assert (np.asarray(a[:, SPARE:]) != np.asarray(was[:, SPARE:])).any()


@pytest.mark.parametrize("form", ["pre-gathered", "windowed-read"])
def test_flat_rows_serve_what_the_heads_axis_serves(form, monkeypatch):
    """tiny-afmoe with K/V heads of 128 (2 of them: rows of 256), its MoE
    layers held one by one as the batcher holds them: a one-device pool
    stores a position's heads side by side (ops/paged_kvcache.
    heads_in_rows), a mesh's keeps them as an axis. conftest.
    flat_rows_scenario over both: the wave's write, a tail over a prefix
    hit through the windowed layers' bounded prefix read, a chunked
    prompt's next chunk, then the decode chunk over the pre-gathered
    table and over the in-loop gather with the windowed layers' bounded
    read (contexts of 21 and 24 behind a window of 8), the full layer
    without rotation, the gate on: the same tokens, logits and planes to
    a float32's rounding."""
    from conftest import (
        assert_flat_rows_serve_the_same, flat_rows_scenario)
    if form != "pre-gathered":
        monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    cfg = cfg32().replace(head_dim=128)
    held = held_one_by_one(
        init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    flat, by_heads = (flat_rows_scenario(held, cfg, devices)
                      for devices in (1, 4))
    assert_flat_rows_serve_the_same(flat, by_heads)
    # (pool positions, window positions): the bounded read was taken
    assert (int(flat[2][3]), int(flat[2][4])) == (
        (64, 64) if form == "pre-gathered" else (64, 16))


def test_a_scanned_stack_keeps_the_traced_window(params):
    """Stacked layers of mixed windows under one scan keep the traced
    leaf; a segment of one window, and a layer on its own, get a
    constant."""
    cfg = cfg32()
    seg = transformer._static_window_cfg
    assert seg(cfg, cfg, 1, 4).attn_windows == cfg.attn_windows
    one = seg(cfg, cfg, 0, 1)
    assert (one.attn_windows, one.sliding_window) == (None, WINDOW)
    full = seg(cfg, cfg, 4, 1)
    assert (full.attn_windows, full.sliding_window) == (None, None)
    assert transformer._layer_kind(cfg, None) == "full"
    assert transformer._layer_kind(cfg, WINDOW) == "win"
    assert transformer._layer_kind(cfg.replace(
        attn_windows=None, rope_layers=None), None) is None


# ---- (c) the gate --------------------------------------------------------

def test_a_zero_gate_halves_the_attention_output(params):
    """sigmoid(0) = 1/2: with attn_gate's weights zero the model is the
    ungated one with its o projection halved."""
    cfg = cfg32()
    toks = tokens(20, seed=5)

    def edit(tree, fn):
        out = jax.tree.map(lambda a: a, tree)
        for seg in ("layers", "layers_dense"):
            fn(out[seg])
        return out
    zero = edit(params, lambda lp: lp["attn_gate"].update(
        w=jnp.zeros_like(lp["attn_gate"]["w"])))

    def halve(lp):
        del lp["attn_gate"]
        lp["o"] = {"w": lp["o"]["w"] * 0.5}
    np.testing.assert_allclose(
        dense_logits(cfg, zero, toks),
        dense_logits(cfg.replace(attn_gate=False), edit(params, halve), toks),
        rtol=0, atol=1e-6)
    assert np.abs(dense_logits(cfg, zero, toks)
                  - dense_logits(cfg, params, toks)).max() > 1e-3


# ---- (d) the wave bound --------------------------------------------------

def test_a_wave_over_a_long_prefix_is_cut(monkeypatch):
    """Four prompts behind one cached prefix of 12 blocks: under a budget
    of two rows a wave they are admitted two a step, in order, the cut
    ones first next step, and generate what the unbounded order does."""
    cfg = cfg32()
    shared = tokens(12 * BS, seed=4).tolist()
    prompts = [shared + tokens(5, seed=20 + i).tolist() for i in range(4)]
    warm = [shared + [7]]                    # leaves the prefix cached

    def run(budget):
        monkeypatch.setattr(batcher_mod, "WAVE_SCORE_BUDGET", budget)
        trace.get_tracer().clear()
        b, reqs, order = serve(cfg, warm + prompts, new=6, slots=8)
        waves = [s.attrs for s in trace.get_tracer().spans()
                 if s.name == "batcher.admit_wave"
                 and s.attrs["prefix_bucket"] == 16
                 and s.attrs["tail_bucket"] == 8]
        return b, reqs, order, waves
    # an 8-token tail over a 16-block prefix bucket: 2 rows fit, 4 do not
    whole = batcher_mod.WAVE_SCORE_BUDGET
    b, reqs, order, waves = run(2 * 8 * (16 * BS + 8))
    assert order[1:] == [1, 2, 3, 4]
    assert [w["members"] for w in waves[-2:]] == [2, 2]
    assert waves[-2]["bounded"] == 1 and waves[-1]["bounded"] == 0
    assert b.metrics.snapshot()["counters"][
        "batcher_admit_waves_bounded"] == 1
    assert waves[-1]["prefix_positions"] == 2 * len(shared)
    assert (waves[-1]["gathered_full"], waves[-1]["gathered_win"]) \
        == (2 * 16 * BS, 2 * 3 * BS)
    free, reqs_free, _, waves_free = run(whole)
    assert waves_free[-1]["members"] == 4 and waves_free[-1]["bounded"] == 0
    assert free.metrics.snapshot()["counters"][
        "batcher_admit_waves_bounded"] == 0
    assert [r.tokens for r in reqs] == [r.tokens for r in reqs_free]


def test_the_accepted_cells_widest_wave_is_inside_the_budget():
    """64 rows of a 512 tail over no cached prefix (one dummy block of
    16) is what kanana's cell forms; 2 rows over 512 blocks trinity's."""
    assert 64 * 512 * (1 * 16 + 512) <= batcher_mod.WAVE_SCORE_BUDGET
    assert 2 * 512 * (512 * 16 + 512) <= batcher_mod.WAVE_SCORE_BUDGET \
        < 4 * 512 * (512 * 16 + 512)


# ---- the attention backend is not the batcher's ----------------------------

@pytest.mark.parametrize("asked,env", [
    ("auto", {"DLI_ATTENTION": "pallas"}),
    ("pallas", {}),
])
def test_a_request_for_pallas_attention_serves_as_auto_does(asked, env,
                                                            monkeypatch):
    """Per-layer windows: the in-loop gather either way."""
    from conftest import served_as_under_auto
    served_as_under_auto(lambda attn_backend: ContinuousBatcher(
        cfg32().replace(attn_backend=attn_backend), None, slots=2,
        num_blocks=16, block_size=BS, max_seq=32, kv_host_mb=0),
        asked, env, monkeypatch)


def test_the_registry_has_the_source_sizes():
    cfg = get_config("trinity-mini")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (32, 2048, 32, 4, 128, 200192)
    assert cfg.attn_windows == ((2048,) * 3 + (None,)) * 8
    assert cfg.rope_layers == ((1,) * 3 + (0,)) * 8
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_shared_experts,
            cfg.dense_prefix_layers, cfg.moe_routed_scale) \
        == (128, 8, 1, 2, 2.826)
    assert cfg.attn_gate and cfg.qk_norm == "rms_head" \
        and cfg.post_block_norms and cfg.embed_scale == 2048 ** 0.5
    # the benchmark's cut: a dense layer and one period
    cut = cfg.replace(num_layers=5, dense_prefix_layers=1,
                      attn_windows=[2048] * 4 + [None],
                      rope_layers=[1, 1, 1, 1, 0])
    assert cut.dense_segment_cfg().attn_windows == (2048,)
    assert cut.moe_segment_cfg().rope_layers == (1, 1, 1, 0)


# ---- (e) an HF-named state dict through convert.py -----------------------

def hf_state_dict(cfg, params):
    """The tree under transformers' afmoe names (modeling_afmoe.py),
    linear weights transposed to torch's [out, in]."""
    sd = {"model.embed_tokens.weight": params["embed"]["tokens"],
          "model.norm.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"]["w"].T}
    nd = cfg.dense_prefix_layers
    for i in range(cfg.num_layers):
        seg, j = (("layers_dense", i) if i < nd else ("layers", i - nd))
        lp = jax.tree.map(lambda a: a[j], params[seg])
        p = f"model.layers.{i}."
        for ours, theirs in (("attn_norm", "input_layernorm"),
                             ("attn_post_norm", "post_attention_layernorm"),
                             ("mlp_norm", "pre_mlp_layernorm"),
                             ("mlp_post_norm", "post_mlp_layernorm"),
                             ("q_norm", "self_attn.q_norm"),
                             ("k_norm", "self_attn.k_norm")):
            sd[p + theirs + ".weight"] = lp[ours]["scale"]
        for ours, theirs in (("q", "q_proj"), ("k", "k_proj"),
                             ("v", "v_proj"), ("o", "o_proj"),
                             ("attn_gate", "gate_proj")):
            sd[p + f"self_attn.{theirs}.weight"] = lp[ours]["w"].T
        if i < nd:
            for nm in ("gate", "up", "down"):
                sd[p + f"mlp.{nm}_proj.weight"] = lp[nm]["w"].T
            continue
        sd[p + "mlp.router.gate.weight"] = lp["router"]["w"].T
        sd[p + "mlp.expert_bias"] = lp["router"]["bias"]
        for nm in ("gate", "up", "down"):
            sd[p + f"mlp.shared_experts.{nm}_proj.weight"] = \
                lp[f"shared_{nm}"]["w"].T
            for e in range(cfg.num_experts):
                sd[p + f"mlp.experts.{e}.{nm}_proj.weight"] = \
                    lp["experts"][nm]["w"][e].T
    return {k: np.asarray(v) for k, v in sd.items()}


def test_an_hf_named_state_dict_converts_to_the_reference_logits(params):
    cfg = cfg32()
    hf = types.SimpleNamespace(
        model_type="afmoe", name_or_path="tiny-afmoe", vocab_size=256,
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=5, num_dense_layers=1, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, max_position_embeddings=256,
        rms_norm_eps=1e-5, hidden_act="silu", rope_theta=10000.0,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        sliding_window=WINDOW, mup_enabled=True, num_experts=16,
        num_experts_per_tok=4, score_func="sigmoid", route_norm=True,
        route_scale=2.826, num_shared_experts=1, tie_word_embeddings=False)
    got_cfg = convert.config_from_hf(hf)
    assert got_cfg.replace(dtype="float32", attn_backend="xla") == cfg
    got = convert.convert_state_dict(got_cfg, hf_state_dict(cfg, params),
                                     dtype=jnp.float32)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    toks = tokens(3 * WINDOW, seed=8)
    ref = ref_logits(got_cfg, got, toks)
    assert np.abs(dense_logits(cfg, got, toks) - ref).max() \
        < 1e-4 * spread(ref)
    assert np.abs(ref_logits(cfg, params, toks) - ref).max() == 0
