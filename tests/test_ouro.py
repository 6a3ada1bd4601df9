"""Ouro (a looped language model) at toy widths, `tiny-ouro`: 3 layers
under sandwich norms run 3 times a token over one set of weights, the
final norm between passes, a K and V plane a (step, layer) pair: 9. The
float32 reference is models/reference/ouro_ref.py, which imports nothing
from the package."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import convert, transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.reference import ouro_ref
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    init_paged_cache)
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime import kvtier, kvwire
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher
from distributed_llm_inferencing_tpu.utils import trace
from conftest import jitted

BS = 4
# float32 against float32 on the CPU: the two sum in another order. The
# limit is on the largest logit error of a position over the spread of
# the reference's logits; what is found is 1e-6..1e-5, the controls
# (a step left out, a step's planes read for another's) read 0.1..1.
TOL = 1e-4


def cfg32(**kw):
    return get_config("tiny-ouro").replace(dtype="float32",
                                           attn_backend="xla", **kw)


@pytest.fixture(scope="module")
def params():
    """Seeded random weights; the norms' scales and the exit gate too, or
    a norm left out or misplaced would go unseen behind scales of one."""
    p = init_params(cfg32(), jax.random.PRNGKey(0), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def jitter(a):
        return a * (1.0 + 0.3 * jax.random.normal(next(keys), a.shape))
    for name in ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"):
        p["layers"][name]["scale"] = jitter(p["layers"][name]["scale"])
    p["final_norm"]["scale"] = jitter(p["final_norm"]["scale"])
    p["exit_gate"]["b"] = jnp.asarray([0.3], jnp.float32)
    return p


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, n).astype(np.int32)


def ref_logits(cfg, params, toks, steps=None):
    arch = ouro_ref.arch_of(cfg)
    return np.asarray(ouro_ref.forward(params, arch, jnp.asarray(toks),
                                       steps=steps))


def err(got, ref):
    return float(np.abs(np.asarray(got) - ref).max() / ref.std())


def dense_logits(cfg, params, toks):
    cache = init_cache(cfg, 1, 64, dtype=jnp.float32)
    logits, cache = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(toks[None]), jnp.asarray([len(toks)]), cache)
    return np.asarray(logits[0], np.float32), cache


# ---- (a) forward ---------------------------------------------------------

def test_forward_matches_the_reference(params):
    cfg = cfg32()
    toks = tokens(24, seed=1)
    ref = ref_logits(cfg, params, toks)
    got, cache = dense_logits(cfg, params, toks)
    assert cache.k.shape[0] == 9
    assert err(got, ref) < TOL
    # then one decode step through the dense cache's nine planes
    nxt = int(np.argmax(ref[-1]))
    logits, _ = jitted(transformer.decode_step)(
        params, cfg, jnp.asarray([[nxt]]), cache)
    ref2 = ref_logits(cfg, params, np.append(toks, nxt))
    assert err(logits[0, 0], ref2[-1]) < TOL


def test_a_step_left_out_fails_the_tolerance(params):
    """T - 1 steps, either side: the system at two steps against the
    reference at three, and the reference's own two against its three."""
    toks = tokens(24, seed=1)
    ref = ref_logits(cfg32(), params, toks)
    got, _ = dense_logits(cfg32(loop_steps=2), params, toks)
    assert err(got, ref) > 100 * TOL
    assert err(ref_logits(cfg32(), params, toks, steps=2), ref) > 100 * TOL


def test_the_exit_rule_runs_every_step_at_threshold_one(params):
    lam = jnp.asarray([[0.2, 0.9], [0.5, 0.999], [0.1, 0.3]])
    step, mass = ouro_ref.exit_steps(lam, 1.0)
    assert step.tolist() == [2, 2]
    assert np.allclose(mass[-1], 1.0) and bool((mass[:-1] < 1.0).all())
    # below 1 the first step whose cumulative mass reaches it
    step, _ = ouro_ref.exit_steps(lam, 0.55)
    assert step.tolist() == [1, 0]
    gate = ouro_ref.exit_gate(params, ouro_ref.arch_of(cfg32()),
                              jnp.ones((2, 64)))
    assert gate.shape == (2,) and bool(((gate > 0) & (gate < 1)).all())


# ---- (b) the paged pool, in chunks, on logits -----------------------------

def paged_logits(cfg, params, seq, n_pre, n_tail, steps, k, spoil=None):
    """Prefill `n_pre` tokens, then a tail of `n_tail` over them as a
    cached prefix (a prefix hit), then `steps` decode steps in chunks of
    `k` fed the sequence's own tokens: logits at the tail's last
    position and at every decode step. `spoil(paged)` edits the pool
    between prefill and decode."""
    prefill = jax.jit(lambda *a: transformer.paged_prefill_tail(
        params, cfg, *a))
    paged = init_paged_cache(cfg, 40, BS, dtype=jnp.float32)
    pre_blocks = np.arange(1, 1 + n_pre // BS)
    tail_blocks = np.arange(20, 20 + -(-n_tail // BS))
    _, paged = prefill(
        jnp.asarray(seq[None, :n_pre]), jnp.asarray([n_pre]),
        jnp.asarray(pre_blocks[None]), jnp.zeros((1, 1), jnp.int32),
        jnp.asarray([0]), paged)
    t_pad = len(tail_blocks) * BS
    tail = np.zeros((1, t_pad), np.int32)
    tail[0, :n_tail] = seq[n_pre:n_pre + n_tail]
    pfb = np.zeros((1, 8), np.int32)
    pfb[0, :len(pre_blocks)] = pre_blocks
    logits, paged = prefill(
        jnp.asarray(tail), jnp.asarray([n_tail]),
        jnp.asarray(tail_blocks[None]), jnp.asarray(pfb),
        jnp.asarray([n_pre]), paged)
    out = [np.asarray(logits[0])]
    if spoil is not None:
        paged = spoil(paged)
    n = n_pre + n_tail
    table = np.zeros((1, 16), np.int32)
    table[0, :len(pre_blocks)] = pre_blocks
    table[0, len(pre_blocks):len(pre_blocks) + len(tail_blocks)] = tail_blocks
    used = len(pre_blocks) + len(tail_blocks)
    table[0, used:used + 4] = 30 + np.arange(4)
    z = jnp.zeros((1,), jnp.int32)
    chunk = jax.jit(lambda *a: transformer.decode_chunk_with_logits(
        params, cfg, 1, *a, 0))
    # a chunk samples its own next token, so a chunk of k is fed token by
    # token here (k = 1) ...
    for t in range(steps):
        *_, paged, lg = chunk(
            jnp.asarray(seq[n + t:n + t + 1]), paged, jnp.asarray(table),
            jnp.asarray([n + t]), z, z, jnp.ones((1,), jnp.float32), z,
            jnp.ones((1,), jnp.float32), jnp.zeros((1,), bool), z + 1, z - 1)
        out.append(np.asarray(lg[0, 0]))
    if k > 1:
        # ... and a chunk of k, greedy, runs on from there through its
        # side buffers: its own tokens and their logits
        big = jax.jit(lambda *a: transformer.decode_chunk_with_logits(
            params, cfg, k, *a, 0))
        toks, emits, *_, paged, lg = big(
            jnp.asarray(seq[n + steps:n + steps + 1]), paged,
            jnp.asarray(table), jnp.asarray([n + steps]), z, z,
            jnp.ones((1,), jnp.float32), z, jnp.ones((1,), jnp.float32),
            jnp.zeros((1,), bool), z + k, z - 1)
        assert bool(np.asarray(emits).all())
        return out, np.asarray(toks[:, 0]), np.asarray(lg[:, 0])
    return out, None, None


def test_paged_logits_match_the_reference(params):
    """Prefill, a tail over the cached prefix, decode steps and a chunk
    of 4 through its side buffers, on logits (the pool pre-gathered, as a
    toy pool is; the batcher's test below takes the in-loop gather and
    the ladder's switch, as the chip's cell does)."""
    cfg = cfg32()
    n_pre, n_tail, steps, k = 16, 6, 3, 4
    seq = tokens(n_pre + n_tail + steps + 1, seed=11)
    out, toks, lg = paged_logits(cfg, params, seq, n_pre, n_tail, steps, k)
    ref = ref_logits(cfg, params, seq)
    n = n_pre + n_tail
    for t, got in enumerate(out):
        assert err(got, ref[n - 1 + t]) < TOL, t
    # the chunk of 4: the reference over the chunk's own tokens
    full = np.concatenate([seq, toks[:-1]])
    ref2 = ref_logits(cfg, params, full)
    for t in range(k):
        assert err(lg[t], ref2[n + steps + t]) < TOL, t
        assert int(np.argmax(ref2[n + steps + t])) == int(toks[t])


def test_a_step_reading_another_steps_planes_fails_the_tolerance(params):
    """Step u handed step u - 1's planes (the pool rolled by one step's
    layers between prefill and decode): decode then attends the wrong
    step's K and V, and no position is inside the tolerance."""
    cfg = cfg32()
    L = cfg.num_layers
    n_pre, n_tail, steps = 16, 6, 3
    seq = tokens(n_pre + n_tail + steps + 1, seed=11)

    def roll(paged):
        return type(paged)(*(jnp.roll(p, L, axis=0)
                             for p in paged.planes()))
    out, _, _ = paged_logits(cfg, params, seq, n_pre, n_tail, steps, 1,
                             spoil=roll)
    ref = ref_logits(cfg, params, seq)
    n = n_pre + n_tail
    assert err(out[0], ref[n - 1]) < TOL          # before the roll
    for t in range(1, steps + 1):
        assert err(out[t], ref[n - 1 + t]) > 100 * TOL


# ---- (c) the batcher ------------------------------------------------------

def serve(cfg, prompts, new=12, cap=8, **kw):
    b = ContinuousBatcher(cfg, None, seed=0, slots=4, num_blocks=128,
                          block_size=BS, max_seq=128, prefill_chunk=4,
                          decode_chunk_cap=cap, kv_host_mb=0, **kw)
    greedy = SamplingParams.greedy()
    reqs = [b.submit(p, max_new_tokens=new, sampling=greedy, seed=0)
            for p in prompts]
    while b.inflight():
        b.step()
    return b, reqs


def test_the_batcher_serves_what_the_reference_computes(monkeypatch):
    """submit -> admission of a 24-token shared prefix in chunks of 16, a
    tail over the cached prefix (the second prompt hits it in the radix
    cache), decode chunks of 8 in the form the chip's cell takes (the
    in-loop gather under the ladder's switch): the greedy tokens are the
    reference's argmax at every position, the pool has nine planes, a
    weight pass counts three stack passes."""
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    cfg = cfg32()
    shared = tokens(24, seed=3).tolist()
    prompts = [shared + tokens(n, seed=n).tolist() for n in (9, 5)]
    b, reqs = serve(cfg, prompts, new=10)
    assert b.paged.k.shape[0] == 9 and "exit_gate" in b.params
    for r in reqs:
        assert r.error is None and len(r.tokens) == 10
        seq = r.prompt + r.tokens
        ref = ref_logits(cfg, b.params, seq[:-1])
        assert np.argmax(ref[len(r.prompt) - 1:], -1).tolist() == r.tokens
    snap = b.metrics.snapshot()
    c = snap["counters"]
    assert c["prefill_cached_tokens"] == len(shared)
    assert c["batcher_stack_passes"] == 3 * c["batcher_weight_passes"] > 0
    # 9 planes x (K and V) x 4 heads x 16 x 4 bytes
    assert snap["gauges"]["batcher_kv_bytes_per_token"] == 9 * 2 * 4 * 16 * 4
    spans = trace.get_tracer().spans()
    for name in ("batcher.decode_chunk", "batcher.admit_wave"):
        last = [s for s in spans if s.name == name][-1]
        assert last.attrs["loop_steps"] == 3


def test_a_model_without_a_loop_counts_one_stack_pass():
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    b, reqs = serve(cfg, [tokens(9, seed=2).tolist()], new=5)
    c = b.metrics.snapshot()["counters"]
    assert c["batcher_stack_passes"] == c["batcher_weight_passes"] > 0
    chunk = [s for s in trace.get_tracer().spans()
             if s.name == "batcher.decode_chunk"][-1]
    assert chunk.attrs["loop_steps"] == 1


def test_what_does_not_carry_the_loop_is_refused_by_name():
    with pytest.raises(ValueError, match="speculative decoding"):
        ContinuousBatcher(cfg32(), None, slots=2, num_blocks=16,
                          block_size=BS, max_seq=32, kv_host_mb=0,
                          speculative="ngram")


@pytest.mark.parametrize("asked,env", [
    ("auto", {"DLI_ATTENTION": "pallas"}),
    ("pallas", {}),
])
def test_a_request_for_pallas_attention_serves_as_auto_does(asked, env,
                                                            monkeypatch):
    from conftest import served_as_under_auto
    served_as_under_auto(lambda attn_backend: ContinuousBatcher(
        cfg32().replace(attn_backend=attn_backend), None, slots=2,
        num_blocks=16, block_size=BS, max_seq=32, kv_host_mb=0),
        asked, env, monkeypatch)


def test_a_pipeline_is_refused_by_name():
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    with pytest.raises(ValueError, match="pp > 1"):
        ContinuousBatcher(cfg32(), None, slots=2, num_blocks=16,
                          block_size=BS, max_seq=32, kv_host_mb=0,
                          mesh_spec=MeshSpec(pp=3))


def test_the_speculative_chunk_refuses_a_loop(params):
    with pytest.raises(ValueError, match="looped"):
        transformer.paged_speculative_chunk(
            params, cfg32(), 1, 2, *([None] * 14), 0)


# ---- (d) the plane axis is the configuration's cache planes --------------

@pytest.mark.parametrize("model,planes", [("tiny-ouro", 9),
                                          ("tiny-llama", 4)])
@pytest.mark.parametrize("where", ["init_paged_cache", "init_cache",
                                   "bytes_per_token", "host_arena_block",
                                   "kvwire_frame"])
def test_the_plane_axis_is_the_cache_planes(model, planes, where):
    cfg = get_config(model).replace(dtype="float32")
    assert cfg.cache_planes == planes == cfg.loop_steps * cfg.num_layers
    paged = init_paged_cache(cfg, 6, BS)
    per_token = planes * 2 * cfg.num_kv_heads * cfg.head_dim * 4
    if where == "init_paged_cache":
        assert paged.k.shape == paged.v.shape == (
            planes, 6, BS, cfg.num_kv_heads, cfg.head_dim)
    elif where == "init_cache":
        cache = init_cache(cfg, 2, 16)
        assert cache.k.shape == cache.v.shape == (
            planes, 2, 16, cfg.num_kv_heads, cfg.head_dim)
    elif where == "bytes_per_token":
        assert paged.bytes_per_token == per_token
    else:
        # a block as the eviction hook hands it to the arena and the wire
        # (batcher._offload_evicted): every plane's [:, block]
        pages = [np.asarray(p[:, 3]) + i for i, p in
                 enumerate(paged.planes())]
        assert all(p.shape[0] == planes for p in pages)
        if where == "host_arena_block":
            arena = kvtier.HostKVArena(1 << 20)
            assert arena.put("d", pages)
            assert arena.stats()["bytes"] == per_token * BS
            got = arena.get("d")
        else:
            blocks, end = kvwire.decode_frames(
                [kvwire.encode_frame("d", pages), kvwire.encode_end(1, [])])
            got = blocks["d"]
        assert len(got) == 2 and all(
            np.array_equal(g, p) for g, p in zip(got, pages))


def test_the_registry_has_the_source_sizes():
    cfg = get_config("ouro-2.6b")
    assert (cfg.num_layers, cfg.loop_steps, cfg.cache_planes,
            cfg.hidden_size, cfg.intermediate_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size, cfg.rope_theta,
            cfg.norm_eps) == (48, 4, 192, 2048, 5632, 16, 16, 128, 49152,
                              1e6, 1e-6)
    assert cfg.post_block_norms and not cfg.tie_word_embeddings \
        and not cfg.attn_bias
    # bf16 K and V of one token over the 192 planes
    assert cfg.cache_planes * 2 * cfg.num_kv_heads * cfg.head_dim * 2 \
        == 1_572_864


# ---- (e) a published checkpoint's names through convert.py ----------------

def hf_state_dict(cfg, params):
    """The tree under the published checkpoint's names (modeling_ouro.py),
    linear weights transposed to torch's [out, in]."""
    sd = {"model.embed_tokens.weight": params["embed"]["tokens"],
          "model.norm.weight": params["final_norm"]["scale"],
          "model.early_exit_gate.weight": params["exit_gate"]["w"].T,
          "model.early_exit_gate.bias": params["exit_gate"]["b"],
          "lm_head.weight": params["lm_head"]["w"].T}
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        p = f"model.layers.{i}."
        for ours, theirs in (("attn_norm", "input_layernorm"),
                             ("attn_post_norm", "input_layernorm_2"),
                             ("mlp_norm", "post_attention_layernorm"),
                             ("mlp_post_norm",
                              "post_attention_layernorm_2")):
            sd[p + theirs + ".weight"] = lp[ours]["scale"]
        for nm in ("q", "k", "v", "o"):
            sd[p + f"self_attn.{nm}_proj.weight"] = lp[nm]["w"].T
        for nm in ("gate", "up", "down"):
            sd[p + f"mlp.{nm}_proj.weight"] = lp[nm]["w"].T
    return {k: np.asarray(v) for k, v in sd.items()}


def test_a_published_state_dict_converts_to_the_reference_logits(params):
    cfg = cfg32()
    hf = types.SimpleNamespace(
        model_type="ouro", name_or_path="tiny-ouro", vocab_size=256,
        hidden_size=64, intermediate_size=128, num_hidden_layers=3,
        total_ut_steps=3, early_exit_threshold=1.0, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, max_position_embeddings=256,
        rms_norm_eps=1e-6, hidden_act="silu", rope_theta=1000000,
        use_sliding_window=False, sliding_window=None,
        tie_word_embeddings=False)
    got_cfg = convert.config_from_hf(hf)
    assert got_cfg.replace(dtype="float32", attn_backend="xla") == cfg
    got = convert.convert_state_dict(got_cfg, hf_state_dict(cfg, params),
                                     dtype=jnp.float32)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    toks = tokens(20, seed=8)
    ref = ref_logits(got_cfg, got, toks)
    assert err(dense_logits(cfg, got, toks)[0], ref) < TOL
    assert err(ref, ref_logits(cfg, params, toks)) < 1e-6
    hf.early_exit_threshold = 0.5
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        convert.config_from_hf(hf)


# ---- (f) the operator's reduction splits a pass by loop step --------------

@pytest.mark.parametrize("op_name,scope", [
    ("jit(chunk)/while/body/loop_step_2/while/body/mlp/dot_general",
     "loop_step_2:mlp"),
    ("jit(chunk)/while/body/loop_step_0/while/body/closed_call/add",
     "loop_step_0:(no scope)"),
    ("jit(admit)/loop_step_3/kv_write/dynamic_update_slice",
     "loop_step_3:kv_write"),
    ("jit(chunk)/while/body/attention/win/dot_general", "attention/win"),
    ("jit(chunk)/while/body/lm_head/dot_general", "lm_head"),
])
def test_profile_summary_names_the_loop_step(op_name, scope):
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "profile_summary", Path(__file__).resolve().parents[1]
        / "scripts" / "profile_summary.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.scope_of(op_name) == scope
