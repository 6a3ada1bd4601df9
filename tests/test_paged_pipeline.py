"""Pipeline-parallel continuous batching (parallel/paged_pipeline.py).

The contract: a batcher on a pp>1 mesh serves requests with outputs
identical to the single-stage batcher — admission waves, decode chunks,
prefix reuse and per-request PRNG streams all preserved — while the
layer stack (params AND paged pool) lives sharded across stages. Run on
the 8-virtual-CPU-device mesh (conftest.py), the same harness the dryrun
uses (SURVEY.md §4).
"""

import numpy as np

from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
from conftest import shared_batcher as Batcher

CFG = get_config("tiny-llama").replace(dtype="float32", attn_backend="xla")

def _run(b, reqs, steps=200):
    for _ in range(steps):
        b.step()
        if all(r.done.is_set() for r in reqs):
            break
    return [r.wait() for r in reqs]


def _submit_mixed(b):
    rng = np.random.default_rng(0)
    base = rng.integers(0, 256, 6).tolist()
    prompts = [(base * 4)[:20],
               rng.integers(0, 256, 9).tolist(),
               rng.integers(0, 256, 13).tolist()]
    return [
        b.submit(prompts[0], max_new_tokens=14,
                 sampling=SamplingParams.greedy(), seed=1),
        b.submit(prompts[1], max_new_tokens=10,
                 sampling=SamplingParams(temperature=0.8, top_k=40), seed=2),
        b.submit(prompts[2], max_new_tokens=12,
                 sampling=SamplingParams.greedy(), seed=3),
    ]


def test_pp_batcher_matches_dense():
    """pp=2 batcher ≡ single-stage batcher: same tokens for greedy AND
    sampled requests (per-slot PRNG streams are data, so the pipelined
    program must reproduce them bit-for-bit)."""
    dense = Batcher(CFG, num_blocks=96, block_size=8, slots=4,
                    max_seq=64, seed=0)
    want = _run(dense, _submit_mixed(dense))

    pp = Batcher(CFG, num_blocks=96, block_size=8, slots=4,
                 max_seq=64, seed=0, mesh_spec=MeshSpec(pp=2))
    got = _run(pp, _submit_mixed(pp))
    assert got == want, (got, want)


def test_pp_batcher_eos_budget_and_inflight_admission():
    """Per-slot eos stops a pp-scheduled slot mid-chunk; freed slots
    admit queued requests mid-flight exactly like the dense batcher."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, n).tolist() for n in (8, 11, 9, 7, 12)]

    def run(mesh_spec):
        b = Batcher(CFG, num_blocks=96, block_size=8, slots=2,
                    max_seq=64, seed=0, mesh_spec=mesh_spec)
        # more requests than slots: forces queueing + in-flight admission
        reqs = [b.submit(p, max_new_tokens=6 + i,
                         sampling=SamplingParams.greedy(), seed=10 + i)
                for i, p in enumerate(prompts)]
        return _run(b, reqs)

    want = run(None)
    got = run(MeshSpec(pp=2))
    assert got == want, (got, want)

    # eos: derive it from a full run, then check truncation matches
    b = Batcher(CFG, num_blocks=96, block_size=8, slots=2,
                max_seq=64, seed=0, mesh_spec=MeshSpec(pp=2))
    r_full = b.submit(prompts[0], max_new_tokens=10,
                      sampling=SamplingParams.greedy(), seed=10)
    full = _run(b, [r_full])[0]
    eos = full[4]
    b2 = Batcher(CFG, num_blocks=96, block_size=8, slots=2,
                 max_seq=64, seed=0, mesh_spec=MeshSpec(pp=2))
    r_eos = b2.submit(prompts[0], max_new_tokens=10,
                      sampling=SamplingParams.greedy(), seed=10,
                      eos_token_id=eos)
    got_eos = _run(b2, [r_eos])[0]
    if eos not in full[:4]:
        assert got_eos == full[:4], (got_eos, full)
    assert eos not in got_eos


def test_pp_batcher_prefix_reuse():
    """Radix prefix hits survive the pp pool layout: a second request
    sharing a long prompt prefix admits with a cached prefix (fewer
    fresh blocks) and still matches the dense batcher's tokens."""
    rng = np.random.default_rng(3)
    head = rng.integers(0, 256, 24).tolist()
    p1 = head + rng.integers(0, 256, 4).tolist()
    p2 = head + rng.integers(0, 256, 5).tolist()

    def run(mesh_spec):
        b = Batcher(CFG, num_blocks=96, block_size=8, slots=2,
                    max_seq=64, seed=0, mesh_spec=mesh_spec)
        r1 = b.submit(p1, max_new_tokens=6,
                      sampling=SamplingParams.greedy(), seed=1)
        out1 = _run(b, [r1])[0]
        hits0 = b.pool.stats()["prefix_hits"]
        r2 = b.submit(p2, max_new_tokens=6,
                      sampling=SamplingParams.greedy(), seed=2)
        out2 = _run(b, [r2])[0]
        hit = b.pool.stats()["prefix_hits"] > hits0
        return out1, out2, hit

    w1, w2, whit = run(None)
    g1, g2, ghit = run(MeshSpec(pp=2))
    assert (g1, g2) == (w1, w2)
    assert ghit == whit


def test_pp_batcher_lockstep_replay_evolves_identical_cache():
    """The lockstep contract extends to the pp program kinds: a follower
    replaying the leader's broadcast admit/decode args (JSON round-trip)
    evolves a bit-identical pp-sharded paged pool."""
    import json
    import jax

    mk = lambda: Batcher(  # noqa: E731
        CFG, num_blocks=64, block_size=8, slots=2, max_seq=64, seed=0,
        mesh_spec=MeshSpec(pp=2))
    leader, follower = mk(), mk()

    def hook(kind, args, run):
        follower.replay(kind, json.loads(json.dumps(args)))
        return run()

    leader.program_hook = hook
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, 9).tolist(),
               rng.integers(0, 256, 12).tolist()]
    reqs = [leader.submit(p, max_new_tokens=8,
                          sampling=SamplingParams.greedy(), seed=20 + i)
            for i, p in enumerate(prompts)]
    outs = _run(leader, reqs)
    assert all(len(o) == 8 for o in outs)
    np.testing.assert_array_equal(np.asarray(jax.device_get(leader.paged.k)),
                                  np.asarray(jax.device_get(follower.paged.k)))
    np.testing.assert_array_equal(np.asarray(jax.device_get(leader.paged.v)),
                                  np.asarray(jax.device_get(follower.paged.v)))


def test_pp_batcher_kv8_matches_dense_kv8():
    """int8 KV cache composes with pipeline parallelism: the pp batcher
    over a quantized pool reproduces the single-stage kv8 batcher's
    tokens exactly (same quantize-at-write / dequantize-at-read points,
    so the rounding is identical)."""
    kcfg = CFG.replace(kv_quant="int8")
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 14)]

    def run(mesh_spec):
        b = Batcher(kcfg, num_blocks=96, block_size=8, slots=2,
                    max_seq=64, seed=0, mesh_spec=mesh_spec)
        reqs = [b.submit(p, max_new_tokens=8,
                         sampling=SamplingParams.greedy(), seed=30 + i)
                for i, p in enumerate(prompts)]
        return _run(b, reqs)

    want = run(None)
    got = run(MeshSpec(pp=2))
    assert got == want, (got, want)


def test_pp_batcher_rejects_unsupported_combos():
    # slots round UP to a pp multiple
    b = Batcher(CFG, num_blocks=32, block_size=8, slots=3,
                max_seq=64, mesh_spec=MeshSpec(pp=2))
    assert b.slots == 4


def test_pp_spec_chunk_matches_single_stage():
    """paged_speculative_chunk_pp ≡ paged_speculative_chunk: identical
    (toks, keeps, eos_seen) AND an identical committed pool — verified
    by decoding a follow-up chunk from each resulting cache."""
    import jax
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.models import transformer
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        init_paged_cache, PagedKVCache)
    from distributed_llm_inferencing_tpu.parallel import paged_pipeline
    from distributed_llm_inferencing_tpu.parallel.mesh import create_mesh

    cfg = CFG
    rng = np.random.default_rng(0)
    base = rng.integers(0, 256, 6).tolist()
    prompts = [(base * 4)[:20], rng.integers(0, 256, 9).tolist(),
               (base * 3)[:14], (base * 4)[:18]]
    r = len(prompts)
    bs, mb = 8, 8
    from distributed_llm_inferencing_tpu.models.params import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))
    paged0 = init_paged_cache(cfg, r * mb + 1, bs)
    tables = np.zeros((r, mb), np.int32)
    toks = np.zeros((r, 24), np.int32)
    tail_len = np.asarray([len(p) - 1 for p in prompts], np.int32)
    nb = 1
    for i, p in enumerate(prompts):
        toks[i, :len(p) - 1] = p[:-1]
        tables[i] = np.arange(nb, nb + mb)
        nb += mb
    _, paged0 = transformer.paged_prefill_tail(
        params, cfg, jnp.asarray(toks), jnp.asarray(tail_len),
        jnp.asarray(tables[:, :3]), jnp.zeros((r, 1), jnp.int32),
        jnp.zeros((r,), jnp.int32), paged0)
    cur = jnp.asarray([p[-1] for p in prompts], jnp.int32)
    cl = jnp.asarray(tail_len)
    hist = np.zeros((r, 64), np.int32)
    for i, p in enumerate(prompts):
        hist[i, :len(p)] = p
    hist = jnp.asarray(hist)

    seeds = jnp.asarray([11, 22, 33, 44], jnp.int32)
    steps0 = jnp.zeros((r,), jnp.int32)
    temps = jnp.asarray([1.0, 1.0, 0.8, 1.0], jnp.float32)
    tks = jnp.asarray([0, 0, 40, 0], jnp.int32)
    tps = jnp.asarray([1.0, 1.0, 0.9, 1.0], jnp.float32)
    ds = jnp.asarray([False, False, True, False])
    budget = jnp.full((r,), 10, jnp.int32)
    eos = jnp.full((r,), -1, jnp.int32)
    args = (cur, hist, paged0, jnp.asarray(tables), cl, seeds, steps0,
            temps, tks, tps, ds, budget, eos)

    w_toks, w_keeps, w_eos, w_paged = transformer.paged_speculative_chunk(
        params, cfg, 10, 3, *args, dummy_block=0)

    mesh = create_mesh(MeshSpec(pp=2))
    # the batcher launches this inside jit (a shard_map with a manual-pp
    # subset needs the surrounding jit); mirror that here
    pp_fn = jax.jit(lambda *a: paged_pipeline.paged_speculative_chunk_pp(
        params, cfg, 10, 3, *a, dummy_block=0, mesh=mesh))
    g_toks, g_keeps, g_eos, g_paged = pp_fn(*args)

    np.testing.assert_array_equal(np.asarray(w_keeps), np.asarray(g_keeps))
    np.testing.assert_array_equal(np.asarray(w_eos), np.asarray(g_eos))
    # only kept entries are defined outputs
    for t in range(10):
        for i in range(r):
            n = int(w_keeps[t, i])
            np.testing.assert_array_equal(
                np.asarray(w_toks[t, i, :n]), np.asarray(g_toks[t, i, :n]))

    # committed pools must agree where it matters: decode a plain chunk
    # from each and compare the emitted tokens
    cl2 = cl + np.asarray(w_keeps).sum(axis=0).astype(np.int32)
    cur2 = jnp.asarray([
        int(np.asarray(w_toks[t, i, :int(w_keeps[t, i])])[-1])
        for i in range(r)
        for t in [max(tt for tt in range(10) if int(w_keeps[tt, i]) > 0)]
    ], jnp.int32)
    follow = lambda pg: transformer.paged_decode_chunk(  # noqa: E731
        params, cfg, 4, cur2, pg, jnp.asarray(tables), cl2, seeds, steps0,
        temps, tks, tps, ds, jnp.full((r,), 4, jnp.int32), eos,
        dummy_block=0)
    ft, fe, *_ = follow(w_paged)
    gt, ge, *_ = follow(PagedKVCache(
        k=jnp.asarray(g_paged.k), v=jnp.asarray(g_paged.v),
        k_scale=g_paged.k_scale, v_scale=g_paged.v_scale))
    np.testing.assert_array_equal(np.asarray(fe), np.asarray(ge))
    np.testing.assert_array_equal(np.asarray(ft) * np.asarray(fe),
                                  np.asarray(gt) * np.asarray(ge))


def test_pp_batcher_speculative_matches_single_stage():
    """Batcher-level: speculative serving on a pp=2 mesh ≡ the
    single-stage speculative batcher for greedy AND sampled requests,
    across multiple chunks (pool commits included)."""
    def run(mesh_spec):
        b = Batcher(CFG, num_blocks=96, block_size=8, slots=4,
                    max_seq=64, seed=0, mesh_spec=mesh_spec,
                    speculative="ngram", spec_gamma=3)
        return _run(b, _submit_mixed(b))

    want = run(None)
    got = run(MeshSpec(pp=2))
    assert got == want, (got, want)
