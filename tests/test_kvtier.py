"""Cluster prefix-cache tier (runtime/kvtier.py + batcher/master wiring).

Covers the acceptance-critical invariants:
- radix evict -> host offload -> restore round trip is BITWISE identical
  to a cold prefill (greedy and sampled),
- the host arena respects its LRU byte bound under pressure,
- same-wave duplicate-prefix admission reuses the earlier member's radix
  insert,
- prefix-digest advertisement + the master's affinity pick, including
  the load threshold (no convoys) and the staleness drop-out,
- the radix/prefix counters reach the Prometheus exposition,
- the persisted node row strips the ephemeral digest advertisement.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime import kvtier
from conftest import shared_batcher as Batcher

CFG = get_config("tiny-llama").replace(dtype="float32", attn_backend="xla")
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def run_until_done(b, reqs, max_steps=400):
    for _ in range(max_steps):
        b.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("not done")


def run_one(b, prompt, n=8, sampling=None, seed=3):
    r = b.submit(prompt, max_new_tokens=n,
                 sampling=sampling or SamplingParams.greedy(), seed=seed)
    run_until_done(b, [r])
    return r.wait()


def make_batcher(kv_host_mb, num_blocks=24):
    # small pool: eviction pressure is the point
    return Batcher(CFG, PARAMS, num_blocks=num_blocks,
                   block_size=8, slots=2, max_seq=128,
                   kv_host_mb=kv_host_mb)


# ---- digests / arena units ---------------------------------------------

def test_chain_digests_share_prefix():
    a = kvtier.token_chain_digests(list(range(32)), 8)
    b = kvtier.token_chain_digests(list(range(24)) + [99] * 8, 8)
    assert len(a) == 4 and a[:3] == b[:3] and a[3] != b[3]
    t1 = kvtier.text_chain_digests("x" * 48 + "A" * 16, 16)
    t2 = kvtier.text_chain_digests("x" * 48 + "B" * 16, 16)
    assert t1[:3] == t2[:3] and t1[3] != t2[3]


def test_arena_lru_bound_under_pressure():
    page = np.zeros((4, 8), np.float32)   # 128 B
    arena = kvtier.HostKVArena(capacity_bytes=4 * page.nbytes)
    for i in range(10):
        assert arena.put(f"d{i}", [page])
    st = arena.stats()
    assert st["blocks"] == 4 and st["bytes"] <= arena.capacity_bytes
    assert st["dropped"] == 6
    # LRU order: oldest gone, newest present; get() touches
    assert arena.get("d0") is None and arena.get("d9") is not None
    assert arena.get("d6") is not None
    arena.put("d10", [page])              # drops d7, not the touched d6
    assert arena.get("d6") is not None and arena.get("d7") is None
    # a block bigger than the whole budget is refused, never stored
    assert not arena.put("huge", [np.zeros((1024,), np.float64)])


def test_arena_int8_lru_counts_stored_bytes():
    """An int8 arena's LRU bound and occupancy run on STORED
    (quantized) bytes, so the same budget holds ~4x the blocks of a
    native arena — and the occupancy the arena-full routing guard
    (DLI_SCHED_ARENA_FULL) sees is the honest quantized budget, while
    logical_bytes still carries the full-precision equivalent."""
    rng = np.random.default_rng(7)
    page = rng.standard_normal((2, 8, 2, 4)).astype(np.float32)  # 512 B
    native = kvtier.HostKVArena(capacity_bytes=4 * page.nbytes)
    int8 = kvtier.HostKVArena(capacity_bytes=4 * page.nbytes,
                              dtype="int8")
    for i in range(16):
        assert native.put(f"d{i}", [page])
        assert int8.put(f"d{i}", [page])
    sn, sq = native.stats(), int8.stats()
    assert sn["blocks"] == 4 and sn["dropped"] == 12
    assert sq["blocks"] > sn["blocks"] * 3      # the density claim
    assert sq["dropped"] == 16 - sq["blocks"]
    for st in (sn, sq):
        assert st["bytes"] <= st["capacity_bytes"]
        assert st["occupancy"] == st["bytes"] / st["capacity_bytes"]
    # honest accounting: int8 stores fewer bytes than it represents
    assert sq["bytes"] < sq["logical_bytes"] / 3.5
    assert sn["bytes"] == sn["logical_bytes"]
    # restore path dequantizes to the logical page, bounded error
    got = int8.get("d15")
    assert got is not None and got[0].shape == page.shape
    assert got[0].dtype == np.float32
    assert float(np.max(np.abs(got[0] - page))) < 0.05


def test_estimate_survives_malformed_advertisement():
    """The advertisement crossed the wire from a worker: malformed
    shapes must score 0, never raise — estimate_cached_tokens runs on
    the master's dispatcher threads, which have no exception net."""
    prompt = "x" * 64
    for bad in ({"chunk": 16, "top": [["ab", "NaN-ish"]]},
                {"chunk": 16, "top": [["ab", None]]},
                {"chunk": 16, "top": [["ab"]]},          # short pair
                {"chunk": 16, "top": ["abc"]},           # not pairs
                {"chunk": 16, "top": 7},
                {"chunk": "x", "top": [["ab", 4]]},
                {"chunk": 0, "top": [["ab", 4]]},
                {"top": [["ab", 4]]}, "nope", None, 42):
        assert kvtier.estimate_cached_tokens(prompt, bad) == 0


def test_advertise_honors_top_k_chains_for_deep_prompts():
    """top_k bounds CHAINS, not raw digest entries: top_k deep (64-chunk)
    prompt families must ALL stay advertised, each downsampled to
    geometric depths, and a prompt sharing a partial depth still gets a
    positive (conservative) estimate."""
    idx = kvtier.PrefixDigestIndex(chunk=4, top_k=8)
    sys_prompts = [f"<{g}>" + ("s%d" % g) * 140 for g in range(8)]
    for p in sys_prompts:
        idx.note(p, 256)     # 64+ full 4-byte chunks each
    adv = idx.advertise()
    assert len(adv["top"]) <= 8 * 8    # ~7 depths per chain
    for p in sys_prompts:              # every family still routable
        assert kvtier.estimate_cached_tokens(p + "tail", adv) > 0
        # a prompt sharing only the first ~32 chunks matches a
        # shallower advertised depth with a smaller estimate
        part = kvtier.estimate_cached_tokens(p[:130] + "Z" * 64, adv)
        assert 0 < part < kvtier.estimate_cached_tokens(p + "t", adv)
    # a shorter chain that is a prefix of a longer one merges (one
    # family = one chain, not one per prompt length)
    idx2 = kvtier.PrefixDigestIndex(chunk=4, top_k=8)
    idx2.note("AAAA" * 8, 32)
    idx2.note("AAAA" * 16, 64)
    assert len(idx2._chains) == 1


def test_digest_index_advertises_bounded_top_k():
    idx = kvtier.PrefixDigestIndex(chunk=8, top_k=4)
    for g in range(50):
        idx.note(f"<{g:03d}>" + "s" * 28, 32)
    adv = idx.advertise()
    assert adv["chunk"] == 8
    assert 0 < len(adv["top"]) <= idx.top_k * 4
    # estimate: deepest matching digest wins, token estimate positive
    est = kvtier.estimate_cached_tokens("<049>" + "s" * 28 + "tail", adv)
    assert est > 0
    assert kvtier.estimate_cached_tokens("<999>" + "z" * 40, adv) == 0


# ---- evict -> offload -> restore round trip ----------------------------

@pytest.fixture(scope="module")
def tier_batcher():
    return make_batcher(kv_host_mb=64)


@pytest.fixture(scope="module")
def cold_batcher():
    return make_batcher(kv_host_mb=0)


def _evict_everything(b, rng, n_prompts=6):
    """Flood the small pool with distinct prompts so earlier radix
    prefixes evict (offloading to the arena when the tier is on)."""
    for _ in range(n_prompts):
        run_one(b, rng.integers(0, 256, 40).tolist(), n=4)


def test_restore_bitwise_identical_greedy(tier_batcher, cold_batcher):
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 256, 40).tolist()
    cold = run_one(cold_batcher, prompt)
    assert run_one(tier_batcher, prompt) == cold
    _evict_everything(tier_batcher, rng)
    base = tier_batcher.metrics.snapshot()["counters"].get(
        "kvtier_restored_blocks", 0)
    again = run_one(tier_batcher, prompt)
    counters = tier_batcher.metrics.snapshot()["counters"]
    assert counters.get("kvtier_restored_blocks", 0) > base, \
        "prompt KV was not restored from the host arena"
    assert again == cold
    assert counters.get("kvtier_offloaded_blocks", 0) > 0


def test_restore_bitwise_identical_sampled(tier_batcher, cold_batcher):
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 256, 40).tolist()
    sp = SamplingParams(temperature=0.9, top_k=7, top_p=0.95,
                        do_sample=True)
    cold = run_one(cold_batcher, prompt, sampling=sp, seed=11)
    assert run_one(tier_batcher, prompt, sampling=sp, seed=11) == cold
    _evict_everything(tier_batcher, rng)
    again = run_one(tier_batcher, prompt, sampling=sp, seed=11)
    assert again == cold


def test_restore_after_pool_rebuild_cold_radix(cold_batcher):
    """The arena outlives radix content entirely: a FRESH tier batcher
    that offloaded everything restores into an empty radix match."""
    rng = np.random.default_rng(7)
    b = make_batcher(kv_host_mb=64, num_blocks=16)
    prompt = rng.integers(0, 256, 40).tolist()
    cold = run_one(cold_batcher, prompt)
    first = run_one(b, prompt)
    _evict_everything(b, rng, n_prompts=4)
    blocks, n = b.pool.match_prefix(prompt[:39])
    b.pool.release(blocks)
    assert n == 0, "radix should have evicted the prompt under pressure"
    assert run_one(b, prompt) == cold == first


@pytest.mark.parametrize("host_dtype", ["native", "int8"])
def test_a_flat_pools_blocks_lie_in_the_arena_by_heads(host_dtype,
                                                       monkeypatch):
    """A one-device pool of 4 K/V heads of 128 stores a position's heads
    in one row of 512 (ops/paged_kvcache.heads_in_rows); the arena, and
    with it the wire and migration, keep a block by heads,
    [L, bs, Hkv, w]: what a peer with the heads' axis (a mesh) sends and
    takes, and what an int8 arena's per-(layer, head) scales are made
    over. Evict, offload, restore: bitwise the cold run's tokens in
    native mode; the int8 arena restores and its records hold a scale a
    head."""
    from distributed_llm_inferencing_tpu.ops import kvblock_quant as kvq
    monkeypatch.setenv("DLI_KV_HOST_DTYPE", host_dtype)
    cfg = CFG.replace(head_dim=128)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    kw = dict(num_blocks=24, block_size=8, slots=2, max_seq=128)
    b = Batcher(cfg, params, kv_host_mb=64, **kw)
    L = cfg.num_layers
    assert b.paged.k.shape == (L, 25, 8, 1, 512)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 256, 40).tolist()
    cold = run_one(Batcher(cfg, params, kv_host_mb=0, **kw), prompt)
    assert run_one(b, prompt) == cold
    _evict_everything(b, rng)
    digest = b.kvtier.block_digests(prompt[:8])[0]
    stored = b.kvtier.arena.peek_stored(digest)
    if host_dtype == "int8":
        assert kvq.is_quantized_block(stored)
        assert [e["scale"].shape for e in stored["pages"]] == [(L, 4)] * 2
    assert [p.shape for p in b.kvtier.arena.peek_pages(digest)] \
        == [(L, 8, 4, 128)] * 2
    base = b.metrics.snapshot()["counters"].get("kvtier_restored_blocks", 0)
    again = run_one(b, prompt)
    assert b.metrics.snapshot()["counters"]["kvtier_restored_blocks"] > base
    if host_dtype == "native":
        assert again == cold


# ---- same-wave duplicate prefix ----------------------------------------

def test_same_wave_duplicate_prefix_hits_earlier_insert():
    rng = np.random.default_rng(7)
    b = make_batcher(kv_host_mb=0, num_blocks=48)
    shared = rng.integers(0, 256, 32).tolist()
    r1 = b.submit(shared + [1, 2, 3], max_new_tokens=4,
                  sampling=SamplingParams.greedy())
    r2 = b.submit(shared + [7, 8, 9], max_new_tokens=4,
                  sampling=SamplingParams.greedy())
    run_until_done(b, [r1, r2])
    c = b.metrics.snapshot()["counters"]
    # the second member deferred one wave and re-matched the first
    # member's freshly inserted prefix blocks: 4 shared blocks cached
    assert c.get("prefill_cached_tokens", 0) >= 32
    assert b.pool.stats()["prefix_hits"] >= 1
    # and both outputs match their independently-generated twins
    b2 = make_batcher(kv_host_mb=0, num_blocks=48)
    assert r1.tokens == run_one(b2, shared + [1, 2, 3], n=4)
    assert r2.tokens == run_one(b2, shared + [7, 8, 9], n=4)


def test_cold_chunked_prefill_counts_zero_cached_tokens():
    """A single cold request whose prefill chunks across several passes
    re-matches its OWN earlier blocks on each resumption — that must not
    count as cached prefill (it would inflate the A/B's cached-fraction
    acceptance metric for traffic with no sharing at all)."""
    rng = np.random.default_rng(7)
    b = Batcher(CFG, PARAMS, num_blocks=24, block_size=8,
                slots=2, max_seq=128, kv_host_mb=0,
                prefill_chunk=4)    # 32-token chunks
    run_one(b, rng.integers(0, 256, 100).tolist(), n=4)
    c = b.metrics.snapshot()["counters"]
    assert c.get("prefill_uncached_tokens", 0) >= 100   # >= 3 passes ran
    assert c.get("prefill_cached_tokens", 0) == 0


# ---- metrics exposition ------------------------------------------------

def test_radix_and_kvtier_counters_reach_exposition(tier_batcher):
    # traffic of its own that evicts and offloads: under --dist load the
    # cases above may have run on another worker (alone, it found a pool
    # nothing had been evicted from)
    rng = np.random.default_rng(11)
    _evict_everything(tier_batcher, rng)
    tier_batcher.step()    # epilogue syncs pool counters into metrics
    text = tier_batcher.metrics.prometheus()
    for name in ("dli_radix_prefix_hits_total",
                 "dli_radix_prefix_misses_total",
                 "dli_radix_evictions_total",
                 "dli_kvtier_offloaded_blocks_total",
                 "dli_kvtier_host_bytes",
                 "dli_kvtier_occupancy",
                 "dli_prefill_cached_tokens_total",
                 "dli_prefill_uncached_tokens_total"):
        assert name in text, f"missing {name} in exposition"
    st = tier_batcher.stats()
    assert st["kvtier"]["offloaded"] > 0
    assert st["prefix_digests"] is None or "top" in st["prefix_digests"]


# ---- master affinity routing -------------------------------------------

def _master_with_two_nodes():
    from distributed_llm_inferencing_tpu.runtime.master import Master
    m = Master(":memory:")
    n1 = m.store.add_node("a", "127.0.0.1", 9001, is_active=True)
    n2 = m.store.add_node("b", "127.0.0.1", 9002, is_active=True)
    return m, n1, n2


def _advert(sys_prompt, chunk=16):
    digs = kvtier.text_chain_digests(sys_prompt, chunk)
    return {"chunk": chunk,
            "top": [[d, (i + 1) * chunk] for i, d in enumerate(digs)]}


def _rt(digests=None, queue=0, at=None):
    entry = {"queue": queue, "free": 10}
    if digests is not None:
        entry["digests"] = digests
    return {"queue": queue, "free_blocks": 10,
            "at": time.time() if at is None else at,
            "models": {"tiny-llama": entry}}


def test_affinity_pick_convoy_guard_and_staleness():
    m, n1, n2 = _master_with_two_nodes()
    try:
        sys_prompt = "S" * 64
        m._node_runtime[n1] = _rt(_advert(sys_prompt))
        m._node_runtime[n2] = _rt()
        nodes = m.store.list_nodes(active_only=True)

        pick = m._pick_node("tiny-llama", nodes=nodes,
                            prompt=sys_prompt + "tail-1")
        assert pick["id"] == n1
        c = m.metrics.snapshot()["counters"]
        assert c.get("scheduler_pick_prefix_affinity") == 1

        # FlowKV load-aware rule: the prefix holder is hot -> affinity
        # must NOT convoy; the request goes to the idle node
        m._inflight[n1] = 5
        pick = m._pick_node("tiny-llama", nodes=nodes,
                            prompt=sys_prompt + "tail-2")
        assert pick["id"] == n2
        # a stale advertisement (node silent past SCHED_STALE_S) drops
        # out of affinity scoring entirely
        m._inflight[n1] = 0
        m._node_runtime[n1] = _rt(_advert(sys_prompt),
                                  at=time.time() - 10_000)
        m._pick_node("tiny-llama", nodes=nodes, prompt=sys_prompt + "t3")
        c = m.metrics.snapshot()["counters"]
        assert c.get("scheduler_pick_prefix_affinity") == 1   # unchanged
    finally:
        m.stop()


def test_affinity_disabled_by_zero_weight():
    from distributed_llm_inferencing_tpu.runtime.master import Master
    m = Master(":memory:", prefix_weight=0.0)
    try:
        n1 = m.store.add_node("a", "127.0.0.1", 9001, is_active=True)
        n2 = m.store.add_node("b", "127.0.0.1", 9002, is_active=True)
        sys_prompt = "S" * 64
        m._node_runtime[n1] = _rt(_advert(sys_prompt))
        m._node_runtime[n2] = _rt()
        m._pick_node("tiny-llama",
                     nodes=m.store.list_nodes(active_only=True),
                     prompt=sys_prompt + "tail")
        c = m.metrics.snapshot()["counters"]
        assert "scheduler_pick_prefix_affinity" not in c
    finally:
        m.stop()


def test_persisted_node_row_strips_digest_advertisement():
    m, n1, _ = _master_with_two_nodes()
    try:
        info = {"status": "online", "loaded_models": [{
            "name": "tiny-llama",
            "scheduler": {"queued": 0, "blocks_free": 5,
                          "prefix_digests": {"chunk": 16,
                                             "top": [["aa", 16]]},
                          "pool": {"prefix_hits": 3, "prefix_misses": 1}},
        }]}
        m.store.update_node(n1, info=info)
        import json
        stored = json.loads(m.store.get_node(n1)["info"])
        sch = stored["loaded_models"][0]["scheduler"]
        assert "prefix_digests" not in sch
        assert sch["pool"]["prefix_hits"] == 3   # everything else kept
        # the caller's dict is NOT mutated (the in-memory runtime
        # snapshot still sees the advertisement)
        assert "prefix_digests" in info["loaded_models"][0]["scheduler"]
    finally:
        m.stop()
