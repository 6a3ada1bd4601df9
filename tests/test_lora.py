"""Multi-LoRA adapter serving (models/lora.py, ops/lora.py, the
batcher's per-slot gathered application, and the master's routing).

The contract under test: the batched gathered delta is EXACT — a mixed-
adapter wave emits, per request, bitwise the tokens a dedicated
single-adapter batcher emits, and an adapter's output equals the dense
model with that adapter merged into its weights; the host store is a
bounded LRU tier that never evicts pinned adapters; an adapter problem
FAILS the request loudly (never silently serves base weights); the
master's adapter-affinity pick honors the convoy guard; and a
live-migration resume record carries the adapter with it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import lora as lora_mod
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops import lora as lora_ops
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from conftest import shared_batcher as Batcher

CFG = get_config("tiny-llama").replace(dtype="float32", attn_backend="xla")
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

# scale ~0.8: strong enough that the rank-r delta flips greedy argmax on
# the random-init tiny model (the checkpoint-realistic 0.05 default is a
# ~0.25% relative delta greedy decoding never sees — every differential
# below would pass vacuously against base weights)
A_SRC = "synth:rank=4,seed=3,scale=0.8"
B_SRC = "synth:rank=8,seed=9,scale=0.8"


def _mk(**kw):
    kw.setdefault("num_blocks", 128)
    kw.setdefault("block_size", 8)
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq", 96)
    return Batcher(CFG, PARAMS, **kw)


def _drain(b, reqs, limit=2000):
    for _ in range(limit):
        b.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("batcher did not drain")


def _prompt(i, n=7):
    return np.random.default_rng(100 + i).integers(0, 256, n).tolist()


# ---- ops: the gathered delta vs a plain per-row delta -----------------


def test_gathered_delta_math():
    """gathered_delta == x @ A[id] @ B[id] per row, and slot 0 (zero
    pack rows) is an exact-zero delta, not a small one."""
    rng = np.random.default_rng(5)
    S, din, rmax, dout, B, T = 3, 8, 4, 6, 4, 2
    a = rng.standard_normal((S, din, rmax)).astype(np.float32)
    b = rng.standard_normal((S, rmax, dout)).astype(np.float32)
    a[0] = 0.0
    b[0] = 0.0
    x = rng.standard_normal((B, T, din)).astype(np.float32)
    ids = np.array([0, 1, 2, 1], np.int32)
    got = np.asarray(lora_ops.gathered_delta(
        jnp.asarray(x), {"a": jnp.asarray(a), "b": jnp.asarray(b)},
        jnp.asarray(ids)))
    for r in range(B):
        want = x[r] @ a[ids[r]] @ b[ids[r]]
        np.testing.assert_allclose(got[r], want, rtol=1e-5, atol=1e-5)
    assert np.all(got[0] == 0.0)


# ---- host store: LRU by bytes, pinning, occupancy ---------------------


def test_host_store_lru_pinning_and_occupancy():
    ads = [lora_mod.synthesize(CFG, f"ad{i}", rank=2, seed=i)
           for i in range(4)]
    per = ads[0].nbytes
    store = lora_mod.LoRAHostStore(capacity_mb=2.5 * per / 2**20)
    assert store.put(ads[0]) == []
    assert store.put(ads[1]) == []
    st = store.stats()
    assert st["adapters"] == 2 and st["bytes"] == 2 * per
    # touch ad0 so ad1 becomes LRU; the third insert evicts ad1
    assert store.get("ad0") is not None
    assert store.put(ads[2]) == ["ad1"]
    assert sorted(store.names()) == ["ad0", "ad2"]
    assert store.stats()["evictions"] == 1
    # every resident adapter pinned: put must refuse AND roll back
    with pytest.raises(ValueError, match="pinned"):
        store.put(ads[3], pinned={"ad0", "ad2"})
    assert sorted(store.names()) == ["ad0", "ad2"]
    assert store.stats()["bytes"] == 2 * per
    # an adapter larger than the whole budget is refused outright
    big = lora_mod.synthesize(CFG, "big", rank=16, seed=9)
    with pytest.raises(ValueError, match="exceeds"):
        lora_mod.LoRAHostStore(capacity_mb=big.nbytes / 2**21).put(big)
    # peek must not touch recency: ad0 stays LRU and is evicted next
    store.get("ad2")
    assert store.peek("ad0") is not None
    assert store.put(ads[3]) == ["ad0"]


# ---- delta exactness: adapter serving == dense merged weights ---------


def _merged_params(ad):
    layers = dict(PARAMS["layers"])
    for t in ad.targets:
        w = np.asarray(layers[t]["w"], np.float32).copy()
        for li, lp in enumerate(ad.layers):
            a, b = lp[t]
            w[li] = lora_ops.merge_into_dense(w[li], a, b, ad.scale)
        layers[t] = dict(layers[t], w=jnp.asarray(w, jnp.float32))
    return dict(PARAMS, layers=layers)


def test_adapter_equals_merged_dense_greedy():
    """Greedy tokens through the gathered per-slot delta match the
    dense model with the adapter merged into its weights (token-level:
    the two formulations differ in fp summation order)."""
    ad = lora_mod.resolve(CFG, "diff", "synth:rank=4,seed=5,scale=0.9")
    prompts = [_prompt(i) for i in range(3)]

    b = _mk()
    b.load_adapter("diff", "synth:rank=4,seed=5,scale=0.9")
    reqs = [b.submit(p, max_new_tokens=8, sampling=SamplingParams.greedy(),
                     seed=50 + i, adapter="diff")
            for i, p in enumerate(prompts)]
    _drain(b, reqs)
    base_reqs = [b.submit(p, max_new_tokens=8,
                          sampling=SamplingParams.greedy(), seed=50)
                 for p in prompts]
    _drain(b, base_reqs)

    merged = Batcher(CFG, _merged_params(ad), num_blocks=128,
                     block_size=8, slots=4, max_seq=96)
    mreqs = [merged.submit(p, max_new_tokens=8,
                           sampling=SamplingParams.greedy(), seed=50 + i)
             for i, p in enumerate(prompts)]
    _drain(merged, mreqs)
    for r, mr, br in zip(reqs, mreqs, base_reqs):
        assert r.tokens == mr.tokens
    # the adapter actually changed SOMETHING vs base — otherwise the
    # equality above proves nothing
    assert any(r.tokens != br.tokens for r, br in zip(reqs, base_reqs))


# ---- mixed-adapter waves: bitwise vs dedicated batchers ---------------


def test_mixed_wave_bitwise_vs_dedicated():
    """One wave mixing base + two adapters (greedy AND sampled rows)
    emits, per request, bitwise the tokens dedicated single-adapter
    batchers emit for the same (prompt, sampling, seed)."""
    sampled = SamplingParams(do_sample=True, temperature=0.9)
    specs = []   # (adapter, prompt, sampling, seed)
    for i in range(6):
        ad = (None, "a1", "a2")[i % 3]
        sp = SamplingParams.greedy() if i < 3 else sampled
        specs.append((ad, _prompt(i, 5 + i % 4), sp, 900 + i))

    mixed = _mk()
    mixed.load_adapter("a1", A_SRC)
    mixed.load_adapter("a2", B_SRC)
    reqs = [mixed.submit(p, max_new_tokens=8, sampling=sp, seed=seed,
                         adapter=ad)
            for ad, p, sp, seed in specs]
    _drain(mixed, reqs)
    got = {seed: r.tokens for (_, _, _, seed), r in zip(specs, reqs)}

    for name in (None, "a1", "a2"):
        ded = _mk()
        if name:
            ded.load_adapter(name, A_SRC if name == "a1" else B_SRC)
        sub = [s for s in specs if s[0] == name]
        dreqs = [ded.submit(p, max_new_tokens=8, sampling=sp, seed=seed,
                            adapter=ad)
                 for ad, p, sp, seed in sub]
        _drain(ded, dreqs)
        for (_, _, _, seed), r in zip(sub, dreqs):
            assert r.tokens == got[seed], \
                f"adapter {name!r} seed {seed} diverged in the mix"


# ---- failure semantics: loud rejection, never silent base -------------


def test_unknown_adapter_rejected_at_submit():
    b = _mk()
    with pytest.raises(ValueError, match="unknown adapter"):
        b.submit(_prompt(0), max_new_tokens=4, adapter="ghost")
    assert not b.queue
    # the batcher still serves base traffic afterwards
    r = b.submit(_prompt(1), max_new_tokens=4,
                 sampling=SamplingParams.greedy(), seed=1)
    _drain(b, [r])
    assert r.error is None and len(r.tokens) == 4


def test_load_failure_is_loud_never_base():
    b = _mk()
    # rank above DLI_LORA_MAX_RANK: refused at load...
    with pytest.raises(ValueError, match="rank"):
        b.load_adapter("fat", "synth:rank=99,seed=1")
    with pytest.raises(ValueError, match="synth param"):
        b.load_adapter("typo", "synth:rnak=4")
    assert b.metrics.snapshot()["counters"]["lora_load_failures"] >= 2
    # ...so a request naming it can never exist, let alone serve base
    with pytest.raises(ValueError, match="unknown adapter"):
        b.submit(_prompt(0), max_new_tokens=4, adapter="fat")
    # unload with live requests refuses; after release it drops
    b.load_adapter("ok", A_SRC)
    r = b.submit(_prompt(2), max_new_tokens=4,
                 sampling=SamplingParams.greedy(), adapter="ok")
    with pytest.raises(ValueError, match="live requests"):
        b.unload_adapter("ok")
    _drain(b, [r])
    assert b.unload_adapter("ok") is True
    assert "ok" not in b.lora_stats()["resident"]


def test_slot_exhaustion_fails_admission():
    """More DISTINCT live adapters than device slots: the overflow
    request fails with the slots error, siblings complete."""
    b = _mk(slots=4)
    b._lora_slot_names = [None, None]   # 1 device slot
    b.load_adapter("s1", A_SRC)
    b.load_adapter("s2", B_SRC)
    r1 = b.submit(_prompt(0), max_new_tokens=8,
                  sampling=SamplingParams.greedy(), adapter="s1")
    r2 = b.submit(_prompt(1), max_new_tokens=8,
                  sampling=SamplingParams.greedy(), adapter="s2")
    _drain(b, [r1, r2])
    assert r1.error is None
    assert r2.error is not None and "slots" in r2.error
    assert r2.tokens == []   # failed loudly, served nothing


# ---- migration: the resume record carries the adapter -----------------


def test_migration_resume_carries_adapter():
    src = _mk()
    src.load_adapter("mig", A_SRC)
    req = src.submit(_prompt(3), max_new_tokens=12,
                     sampling=SamplingParams.greedy(), seed=7,
                     adapter="mig", chunk_cap=2)
    for _ in range(200):
        src.step()
        if len(req.tokens) >= 4:
            break
    assert 4 <= len(req.tokens) < 12 and not req.done.is_set()
    req._migrate_requested = True
    for _ in range(50):
        src.step()
        if req.done.is_set():
            break
    rec = req.resume_record
    assert rec is not None and rec["adapter"] == "mig"

    dst = _mk()
    dst.load_adapter("mig", A_SRC)
    cont = dst.submit(rec["prompt_tokens"],
                      max_new_tokens=rec["max_new_tokens"],
                      sampling=SamplingParams.greedy(), resume=rec)
    assert cont.adapter == "mig"
    _drain(dst, [cont])

    whole = _mk()
    whole.load_adapter("mig", A_SRC)
    ref = whole.submit(_prompt(3), max_new_tokens=12,
                       sampling=SamplingParams.greedy(), seed=7,
                       adapter="mig")
    _drain(whole, [ref])
    # cont.tokens holds carried + newly decoded: the whole stream must
    # be bitwise the unmigrated run's
    assert cont.tokens[:len(rec["tokens"])] == rec["tokens"]
    assert cont.tokens == ref.tokens


# ---- master: registry validation + adapter-affinity convoy guard ------


def _master():
    from distributed_llm_inferencing_tpu.runtime.master import Master
    return Master(":memory:")


def test_registry_validation_and_submit_gate():
    m = _master()
    try:
        bad = m.api_register_adapter({"adapter": "x y", "source": "synth"})
        assert bad[0] == 400
        bad = m.api_register_adapter({"adapter": "ok"})
        assert bad[0] == 400 and "source" in bad[1]["message"]
        r = m.api_register_adapter({"adapter": "ten-a", "source": A_SRC,
                                    "model_name": "tiny-llama"})
        assert r["status"] == "success"
        assert m.adapter_registry()["ten-a"]["model"] == "tiny-llama"
        # unregistered adapter: structured 400 at the front door
        code, body = m.api_submit({"model_name": "tiny-llama",
                                   "prompt": "hi", "adapter": "ghost"})
        assert code == 400 and "not registered" in body["message"]
        # registered for ANOTHER model: also a 400, naming the mismatch
        code, body = m.api_submit({"model_name": "tiny-gpt2",
                                   "prompt": "hi", "adapter": "ten-a"})
        assert code == 400 and "tiny-llama" in body["message"]
    finally:
        m.stop()


def test_adapter_affinity_convoy_guard():
    from distributed_llm_inferencing_tpu.utils import clock
    m = _master()
    try:
        cands = [{"id": 1, "name": "n1"}, {"id": 2, "name": "n2"}]

        def snap(queue, resident):
            return {"at": clock.now(), "queue": queue, "models": {},
                    "adapters": {"tiny-llama": {"resident": resident,
                                                "bytes": 0}}}

        def pick(q1, q2, res1, res2, slo=None):
            m._node_runtime = {1: snap(q1, res1), 2: snap(q2, res2)}
            return m._score_pick(cands, model="tiny-llama",
                                 slo_class=slo, adapter="ad")

        # resident + within slack: affinity wins
        n, reason = pick(0, 0, ["ad"], [])
        assert (n["id"], reason) == (1, "adapter_affinity")
        # resident node overloaded beyond the slack: the convoy guard
        # sends the request to the cold node instead
        n, reason = pick(50, 0, ["ad"], [])
        assert n["id"] == 2 and reason != "adapter_affinity"
        # latency class zeroes the slack: one queued request is enough
        # to lose the affinity
        n, reason = pick(1, 0, ["ad"], [], slo="latency")
        assert n["id"] == 2 and reason != "adapter_affinity"
        # affinity must SEPARATE candidates: all-resident (and equally
        # loaded) means nothing to win, load policy decides
        n, reason = pick(0, 0, ["ad"], ["ad"])
        assert reason != "adapter_affinity"
    finally:
        m.stop()
