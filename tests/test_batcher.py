"""Continuous batcher: scheduling, prefix reuse, preemption, streaming.

Oracle for token content is the dense-cache engine in greedy mode (dense ≡
paged is pinned separately in tests/test_paged.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher
from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
# one trace a program for all the file's cases
from conftest import shared_batcher as Batcher

CFG = get_config("tiny-llama").replace(dtype="float32", attn_backend="xla")
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def run_until_done(b, reqs, max_steps=400):
    for _ in range(max_steps):
        b.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError(
        f"not done after {max_steps} steps: "
        f"{[(r.done.is_set(), r.error, len(r.tokens)) for r in reqs]}")


@functools.lru_cache(maxsize=None)
def _engine():
    return InferenceEngine(CFG, PARAMS, max_seq=128)


def dense_greedy(prompt, n):
    eng = _engine()
    return eng.generate([prompt], max_new_tokens=n,
                        sampling=SamplingParams.greedy()).tokens[0]


def test_single_request_matches_engine():
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=4, max_seq=128)
    prompt = rng.integers(0, CFG.vocab_size, 13).tolist()
    r = b.submit(prompt, max_new_tokens=20, sampling=SamplingParams.greedy())
    run_until_done(b, [r])
    assert r.wait() == dense_greedy(prompt, 20)
    assert r.ttft_ms is not None and r.finished_at is not None


def test_concurrent_mixed_sampling():
    """Slots advance together; per-slot sampling params are independent."""
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=128, block_size=8,
                slots=4, max_seq=128)
    greedy_prompt = rng.integers(0, CFG.vocab_size, 9).tolist()
    reqs = [b.submit(greedy_prompt, max_new_tokens=15,
                     sampling=SamplingParams.greedy())]
    for i in range(5):   # more requests than slots -> queueing
        p = rng.integers(0, CFG.vocab_size, 5 + i).tolist()
        reqs.append(b.submit(p, max_new_tokens=10 + i,
                             sampling=SamplingParams(temperature=0.7)))
    run_until_done(b, reqs)
    for i, r in enumerate(reqs):
        assert r.error is None, r.error
        want = 15 if i == 0 else 10 + (i - 1)
        assert len(r.tokens) == want
    # the greedy request must be bit-identical to the engine even though it
    # shared decode steps with sampling requests
    assert reqs[0].tokens == dense_greedy(greedy_prompt, 15)


def test_prefix_cache_reuse_across_requests():
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=2, max_seq=128)
    sys_prompt = rng.integers(0, CFG.vocab_size, 24).tolist()  # 3 full blocks
    p1 = sys_prompt + rng.integers(0, CFG.vocab_size, 4).tolist()
    r1 = b.submit(p1, max_new_tokens=5, sampling=SamplingParams.greedy())
    run_until_done(b, [r1])
    misses_before = b.pool.stats()["prefix_misses"]

    p2 = sys_prompt + rng.integers(0, CFG.vocab_size, 6).tolist()
    r2 = b.submit(p2, max_new_tokens=5, sampling=SamplingParams.greedy())
    run_until_done(b, [r2])
    st = b.pool.stats()
    assert st["prefix_hits"] >= 1, st      # shared blocks were reused
    assert st["prefix_misses"] == misses_before
    assert r2.wait() == dense_greedy(p2, 5)   # reuse didn't change tokens


def test_identical_prompt_full_hit():
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=2, max_seq=128)
    prompt = rng.integers(0, CFG.vocab_size, 17).tolist()
    r1 = b.submit(prompt, max_new_tokens=6, sampling=SamplingParams.greedy())
    run_until_done(b, [r1])
    r2 = b.submit(prompt, max_new_tokens=6, sampling=SamplingParams.greedy())
    run_until_done(b, [r2])
    assert r1.wait() == r2.wait()


def test_preemption_under_memory_pressure():
    """A pool too small for all requests still completes every request
    correctly via preempt-and-resume."""
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=10, block_size=8,
                slots=3, max_seq=80)
    prompts = [rng.integers(0, CFG.vocab_size, 12).tolist() for _ in range(3)]
    reqs = [b.submit(p, max_new_tokens=12, sampling=SamplingParams.greedy())
            for p in prompts]
    run_until_done(b, reqs)
    for p, r in zip(prompts, reqs):
        assert r.error is None, r.error
        assert r.wait() == dense_greedy(p, 12)


def test_pool_exhausted_is_reported():
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=2, block_size=8,
                slots=2, max_seq=64)
    r = b.submit(rng.integers(0, CFG.vocab_size, 30).tolist(),
                 max_new_tokens=4)
    for _ in range(20):
        b.step()
        if r.done.is_set():
            break
    assert r.error and "exhausted" in r.error
    with pytest.raises(RuntimeError):
        r.wait()


def test_streaming_and_eos():
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=2, max_seq=128)
    prompt = rng.integers(0, CFG.vocab_size, 11).tolist()
    full = dense_greedy(prompt, 10)
    # use the 4th generated token as "eos": generation must stop before it
    eos = full[3]
    want = full[:3] if eos not in full[:3] else None
    seen = []
    r = b.submit(prompt, max_new_tokens=10, sampling=SamplingParams.greedy(),
                 eos_token_id=eos, stream_cb=seen.append)
    run_until_done(b, [r])
    got = r.wait()
    if want is not None:
        assert got == want
    assert seen == got          # streamed exactly the kept tokens, in order
    assert eos not in got


def test_seeded_sampling_reproducible_across_interleavings():
    """A request's sampled output depends only on (params, prompt, seed) —
    not on what else shares its decode steps."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CFG.vocab_size, 10).tolist()
    sp = SamplingParams(temperature=0.9, top_k=40, top_p=0.9)

    b1 = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                 slots=4, max_seq=128)
    alone = b1.submit(prompt, max_new_tokens=12, sampling=sp, seed=1234)
    run_until_done(b1, [alone])

    b2 = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                 slots=4, max_seq=128)
    noise = [b2.submit(rng.integers(0, CFG.vocab_size, 6 + i).tolist(),
                       max_new_tokens=20, sampling=sp, seed=i)
             for i in range(3)]
    crowded = b2.submit(prompt, max_new_tokens=12, sampling=sp, seed=1234)
    run_until_done(b2, noise + [crowded])
    assert crowded.wait() == alone.wait()


def test_cancel_frees_slot():
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=2, max_seq=128)
    r = b.submit(rng.integers(0, CFG.vocab_size, 8).tolist(),
                 max_new_tokens=100, sampling=SamplingParams.greedy())
    b.step()
    assert not r.done.is_set()
    r.cancel()
    b.step()
    assert r.done.is_set() and r.error == "cancelled"
    assert b.stats()["active"] == 0
    # its blocks came back
    assert b.pool.free_count() > 0


def test_stop_drains_inflight_requests():
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=1, max_seq=128)
    active = b.submit(rng.integers(0, CFG.vocab_size, 8).tolist(),
                      max_new_tokens=100)
    queued = b.submit(rng.integers(0, CFG.vocab_size, 8).tolist(),
                      max_new_tokens=100)
    b.step()
    b.stop()   # no thread started; must still fail both requests
    assert active.done.is_set() and queued.done.is_set()
    with pytest.raises(RuntimeError, match="stopped"):
        queued.wait(timeout=1)


def test_background_thread_serving():
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=4, max_seq=128)
    b.start()
    try:
        prompt = rng.integers(0, CFG.vocab_size, 8).tolist()
        reqs = [b.submit(prompt, max_new_tokens=8,
                         sampling=SamplingParams.greedy())
                for _ in range(6)]
        outs = [r.wait(timeout=300) for r in reqs]
        assert all(o == outs[0] for o in outs)
    finally:
        b.stop()
    st = b.stats()
    assert st["active"] == 0 and st["tokens_out"] >= 48

class OpCounter:
    """program_hook stand-in that counts dispatched programs by kind."""

    def __init__(self):
        self.ops = []

    def __call__(self, kind, args, run):
        self.ops.append((kind, args))
        return run()

    def count(self, kind):
        return sum(1 for k, _ in self.ops if k == kind)


def test_chunked_decode_amortizes_dispatches():
    """K-token on-device chunks: a 40-token generation costs a handful of
    dispatched programs, not one per token (the round-2 batcher's 6.6x
    regression vs the engine)."""
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=128, block_size=8,
                slots=4, max_seq=128)
    counter = OpCounter()
    b.program_hook = counter
    prompts = [rng.integers(0, CFG.vocab_size, 12).tolist() for _ in range(4)]
    reqs = [b.submit(p, max_new_tokens=40, sampling=SamplingParams.greedy())
            for p in prompts]
    run_until_done(b, reqs)
    for p, r in zip(prompts, reqs):
        assert r.wait() == dense_greedy(p, 40)
    # burst of 4 same-bucket prompts = ONE admission program; 39 post-first
    # tokens = chunk 32 then round-up chunk 8 (overshoot masked by budgets)
    # = 2 decode programs
    assert counter.count("admit") == 1, counter.ops
    assert counter.count("decode") <= 3, counter.ops
    assert len(counter.ops) <= 4


def test_wave_admission_one_dispatch_for_burst():
    """A burst of same-bucket requests admits in one batched program with
    first-token sampling fused in (no separate sample dispatch)."""
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=128, block_size=8,
                slots=8, max_seq=128)
    counter = OpCounter()
    b.program_hook = counter
    prompts = [rng.integers(0, CFG.vocab_size, 9).tolist() for _ in range(6)]
    reqs = [b.submit(p, max_new_tokens=1, sampling=SamplingParams.greedy())
            for p in prompts]
    run_until_done(b, reqs)
    assert counter.count("admit") == 1
    assert counter.count("decode") == 0
    for p, r in zip(prompts, reqs):
        assert r.wait() == dense_greedy(p, 1)


def test_eos_mid_chunk_stops_on_device():
    """Per-slot eos masks inside the chunk: tokens after the eos step are
    never emitted even though the program ran past it."""
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=2, max_seq=128)
    prompt = rng.integers(0, CFG.vocab_size, 11).tolist()
    full = dense_greedy(prompt, 30)
    eos = full[10]   # eos lands mid-chunk (after the 32-chunk starts)
    first = full.index(eos)
    r = b.submit(prompt, max_new_tokens=30, sampling=SamplingParams.greedy(),
                 eos_token_id=eos)
    run_until_done(b, [r])
    assert r.wait() == full[:first]
    assert b.stats()["active"] == 0 and b.pool.free_count() > 0


def test_mixed_budgets_mid_chunk():
    """Slots with different max_new_tokens share chunks; budget masks stop
    each at its own limit."""
    rng = np.random.default_rng(0)
    b = Batcher(CFG, PARAMS, num_blocks=128, block_size=8,
                slots=4, max_seq=128)
    prompts = [rng.integers(0, CFG.vocab_size, 7 + i).tolist()
               for i in range(4)]
    wants = [3, 17, 33, 50]
    reqs = [b.submit(p, max_new_tokens=w, sampling=SamplingParams.greedy())
            for p, w in zip(prompts, wants)]
    run_until_done(b, reqs)
    for p, w, r in zip(prompts, wants, reqs):
        assert len(r.wait()) == w
        assert r.wait() == dense_greedy(p, w)


# ---- mesh-sharded batching (tensor/expert parallel) ---------------------
# The batcher's single program partitions over a tp/ep mesh via GSPMD
# (runtime/batcher.py mesh_spec) — the round-2 lift of the old
# single-device-only restriction.

from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec  # noqa: E402


def test_tp_sharded_batcher_matches_dense_engine():
    rng = np.random.default_rng(0)
    spec = MeshSpec(tp=2)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=4, max_seq=128, mesh_spec=spec)
    assert b.stats()["mesh"]["tp"] == 2
    prompt = rng.integers(0, CFG.vocab_size, 13).tolist()
    r = b.submit(prompt, max_new_tokens=16, sampling=SamplingParams.greedy())
    run_until_done(b, [r])
    eng = InferenceEngine(CFG, PARAMS, mesh_spec=spec, max_seq=128)
    want = eng.generate([prompt], max_new_tokens=16,
                        sampling=SamplingParams.greedy()).tokens[0]
    assert r.wait() == want


def test_tp_sharded_batcher_concurrent_and_prefix_reuse():
    rng = np.random.default_rng(0)
    spec = MeshSpec(tp=4)
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=4, max_seq=128, mesh_spec=spec)
    sys_prompt = rng.integers(0, CFG.vocab_size, 16).tolist()  # 2 full blocks
    prompts = [sys_prompt + rng.integers(0, CFG.vocab_size, 3 + i).tolist()
               for i in range(4)]
    reqs = [b.submit(p, max_new_tokens=8, sampling=SamplingParams.greedy())
            for p in prompts]
    run_until_done(b, reqs)
    assert b.pool.stats()["prefix_hits"] >= 1
    for p, r in zip(prompts, reqs):
        assert r.wait() == dense_greedy(p, 8)


def test_ep_sharded_batcher_moe():
    rng = np.random.default_rng(0)
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.models.params import init_params
    import jax
    cfg = get_config("tiny-mixtral").replace(dtype="float32",
                                             attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    spec = MeshSpec(ep=2, tp=2)
    b = Batcher(cfg, params, num_blocks=64, block_size=8,
                slots=2, max_seq=128, mesh_spec=spec)
    prompt = rng.integers(0, cfg.vocab_size, 11).tolist()
    r = b.submit(prompt, max_new_tokens=8, sampling=SamplingParams.greedy())
    run_until_done(b, [r])
    eng = InferenceEngine(cfg, params, max_seq=128)
    want = eng.generate([prompt], max_new_tokens=8,
                        sampling=SamplingParams.greedy()).tokens[0]
    assert r.wait() == want


def test_batcher_rejects_non_tensor_axes():
    # dp/sp stay rejected (the slot scheduler owns the batch dim; decode
    # chunks never span one sequence); pp>1 is now a supported serving
    # mode (tests/test_paged_pipeline.py)
    for spec in (MeshSpec(dp=2), MeshSpec(sp=2)):
        with pytest.raises(ValueError, match="tp/ep"):
            Batcher(CFG, PARAMS, num_blocks=16, block_size=8,
                    slots=2, max_seq=64, mesh_spec=spec)


# ---------------- chunked prefill ----------------

def test_chunked_prefill_matches_monolithic():
    """A prompt admitted in chunks (via radix re-entry) must produce the
    exact token trajectory of a monolithic admission, and the chunked
    batcher must actually have taken >1 admission pass."""
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 50).tolist()   # 7 blocks @ bs 8

    def run(chunk):
        b = Batcher(cfg, num_blocks=64, block_size=8, slots=2,
                    max_seq=128, seed=0, prefill_chunk=chunk)
        r = b.submit(prompt, max_new_tokens=8,
                     sampling=SamplingParams.greedy())
        for _ in range(60):
            b.step()
            if r.done.is_set():
                break
        assert r.wait(), r.error
        return r.tokens, b.stats()

    mono, s0 = run(None)
    chunked, s1 = run(2)   # 2-block (16-token) chunks -> 3 partial passes
    assert s0["chunked_admissions"] == 0
    assert s1["chunked_admissions"] >= 3
    assert chunked == mono


def test_chunked_prefill_decode_interleaves():
    """While a long prompt admits chunk by chunk, an already-active
    request must keep generating between the chunks (the whole point:
    bounded decode stalls)."""
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(1)
    b = Batcher(cfg, num_blocks=64, block_size=8, slots=2,
                max_seq=128, seed=0, prefill_chunk=1)
    short = b.submit([1, 2, 3], max_new_tokens=100,
                     sampling=SamplingParams.greedy())
    b.step()                      # admit short; it starts decoding
    long_prompt = rng.integers(0, 256, 60).tolist()
    longr = b.submit(long_prompt, max_new_tokens=4,
                     sampling=SamplingParams.greedy())
    progress = [(len(short.tokens), len(longr.tokens))]
    for _ in range(80):
        b.step()
        progress.append((len(short.tokens), len(longr.tokens)))
        if short.done.is_set() and longr.done.is_set():
            break
    assert short.wait() and longr.wait()
    assert len(longr.tokens) == 4
    # decode interleaved with the long prompt's chunked admission: the
    # short stream grew in >= 2 steps BEFORE the long stream's first
    # token (i.e. during its multi-step admission)
    grew_during_admission = sum(
        1 for (s0, l0), (s1, l1) in zip(progress, progress[1:])
        if l1 == 0 and s1 > s0)
    assert grew_during_admission >= 2, progress
    assert b.stats()["chunked_admissions"] >= 7


def test_chunked_prefill_cancel_mid_admission():
    """Cancelling between chunks must finish the request without binding
    a slot and leak no blocks."""
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(2)
    b = Batcher(cfg, num_blocks=64, block_size=8, slots=2,
                max_seq=128, seed=0, prefill_chunk=1)
    free0 = b.pool.free_count()
    r = b.submit(rng.integers(0, 256, 40).tolist(), max_new_tokens=4,
                 sampling=SamplingParams.greedy())
    b.step()                      # first chunk admitted, request requeued
    r.cancel()
    for _ in range(10):
        b.step()
        if r.done.is_set():
            break
    assert r.done.is_set() and not r.tokens
    # all non-radix references returned; radix-held blocks are evictable
    # (free_count counts refcount-0 radix leaves as reclaimable or not —
    # either way active references must be zero)
    assert b.stats()["active"] == 0
    assert b.pool.free_count() + 40 // 8 + 1 >= free0 - 1


def test_chunked_prefill_progresses_with_all_slots_busy():
    """Partial admissions need no decode slot: a long prompt's chunks
    must land while every slot is occupied by active decodes."""
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(3)
    b = Batcher(cfg, num_blocks=64, block_size=8, slots=1,
                max_seq=128, seed=0, prefill_chunk=1)
    hog = b.submit([1, 2, 3], max_new_tokens=120,
                   sampling=SamplingParams.greedy())
    b.step()                      # the only slot is now decoding
    assert b.stats()["active"] == 1
    longr = b.submit(rng.integers(0, 256, 40).tolist(), max_new_tokens=2,
                     sampling=SamplingParams.greedy())
    for _ in range(3):
        b.step()
    # the long prompt chunk-admitted while the slot stayed busy
    assert b.stats()["chunked_admissions"] >= 2
    assert not longr.done.is_set() or not longr.error
    for _ in range(60):
        b.step()
        if hog.done.is_set() and longr.done.is_set():
            break
    assert hog.wait() and longr.wait()


@pytest.mark.parametrize("mate,full", [
    (SamplingParams(top_k=50), False),                  # prefix tier only
    (SamplingParams.greedy(), False),                   # no draw at all
    (SamplingParams(top_k=0, top_p=0.9), True),         # top-k off
    (SamplingParams(top_k=10_000), True),               # beyond PREFIX_K
])
def test_sample_full_passes_counts_the_full_tier(mate, full):
    """batcher_sample_full_passes rises by a chunk's passes when some
    sampling row of the chunk cannot take sample_batch's prefix tier
    (top_k off or beyond PREFIX_K), and stays put for a k = 50 fleet:
    its ratio to batcher_weight_passes is the full tier's share."""
    b = Batcher(CFG, PARAMS, num_blocks=64, block_size=8,
                slots=4, max_seq=128)
    reqs = [b.submit([3, 4, 5], max_new_tokens=12,
                     sampling=SamplingParams(top_k=50)),
            b.submit([6, 7, 8, 9], max_new_tokens=12, sampling=mate)]
    run_until_done(b, reqs)
    c = b.metrics.snapshot()["counters"]
    assert c["batcher_weight_passes"] > 0
    assert c.get("batcher_sample_full_passes", 0) == (
        c["batcher_weight_passes"] if full else 0)


def _rung_batcher(make=Batcher):
    # 16 blocks of 8 a slot: rungs of 16, 32, 48, 64, 96, 128 positions
    return make(CFG, PARAMS, num_blocks=64, block_size=8, slots=2,
                max_seq=128, decode_chunk_cap=8)


RUNG_PROMPTS = [np.random.default_rng(25).integers(
                    0, CFG.vocab_size, 25).tolist(),
                np.random.default_rng(6).integers(
                    0, CFG.vocab_size, 6).tolist()]


def _serve_across_a_rung(b):
    """Two requests, one greedy and one on the cells' sampling; the first
    one's context passes 32 positions mid-generation. Returns what each
    streamed."""
    streamed = [[], []]
    samplings = [SamplingParams.greedy(),
                 SamplingParams(temperature=0.7, top_k=0, top_p=0.9)]
    reqs = [b.submit(p, max_new_tokens=24, sampling=sp, seed=11,
                     stream_cb=streamed[i].append)
            for i, (p, sp) in enumerate(zip(RUNG_PROMPTS, samplings))]
    run_until_done(b, reqs)
    assert [r.wait() for r in reqs] == streamed
    return streamed


def test_context_crossing_a_rung_streams_the_full_extents_tokens(
        monkeypatch):
    """A request whose context passes a rung of the pool ladder (32 of
    128 positions) mid-generation: the rung is chosen inside the decode
    program, so
    nothing compiles after warm_decode_programs() and the program keys
    are the ones warmed; the tokens are those of the ladder patched to
    the full extent alone, and the dense engine's."""
    from distributed_llm_inferencing_tpu.models import transformer
    # (program tables of their own: one is warmed and its keys counted,
    # the other traces under a patched ladder)
    b = _rung_batcher(ContinuousBatcher)
    assert b.warm_decode_programs() == len(b.decode_chunks)
    keys = set(b._decode_fns)
    got = _serve_across_a_rung(b)
    assert set(b._decode_fns) == keys
    # AOT executables: a shape they were not compiled for would raise
    assert not any(hasattr(fn, "lower") for fn in b._decode_fns.values())
    assert got[0] == dense_greedy(RUNG_PROMPTS[0], 24)

    monkeypatch.setattr(transformer, "_pool_ladder",
                        lambda mb, scanned=True: (mb,))
    full = _rung_batcher(ContinuousBatcher)
    assert _serve_across_a_rung(full) == got
    c = full.metrics.snapshot()["counters"]
    assert c["batcher_decode_pool_positions"] \
        == 128 * c["batcher_weight_passes"]


def test_decode_pool_positions_counts_each_chunks_rung():
    """batcher_decode_pool_positions rises by the rung's positions a pass
    of every chunk, and the chunk's span says which rung it took: its
    ratio to batcher_weight_passes is the mean extent a pass read."""
    from distributed_llm_inferencing_tpu.utils import trace
    b = _rung_batcher()
    seen = {s.span_id for s in trace.get_tracer().spans()}
    _serve_across_a_rung(b)
    chunks = [s.attrs for s in trace.get_tracer().spans()
              if s.name == "batcher.decode_chunk"
              and s.span_id not in seen]
    rungs = [a["pool_positions"] for a in chunks]
    assert rungs == sorted(rungs) and set(rungs) == {32, 48}, rungs
    c = b.metrics.snapshot()["counters"]
    assert c["batcher_decode_pool_positions"] == sum(
        a["pool_positions"] * a["k"] for a in chunks)
    assert c["batcher_weight_passes"] == sum(a["k"] for a in chunks)
