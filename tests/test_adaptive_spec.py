"""Adaptive speculation (ops/speculative.py AdaptiveSpecController).

The contract: ``speculative="ngram"`` must never end up slower than plain
decode. Drafting stays on while it pays (high-repetition workloads),
gamma shrinks as acceptance drops, and a draft-hostile workload converges
to plain decode — with periodic probes bounding the cost of being wrong
in either direction. Token CONTENT is invariant throughout: greedy
speculative output is bit-identical to plain decode whichever mode each
individual chunk ran in, so every integration test also asserts output
equality against the plain batcher/engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.ops.speculative import (
    AdaptiveSpecController)
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher
from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

CFG = get_config("tiny-llama").replace(dtype="float32", attn_backend="xla")
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


# ---- controller policy (pure, no jax) ---------------------------------

def test_high_acceptance_keeps_drafting_and_grows_gamma():
    c = AdaptiveSpecController(8, warmup=2)
    c.gamma = 2
    for _ in range(12):
        g = c.choose()
        assert g > 0
        c.record("spec", emitted=5 * g, elapsed_s=0.01,
                 drafted=5 * g, accepted=4 * g)
    assert c.mode == "spec"
    assert c.gamma == 8          # grew back to the configured max
    assert c.fallbacks == 0


def test_low_acceptance_shrinks_gamma_then_falls_back():
    c = AdaptiveSpecController(8, warmup=2)
    gammas = []
    for _ in range(40):
        g = c.choose()
        if g == 0:
            break
        gammas.append(g)
        c.record("spec", emitted=5, elapsed_s=0.01, drafted=5 * g,
                 accepted=0)
    assert c.mode == "plain"
    assert c.fallbacks == 1
    assert min(gammas) == 2      # tried shorter drafts before giving up
    # steady state: plain with at most one probe per probe_every chunks
    probes = sum(1 for _ in range(c.probe_every) if c.choose() > 0)
    assert probes == 1


def test_measured_losing_tps_falls_back_despite_acceptance():
    """Full acceptance does not save drafting when the measured clock
    says plain is faster (the BENCH_r05 failure mode: dispatch-dominated
    host where even perfect drafts lose to big plain chunks)."""
    c = AdaptiveSpecController(4, warmup=2)
    c.record("plain", emitted=32, elapsed_s=0.01)   # plain: 3200 tok/s
    for _ in range(10):
        if c.choose() == 0:
            break
        c.record("spec", emitted=5, elapsed_s=0.01,  # spec: 500 tok/s
                 drafted=4, accepted=4)
    assert c.mode == "plain"
    assert c.fallbacks == 1


def test_probe_recovers_when_workload_turns_repetitive():
    c = AdaptiveSpecController(4, warmup=2, probe_every=4)
    for _ in range(20):          # drive into plain
        g = c.choose()
        if g == 0:
            continue
        c.record("spec", emitted=1, elapsed_s=0.01, drafted=g, accepted=0)
        if c.mode == "plain":
            break
    assert c.mode == "plain"
    # workload turns draft-friendly: probes now measure high acceptance
    for _ in range(4 * c.probe_every):
        g = c.choose()
        if g == 0:
            c.record("plain", emitted=4, elapsed_s=0.01)
        else:
            c.record("spec", emitted=5 * g, elapsed_s=0.001,
                     drafted=5 * g, accepted=4 * g)
        if c.mode == "spec":
            break
    assert c.mode == "spec"
    assert c.reactivations == 1


def test_spec_mode_plain_probe_arms_tps_fallback():
    """High acceptance alone must not pin a losing spec arm forever: a
    periodic PLAIN probe in spec mode measures the other arm, after
    which the tok/s clause can fall back (the BENCH_r05 shape —
    dispatch-dominated host where drafting loses at full acceptance)."""
    c = AdaptiveSpecController(4, warmup=2, probe_every=4)
    saw_plain_probe = False
    for _ in range(40):
        g = c.choose()
        if g == 0:
            if c.mode == "spec":
                saw_plain_probe = True
            c.record("plain", emitted=32, elapsed_s=0.01)   # 3200 tok/s
        else:
            c.record("spec", emitted=5, elapsed_s=0.01,     # 500 tok/s
                     drafted=g, accepted=g)                 # full accept
        if c.mode == "plain" and c.fallbacks:
            break
    assert saw_plain_probe
    assert c.mode == "plain" and c.fallbacks == 1


def test_zero_gamma_request_runs_plain_without_controller():
    """spec_gamma=0 is an explicit zero-draft request: the adaptive
    controller must not clamp it up to gamma=1 drafting."""
    b = ContinuousBatcher(CFG, PARAMS, num_blocks=64, block_size=8,
                          slots=2, max_seq=96, speculative="ngram",
                          spec_gamma=0)
    r = b.submit([1, 2, 3, 4], max_new_tokens=8,
                 sampling=SamplingParams.greedy())
    _drain(b, [r])
    assert r._spec_ctl is None
    assert r.tokens == _plain_tokens([[1, 2, 3, 4]], 8)[0]
    assert b.stats()["spec_accepted_tokens"] == 0   # nothing was drafted


def test_compiled_chunks_excluded_from_throughput():
    c = AdaptiveSpecController(4)
    c.record("spec", emitted=5, elapsed_s=10.0, drafted=4, accepted=4,
             compiled=True)      # cold compile: must not poison the EMA
    assert c.spec_tps is None
    c.record("spec", emitted=5, elapsed_s=0.01, drafted=4, accepted=4)
    assert c.spec_tps == pytest.approx(500.0)


# ---- batcher integration ----------------------------------------------

def _plain_tokens(prompts, n, sampling=None, seed0=None):
    b = ContinuousBatcher(CFG, PARAMS, num_blocks=256, block_size=8,
                          slots=4, max_seq=160)
    reqs = [b.submit(p, max_new_tokens=n,
                     sampling=sampling or SamplingParams.greedy(),
                     seed=None if seed0 is None else seed0 + i)
            for i, p in enumerate(prompts)]
    _drain(b, reqs)
    return [r.tokens for r in reqs]


def _drain(b, reqs, limit=600):
    for _ in range(limit):
        b.step()
        if all(r.done.is_set() for r in reqs):
            for r in reqs:
                assert r.error is None, r.error
            return
    raise AssertionError("batcher did not drain")


def _spec_batcher():
    # whole workloads against the plain batcher; what a wave of mixed
    # requests does per slot is tests/test_spec_wave.py's
    b = ContinuousBatcher(CFG, PARAMS, num_blocks=256, block_size=8,
                          slots=4, max_seq=160, speculative="ngram",
                          spec_gamma=3)
    b.DECODE_CHUNKS = (4, 2, 1)   # many small chunks -> many decisions
    return b


def test_repetitive_workload_keeps_drafting():
    """Greedy decode of this model on a repeated prompt degenerates into
    a repeating loop a few tokens in — prompt-lookup's best case. The
    controller must ride out the (genuinely draft-hostile) first tokens
    without abandoning drafting (min_evidence), then keep it on."""
    rng = np.random.default_rng(3)
    base = rng.integers(0, CFG.vocab_size, 4).tolist()
    prompts = [(base * 8)[:24] for _ in range(4)]
    b = _spec_batcher()
    reqs = [b.submit(p, max_new_tokens=64, sampling=SamplingParams.greedy())
            for p in prompts]
    _drain(b, reqs)
    for r in reqs:   # every request's own controller rode it out
        sa = r._spec_ctl.stats()
        assert sa["mode"] == "spec", sa
        assert sa["fallbacks"] == 0
    assert b.stats()["spec_wave"]["dispatches"] > 0
    assert b.stats()["spec_accepted_tokens"] > 0   # drafts actually landed
    assert [r.tokens for r in reqs] == _plain_tokens(prompts, 64)


def test_adversarial_workload_converges_to_plain():
    """Draft-hostile by construction: full-vocab sampling (top_k=0) is
    outside the covered prefix tier, so acceptance is zero BY DESIGN
    (ops/speculative.py accept_rejection_batch) — deterministic, not a
    hope about the model. The controller must fall back and run the tail
    as plain chunks — the 'within tolerance of plain throughput'
    guarantee, asserted structurally (post-fallback chunks are real
    plain dispatches; wall-clock on a shared CI box is noise). Uncovered
    sampled rows draw the same token the plain chunk would, so output
    stays bit-identical to the plain batcher under matching seeds."""
    rng = np.random.default_rng(3)
    sp = SamplingParams(temperature=1.0, top_k=0, top_p=1.0)
    prompts = [rng.integers(0, CFG.vocab_size, 24).tolist()
               for _ in range(4)]
    b = _spec_batcher()
    reqs = [b.submit(p, max_new_tokens=48, sampling=sp, seed=100 + i)
            for i, p in enumerate(prompts)]
    _drain(b, reqs)
    for r in reqs:
        sa = r._spec_ctl.stats()
        assert sa["mode"] == "plain", sa
        assert sa["fallbacks"] >= 1
        assert sa["plain_chunks"] > 0          # the tail really ran plain
        assert sa["spec_chunks"] <= 8, sa      # gave up fast, probes bounded
    # every width 0: the step dispatched true plain chunk programs
    assert b.stats()["chunk_sizes"]
    assert [r.tokens for r in reqs] == _plain_tokens(prompts, 48,
                                                     sampling=sp,
                                                     seed0=100)


def test_lockstep_plain_chunks_keep_follower_history_in_sync():
    """Adaptive fallback under lockstep: plain 'decode' broadcasts must
    carry admission-time history deltas (and followers must mirror the
    per-chunk appends), or a row admitted while the controller sits in
    plain mode leaves a permanent hole in the follower's drafting
    history that the next spec probe's delta skips forever."""
    rng = np.random.default_rng(3)
    import json
    mk = lambda: ContinuousBatcher(  # noqa: E731
        CFG, PARAMS, num_blocks=64, block_size=8, slots=2, max_seq=96,
        seed=0, speculative="ngram", spec_gamma=3)
    leader, follower = mk(), mk()
    kinds = []

    def hook(kind, args, run):
        wire = json.loads(json.dumps(args))   # JSON-safety incl. deltas
        kinds.append(kind)
        follower.replay(kind, wire)
        return run()

    leader.program_hook = hook
    prompts = [(rng.integers(0, CFG.vocab_size, 3).tolist() * 7)[:20],
               rng.integers(0, CFG.vocab_size, 9).tolist()]
    reqs = [leader.submit(p, max_new_tokens=10,
                          sampling=SamplingParams.greedy(), seed=31 + i)
            for i, p in enumerate(prompts)]
    # force the all-hostile fallback from the start: every request's own
    # controller sits in plain mode, so every chunk until the first
    # probe runs PLAIN, including the one right after admission
    for r in reqs:
        r._spec_ctl = AdaptiveSpecController(3)
        r._spec_ctl.mode = "plain"
    for _ in range(80):
        leader.step()
        if all(r.done.is_set() for r in reqs):
            break
    assert all(len(r.wait()) == 10 for r in reqs)
    assert "decode" in kinds          # the fallback path really ran
    # histories bit-identical (the SPMD input of any later spec probe);
    # watermarks may lag on the follower — a promoted follower merely
    # re-broadcasts rows, which is harmless over-send, never a hole
    np.testing.assert_array_equal(follower._hist, leader._hist)


# ---- engine integration -----------------------------------------------

@pytest.mark.parametrize("repetitive", [True, False])
def test_engine_adaptive_spec_output_invariant(repetitive, monkeypatch):
    """The single-stream engine loop consults the same controller: output
    must equal plain greedy decode whether chunks ran drafted or plain
    (the adversarial arm exercises the mid-generation fallback path)."""
    rng = np.random.default_rng(3)
    monkeypatch.setenv("DLI_SPEC_ADAPTIVE", "1")
    eng = InferenceEngine(CFG, PARAMS, max_seq=160)
    if repetitive:
        base = rng.integers(0, CFG.vocab_size, 4).tolist()
        prompt = (base * 8)[:24]
    else:
        prompt = rng.integers(0, CFG.vocab_size, 24).tolist()
    g = SamplingParams.greedy()
    plain = eng.generate([prompt], max_new_tokens=40, sampling=g).tokens[0]
    spec = eng.generate([prompt], max_new_tokens=40, sampling=g,
                        speculative="ngram", spec_gamma=4).tokens[0]
    assert spec == plain
