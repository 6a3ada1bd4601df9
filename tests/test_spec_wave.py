"""Wave-level batched speculation (runtime/batcher.py _step_spec_wave).

The contract: ONE fused draft+verify pass serves the whole active wave
with per-slot draft widths as data, each request arbitrated by its OWN
AdaptiveSpecController — a draft-hostile request converges to width 0
and rides the wave's verify pass as plain decode (no wave-wide fallback
cliff), greedy token content is bitwise invariant to the width
assignment, and the lockstep broadcast carries everything a follower
needs to replay the identical programs.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher
from distributed_llm_inferencing_tpu.utils import clock

CFG = get_config("tiny-llama").replace(dtype="float32", attn_backend="xla")
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _drain(b, reqs, limit=600):
    for _ in range(limit):
        b.step()
        if all(r.done.is_set() for r in reqs):
            for r in reqs:
                assert r.error is None, r.error
            return
    raise AssertionError("batcher did not drain")


class _TickClock(clock.SystemClock):
    """Every read advances one fixed tick, so each chunk of a given kind
    'takes' the same time and the controllers' one clock-driven clause
    (measured spec tok/s against plain tok/s, ops/speculative.py) becomes
    a function of emitted tokens alone — not of how loaded the host is
    (the test used to fail beside five other xdist workers)."""

    def __init__(self):
        self._t = 1.7e9

    def now(self):
        self._t += 1e-3
        return self._t

    monotonic = now


@pytest.fixture
def tick_clock():
    prev = clock.get_clock()
    clock.set_clock(_TickClock())
    yield
    clock.set_clock(prev)


def _mk(speculative="ngram", slots=4, spec_gamma=3,
        spec_adaptive=None, small_chunks=True):
    b = ContinuousBatcher(CFG, PARAMS, num_blocks=256, block_size=8,
                          slots=slots, max_seq=160,
                          speculative=speculative, spec_gamma=spec_gamma,
                          spec_adaptive=spec_adaptive)
    if small_chunks:
        b.DECODE_CHUNKS = (4, 2, 1)   # many chunks -> many decisions
    return b


def _repetitive(rng, n=24):
    """A prompt that repeats four tokens drawn from ``rng``: every test
    brings a generator of its own, so its prompts do not depend on which
    tests the process ran before it."""
    base = rng.integers(0, CFG.vocab_size, 4).tolist()
    return (base * (n // 4 + 2))[:n]


def _run(b, prompts, n=24, sampling=None, seed0=900):
    reqs = [b.submit(p, max_new_tokens=n,
                     sampling=sampling or SamplingParams.greedy(),
                     seed=seed0 + i) for i, p in enumerate(prompts)]
    _drain(b, reqs)
    return [r.tokens for r in reqs], reqs


# ---- bitwise greedy parity (the acceptance bar) -----------------------


def test_greedy_bitwise_wave_and_plain():
    """Greedy outputs identical across the plain batcher and wave
    speculation — mixed repetitive/random prompts so both
    accepted-heavy and miss-heavy slots are exercised."""
    rng = np.random.default_rng(17)
    prompts = [_repetitive(rng), rng.integers(0, 256, 11).tolist(),
               _repetitive(rng, 20), rng.integers(0, 256, 7).tolist()]
    plain, _ = _run(ContinuousBatcher(CFG, PARAMS, num_blocks=256,
                                      block_size=8, slots=4, max_seq=160),
                    prompts)
    on, _ = _run(_mk(), prompts)
    assert on == plain


def test_wave_drafts_actually_accept():
    """On a repetitive workload the wave path must land accepted drafts
    (tokens-per-weight-pass > 1) and count them in the wave metrics."""
    rng = np.random.default_rng(17)
    b = _mk()
    prompts = [_repetitive(rng) for _ in range(4)]
    _run(b, prompts, n=32)
    snap = b.metrics.snapshot()["counters"]
    assert snap.get("spec_wave_dispatches", 0) > 0
    assert snap.get("spec_wave_accepted_tokens", 0) > 0
    assert snap["spec_wave_accepted_tokens"] \
        <= snap["spec_wave_drafted_tokens"]
    assert b.stats()["spec_accepted_tokens"] > 0
    # amortization: accepted drafts mean strictly more tokens than
    # weight passes over the run
    assert snap["batcher_tokens_emitted"] > snap["batcher_weight_passes"]


# ---- per-slot heterogeneity: no wave-wide cliff -----------------------


def test_hostile_slot_rides_wave_while_friendly_keeps_drafting(tick_clock):
    """One draft-hostile request (top_k=0 full-vocab sampling: acceptance
    is zero BY DESIGN, ops/speculative.py) shares the wave with three
    repetitive greedy requests. The wave must keep the friendly slots
    drafting, with no wave-wide fallback cliff (accepted tokens keep
    growing), while the hostile request's own controller
    falls back — and its tokens stay bit-identical to the plain batcher
    (uncovered rows draw the plain chunk's exact sample)."""
    sp_hostile = SamplingParams(temperature=1.0, top_k=0, top_p=1.0)
    b = _mk()
    rng = np.random.default_rng(17)
    friendly = [b.submit(_repetitive(rng), max_new_tokens=48,
                         sampling=SamplingParams.greedy(), seed=10 + i)
                for i in range(3)]
    hostile_prompt = rng.integers(0, CFG.vocab_size, 24).tolist()
    hostile = b.submit(hostile_prompt, max_new_tokens=48,
                       sampling=sp_hostile, seed=77)
    _drain(b, friendly + [hostile])

    # the hostile request's own controller gave up drafting...
    assert hostile._spec_ctl is not None
    assert hostile._spec_ctl.mode == "plain", hostile._spec_ctl.stats()
    # ...while the friendly ones kept it on (no wave-wide cliff)
    for r in friendly:
        assert r._spec_ctl.mode == "spec", r._spec_ctl.stats()
        assert r._spec_acc > 0
    # hostile slot rode shared verify passes as plain decode
    snap = b.metrics.snapshot()["counters"]
    assert snap.get("spec_wave_plain_rides", 0) > 0

    # bit-identical to the plain batcher for the hostile request
    pb = ContinuousBatcher(CFG, PARAMS, num_blocks=256, block_size=8,
                           slots=4, max_seq=160)
    pr = pb.submit(hostile_prompt, max_new_tokens=48, sampling=sp_hostile,
                   seed=77)
    _drain(pb, [pr])
    assert hostile.tokens == pr.tokens


@pytest.mark.slow   # covered in check.sh's dedicated step; the per-slot
                    # heterogeneity invariant stays in bare tier-1 via
                    # test_hostile_slot_rides_wave_while_friendly_keeps_drafting
def test_all_hostile_wave_falls_back_to_true_plain_chunks():
    """When EVERY request converges to width 0 the step runs real plain
    programs (not degenerate all-zero verify passes) — visible as plain
    controller modes and bit-identical output."""
    rng = np.random.default_rng(17)
    sp = SamplingParams(temperature=1.0, top_k=0, top_p=1.0)
    prompts = [rng.integers(0, CFG.vocab_size, 20).tolist()
               for _ in range(4)]
    b = _mk()
    toks, reqs = _run(b, prompts, n=40, sampling=sp, seed0=300)
    for r in reqs:
        assert r._spec_ctl.mode == "plain", r._spec_ctl.stats()
    plain, _ = _run(ContinuousBatcher(CFG, PARAMS, num_blocks=256,
                                      block_size=8, slots=4, max_seq=160),
                    prompts, n=40, sampling=sp, seed0=300)
    assert toks == plain


def test_zero_gamma_wave_runs_plain_without_controllers():
    """spec_gamma=0: an explicit zero-draft request —
    no per-request controllers, plain chunks, plain-identical output."""
    b = _mk(spec_gamma=0, small_chunks=False)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8]]
    toks, reqs = _run(b, prompts, n=8)
    assert reqs[0]._spec_ctl is None
    assert b.stats()["spec_accepted_tokens"] == 0
    plain, _ = _run(ContinuousBatcher(CFG, PARAMS, num_blocks=256,
                                      block_size=8, slots=4, max_seq=160),
                    prompts, n=8)
    assert toks == plain


def test_fixed_width_wave_without_adaptivity():
    """spec_adaptive=False pins every slot at the full static width —
    wave dispatches happen, no controllers exist, greedy parity holds."""
    rng = np.random.default_rng(17)
    b = _mk(spec_adaptive=False)
    prompts = [_repetitive(rng), _repetitive(rng, 20)]
    toks, reqs = _run(b, prompts, n=16)
    for r in reqs:
        assert r._spec_ctl is None
    assert b.metrics.snapshot()["counters"]["spec_wave_dispatches"] > 0
    plain, _ = _run(ContinuousBatcher(CFG, PARAMS, num_blocks=256,
                                      block_size=8, slots=4, max_seq=160),
                    prompts, n=16)
    assert toks == plain


# ---- ledger + stats ----------------------------------------------------


def test_cost_ledger_attributes_draft_and_verify_tokens():
    rng = np.random.default_rng(17)
    b = _mk()
    prompts = [_repetitive(rng) for _ in range(4)]
    _, reqs = _run(b, prompts, n=32)
    for r in reqs:
        cost = r.cost
        assert cost is not None
        assert cost["spec_drafted_tokens"] > 0
        assert cost["spec_accepted_tokens"] + cost["spec_rejected_tokens"] \
            == cost["spec_drafted_tokens"]
        assert cost["weight_passes"] > 0 and cost["decode_tokens"] > 0
    # speculation's whole point: the wave accepted drafts somewhere,
    # and the ledger's accounting reconciles with the wave counters
    snap = b.metrics.snapshot()["counters"]
    assert sum(r.cost["spec_accepted_tokens"] for r in reqs) \
        == snap["spec_wave_accepted_tokens"] > 0
    assert sum(r.cost["spec_drafted_tokens"] for r in reqs) \
        == snap["spec_wave_drafted_tokens"]


def test_spec_wave_stats_surface():
    rng = np.random.default_rng(17)
    b = _mk()
    reqs = [b.submit(_repetitive(rng), max_new_tokens=24,
                     sampling=SamplingParams.greedy(), seed=5)]
    for _ in range(3):
        b.step()
    st = b.stats()["spec_wave"]
    assert st is not None
    assert st["dispatches"] >= 1
    assert st["active_controllers"] >= 1
    _drain(b, reqs)
    assert _mk(speculative=None).stats()["spec_wave"] is None


def test_wave_metrics_reach_tsdb_catalog():
    """The telemetry plane must retain the amortization metrics: a scrape
    of the batcher's exposition ingested into the TSDB lands
    ``decode_tokens_per_weight_pass`` (gauge) and the ``spec_wave_*``
    counters (as rates) in the catalog — including BEFORE any decode ran
    (the batcher pre-registers them at 0, so 'no samples yet' can never
    read as 'metric not exported')."""
    rng = np.random.default_rng(17)
    from distributed_llm_inferencing_tpu.runtime.tsdb import TSDB
    from distributed_llm_inferencing_tpu.utils.metrics import (
        parse_prometheus)
    b = _mk()
    exposition = b.metrics.prometheus()       # pre-decode scrape
    ts = TSDB(window_s=60, step_s=1)
    ts.ingest_prometheus("w0", parse_prometheus(exposition), t=100.0)
    cat = ts.catalog()["w0"]
    assert "decode_tokens_per_weight_pass" in cat
    assert "spec_wave_dispatches" in cat
    assert "spec_wave_accepted_tokens" in cat
    assert "spec_wave_drafted_tokens" in cat
    # after a run the gauge carries the amortization signal
    _run(b, [_repetitive(rng) for _ in range(2)], n=16)
    ts.ingest_prometheus("w0", parse_prometheus(b.metrics.prometheus()),
                         t=101.0)
    pts = ts.query("decode_tokens_per_weight_pass", node="w0", now=102.0)
    assert pts and pts[0]["points"]


def test_profiler_tags_spec_phases():
    """/api/profile attribution: wave chunks must land their wall time
    in the spec_draft / spec_verify phases, not plain dispatch."""
    rng = np.random.default_rng(17)
    from distributed_llm_inferencing_tpu.utils.profiler import PhaseProfiler
    b = _mk()
    b.profiler = PhaseProfiler(enabled=True, sample_every=1)
    _run(b, [_repetitive(rng) for _ in range(2)], n=16)
    phases = b.profiler.summary()["phases"]
    assert "spec_verify" in phases, phases
    assert "spec_draft" in phases, phases
    assert phases["spec_verify"]["s"] > 0


# ---- lockstep replay ---------------------------------------------------


def test_wave_lockstep_broadcast_carries_widths_not_history():
    """The lockstep invariant under wave speculation: spec_decode
    broadcasts ship per-slot widths + history DELTAS (never the full
    history), and a follower replaying the JSON'd programs reconstructs
    the leader's drafting history and emits identical programs."""
    rng = np.random.default_rng(17)
    mk = lambda: ContinuousBatcher(  # noqa: E731
        CFG, PARAMS, num_blocks=64, block_size=8, slots=2, max_seq=96,
        seed=0, speculative="ngram", spec_gamma=3)
    leader, follower = mk(), mk()
    spec_payloads = []

    def hook(kind, args, run):
        wire = json.loads(json.dumps(args))   # prove JSON-safety
        if kind == "spec_decode":
            assert "hist" not in wire, "full history must not broadcast"
            assert "gammas" in wire and len(wire["gammas"]) == 2
            spec_payloads.append(wire)
        follower.replay(kind, wire)
        return run()

    leader.program_hook = hook
    prompts = [_repetitive(rng, 20), rng.integers(0, 256, 7).tolist()]
    reqs = [leader.submit(p, max_new_tokens=12,
                          sampling=SamplingParams.greedy(), seed=9 + i)
            for i, p in enumerate(prompts)]
    for _ in range(60):
        leader.step()
        if all(r.done.is_set() for r in reqs):
            break
    outs = [r.wait() for r in reqs]
    assert all(len(o) == 12 for o in outs)
    assert spec_payloads, "wave speculative chunks must have dispatched"
    # delta amortization: only the first chunk after admission syncs rows
    assert spec_payloads[0]["hist_delta"], spec_payloads[0]
    for p in spec_payloads[1:]:
        assert p["hist_delta"] == [], p["hist_delta"]
    np.testing.assert_array_equal(follower._hist, leader._hist)


# ---- eos / streaming under wave widths --------------------------------


def test_wave_eos_and_stream_order():
    plain = ContinuousBatcher(CFG, PARAMS, num_blocks=64, block_size=8,
                              slots=2, max_seq=128, seed=0)
    # seed 17: a prompt whose greedy continuation does not loop on one
    # token, so it has a usable eos
    prompt = _repetitive(np.random.default_rng(17), 18)
    r0 = plain.submit(prompt, max_new_tokens=10,
                      sampling=SamplingParams.greedy())
    _drain(plain, [r0])
    full = r0.tokens
    # first position whose token does not appear earlier: cutting there
    # is unambiguous even on a degenerate repetition loop
    cut = next((i for i in range(1, len(full))
                if full[i] not in full[:i]), None)
    if cut is None:
        pytest.skip("fully degenerate repetition: no usable eos")
    eos = full[cut]

    b = _mk(slots=2)
    seen = []
    r = b.submit(prompt, max_new_tokens=10,
                 sampling=SamplingParams.greedy(), eos_token_id=eos,
                 stream_cb=seen.append)
    _drain(b, [r])
    assert r.tokens == full[:cut]
    assert seen == r.tokens


@pytest.mark.slow   # ~10s of sampling; the dedicated check.sh step runs
                    # it (no -m filter there), bare tier-1 skips
def test_wave_sampled_distribution_against_noise_floor():
    """Sampled mode under wave widths: empirical distribution of the
    speculative-verified positions must sit within the plain-vs-plain
    sampling noise floor (same calibration as the pre-wave suite)."""
    rng = np.random.default_rng(17)
    prompt = (rng.integers(0, 256, 4).tolist() * 5)[:18]
    sp = SamplingParams(temperature=1.2, top_k=8, top_p=0.95)
    n = 100

    def collect(wave, seed0):
        b = ContinuousBatcher(CFG, PARAMS, num_blocks=256, block_size=8,
                              slots=8, max_seq=64, seed=0,
                              speculative="ngram" if wave else None,
                              spec_gamma=2)
        reqs = [b.submit(prompt, max_new_tokens=3, sampling=sp,
                         seed=seed0 + s) for s in range(n)]
        _drain(b, reqs)
        counts = {}
        for r in reqs:
            for pos in (1, 2):
                key = (pos, r.tokens[pos])
                counts[key] = counts.get(key, 0) + 1
        return counts

    def tv(a, b):
        support = set(a) | set(b)
        return sum(abs(a.get(t, 0) - b.get(t, 0))
                   for t in support) / (2 * 2 * n)

    plain_a = collect(False, 0)
    plain_b = collect(False, 5000)
    wave_a = collect(True, 0)
    tv_null = tv(plain_a, plain_b)
    tv_wave = tv(wave_a, plain_a)
    assert tv_wave < 1.5 * tv_null + 0.08, (tv_wave, tv_null)
