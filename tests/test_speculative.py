"""Speculative decoding (ops/speculative.py + engine integration).

The contract is output EQUIVALENCE: greedy speculative decode must be
bit-identical to plain greedy decode (acceptance keeps exactly the tokens
argmax would have produced), and sampling mode must preserve the target
distribution (delta-draft leave-one-out rejection). Speed is asserted
only structurally — fewer dispatched steps than emitted tokens on a
draft-friendly (repetitive) input.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.ops.speculative import propose_ngram
from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
from conftest import shared_batcher as Batcher

CFG = get_config("tiny-llama").replace(dtype="float32", attn_backend="xla")
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def test_propose_ngram():
    hist = [1, 2, 3, 4, 9, 9, 1, 2]
    # trailing bigram (1,2) occurred at 0 -> continuation 3, 4, 9...
    assert propose_ngram(hist, 3) == [3, 4, 9]
    # continuation shorter than gamma -> padded with its last token
    assert propose_ngram([5, 6, 7, 5, 6], 4) == [7, 5, 6, 6]
    assert propose_ngram([1, 2, 3], 4) is None          # no earlier hit
    assert propose_ngram([1, 2], 4) is None             # too short


def _engine():
    return InferenceEngine(CFG, PARAMS, max_seq=128)


def test_greedy_speculative_matches_plain_repetitive():
    """Repetitive prompt = high draft acceptance; output must still be
    bit-identical to plain greedy decode."""
    rng = np.random.default_rng(0)
    pattern = rng.integers(0, CFG.vocab_size, 5).tolist()
    prompt = (pattern * 4)[:18]
    eng = _engine()
    plain = eng.generate([prompt], max_new_tokens=24,
                         sampling=SamplingParams.greedy())
    spec = eng.generate([prompt], max_new_tokens=24,
                        sampling=SamplingParams.greedy(),
                        speculative="ngram", spec_gamma=4)
    assert spec.tokens[0] == plain.tokens[0]


def test_greedy_speculative_matches_plain_random():
    """Random prompt = few/no draft hits; correctness must not depend on
    acceptance rate."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CFG.vocab_size, 13).tolist()
    eng = _engine()
    plain = eng.generate([prompt], max_new_tokens=16,
                         sampling=SamplingParams.greedy())
    spec = eng.generate([prompt], max_new_tokens=16,
                        sampling=SamplingParams.greedy(),
                        speculative="ngram", spec_gamma=3)
    assert spec.tokens[0] == plain.tokens[0]


def test_speculative_fewer_steps_on_acceptance():
    """Tiny random-init models repeat themselves under greedy decode, so
    the n-gram draft should land accepts — fewer verify dispatches than
    tokens. (Structural speed proxy; wall-clock is hardware-dependent.)
    A generator of its own: three of eight patterns draw no accept, and
    the module's shared one hands out whatever the tests a worker ran
    before this one left."""
    pattern = np.random.default_rng(3).integers(
        0, CFG.vocab_size, 4).tolist()
    prompt = (pattern * 5)[:19]
    eng = _engine()
    spec = eng.generate([prompt], max_new_tokens=30,
                        sampling=SamplingParams.greedy(),
                        speculative="ngram", spec_gamma=4)
    assert len(spec.tokens[0]) == 30
    assert spec.steps < 30, spec.steps


def test_speculative_eos_and_seeding():
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CFG.vocab_size, 9).tolist()
    eng = _engine()
    full = eng.generate([prompt], max_new_tokens=12,
                        sampling=SamplingParams.greedy(),
                        speculative="ngram").tokens[0]
    eos = full[5]
    want = full[:5] if eos not in full[:5] else None
    got = eng.generate([prompt], max_new_tokens=12,
                       sampling=SamplingParams.greedy(),
                       speculative="ngram", eos_token_id=eos).tokens[0]
    if want is not None:
        assert got == want
    assert eos not in got
    # sampling mode: deterministic given the seed
    sp = SamplingParams(temperature=0.9, top_k=40, top_p=0.9)
    a = eng.generate([prompt], max_new_tokens=15, sampling=sp, seed=7,
                     speculative="ngram").tokens[0]
    b = eng.generate([prompt], max_new_tokens=15, sampling=sp, seed=7,
                     speculative="ngram").tokens[0]
    assert a == b and len(a) == 15


def test_speculative_sampling_distribution_preserved():
    """Delta-draft rejection must keep the target distribution: with a
    sharply peaked next-token distribution and an adversarial draft, the
    emitted first token's empirical frequencies must match plain decode's
    across seeds."""
    rng = np.random.default_rng(0)
    prompt = (rng.integers(0, CFG.vocab_size, 4).tolist() * 5)[:18]
    eng = _engine()
    sp = SamplingParams(temperature=1.2, top_k=8, top_p=0.95)
    plain_counts: dict = {}
    spec_counts: dict = {}
    n = 120
    for seed in range(n):
        p = eng.generate([prompt], max_new_tokens=2, sampling=sp,
                         seed=seed).tokens[0]
        s = eng.generate([prompt], max_new_tokens=2, sampling=sp, seed=seed,
                         speculative="ngram", spec_gamma=2).tokens[0]
        # token 0 comes from the same prefill+sample path in both modes —
        # compare token 1, the first speculative-verified position
        plain_counts[p[1]] = plain_counts.get(p[1], 0) + 1
        spec_counts[s[1]] = spec_counts.get(s[1], 0) + 1
    support = set(plain_counts) | set(spec_counts)
    tv = sum(abs(plain_counts.get(t, 0) - spec_counts.get(t, 0))
             for t in support) / (2 * n)
    # total-variation distance between the two empirical distributions;
    # ~sqrt(k/n) noise floor — generous bound catches real skew
    assert tv < 0.25, (tv, plain_counts, spec_counts)


# ---------------- on-device drafting ----------------

def test_propose_ngram_device_matches_host():
    """Differential: the vectorized device proposer must agree with the
    host propose_ngram on random histories (where the host finds a
    draft), and report has_draft=False exactly when the host returns
    None."""
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.ops.speculative import (
        propose_ngram, propose_ngram_device)
    rng = np.random.default_rng(0)
    H, R, G = 48, 16, 4
    hist = np.zeros((R, H), np.int32)
    lens = np.zeros((R,), np.int32)
    rows = []
    for r in range(R):
        n = int(rng.integers(3, H))
        # small vocab => plenty of repeated bigrams
        row = rng.integers(0, 5, n).tolist()
        rows.append(row)
        hist[r, :n] = row
        lens[r] = n
    drafts, has = propose_ngram_device(
        jnp.asarray(hist), jnp.asarray(lens), G)
    drafts, has = np.asarray(drafts), np.asarray(has)
    for r in range(R):
        want = propose_ngram(rows[r], G)
        assert has[r] == (want is not None), (r, rows[r])
        if want is not None:
            assert drafts[r].tolist() == want, (r, rows[r],
                                                drafts[r].tolist(), want)


def test_propose_ngram_device_short_histories():
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.ops.speculative import (
        propose_ngram_device)
    hist = jnp.asarray([[7, 0, 0, 0], [7, 7, 0, 0]], jnp.int32)
    drafts, has = propose_ngram_device(hist, jnp.asarray([1, 2]), 3)
    assert not bool(has[0]) and not bool(has[1])
    # fallback drafts repeat the current token
    assert np.asarray(drafts).tolist() == [[7, 7, 7], [7, 7, 7]]


def _paged_setup(prompts, cfg, num_blocks=64, bs=8, mb=8):
    """Prefill prompts into a fresh paged cache via the admission path;
    returns (paged, block_tables, context_lens, tokens=last prompt tok)."""
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.models import transformer
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        init_paged_cache)
    import jax
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    paged = init_paged_cache(cfg, num_blocks, bs)
    r = len(prompts)
    t = max(len(p) for p in prompts)
    t = -(-t // bs) * bs
    toks = np.zeros((r, t), np.int32)
    tail_len = np.zeros((r,), np.int32)
    tail_blocks = np.zeros((r, t // bs), np.int32)
    nb = 1   # block 0 = dummy
    tables = np.zeros((r, mb), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p) - 1] = p[:-1]
        tail_len[i] = len(p) - 1
        nblk = t // bs
        tail_blocks[i] = np.arange(nb, nb + nblk)
        tables[i, :nblk] = tail_blocks[i]
        # growth blocks for decode
        tables[i, nblk:] = np.arange(nb + nblk, nb + mb)
        nb += mb
    _, paged = transformer.paged_prefill_tail(
        params, cfg, jnp.asarray(toks), jnp.asarray(tail_len),
        jnp.asarray(tail_blocks), jnp.zeros((r, 1), jnp.int32),
        jnp.zeros((r,), jnp.int32), paged)
    cur = np.asarray([p[-1] for p in prompts], np.int32)
    cl = np.asarray([len(p) - 1 for p in prompts], np.int32)
    return params, paged, jnp.asarray(tables), jnp.asarray(cl), \
        jnp.asarray(cur)


def test_paged_speculative_chunk_matches_plain_chunk():
    """Greedy rows: bit-identical tokens to the plain decode chunk (the
    acceptance rule only skips ahead). The sampling row runs exact
    rejection sampling — trajectory diverges from plain by design, but
    must be budget-exact and deterministic given its seed. Exercised
    with a repetitive prompt so drafts actually accept."""
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.models import transformer
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(0)
    base = rng.integers(0, 256, 6).tolist()
    prompts = [(base * 4)[:20],                      # repetitive: drafts hit
               rng.integers(0, 256, 9).tolist(),     # arbitrary
               (base * 3)[:14]]                      # repetitive + sampled
    params, paged0, tables, cl0, cur0 = _paged_setup(prompts, cfg)

    n_new = 12
    seeds = jnp.asarray([11, 22, 33], jnp.int32)
    steps0 = jnp.zeros((3,), jnp.int32)
    temps = jnp.asarray([1.0, 1.0, 0.8], jnp.float32)
    tks = jnp.asarray([0, 0, 40], jnp.int32)
    tps = jnp.asarray([1.0, 1.0, 0.9], jnp.float32)
    ds = jnp.asarray([False, False, True])
    budget = jnp.full((3,), n_new, jnp.int32)
    eos = jnp.full((3,), -1, jnp.int32)

    ptoks, pemits, *_ = transformer.paged_decode_chunk(
        params, cfg, n_new, cur0, paged0, tables, cl0, seeds, steps0,
        temps, tks, tps, ds, budget, eos, dummy_block=0)
    plain = [[int(ptoks[t, r]) for t in range(n_new) if bool(pemits[t, r])]
             for r in range(3)]

    def run_spec():
        stoks, keeps, _, _ = transformer.paged_speculative_chunk(
            params, cfg, 12, 3, cur0, _hist(prompts, 64), paged0, tables,
            cl0, seeds, steps0, temps, tks, tps, ds, budget, eos,
            dummy_block=0)
        out = [[], [], []]
        for t in range(12):
            for r in range(3):
                out[r].extend(int(x) for x in
                              np.asarray(stoks[t, r, :int(keeps[t, r])]))
        return out

    spec = run_spec()
    assert spec[0] == plain[0], (spec[0], plain[0])   # greedy: bit-identical
    assert spec[1] == plain[1], (spec[1], plain[1])
    assert len(spec[2]) == n_new                      # sampled: budget exact
    assert run_spec()[2] == spec[2]                   # and seed-deterministic


def _hist(prompts, h):
    import jax.numpy as jnp
    r = len(prompts)
    out = np.zeros((r, h), np.int32)
    for i, p in enumerate(prompts):
        out[i, :len(p)] = p
    return jnp.asarray(out)


def test_paged_speculative_chunk_eos_and_budget():
    """Per-slot eos inside an accepted run truncates at it; budgets are
    exact (never exceeded even when a full gamma+1 run would)."""
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.models import transformer
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(1)
    base = rng.integers(0, 256, 5).tolist()
    prompts = [(base * 5)[:22], (base * 5)[:22]]
    params, paged0, tables, cl0, cur0 = _paged_setup(prompts, cfg)

    seeds = jnp.zeros((2,), jnp.int32)
    steps0 = jnp.zeros((2,), jnp.int32)
    ones = jnp.ones((2,), jnp.float32)
    ds = jnp.zeros((2,), bool)
    # row 0: tiny budget; row 1: eos = its first plain-decode token
    ptoks, pemits, *_ = transformer.paged_decode_chunk(
        params, cfg, 4, cur0, paged0, tables, cl0, seeds, steps0, ones,
        jnp.zeros((2,), jnp.int32), ones, ds, jnp.full((2,), 4, jnp.int32),
        jnp.full((2,), -1, jnp.int32), dummy_block=0)
    first_tok = int(ptoks[1, 1]) if bool(pemits[1, 1]) else int(ptoks[0, 1])

    budget = jnp.asarray([3, 10], jnp.int32)
    eos = jnp.asarray([-1, first_tok], jnp.int32)
    stoks, keeps, eos_seen, _ = transformer.paged_speculative_chunk(
        params, cfg, 8, 3, cur0, _hist(prompts, 64), paged0, tables,
        cl0, seeds, steps0, ones, jnp.zeros((2,), jnp.int32), ones, ds,
        budget, eos, dummy_block=0)
    out = [[], []]
    for t in range(8):
        for r in range(2):
            out[r].extend(int(x) for x in
                          np.asarray(stoks[t, r, :int(keeps[t, r])]))
    assert len(out[0]) == 3                     # budget exact
    assert first_tok not in out[1]              # eos never emitted
    eos_seen = np.asarray(eos_seen)
    assert not eos_seen[-1, 0]                  # budget death, not eos
    assert eos_seen[-1, 1]                      # eos reported to the host


def test_batcher_speculative_matches_plain():
    """Batched speculative serving: greedy requests produce bit-identical
    outputs to the plain batcher (exact acceptance); the sampled request
    runs exact rejection sampling — right length, deterministic given its
    seed — and draft tokens were accepted on the repetitive prompts."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(0)
    base = rng.integers(0, 256, 6).tolist()
    rep = (base * 4)[:20]
    arb = rng.integers(0, 256, 9).tolist()

    def run(spec):
        b = Batcher(
            cfg, num_blocks=96, block_size=8, slots=3, max_seq=128, seed=0,
            speculative="ngram" if spec else None, spec_gamma=3)
        reqs = [
            b.submit(rep, max_new_tokens=14, sampling=SamplingParams.greedy(),
                     seed=1),
            b.submit(arb, max_new_tokens=10, sampling=SamplingParams.greedy(),
                     seed=2),
            b.submit(rep, max_new_tokens=12,
                     sampling=SamplingParams(temperature=0.8, top_k=40),
                     seed=3),
        ]
        for _ in range(120):
            b.step()
            if all(r.done.is_set() for r in reqs):
                break
        return [r.wait() for r in reqs], b.stats()

    plain, _ = run(False)
    spec, st = run(True)
    assert spec[0] == plain[0], (spec[0], plain[0])
    assert spec[1] == plain[1], (spec[1], plain[1])
    assert len(spec[2]) == 12
    spec2, _ = run(True)
    assert spec2[2] == spec[2]          # sampled: seed-deterministic
    assert st["spec_accepted_tokens"] >= 1, st


def test_batcher_speculative_sampled_accepts_drafts():
    """do_sample requests must get real accepted-draft speedups:
    a lone sampled request on a highly repetitive prompt
    accepts at least one draft token."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(3)
    base = rng.integers(0, 256, 4).tolist()
    prompt = (base * 6)[:22]
    b = Batcher(cfg, num_blocks=64, block_size=8, slots=2,
                max_seq=128, seed=0, speculative="ngram",
                spec_gamma=3)
    # low temperature peaks the target distribution, so in-pattern drafts
    # carry high acceptance probability (the tiny random-init model's
    # sampled trajectories wander; near-greedy keeps them on-pattern)
    r = b.submit(prompt, max_new_tokens=48,
                 sampling=SamplingParams(temperature=0.05, top_k=20), seed=5)
    for _ in range(120):
        b.step()
        if r.done.is_set():
            break
    assert len(r.wait()) == 48
    assert b.stats()["spec_accepted_tokens"] >= 1, b.stats()


def test_batcher_speculative_lockstep_hist_delta():
    """The lockstep broadcast must NOT carry the full drafting history:
    spec_decode args ship per-slot deltas (non-empty only right after an
    admission), and a follower replaying the JSON'd programs reconstructs
    the leader's history rows and cache evolution exactly."""
    import json
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(2)
    base = rng.integers(0, 256, 5).tolist()
    prompts = [(base * 5)[:20], rng.integers(0, 256, 7).tolist()]

    mk = lambda: Batcher(  # noqa: E731
        cfg, num_blocks=64, block_size=8, slots=2, max_seq=96, seed=0,
        speculative="ngram", spec_gamma=3)
    leader, follower = mk(), mk()
    spec_payloads = []

    def hook(kind, args, run):
        wire = json.loads(json.dumps(args))   # prove JSON-safety
        if kind == "spec_decode":
            assert "hist" not in wire, "full history must not broadcast"
            spec_payloads.append(wire)
        follower.replay(kind, wire)
        return run()

    leader.program_hook = hook
    reqs = [leader.submit(p, max_new_tokens=12,
                          sampling=SamplingParams.greedy(), seed=9 + i)
            for i, p in enumerate(prompts)]
    for _ in range(60):
        leader.step()
        if all(r.done.is_set() for r in reqs):
            break
    outs = [r.wait() for r in reqs]
    assert all(len(o) == 12 for o in outs)

    assert spec_payloads, "speculative chunks must have been dispatched"
    # delta amortization: only the first chunk after admission syncs rows
    assert spec_payloads[0]["hist_delta"], spec_payloads[0]
    for p in spec_payloads[1:]:
        assert p["hist_delta"] == [], p["hist_delta"]
    # follower reconstructed the leader's history exactly
    np.testing.assert_array_equal(follower._hist, leader._hist)


def test_accept_rejection_batch_matches_analytic_probability():
    """The acceptance math itself, against closed form: with a fixed
    peaked distribution and the draft equal to the favored token, the
    expected accepted count is p + p^2 + ... + p^G for
    p = exp(l)/(exp(l) + (k-1)) under temp-1 top-k warping. Empirical
    mean over seeds must land on it; and rejected-position residuals must
    never re-emit the rejected draft."""
    import jax
    from distributed_llm_inferencing_tpu.ops.speculative import (
        accept_rejection_batch)
    G, V, L = 3, 64, 5.0
    logits = np.zeros((1, G + 1, V), np.float32)
    logits[..., 7] = L
    drafts = np.full((1, G), 7, np.int32)
    args = dict(temps=jnp.asarray([1.0], jnp.float32),
                top_ks=jnp.asarray([20], jnp.int32),
                top_ps=jnp.asarray([0.95], jnp.float32),
                ds=jnp.asarray([True]))
    fn = jax.jit(lambda s: accept_rejection_batch(
        jnp.asarray(logits), jnp.asarray(drafts), s,
        jnp.zeros((1,), jnp.int32), **args))
    n_accs, toks = [], []
    for s in range(400):
        t, n_emit = fn(jnp.asarray([s], jnp.int32))
        n_accs.append(int(n_emit[0]) - 1)
        toks.append(np.asarray(t[0]))
    p = np.exp(L) / (np.exp(L) + 19)   # top-20 keeps 19 competitors
    want = sum(p ** i for i in range(1, G + 1))        # ~2.37
    got = np.mean(n_accs)
    assert abs(got - want) < 0.12, (got, want)
    # rejection residuals exclude the rejected draft
    for n_acc, t in zip(n_accs, toks):
        if n_acc < G:
            assert t[n_acc] != 7, (n_acc, t)


def test_batcher_speculative_sampling_distribution_preserved():
    """Exact rejection sampling at the batcher level: across many seeds,
    the speculative-verified tokens' empirical distribution must match
    the plain batcher's. The distributions are conditional mixtures over
    the admission token, so the pass bound is CALIBRATED against the
    plain-vs-plain sampling noise floor at the same sample size instead
    of a fixed constant."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(0)
    prompt = (rng.integers(0, 256, 4).tolist() * 5)[:18]
    sp = SamplingParams(temperature=1.2, top_k=8, top_p=0.95)
    n = 120

    def collect(spec, seed0):
        b = Batcher(cfg, num_blocks=256, block_size=8, slots=8,
                    max_seq=64, seed=0,
                    speculative="ngram" if spec else None,
                    spec_gamma=2)
        reqs = [b.submit(prompt, max_new_tokens=3, sampling=sp,
                         seed=seed0 + s) for s in range(n)]
        for _ in range(600):
            b.step()
            if all(r.done.is_set() for r in reqs):
                break
        counts: dict = {}
        for r in reqs:
            toks = r.wait()
            # token 0 is the admission sample (same path in both modes);
            # positions 1 and 2 are speculative-verified
            for pos in (1, 2):
                key = (pos, toks[pos])
                counts[key] = counts.get(key, 0) + 1
        return counts

    def tv(a, b):
        support = set(a) | set(b)
        return sum(abs(a.get(t, 0) - b.get(t, 0))
                   for t in support) / (2 * 2 * n)

    plain_a = collect(False, 0)
    plain_b = collect(False, 5000)     # same dist, fresh seeds: noise floor
    spec_a = collect(True, 0)
    tv_null = tv(plain_a, plain_b)
    tv_spec = tv(spec_a, plain_a)
    assert tv_spec < 1.5 * tv_null + 0.08, (tv_spec, tv_null)


def test_batcher_speculative_eos_and_stream():
    """eos cuts a speculative run mid-chunk; streamed tokens match kept
    tokens in order."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    rng = np.random.default_rng(1)
    base = rng.integers(0, 256, 5).tolist()
    prompt = (base * 4)[:18]

    plain = Batcher(cfg, num_blocks=64, block_size=8, slots=2,
                    max_seq=128, seed=0)
    r0 = plain.submit(prompt, max_new_tokens=10,
                      sampling=SamplingParams.greedy())
    for _ in range(40):
        plain.step()
        if r0.done.is_set():
            break
    full = r0.wait()
    eos = full[4]
    want = full[:4] if eos not in full[:4] else None

    b = Batcher(cfg, num_blocks=64, block_size=8, slots=2,
                max_seq=128, seed=0, speculative="ngram",
                spec_gamma=3)
    seen = []
    r = b.submit(prompt, max_new_tokens=10,
                 sampling=SamplingParams.greedy(), eos_token_id=eos,
                 stream_cb=seen.append)
    for _ in range(40):
        b.step()
        if r.done.is_set():
            break
    got = r.wait()
    if want is not None:
        assert got == want, (got, want)
    assert seen == got
    assert eos not in got
