"""Speculative decoding: n-gram self-draft proposal + one-pass verification.

Decode is HBM-bound: each autoregressive step streams the full weight set
for ONE token of progress. Speculative decoding converts spare MXU compute
into tokens — score gamma cheap draft tokens in a single forward pass
(prefill-style, s = gamma + 1) and keep the prefix the model agrees with.
With a delta draft (our proposals are deterministic) the standard
leave-one-out rejection rule preserves the target sampling distribution
EXACTLY; greedy verification is exact trivially.

The draft source is *prompt lookup* (self-drafting): the continuation of
the most recent earlier occurrence of the current n-gram in the token
history. Free to compute host-side (the host already holds every emitted
token), surprisingly strong on repetitive serving workloads
(summarization, code edits, RAG quoting the context), and requiring no
second model — the right first speculation tier for a serving stack.
No reference counterpart at any level (its loop was HF ``generate()``,
reference worker/app.py:297-305).

Verification runs entirely on device (ops/sampling.py warp_logits gives
the same warped distribution ``sample`` draws from); the host syncs once
per verify step and receives up to gamma+1 tokens.

Drafting is a bet, and ``AdaptiveSpecController`` is the bankroll
manager: it tracks the rolling draft-acceptance rate and the *measured*
tok/s of the speculative vs plain arms, shrinks gamma when drafts miss,
falls back to plain decode when drafting measurably loses, and re-probes
periodically so a workload turning repetitive flips it back on. The
continuous batcher consults it every chunk (runtime/batcher.py
_step_spec_wave), which is what makes ``speculative="ngram"`` safe to
leave on.
"""

from __future__ import annotations

import collections
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.config import ModelConfig
from distributed_llm_inferencing_tpu.ops.sampling import (
    PREFIX_K, SamplingParams, nucleus_mask_sorted, sample_batch, warp_logits)


class AdaptiveSpecController:
    """Chunk-by-chunk decision: draft (and at what gamma) or run plain
    decode — so ``speculative="ngram"`` can never lose to plain for long.

    Drafting pays only when drafts get accepted: a rejected draft still
    costs a (gamma+1)-wide verify forward, and BENCH_r05 measured the
    always-on path at 5.54 tok/s vs 17.04 plain on a draft-hostile
    workload. The controller is *empirical*, not model-based — it trusts
    measured throughput over any cost model:

    - EMAs of decode tokens/s for the spec and plain arms (chunks that
      just compiled are excluded: compile time is not decode time).
    - A rolling acceptance rate (accepted draft tokens / drafted tokens)
      over the last ``window`` speculative chunks.
    - In spec mode: acceptance below ``min_accept`` halves gamma (a
      shorter draft wastes less verify width), and below-min at the
      floor — or measured spec tok/s clearly under plain — falls back to
      plain. Acceptance above ``grow_accept`` doubles gamma back toward
      the configured maximum.
    - Probes keep BOTH arms measured: in plain mode every
      ``probe_every`` chunks one speculative probe runs (a workload
      turning repetitive flips drafting back on), and in spec mode one
      PLAIN probe runs on the same cadence — without it ``plain_tps``
      would stay unmeasured and a high-acceptance workload on a
      dispatch-dominated host (BENCH_r05's regression: drafting loses
      even at full acceptance) could pin the slow arm forever. The
      probe overhead, 1/probe_every, bounds the cost of being wrong in
      either direction.

    The batcher owns the measurements (runtime/batcher.py
    _step_spec_wave); this object owns the policy, so the engine or a
    future tree-drafting tier can reuse it unchanged.

    Determinism note: greedy output is mode-invariant, so adaptivity
    never changes greedy tokens. Sampled REALIZATIONS can differ between
    a drafted and a plain chunk (same distribution, different draws);
    acceptance-driven decisions are PRNG-deterministic per (seed,
    position), and the one clock-driven clause (tok/s comparison) only
    arms once BOTH arms have been measured — i.e. after the first
    cross-arm probe or fallback, at earliest ``probe_every`` chunks in —
    so short generations stay bit-reproducible and long-running sampled
    workloads trade strict replay for never-slower-than-plain.
    """

    def __init__(self, gamma_max: int, *, window: int = 16,
                 probe_every: int = 32, warmup: int = 3,
                 min_evidence: int = 3, min_accept: float = 0.12,
                 grow_accept: float = 0.5, hysteresis: float = 0.9,
                 ema_alpha: float = 0.3):
        self.gamma_max = max(1, int(gamma_max))
        self.gamma = self.gamma_max
        self.mode = "spec"           # "spec" | "plain"
        self.window = window
        self.probe_every = probe_every
        self.warmup = warmup
        self.min_evidence = max(1, min_evidence)
        self.min_accept = min_accept
        self.grow_accept = grow_accept
        self.hysteresis = hysteresis
        self.ema_alpha = ema_alpha
        self.spec_tps: Optional[float] = None
        self.plain_tps: Optional[float] = None
        self.fallbacks = 0           # spec -> plain transitions
        self.reactivations = 0       # plain -> spec transitions
        self._accept = collections.deque(maxlen=window)  # (accepted, drafted)
        self._spec_chunks = 0
        self._plain_chunks = 0
        self._since_probe = 0        # plain mode: chunks since spec probe
        self._since_plain_probe = 0  # spec mode: chunks since plain probe

    # ---- decision ------------------------------------------------------

    def choose(self) -> int:
        """Gamma for the next chunk; 0 means run plain decode."""
        if self.mode == "spec":
            self._since_plain_probe += 1
            if (self._spec_chunks >= self.warmup
                    and self._since_plain_probe >= self.probe_every):
                self._since_plain_probe = 0
                return 0             # plain probe: measure the other arm
            return self.gamma
        self._since_probe += 1
        if self._since_probe >= self.probe_every:
            self._since_probe = 0
            return self.gamma        # spec probe
        return 0

    # ---- feedback ------------------------------------------------------

    def acceptance(self) -> Optional[float]:
        drafted = sum(d for _, d in self._accept)
        if not drafted:
            return None
        return sum(a for a, _ in self._accept) / drafted

    def _ema(self, prev: Optional[float], x: float) -> float:
        if prev is None:
            return x
        return prev + self.ema_alpha * (x - prev)

    def record(self, mode: str, *, emitted: int, elapsed_s: float,
               drafted: int = 0, accepted: int = 0,
               compiled: bool = False) -> None:
        """Feed one chunk's measurements back. ``drafted``/``accepted``
        are draft-token counts for spec chunks; ``compiled`` marks a
        chunk whose dispatch included a fresh XLA compile (throughput
        excluded — it would poison the EMA for dozens of chunks)."""
        # elapsed at/below clock resolution is unmeasurable, not "0
        # tok/s" — recording zero would drag a WINNING arm's EMA down
        tps = emitted / elapsed_s if elapsed_s > 0 else None
        if mode == "spec":
            self._spec_chunks += 1
            if drafted:
                self._accept.append((accepted, drafted))
            if not compiled and tps is not None:
                self.spec_tps = self._ema(self.spec_tps, tps)
            self._after_spec()
        else:
            self._plain_chunks += 1
            if not compiled and tps is not None:
                self.plain_tps = self._ema(self.plain_tps, tps)

    def _after_spec(self) -> None:
        if self._spec_chunks < self.warmup:
            return
        # acceptance verdicts need a few chunks of evidence (one noisy
        # post-gamma-shrink chunk must not trigger the next shrink); the
        # plain-mode probe branch below judges on whatever it has — a
        # wrong reactivation just falls back again, a slow one idles
        # probe_every chunks of potential speedup
        acc = (self.acceptance()
               if len(self._accept) >= self.min_evidence else None)
        losing_tps = (self.spec_tps is not None
                      and self.plain_tps is not None
                      and self.spec_tps < self.plain_tps * self.hysteresis)
        if self.mode == "plain":
            # probe verdict: judge THIS probe alone — the window still
            # holds earlier failed probes, and averaging against them
            # would delay reactivation ~window more probe rounds after
            # the workload turns draft-friendly. A wrong single-probe
            # reactivation self-corrects: min_evidence chunks later the
            # spec-mode rules fall back again.
            acc = None
            if self._accept:
                a, d = self._accept[-1]
                acc = a / d if d else None
            if ((acc is not None and acc >= self.grow_accept)
                    or (self.spec_tps is not None
                        and self.plain_tps is not None
                        and self.spec_tps * self.hysteresis
                        > self.plain_tps)):
                self.mode = "spec"
                self.reactivations += 1
                self._since_plain_probe = 0
            return
        if losing_tps or (acc is not None and acc < self.min_accept):
            if self.gamma > 2 and not losing_tps:
                self.gamma = max(2, self.gamma // 2)  # shorter draft first
                self._accept.clear()   # re-measure at the new gamma
            else:
                self.mode = "plain"
                self.fallbacks += 1
                self._since_probe = 0
                # probes must be judged on probe evidence alone — the
                # draft-hostile window that caused the fallback would
                # otherwise dilute a now-repetitive workload's probe for
                # ~window/probe acceptance entries (~4 probe rounds)
                self._accept.clear()
        elif (acc is not None and acc >= self.grow_accept
                and self.gamma < self.gamma_max):
            self.gamma = min(self.gamma_max, self.gamma * 2)

    def export_state(self) -> dict:
        """JSON-safe snapshot of the REQUEST-owned half of the policy
        state — gamma, mode, and the rolling acceptance window — for a
        live-migration resume record (runtime/batcher.py migrate_out).
        The throughput EMAs and probe clocks are deliberately excluded:
        they measure the HOST, and the destination worker seeds those
        from its own shared arbitration state (_seed_wave_ctl)."""
        return {
            "gamma": int(self.gamma), "mode": str(self.mode),
            "accept": [[int(a), int(d)] for a, d in self._accept],
            "spec_chunks": int(self._spec_chunks),
            "plain_chunks": int(self._plain_chunks),
        }

    def load_state(self, state: dict) -> None:
        """Adopt a migrated request's exported policy state. Malformed
        fields are ignored field-by-field — a resume record must never
        be able to crash the destination scheduler."""
        if not isinstance(state, dict):
            return
        try:
            g = int(state.get("gamma", self.gamma))
            self.gamma = min(self.gamma_max, max(1, g))
        except (TypeError, ValueError):
            pass
        if state.get("mode") in ("spec", "plain"):
            self.mode = state["mode"]
        acc = state.get("accept")
        if isinstance(acc, list):
            self._accept.clear()
            for pair in acc[-self.window:]:
                try:
                    a, d = pair
                    self._accept.append((int(a), int(d)))
                except (TypeError, ValueError):
                    continue
        for key, attr in (("spec_chunks", "_spec_chunks"),
                          ("plain_chunks", "_plain_chunks")):
            try:
                setattr(self, attr, max(0, int(state.get(key, 0))))
            except (TypeError, ValueError):
                pass

    def stats(self) -> dict:
        acc = self.acceptance()
        return {
            "mode": self.mode, "gamma": self.gamma,
            "acceptance": None if acc is None else round(acc, 3),
            "spec_tokens_per_s":
                None if self.spec_tps is None else round(self.spec_tps, 1),
            "plain_tokens_per_s":
                None if self.plain_tps is None else round(self.plain_tps, 1),
            "fallbacks": self.fallbacks,
            "reactivations": self.reactivations,
            "spec_chunks": self._spec_chunks,
            "plain_chunks": self._plain_chunks,
        }


def propose_ngram(history: Sequence[int], gamma: int,
                  n: int = 2) -> Optional[List[int]]:
    """Prompt-lookup draft: continuation of the most recent earlier
    occurrence of the trailing ``n``-gram of ``history``. Returns gamma
    tokens (right-padded by repeating the last continuation token), or
    None when the n-gram never occurred before (caller decides whether to
    verify a dummy draft or plain-decode)."""
    h = list(history)
    if len(h) < n + 1:
        return None
    key = h[-n:]
    for i in range(len(h) - n - 1, -1, -1):
        if h[i:i + n] == key:
            cont = h[i + n:i + n + gamma]
            if not cont:
                continue
            return cont + [cont[-1]] * (gamma - len(cont))
    return None


def propose_ngram_device(history, lengths, gamma: int, n: int = 2):
    """Vectorized on-device prompt-lookup drafting for R slots.

    The host version (propose_ngram) forces a host sync per verify step —
    ruinous behind a dispatch round trip. This one is a compare/gather
    over a device-resident token history, so the whole
    draft->verify->accept loop can run inside one chunked program
    (models/transformer.py paged_speculative_chunk).

    history: [R, H] int32 (row r valid to lengths[r]); lengths: [R]
    (number of known tokens incl. the current one). Returns
    (drafts [R, gamma] int32, has_draft [R] bool) with semantics
    matching propose_ngram for n == 2: the continuation of the most
    recent earlier occurrence of the trailing bigram, right-padded by
    the last continuation token (== the last history token, since the
    continuation runs to the end of the history).
    """
    assert n == 2, "device drafting implements the serving default n=2"
    r, h = history.shape
    idx = jnp.arange(h, dtype=jnp.int32)[None, :]                  # [1, H]
    last = jnp.take_along_axis(history, (lengths - 1)[:, None], axis=1)
    prev = jnp.take_along_axis(
        history, jnp.maximum(lengths - 2, 0)[:, None], axis=1)
    nxt = jnp.concatenate(                                          # h[i+1]
        [history[:, 1:], jnp.zeros((r, 1), history.dtype)], axis=1)
    # candidate start i: h[i] == prev, h[i+1] == last; i + 2 < length
    # covers both "continuation non-empty" and "not the trailing bigram
    # itself" (identical constraints for n=2)
    m = ((history == prev) & (nxt == last)
         & (idx + 2 < lengths[:, None]) & (lengths[:, None] >= 3))
    has = jnp.any(m, axis=1)
    pos = jnp.max(jnp.where(m, idx, -1), axis=1)                    # [R]
    # continuation tokens h[pos+2 .. pos+1+gamma], clamped to the last
    # known token (identical to the host version's repeat-last padding)
    g_idx = pos[:, None] + 2 + jnp.arange(gamma, dtype=jnp.int32)[None, :]
    g_idx = jnp.minimum(g_idx, lengths[:, None] - 1)
    drafts = jnp.take_along_axis(history, jnp.maximum(g_idx, 0), axis=1)
    # no-draft rows fall back to repeating the current token (uniform
    # program shape; a bad draft just gets rejected at verification)
    drafts = jnp.where(has[:, None], drafts, last)
    return drafts.astype(jnp.int32), has


def accept_rejection_batch(logits, drafts, seeds, steps, temps, top_ks,
                           top_ps, ds, widths=None):
    """Per-row data-parameterized draft acceptance for the BATCHED
    speculative path (models/transformer.py paged_speculative_chunk):
    one compiled program serves any mix of greedy / sampled requests,
    with sampling parameters as data, and sampled rows get real
    accepted-draft speedups via the same delta-draft leave-one-out
    rejection rule ``verify_step`` applies with static params.

    logits: [R, G+1, V] f32 — position i scores the token after accepting
    i drafts; drafts: [R, G] int32; seeds/steps: [R] int32 — ``steps`` is
    the row's emitted-token count. PRNG keying is per absolute POSITION:
    the acceptance draw for draft i uses stream (seed, steps + i) and the
    stop draw uses (seed, steps + n_acc) — each emitted position's
    randomness is a pure function of (seed, position), invariant to how
    chunk boundaries or the draft width partition the trajectory (the
    old chunk-start keying made a rerun with a different gamma or chunk
    split correlate residual draws with earlier acceptance draws at the
    same (seed, chunk-start) point).
    temps/top_ps: [R] f32; top_ks: [R] int32 (0 disables); ds: [R] bool.

    ``widths`` ([R] int32 in [0, G], default G) is the per-row draft
    width for wave-level speculation (runtime/batcher.py
    _step_spec_wave): row r considers only its first ``widths[r]``
    drafts; a width-0 row accepts nothing and its stop token is an
    ordinary single-token draw from position 0's distribution — plain
    decode riding the verify pass, with greedy rows emitting exactly
    the plain argmax. Running out of width is NOT a rejection: the stop
    token at position ``widths[r]`` draws from the full distribution
    (the bonus-token rule), not the leave-one-out residual.

    Acceptance, per row:
    - greedy (``~ds``): accept draft i while it equals the raw argmax;
      the stop token is the argmax itself — output ≡ plain greedy decode.
    - sampled, covered (0 < k <= PREFIX_K — every realistic serving
      config): the target distribution is ``softmax(nucleus_mask_sorted(
      top_k(scaled)))``, exactly what sample_batch's prefix tier draws
      from. Accept draft i with probability p_i(d_i); on first rejection
      draw the stop token from p_i with d_i masked out (renormalized).
      The residual max(0, p - delta_d) / (1 - p(d)) is p with d removed,
      so the emitted distribution is exactly p.
    - sampled, uncovered (k == 0 or k > PREFIX_K): no acceptance
      (n_acc = 0); the stop token is ``sample_batch``'s draw from the
      full-vocab tier — bit-identical to the plain chunk for these rows.

    Returns (toks_out [R, G+1], n_emit [R]): row r emits
    ``toks_out[r, :n_emit[r]]`` (1..G+1 tokens), before any budget/eos
    clamping the caller applies.
    """
    r, g = drafts.shape
    v = logits.shape[-1]
    ks = min(PREFIX_K, v)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None, None]   # [R,G1,V]
    k = jnp.where(top_ks <= 0, v, jnp.clip(top_ks, 1, v))       # [R]
    covered = k <= ks

    # warped target distribution over the top-KS prefix, per position
    vals, idx = jax.lax.top_k(scaled, ks)                       # [R,G1,KS]
    width = jnp.minimum(k, ks)[:, None, None]
    m, thresh = nucleus_mask_sorted(vals, width, top_ps[:, None, None])
    z = jax.nn.logsumexp(m, axis=-1)                            # [R,G1]

    # p_i(d_i): the draft token's mass under position i's warped dist.
    # Support membership comes from the kept top-k prefix ITSELF, not a
    # value-vs-threshold compare: a draft whose logit exactly ties the
    # threshold but lost the top-k index tiebreak is out-of-support, and
    # the threshold compare would wrongly admit it (while the rejection
    # residual could not then exclude it) — ADVICE r4.
    kept = m > -jnp.inf                                         # [R,G1,KS]
    match = (idx[:, :-1] == drafts[..., None]) & kept[:, :-1]   # [R,G,KS]
    p_draft = jnp.sum(
        jnp.where(match, jnp.exp(m[:, :-1] - z[:, :-1, None]), 0.0),
        axis=-1)                                                # [R,G]

    if widths is None:
        widths = jnp.full((r,), g, jnp.int32)
    widths = jnp.clip(widths.astype(jnp.int32), 0, g)

    # per-row PRNG: each use folds its ABSOLUTE stream position
    # (steps + offset within this verify step), then a spec tag — the
    # draw at a given emitted position is a pure function of
    # (seed, position), independent of chunk-mates, chunk boundaries
    # and the draft width
    def _acc_u(s, t):
        def one(i):
            kk = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(s), t + i), 0x5acc)
            return jax.random.uniform(kk)
        return jax.vmap(one)(jnp.arange(g, dtype=jnp.int32))
    u = jax.vmap(_acc_u)(seeds, steps)                          # [R,G]

    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [R,G1]
    acc_greedy = drafts == targets[:, :-1]
    acc_sample = covered[:, None] & (u < p_draft)
    acc = jnp.where(ds[:, None], acc_sample, acc_greedy)
    acc &= jnp.arange(g, dtype=jnp.int32)[None, :] < widths[:, None]
    prefix = jnp.cumprod(acc.astype(jnp.int32), axis=1)
    n_acc = prefix.sum(axis=1)                           # [R] 0..widths

    # stop token at position n_acc, per mechanism; keyed by its absolute
    # position so the draw is chunk-boundary/width invariant
    k_stop = jax.vmap(lambda s, t: jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(s), t), 0x570b))(
        seeds, steps + n_acc)
    stop_greedy = jnp.take_along_axis(targets, n_acc[:, None],
                                      axis=1)[:, 0]
    m_stop = jnp.take_along_axis(
        m, n_acc[:, None, None], axis=1)[:, 0]                  # [R,KS]
    idx_stop = jnp.take_along_axis(
        idx, n_acc[:, None, None], axis=1)[:, 0]                # [R,KS]
    rejected = jnp.take_along_axis(
        drafts, jnp.minimum(n_acc, g - 1)[:, None], axis=1)[:, 0]
    # ran-out-of-width is a bonus draw, not a rejection: only mask the
    # draft token when a draft at this position was actually judged
    was_rejection = n_acc < widths
    m_res = jnp.where((idx_stop == rejected[:, None])
                      & was_rejection[:, None], -jnp.inf, m_stop)
    j = jax.vmap(lambda kk, l: jax.random.categorical(kk, l))(k_stop, m_res)
    stop_cov = jnp.take_along_axis(idx_stop, j[:, None], axis=1)[:, 0]
    # uncovered sampled rows: identical draw to the plain chunk's
    stop_unc = sample_batch(logits[:, 0], seeds, steps, temps, top_ks,
                            top_ps, ds)
    stop = jnp.where(ds, jnp.where(covered, stop_cov, stop_unc),
                     stop_greedy).astype(jnp.int32)

    pos = jnp.arange(g + 1, dtype=jnp.int32)[None, :]
    draft_pad = jnp.concatenate(
        [drafts, jnp.zeros((r, 1), jnp.int32)], axis=1)
    toks_out = jnp.where(pos == n_acc[:, None], stop[:, None], draft_pad)
    return toks_out, n_acc + 1


def verify_step(params, cfg: ModelConfig, cache, cur, drafts, key,
                sp: SamplingParams):
    """Score ``[cur, drafts...]`` in one forward pass and accept the
    longest draft prefix the target distribution keeps.

    cur: [B] current token (not yet in cache); drafts: [B, G].
    Returns (tokens [B, G+1], n_emit [B], cache, key): row b emits
    ``tokens[b, :n_emit[b]]`` (between 1 and G+1 tokens).

    Acceptance, per row:
    - greedy: accept draft i while it equals the raw argmax; the emitted
      stop token is the argmax itself, so output ≡ plain greedy decode.
    - sampling: delta-draft leave-one-out rejection — accept draft i with
      probability p_i(d_i) under the warped target distribution; on the
      first rejection, sample from p_i with d_i masked out (renormalized).
      This preserves the target distribution exactly (the residual
      max(0, p - delta_d) / (1 - p(d)) is p with d removed).
    All-accepted rows draw a bonus token from the last position.

    Cache semantics: K/V for cur and ALL drafts are written at positions
    [L0, L0+G]; lengths advance only by the accepted count, so rejected
    positions hold garbage that later steps overwrite in order (the cache
    invariant slot == position is preserved).
    """
    b, g = drafts.shape
    toks_in = jnp.concatenate([cur[:, None], drafts], axis=1)   # [B, G+1]
    l0 = cache.lengths
    q_pos = l0[:, None] + jnp.arange(g + 1, dtype=jnp.int32)[None, :]
    logits, cache = transformer.forward(
        params, cfg, toks_in, cache, write_starts=l0, q_positions=q_pos,
        new_lengths=l0 + g + 1, is_prefill=False)
    # (causality masks each query to its own prefix, so the provisional
    # over-long lengths above never leak future K/V into a score)

    key, k_acc, k_stop = jax.random.split(key, 3)
    if sp.do_sample:
        probs = jax.nn.softmax(warp_logits(logits, sp), axis=-1)
        p_draft = jnp.take_along_axis(
            probs[:, :-1], drafts[..., None], axis=-1)[..., 0]   # [B, G]
        acc = jax.random.uniform(k_acc, (b, g)) < p_draft
    else:
        targets = jnp.argmax(logits, axis=-1)                    # [B, G+1]
        acc = drafts == targets[:, :-1]
    prefix = jnp.cumprod(acc.astype(jnp.int32), axis=1)          # [B, G]
    n_acc = prefix.sum(axis=1)                                   # [B] 0..G

    # stop token: position n_acc's distribution, minus the rejected draft
    stop_logits = jnp.take_along_axis(
        warp_logits(logits, sp), n_acc[:, None, None], axis=1)[:, 0]
    rejected = jnp.take_along_axis(   # draft at the stop position (G-clamped)
        drafts, jnp.minimum(n_acc, g - 1)[:, None], axis=1)[:, 0]
    was_rejection = n_acc < g
    mask_rej = (jnp.arange(stop_logits.shape[-1])[None, :]
                == rejected[:, None]) & was_rejection[:, None]
    stop_logits = jnp.where(mask_rej, -jnp.inf, stop_logits)
    if sp.do_sample:
        stop_tok = jax.random.categorical(k_stop, stop_logits, axis=-1)
    else:
        stop_tok = jnp.argmax(stop_logits, axis=-1)
    stop_tok = stop_tok.astype(jnp.int32)

    # emitted = accepted drafts then the stop token
    idx = jnp.arange(g + 1, dtype=jnp.int32)[None, :]
    draft_pad = jnp.concatenate(
        [drafts, jnp.zeros((b, 1), drafts.dtype)], axis=1)
    tokens = jnp.where(idx == n_acc[:, None], stop_tok[:, None], draft_pad)
    n_emit = n_acc + 1
    cache = cache._replace(lengths=l0 + n_emit)
    return tokens, n_emit, cache, key
