"""Token sampling: temperature / top-k / top-p / greedy, fully in XLA.

Mirrors the reference's (hardcoded) sampling configuration —
do_sample=True, top_p=0.95, top_k=50, temperature=0.8
(reference: worker/app.py:297-305) — as the defaults of an explicit
SamplingParams, and implements the pipeline as a jit-friendly pure function
so it fuses into the decode step instead of running host-side per token.

A row's top-k and nucleus cuts are two scalars (the k-th largest logit, the
smallest logit of the nucleus). ``nucleus_thresholds`` finds both exactly by
a search over the logits' bit patterns, a fixed number of masked reductions
over ``[R, V]`` whatever the distribution's shape and at every size: nothing
sorts the vocabulary. The number is the logits' own: a step for every two
bits they have, 8 for a bfloat16 or float16 head's logits (the serving
programs hand them over as computed), 16 for float32 ones.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    # Defaults mirror reference worker/app.py:297-305.
    temperature: float = 0.8
    top_k: int = 50
    top_p: float = 0.95
    do_sample: bool = True

    @staticmethod
    def greedy() -> "SamplingParams":
        return SamplingParams(do_sample=False)


def _mask_top_k(logits, k: int):
    """Keep the k largest logits per row, set the rest to -inf."""
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    kth = jax.lax.top_k(logits, k)[0][..., -1:]  # [., 1] k-th largest value
    return jnp.where(logits < kth, -jnp.inf, logits)


# Bits of a threshold decided by one step of the search: a step weighs
# 2**_SEARCH_BITS - 1 candidate thresholds in one fused pass over [R, V].
# A key has the bits of the logits it orders, so a 16-bit head's search is
# 16 / _SEARCH_BITS = 8 steps and a float32 one's 16. On a v5e at 64 x
# 128,256 float32 logits, 2 bits take 0.72 ms a search, 1 bit 1.46 and
# 4 bits 1.75 (PERF.md, PR 28). Over bfloat16 logits (PR 47,
# scripts/bench_sampler.py) 2 bits take 0.25 ms in steps there and 4 bits
# 0.71; 1 bit, 16 steps of one candidate, is quicker than 2 only while
# both of a step's arrays lie on chip, which is the compiler's to decide
# in each program, and reads twice as often where one comes from HBM.
_SEARCH_BITS = 2


def _key_bits(dtype) -> int:
    return jnp.dtype(dtype).itemsize * 8


def _float_keys(x):
    """float (float32, bfloat16, float16) -> uint32 that orders the same
    way, in the low bits the float has: the sign bit of a non-negative
    flipped, every bit of a negative (-0.0 counts as 0.0)."""
    bits = _key_bits(x.dtype)
    b = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, jnp.zeros_like(x), x),
        jnp.dtype(f"uint{bits}")).astype(jnp.uint32)
    sign = jnp.uint32(1 << (bits - 1))
    return jnp.where(b >= sign, ~b & jnp.uint32((1 << bits) - 1), b | sign)


def _keys_to_float(key, dtype):
    bits = _key_bits(dtype)
    sign = jnp.uint32(1 << (bits - 1))
    b = jnp.where(key >= sign, key ^ sign, ~key)
    return jax.lax.bitcast_convert_type(
        b.astype(jnp.dtype(f"uint{bits}")), dtype)


def _cut_value(key, dtype):
    """The float a found key stands for. A compare that flushes
    subnormals (the TPU's, XLA:CPU's) lets 0.0 reach every subnormal
    candidate above it, so a cut at 0.0 is found as the largest of them:
    it reads as the 0.0 it compares as, a value of the row."""
    v = _keys_to_float(key, dtype)
    return jnp.where(jnp.abs(v) < jnp.finfo(dtype).tiny, jnp.zeros_like(v), v)


def _key_neg_inf(dtype):
    """The least non-NaN key: 0x007FFFFF for float32, 0x007F for
    bfloat16, 0x03FF for float16."""
    return _float_keys(jnp.asarray(-jnp.inf, dtype))


def _largest_key_reaching(x, weight, floor, target):
    """Per row, the largest key ``t`` such that the ``weight`` of the
    row's entries ``v`` with ``v >= floor`` and ``key(v) >= t`` sums to
    at least ``target``. x: [R, V] float32 or a 16-bit float; weight:
    [R, V] f32, non-negative; floor: [R] of x's dtype; target: [R] f32.

    The sum is monotone in ``t`` (a fixed-order float sum of non-negative
    terms, some of them zeroed), so ``t`` is decided from its top bits
    down: (bits of x's dtype) / _SEARCH_BITS steps, each one fused
    compare-select-reduce over the row for every candidate value of the
    step's bits, the same count for any data. Candidates are compared as
    floats of x's dtype (``v >= float(t)``), which orders as the keys
    do."""
    bits = _key_bits(x.dtype)
    fields = jnp.arange(1, 1 << _SEARCH_BITS, dtype=jnp.uint32)
    least = _key_neg_inf(x.dtype)

    def step(i, t):
        lo = (bits - _SEARCH_BITS * (i + 1)).astype(jnp.uint32)
        cand = t[None, :] | (fields[:, None] << lo)              # [C, R]
        # keys under key(-inf) are negative NaNs as floats; every entry
        # lies at or above them
        cut = _keys_to_float(jnp.maximum(cand, least), x.dtype)
        cut = jnp.maximum(cut, floor[None, :])
        reached = jnp.sum(
            jnp.where(x[None] >= cut[:, :, None], weight[None], 0.0),
            axis=-1)                                             # [C, R]
        taken = jnp.sum(reached >= target[None, :], axis=0)
        return t | (taken.astype(jnp.uint32) << lo)

    return jax.lax.fori_loop(0, bits // _SEARCH_BITS, step,
                             jnp.zeros(x.shape[:1], jnp.uint32))


def nucleus_thresholds(x, k, top_ps, scaled=None):
    """The two scalars a row's top-k ∩ top-p mask needs.

    x: [R, V] logits the cuts are found in and apply to, float32 or a
    16-bit float; scaled: [R, V] f32, the logits the probabilities come
    from (temperature applied), a non-decreasing map of ``x`` row by row
    (None: ``x`` as float32); k: [R] int32 in 1..V (V = top-k off);
    top_ps: [R] f32. Returns (kth, thresh), both [R] of x's dtype:
    ``kth`` cuts the row to its k largest logits (the k-th largest; the
    search gives -inf where no row of the batch has k < V: nothing to cut),
    ``thresh`` is the smallest logit of the nucleus — the least value ``v``
    of the row such that the probability of the logits strictly above
    ``v``, renormalised over the top-k set, is below ``top_p`` (HF's
    TopPLogitsWarper: the token that crosses ``top_p`` is kept). The
    sampling support is ``x >= max(kth, thresh)``. Both come from
    ``_largest_key_reaching`` and the row is never sorted;
    tests/test_sampling.py holds them to one descending sort.

    The search decides the bits ``x`` has. A head that computes its
    logits in bfloat16 (or float16) hands them over as they are, beside
    their float32 ``scaled``: dividing by a temperature keeps their
    order, so the cut found among the 16-bit values is the cut a search
    of ``scaled``'s 32 bits finds, in half the steps, and
    ``x >= max(kth, thresh)`` is the same set of tokens. Every mass and
    sum is float32 either way.

    Two things to know:

    1. The mass before a token is a float32 sum: a masked sum in memory
       order here, a cumulative sum in sorted order in a sort-based
       form. Both round the same real number; where it lies within
       float32 summation error of ``top_p`` the boundary token may fall
       on either side.
    2. The top-k set is ``{x >= kth}``: a run of equal logits across the
       k-th place belongs to it whole (it is what the mask by value keeps),
       and the nucleus is normalised over that set, i.e. over exactly the
       tokens that can be drawn. (Until PR 28 the sort normalised over k
       positions, part of such a run, and still kept the whole run.)
    """
    if scaled is None:
        scaled = x.astype(jnp.float32)
    v = x.shape[-1]
    top = jnp.max(scaled, axis=-1)
    neg_inf = jnp.full(x.shape[:1], -jnp.inf, x.dtype)
    kth = jax.lax.cond(
        jnp.any(k < v),
        lambda: _cut_value(_largest_key_reaching(
            x, jnp.ones_like(scaled), neg_inf, k.astype(jnp.float32)),
            x.dtype),
        lambda: neg_inf)
    mass = jnp.exp(scaled - top[:, None])
    total = jnp.sum(jnp.where(x >= kth[:, None], mass, 0.0), axis=-1)
    t = _largest_key_reaching(x, mass, kth, top_ps * total)
    # top_p <= 0 reaches its target at every key: the top token stays
    t = jnp.clip(t, _key_neg_inf(x.dtype), _float_keys(jnp.max(x, axis=-1)))
    return kth, _cut_value(t, x.dtype)


def _mask_top_p(logits, p: float):
    """Nucleus filtering: keep the smallest prefix of the sorted distribution
    with cumulative probability >= p (the token crossing the threshold is
    kept, matching HF's TopPLogitsWarper): the one definition of the
    nucleus threshold, ``nucleus_thresholds``."""
    if p >= 1.0:
        return logits
    lead = logits.shape[:-1]
    rows = logits.reshape((-1, logits.shape[-1]))
    _, thresh = nucleus_thresholds(
        rows, jnp.full(rows.shape[:1], rows.shape[-1], jnp.int32),
        jnp.full(rows.shape[:1], p, jnp.float32))
    return jnp.where(logits < thresh.reshape(lead + (1,)), -jnp.inf, logits)


def warp_logits(logits, params: SamplingParams):
    """Apply the HF warper pipeline (temperature -> top_k -> top_p) and
    return the masked logits [-inf outside the sampling support]. The
    distribution ``softmax(warp_logits(l, p))`` is exactly what ``sample``
    draws from — factored out so speculative verification
    (ops/speculative.py) can accept/reject against the same distribution.
    """
    logits = logits.astype(jnp.float32)
    if not params.do_sample:
        return logits
    t = max(params.temperature, 1e-6)
    logits = logits / t
    logits = _mask_top_k(logits, params.top_k)
    return _mask_top_p(logits, params.top_p)


def sample(logits, key, params: SamplingParams,
           ban_tokens: Optional[jax.Array] = None):
    """Sample next tokens. logits: [..., V] float; returns [...] int32.

    The transform order (temperature -> top_k -> top_p) matches HF
    generate()'s LogitsProcessor ordering so outputs are comparable.

    Hot path: when top_k is active, the nucleus filter runs on the top-k
    subset only — one ``lax.top_k`` instead of a full-vocab sort per decode
    step. This is exact, not an approximation: after the top-k warper the
    distribution is supported on those k tokens, so HF's subsequent top-p
    softmax/cumsum sees exactly the same values.
    """
    logits = logits.astype(jnp.float32)
    if ban_tokens is not None:
        logits = jnp.where(ban_tokens, -jnp.inf, logits)
    if not params.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = max(params.temperature, 1e-6)
    logits = logits / t

    V = logits.shape[-1]
    if 0 < params.top_k < V:
        vals, idx = jax.lax.top_k(logits, params.top_k)  # sorted descending
        if params.top_p < 1.0:
            probs = jax.nn.softmax(vals, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # sorted position i is removed if the cumulative mass *before*
            # it >= p (the crossing token is kept, per HF TopPLogitsWarper)
            vals = jnp.where((cum - probs) < params.top_p, vals, -jnp.inf)
        j = jax.random.categorical(key, vals, axis=-1)
        return jnp.take_along_axis(idx, j[..., None], axis=-1)[..., 0].astype(jnp.int32)

    logits = _mask_top_p(logits, params.top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


# Static prefix width for sample_batch's prefix tier. Rows whose top_k fits
# inside it sample exactly from one lax.top_k and draw over PREFIX_K
# candidates; every other sampling row takes the full tier, which cuts by
# nucleus_thresholds' two scalars and draws over the whole vocabulary.
PREFIX_K = 128


def nucleus_mask_sorted(sorted_vals, width, top_ps):
    """Mask sorted-descending logits to top-k ∩ top-p (HF warper order:
    the token crossing the p threshold is kept).

    sorted_vals: [..., KS] descending; width: [..., 1] int (top-k cut,
    already clamped to KS); top_ps: [..., 1] f32. Returns (masked
    [..., KS] with -inf outside the sampling support, thresh [..., 1] =
    smallest kept logit). ``softmax(masked)`` is exactly the distribution
    ``sample_batch`` draws from for covered rows, which is what lets
    speculative verification (ops/speculative.py accept_rejection_batch)
    accept/reject against the same distribution the plain path samples.
    """
    ks = sorted_vals.shape[-1]
    m = jnp.where(jnp.arange(ks)[(None,) * (sorted_vals.ndim - 1)] < width,
                  sorted_vals, -jnp.inf)
    probs = jax.nn.softmax(m, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps
    num_keep = jnp.maximum(jnp.sum(keep, axis=-1, keepdims=True), 1)
    thresh = jnp.take_along_axis(m, num_keep - 1, axis=-1)
    return jnp.where(m < thresh, -jnp.inf, m), thresh


def sample_batch(logits, seeds, steps, temps, top_ks, top_ps, do_sample):
    """Per-row-parameterized sampling for the continuous batcher.

    logits: [R, V] in the dtype the head computed them in: a bfloat16 or
    float16 head's logits are not to be cast to float32 first, the full
    tier's search walks the bits they have (every probability, sum and
    draw is float32 whatever they are). seeds/steps: [R] int32 — each row
    draws from its OWN
    PRNG stream ``fold_in(PRNGKey(seed), step)``, so a request's output is
    a pure function of (params, prompt, seed), reproducible regardless of
    what other requests share its decode steps or how admission/preemption
    interleaves them. temps/top_ps: [R] f32; top_ks: [R] int32 (0
    disables); do_sample: [R] bool (False -> greedy). Sampling parameters
    are data, not trace constants — one compiled program covers any mix of
    requests.

    Two tiers, each computed only in a step that has a row for it
    (``lax.switch``: neither, prefix, full, both):
    - **prefix** (hot): rows with 0 < k <= PREFIX_K (every realistic
      serving config; the reference hardcoded k=50, worker/app.py:301)
      sample from ``lax.top_k(PREFIX_K)``. Exact: the k-masked
      distribution's support lies inside the prefix, so softmax/top-p
      thresholds over the prefix equal the full-vocab computation.
    - **full**: any sampling row with k == 0 (disabled: the OpenAI and
      vLLM default) or k > PREFIX_K masks the whole vocabulary by the two
      scalars of ``nucleus_thresholds`` and draws from it.
    A row's draw mechanism depends only on its OWN k — a covered row takes
    the prefix draw and an uncovered one the full draw in every branch —
    so chunk-mates never change another request's tokens.
    """
    r, v = logits.shape
    ks = min(PREFIX_K, v)
    k = jnp.where(top_ks <= 0, v, jnp.clip(top_ks, 1, v))
    covered = k <= ks

    def scale(x):
        return x.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]

    keys = jax.vmap(
        lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
    )(seeds, steps)

    def prefix_draw():
        vals, idx = jax.lax.top_k(scale(logits), ks)    # [R, KS] descending
        m, _ = nucleus_mask_sorted(vals, jnp.minimum(k, ks)[:, None],
                                   top_ps[:, None])
        j = jax.vmap(lambda kk, l: jax.random.categorical(kk, l))(keys, m)
        return jnp.take_along_axis(idx, j[:, None], axis=-1)[:, 0]

    def full_draw():
        scaled = scale(logits)
        # the array the cuts are found in and applied to: a 16-bit head's
        # own logits (8 steps a search), else ``scaled`` (16)
        x = logits if logits.dtype.itemsize == 2 else scaled
        kth, thresh = nucleus_thresholds(x, k, top_ps, scaled)
        cut = jnp.maximum(kth, thresh)
        if x is logits:
            # The draw scales the logits again, behind a barrier (XLA
            # would share the two): a float32 [R, V] kept alive across
            # the search for the draw holds the on-chip memory that the
            # search's float32 mass then cannot have, and at 64 x 261,120
            # a step reads the mass from HBM: 134 us where it takes 60
            # (PERF.md, PR 47).
            x, cut = jax.lax.optimization_barrier((x, cut))
            scaled = scale(x)
        masked = jnp.where(x < cut[:, None], -jnp.inf, scaled)
        return jax.vmap(
            lambda kk, l: jax.random.categorical(kk, l))(keys, masked)

    # each tier is computed only in a pass that has a row for it (the
    # top_k of 64 x 128,256 costs 3.2 ms on a v5e, the full tier 0.6 for
    # bfloat16 logits and 1.2 for float32 ones)
    sampled = jax.lax.switch(
        jnp.any(do_sample & covered) + 2 * jnp.any(do_sample & ~covered),
        [lambda: jnp.zeros((r,), jnp.int32),            # greedy rows only
         prefix_draw,
         full_draw,
         lambda: jnp.where(covered, prefix_draw(), full_draw())])
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(do_sample, sampled, greedy).astype(jnp.int32)
