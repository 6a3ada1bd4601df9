"""sample_batch: per-row parameterized sampling (two-tier prefix/full).

The batcher's sampler runs inside every decode-chunk program; these tests
pin (a) masking semantics (top-k, nucleus, greedy), (b) branch purity — a
row's draw never depends on its chunk-mates' configs, the property the
scheduler's reproducibility contract rests on, (c) that the prefix
fast path samples the same *distribution* the full-vocab path does, and
(d) the full tier's thresholds (``nucleus_thresholds``: a search over bit
patterns at every size) against a float64 oracle and against the one
descending sort it replaced, kept here as the reference
(``_thresholds_by_sort``, ``_sorted_full_draw``), at the benchmark cells'
sizes too.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.ops import sampling
from distributed_llm_inferencing_tpu.ops.sampling import (
    PREFIX_K, SamplingParams, nucleus_mask_sorted, sample, sample_batch,
    warp_logits)



_jit_sample = jax.jit(sample_batch)


def _draw(logits, seeds, steps, temps, tks, tps, ds):
    return np.asarray(_jit_sample(
        jnp.asarray(logits, jnp.float32),
        jnp.asarray(seeds, jnp.int32), jnp.asarray(steps, jnp.int32),
        jnp.asarray(temps, jnp.float32), jnp.asarray(tks, jnp.int32),
        jnp.asarray(tps, jnp.float32), jnp.asarray(ds, bool)))


def _draw_many(logits, seed, steps, temp, tk, tp, dtype="float32"):
    """Vectorized multi-step draws for distribution tests (one compile)."""
    logits = jnp.asarray(logits, jnp.dtype(dtype))

    @jax.jit
    def go(steps):
        def one(step):
            return sample_batch(
                logits, jnp.asarray([seed], jnp.int32),
                jnp.asarray([step], jnp.int32),
                jnp.asarray([temp], jnp.float32),
                jnp.asarray([tk], jnp.int32),
                jnp.asarray([tp], jnp.float32), jnp.asarray([True]))[0]
        return jax.vmap(one)(steps)

    return np.asarray(go(jnp.arange(steps, dtype=jnp.int32)))


def test_greedy_rows_are_argmax():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4, 300))
    out = _draw(logits, [1] * 4, [0] * 4, [0.8] * 4, [50] * 4, [0.95] * 4,
                [False] * 4)
    np.testing.assert_array_equal(out, logits.argmax(-1))


def test_sampled_tokens_respect_top_k():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(8, 500))
    for step in range(20):
        out = _draw(logits, list(range(8)), [step] * 8, [1.0] * 8, [5] * 8,
                    [1.0] * 8, [True] * 8)
        for r in range(8):
            top5 = set(np.argsort(logits[r])[-5:])
            assert out[r] in top5


def test_sampled_tokens_respect_top_p():
    # one dominant logit -> nucleus at p=0.5 is exactly that token
    logits = np.zeros((2, 100), np.float32)
    logits[:, 7] = 50.0
    out = _draw(logits, [3, 4], [0, 0], [1.0] * 2, [0] * 2, [0.5] * 2,
                [True] * 2)
    np.testing.assert_array_equal(out, [7, 7])


def test_row_draw_independent_of_chunk_mates():
    """A covered row (k <= PREFIX_K) must sample the SAME token whether its
    chunk-mates are covered (fast branch) or force the full-vocab branch —
    the scheduler's (params, prompt, seed) purity contract."""
    rng = np.random.default_rng(0)
    v = PREFIX_K * 4
    logits = rng.normal(size=(2, v))
    for step in range(10):
        fast = _draw(logits, [11, 12], [step] * 2, [0.9] * 2, [50, 50],
                     [0.95] * 2, [True] * 2)
        # mate switches to k > PREFIX_K -> slow branch for the batch
        slow = _draw(logits, [11, 12], [step] * 2, [0.9] * 2,
                     [50, PREFIX_K + 7], [0.95] * 2, [True] * 2)
        assert fast[0] == slow[0], (step, fast, slow)


def test_uncovered_row_uses_full_vocab():
    """k > PREFIX_K must actually reach beyond the prefix: with uniform
    logits and k = V, draws cover tokens outside the top PREFIX_K."""
    v = PREFIX_K * 8
    logits = np.zeros((1, v), np.float32)
    out = _draw_many(logits, seed=5, steps=64, temp=1.0, tk=0, tp=1.0)
    # ties: top_k picks the first PREFIX_K indices; anything beyond
    # proves the full path sampled the whole support
    assert (out >= PREFIX_K).any()


def test_prefix_path_matches_full_distribution():
    """Empirical frequencies from the prefix fast path match the exact
    k-masked softmax (chi-square-ish loose bound, fixed seeds)."""
    v, k, n = 64, 4, 4000   # v < PREFIX_K -> prefix covers everything
    logits = np.zeros((1, v), np.float32)
    logits[0, :k] = [2.0, 1.5, 1.0, 0.5]
    out = _draw_many(logits, seed=9, steps=n, temp=1.0, tk=k, tp=1.0)
    counts = np.bincount(out, minlength=v)
    assert counts[k:].sum() == 0          # top-k mask held
    p = np.exp(logits[0, :k]) / np.exp(logits[0, :k]).sum()
    np.testing.assert_allclose(counts[:k] / n, p, atol=0.04)


# ---- the full tier: thresholds by search, held to a sort ---------------

V = 2000
ROW_KINDS = ("flat", "peaked", "bf16_ties", "neg_inf", "edges")
MARGIN = 1e-5      # float32 summation error around top_p (docstring, 1.)
# the dtype the logits are handed over in: float32 (a 32-bit search, 16
# steps) or bfloat16 as a bf16 head computes them (16 bits, 8 steps)
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _rows(kind, r, v, seed=0, dtype="float32"):
    """[r, v] float32 logits; for ``bfloat16`` every value is one a bf16
    head can give (handed over as bf16 by ``_as``)."""
    rng = np.random.default_rng([seed, ROW_KINDS.index(kind)])
    x = rng.normal(size=(r, v))
    if kind == "flat":          # what random weights give: a wide nucleus
        x *= 0.3
    elif kind == "peaked":
        x *= 4.0
    elif kind == "bf16_ties":   # a bf16 head's logits: many equal values
        x = np.asarray(jnp.asarray(x * 2, jnp.bfloat16).astype(jnp.float32))
    elif kind == "neg_inf":     # banned tokens
        x[:, ::3] = -np.inf
    else:                       # -0.0 beside 0.0, banned tokens, a row of
        x[:, ::5] = -0.0        # one value, a row of one value and bans
        x[:, 1::5] = 0.0
        x[:, 2::7] = -np.inf
        x[-1] = 1.25
        x[-2] = np.where(np.arange(v) % 2, -np.inf, -3.0)
    if dtype == "bfloat16":
        x = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    return x.astype(np.float32)


def _as(x, dtype):
    return jnp.asarray(x, DTYPES[dtype])


@functools.lru_cache(maxsize=None)
def _input(kind, r, v, seed=0, dtype="float32"):
    """``_rows`` and the float64 oracle's stable descending order of each
    row, once for all the cases over one input (k, p and the form are
    what differs between them)."""
    x = _rows(kind, r, v, seed, dtype)
    x.setflags(write=False)
    return x, [np.argsort(-row.astype(np.float64), kind="stable")
               for row in x]


def _oracle(x, k, p, order=None):
    """(kept, sure) for one row in float64: the top-k set is {x >= kth},
    a token is kept iff the mass strictly above its value, over that set,
    is below p; ``sure`` where that mass is further than MARGIN from p.
    ``order``: the row's stable descending order, where a caller has it."""
    x = x.astype(np.float64)
    v = x.shape[0]
    if order is None:
        order = np.argsort(-x, kind="stable")
    xs = x[order]
    kth = xs[(v if k <= 0 else min(k, v)) - 1]
    in_topk = x >= kth
    es = np.where(xs >= kth, np.exp(xs - xs[0]), 0.0)
    cum = np.concatenate([[0.0], np.cumsum(es)])
    above = cum[np.searchsorted(-xs, -x, side="left")] / cum[-1]
    return in_topk & (above < p), ~in_topk | (np.abs(above - p) > MARGIN)


def _thresholds_by_sort(scaled, k, top_ps):
    """``nucleus_thresholds`` by one descending sort of the vocabulary:
    what ops/sampling.py ran under 2**20 logits a pass until PR 36, kept
    as the reference the search is held to."""
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    in_top_k = jnp.sum(sorted_desc >= kth, axis=-1, keepdims=True)
    _, thresh = nucleus_mask_sorted(sorted_desc, in_top_k, top_ps[:, None])
    return kth[:, 0], thresh[:, 0]


FORMS = {"search": jax.jit(sampling.nucleus_thresholds),
         "sort": jax.jit(_thresholds_by_sort)}


def _kept(x, k, p, form, dtype="float32"):
    """x: float32 values, handed to the form as ``dtype`` (the sort form
    always takes float32). The cuts come back as float32."""
    r, v = x.shape
    kth, thresh = FORMS[form](
        _as(x, dtype if form == "search" else "float32"),
        jnp.full((r,), v if k <= 0 else min(k, v), jnp.int32),
        jnp.full((r,), p, jnp.float32))
    kth, thresh = (np.asarray(c.astype(jnp.float32)) for c in (kth, thresh))
    return x >= np.maximum(kth, thresh)[:, None], kth, thresh


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("p", [0.0, 0.1, 0.9, 1.0])
@pytest.mark.parametrize("k", [0, 1, 129, 500, V])
@pytest.mark.parametrize("kind", ROW_KINDS)
def test_kept_set_matches_float64_oracle(kind, k, p, form, dtype):
    x, orders = _input(kind, 8, V, dtype=dtype)
    kept, kth, thresh = _kept(x, k, p, form, dtype)
    for r in range(x.shape[0]):
        want, sure = _oracle(x[r], k, p, orders[r])
        if p == 0.0:        # the top token stays (and what ties with it)
            want = x[r] == x[r].max()
        np.testing.assert_array_equal(kept[r][sure], want[sure])
        # (at p = 1.0 a long tail lies within the margin of 1.0; a row of
        # one value lies on the boundary whole)
        assert kept[r].any() and (p == 1.0 or kind == "edges"
                                  or sure.mean() > 0.99)
        # the cut is a value of the row
        assert max(kth[r], thresh[r]) in x[r]


def _sorted_full_draw(logits, seeds, steps, temps, top_ks, top_ps):
    """sample_batch's full tier as it was before the search: a descending
    sort of the vocabulary to read two scalars a row."""
    logits = jnp.asarray(logits, jnp.float32)
    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    k = jnp.where(top_ks <= 0, v, jnp.clip(top_ks, 1, v))
    keys = jax.vmap(
        lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
    )(seeds, steps)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    _, thresh = nucleus_mask_sorted(sorted_desc, k[:, None],
                                    top_ps[:, None])
    masked = jnp.where((scaled < kth) | (scaled < thresh), -jnp.inf, scaled)
    return jax.vmap(
        lambda kk, l: jax.random.categorical(kk, l))(keys, masked)


_jit_sorted_full_draw = jax.jit(_sorted_full_draw)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("block, r, v", [
    (0, 64, 8192), (1, 64, 8192), (2, 64, 8192), (3, 64, 8192),
    (4, 16, 32000), (5, 16, 32000),      # a pass of the mistral cells
])
def test_tokens_equal_the_sorted_form_at_the_cells_settings(block, r, v,
                                                            dtype):
    """Random rows in blocks of 64 (a decode pass of the kanana cell) and
    of 16 x 32,000 (one of the mistral cells, which sorted until PR 36)
    at the benchmark's settings, temperature 0.7, top-p 0.9, top-k off:
    the drawn token is the sort-based form's, bit for bit, in every row
    the oracle is sure of (a flat row's tokens weigh about 1e-4 each, so
    in a quarter of such rows some token's mass lies within the margin
    of 0.9; even there the tokens rarely differ). bfloat16: the logits
    go to the sampler as a bf16 head gives them (the 16-bit search), the
    sorted form takes their float32 copy."""
    kind = ROW_KINDS[block % 3]
    x = _rows(kind, r, v, seed=10 + block, dtype=dtype)
    seeds = jnp.arange(r, dtype=jnp.int32) + 1000 * block
    steps = jnp.full((r,), 17 + block, jnp.int32)
    temps = jnp.full((r,), 0.7, jnp.float32)
    tks = jnp.zeros((r,), jnp.int32)
    tps = jnp.full((r,), 0.9, jnp.float32)
    got = np.asarray(_jit_sample(_as(x, dtype), seeds, steps, temps, tks,
                                 tps, jnp.ones((r,), bool)))
    want = np.asarray(_jit_sorted_full_draw(x, seeds, steps, temps, tks,
                                            tps))
    scaled = x / np.float32(0.7)
    sure = np.array([_oracle(scaled[i], 0, 0.9)[1].all() for i in range(r)])
    assert sure.mean() > 0.6
    np.testing.assert_array_equal(got[sure], want[sure])
    assert (got != want).sum() <= 2


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


# rows x vocabulary of a decode pass in the benchmark's cells
CELL_SIZES = [(16, 32000), (64, 128256), (64, 200192), (64, 261120)]
CELL_IDS = ["mistral", "kanana", "trinity", "falcon-h1"]


def _cell_rows(rows, whole):
    """What a cell-size case pins is the vocabulary (the bit patterns the
    search walks at that many columns) and the dtype, not 64 rows of it:
    8 rows (mistral's 16 stay), and the cell's own rows in the one case a
    vocabulary that ``whole`` picks."""
    return rows if whole or rows == 16 else 8


@pytest.mark.parametrize("name", ["sample_batch", "sample", "warp_logits"])
@pytest.mark.parametrize("rows,vocab", CELL_SIZES, ids=CELL_IDS)
def test_no_path_sorts_the_vocabulary(name, rows, vocab):
    """At no cell's size does a sampling path hold a `sort`: not at the
    expert cells' 64 x 128,256 and 64 x 200,192, and since PR 36 not at
    the mistral cells' 16 x 32,000 either."""
    x = jax.ShapeDtypeStruct((rows, vocab), jnp.float32)
    i = jax.ShapeDtypeStruct((rows,), jnp.int32)
    f = jax.ShapeDtypeStruct((rows,), jnp.float32)
    sp = SamplingParams(temperature=0.7, top_k=0, top_p=0.9)
    if name == "sample_batch":
        jaxpr = jax.make_jaxpr(sample_batch)(
            x, i, i, f, i, f, jax.ShapeDtypeStruct((rows,), jnp.bool_))
    elif name == "sample":
        jaxpr = jax.make_jaxpr(
            lambda x, key: sample(x, key, sp))(x, jax.random.PRNGKey(0))
    else:
        jaxpr = jax.make_jaxpr(lambda x: warp_logits(x, sp))(x)
    prims = set(_primitives(jaxpr.jaxpr))
    assert "sort" not in prims, prims
    assert prims & {"while", "scan"}                  # the search's loop


def _loops(jaxpr):
    """(trip count, body's primitives) of every fori_loop in a jaxpr: a
    ``scan`` where the trip count is static, as the search's is."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["length"], set(
                _primitives(eqn.params["jaxpr"].jaxpr))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _loops(sub)


@pytest.mark.parametrize("dtype,trips", [
    ("float32", 16), ("bfloat16", 8), ("float16", 8)])
def test_the_search_runs_a_step_for_every_two_bits_the_logits_have(dtype,
                                                                   trips):
    """The search's loops (top-k's and the nucleus's, in the full tier's
    branch and in the both-tiers branch) run 32 / 2 steps for float32
    logits and 16 / 2 for a 16-bit head's, a compare-select-reduce each;
    nothing in the sampler sorts either."""
    rows, vocab = 64, 128256
    i = jax.ShapeDtypeStruct((rows,), jnp.int32)
    f = jax.ShapeDtypeStruct((rows,), jnp.float32)
    jaxpr = jax.make_jaxpr(sample_batch)(
        jax.ShapeDtypeStruct((rows, vocab), jnp.dtype(dtype)), i, i, f, i,
        f, jax.ShapeDtypeStruct((rows,), jnp.bool_))
    loops = [(n, prims) for n, prims in _loops(jaxpr.jaxpr)
             if "reduce_sum" in prims]
    assert [n for n, _ in loops] == [trips] * 4, loops
    assert all({"ge", "select_n"} <= prims for _, prims in loops)
    assert "sort" not in set(_primitives(jaxpr.jaxpr))


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("kind", ["flat", "bf16_ties"])
@pytest.mark.parametrize("rows,vocab", CELL_SIZES, ids=CELL_IDS)
def test_thresholds_equal_the_sort_at_the_cells_sizes(rows, vocab, kind,
                                                      dtype):
    """The search against the reference sort at each cell's rows x
    vocabulary and settings (temperature 0.7, top-p 0.9, top-k off, and
    top-k 500 beside it): ``kth`` is an order statistic and equal bit
    for bit; the two nucleus cuts keep the same tokens wherever the
    float64 oracle is sure (docstring, 1.: within float32 summation
    error of ``top_p`` the boundary token may fall on either side).
    bfloat16: the scaled logits rounded to bf16 and handed to the search
    as bf16, to the sort as their float32 copy. The cell's whole rows x
    vocabulary in the bf16-ties bfloat16 case, what a cell's head hands
    over; 8 rows of the cell's vocabulary in the others."""
    rows = _cell_rows(rows, (kind, dtype) == ("bf16_ties", "bfloat16"))
    x = _rows(kind, rows, vocab, seed=7) / np.float32(0.7)
    if dtype == "bfloat16":
        x = np.asarray(_as(x, dtype).astype(jnp.float32))
    for k in (0, 500):
        kept = {}
        for form in FORMS:
            kept[form], kth, _ = _kept(x, k, 0.9, form, dtype)
            kept[form + "_kth"] = kth
        if k:
            np.testing.assert_array_equal(kept["search_kth"],
                                          kept["sort_kth"])
        for r in range(0, rows, max(1, rows // 4)):   # the oracle is slow
            want, sure = _oracle(x[r], k, 0.9)
            np.testing.assert_array_equal(kept["search"][r][sure],
                                          want[sure])
            np.testing.assert_array_equal(kept["search"][r][sure],
                                          kept["sort"][r][sure])
        assert (kept["search"] != kept["sort"]).sum() <= rows


@pytest.mark.parametrize("mate", ["covered", "uncovered", "greedy"])
def test_full_tier_row_independent_of_chunk_mates(mate):
    """A full-tier row (top-k off) draws the same token whatever its
    chunk-mate asks for: a prefix-tier row, another full-tier row with
    its own k and p, or a greedy row."""
    v = PREFIX_K * 16
    logits = _rows("flat", 2, v, seed=3)
    tk, tp, ds = {"covered": (50, 0.95, True),
                  "uncovered": (PREFIX_K + 7, 0.5, True),
                  "greedy": (0, 1.0, False)}[mate]
    def draw(step, temps, tks, tps, ds):
        return np.asarray(_jit_sample(
            jnp.asarray(logits), jnp.asarray([21, 22], jnp.int32),
            jnp.full((2,), step, jnp.int32), jnp.asarray(temps, jnp.float32),
            jnp.asarray(tks, jnp.int32), jnp.asarray(tps, jnp.float32),
            jnp.asarray(ds)))

    for step in range(8):
        base = draw(step, [0.7, 0.7], [0, 0], [0.9, 0.9], [True, True])
        mixed = draw(step, [0.7, 1.3], [0, tk], [0.9, tp], [True, ds])
        assert base[0] == mixed[0], (step, base, mixed)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("form", list(FORMS))
def test_tie_across_the_kth_place_small(form, dtype):
    """Docstring, 2.: a run of equal logits across the k-th place is in
    the top-k set whole, and the nucleus is normalised over that set.
    k = 3 over [4, 3, 2, 2, 2, 1, 0]: the set is the first five tokens;
    the mass above 2 is 74.7 / 96.8 = 0.77 < 0.85, so all five stay
    (over three sorted positions, as before PR 28, it would be 0.91, and
    only 4 and 3 would)."""
    x = np.array([[4, 3, 2, 2, 2, 1, 0]], np.float32)
    kept, kth, thresh = _kept(x, 3, 0.85, form, dtype)
    assert kth[0] == 2.0 and thresh[0] == 2.0
    np.testing.assert_array_equal(np.flatnonzero(kept[0]), [0, 1, 2, 3, 4])
    kept, _, thresh = _kept(x, 3, 0.7, form, dtype)  # 54.6 / 96.8 = 0.56
    assert thresh[0] == 3.0
    np.testing.assert_array_equal(np.flatnonzero(kept[0]), [0, 1])


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_tie_across_the_kth_place_is_drawn_from(dtype):
    """The same through sample_batch (PREFIX_K < k < V): with k = 130
    over 129 distinct logits then a run of five equal ones, draws land
    on the run's every token and nowhere below it."""
    v = 400
    logits = np.full((1, v), -5.0, np.float32)
    logits[0, :129] = np.linspace(1.0, 0.5, 129)   # 2**-8 apart: bf16 too
    logits[0, 129:134] = 0.4
    out = _draw_many(logits, seed=2, steps=3000, temp=1.0, tk=130, tp=1.0,
                     dtype=dtype)
    assert out.max() == 133 and set(range(129, 134)) <= set(out.tolist())


@pytest.mark.parametrize("temperature", ["none", "a_row"])
@pytest.mark.parametrize("kind", ["flat", "bf16_ties", "edges"])
@pytest.mark.parametrize("rows,vocab", CELL_SIZES, ids=CELL_IDS)
def test_sixteen_bit_search_equals_the_32_bit_search_bit_for_bit(
        rows, vocab, kind, temperature):
    """For logits a bf16 head gives, the 16-bit search's ``(kth,
    thresh)`` are bit for bit the 32-bit search's on the float32 copy;
    under a temperature a row they are values of the logits that pass
    through the row's division to exactly the cuts the 32-bit search
    finds in the float32 ``scaled``. Top-k off, on and mixed in one
    batch, ``top_p`` from 0 to 1; the kept sets are the same set, and so
    are the sampled tokens. The cell's whole rows x vocabulary in the
    bf16-ties case under a temperature a row; 8 rows of the cell's
    vocabulary in the others."""
    rows = _cell_rows(rows, (kind, temperature) == ("bf16_ties", "a_row"))
    x = _rows(kind, rows, vocab, seed=5, dtype="bfloat16")
    rng = np.random.default_rng(vocab)
    temps = (jnp.ones((rows,), jnp.float32) if temperature == "none" else
             jnp.asarray(rng.uniform(0.3, 1.5, rows), jnp.float32))
    tps = jnp.asarray(rng.choice([0.0, 0.05, 0.5, 0.9, 0.99, 1.0], rows),
                      jnp.float32)
    bits = lambda a: np.asarray(a).view(np.uint32)
    scaled = jnp.asarray(x) / temps[:, None]
    for ks in ([vocab] * rows, [500] * rows,
               list(rng.choice([1, 129, 500, vocab], rows))):
        ks = jnp.asarray(ks, jnp.int32)
        kth16, thresh16 = FORMS["search"](
            _as(x, "bfloat16"), ks, tps,
            None if temperature == "none" else scaled)
        kth32, thresh32 = FORMS["search"](scaled, ks, tps)
        assert kth16.dtype == thresh16.dtype == jnp.bfloat16
        for c16, c32 in ((kth16, kth32), (thresh16, thresh32)):
            np.testing.assert_array_equal(
                bits(c16.astype(jnp.float32) / temps), bits(c32))
        cut16 = np.asarray(jnp.maximum(kth16, thresh16).astype(jnp.float32))
        cut32 = np.asarray(jnp.maximum(kth32, thresh32))
        np.testing.assert_array_equal(x >= cut16[:, None],
                                      np.asarray(scaled) >= cut32[:, None])
    i = jnp.arange(rows, dtype=jnp.int32)
    rest = (i, i + 3, temps, jnp.where(ks == vocab, 0, ks), tps,
            jnp.ones((rows,), bool))
    np.testing.assert_array_equal(_jit_sample(_as(x, "bfloat16"), *rest),
                                  _jit_sample(jnp.asarray(x), *rest))


@pytest.mark.parametrize("kind", ROW_KINDS)
def test_float16_logits_take_the_same_16_bit_search(kind):
    """A float16 head's logits go through the same key functions (the
    sign bit flipped or every bit, 16 of them; the least key 0x03FF):
    cuts and tokens are the float32 copy's, bit for bit."""
    x = np.asarray(jnp.asarray(_rows(kind, 8, V, seed=9), jnp.float16)
                   .astype(jnp.float32))
    x16 = jnp.asarray(x, jnp.float16)
    assert int(sampling._key_neg_inf(jnp.float16)) == 0x03FF
    assert int(sampling._key_neg_inf(jnp.bfloat16)) == 0x007F
    assert int(sampling._key_neg_inf(jnp.float32)) == 0x007FFFFF
    np.testing.assert_array_equal(
        sampling._keys_to_float(sampling._float_keys(x16), jnp.float16),
        jnp.where(x16 == 0, 0, x16))
    tps = jnp.asarray([0.0, 0.1, 0.5, 0.9, 0.9, 0.99, 1.0, 0.9], jnp.float32)
    for ks in ([V] * 8, [1, 129, 500, V] * 2):
        ks = jnp.asarray(ks, jnp.int32)
        for c16, c32 in zip(FORMS["search"](x16, ks, tps),
                            FORMS["search"](jnp.asarray(x), ks, tps)):
            assert c16.dtype == jnp.float16
            np.testing.assert_array_equal(
                np.asarray(c16.astype(jnp.float32)).view(np.uint32),
                np.asarray(c32).view(np.uint32))
    i = jnp.arange(8, dtype=jnp.int32)
    rest = (i, i, jnp.full((8,), 0.7, jnp.float32),
            jnp.where(ks == V, 0, ks), tps, jnp.ones((8,), bool))
    np.testing.assert_array_equal(_jit_sample(x16, *rest),
                                  _jit_sample(jnp.asarray(x), *rest))


# ---- the batcher's programs hand the sampler a bf16 head's logits --------

def _parent_sample_batch(form):
    """sample_batch as the batcher's programs had it before the 16-bit
    search: the logits cast to float32 at the door, then the 32-bit
    search (``search32``) or the descending sort (``sort``)."""
    def parent(logits, seeds, steps, temps, top_ks, top_ps, do_sample):
        logits = logits.astype(jnp.float32)
        if form == "search32":
            return sample_batch(logits, seeds, steps, temps, top_ks,
                                top_ps, do_sample)
        drawn = _sorted_full_draw(logits, seeds, steps, temps, top_ks,
                                  top_ps)
        return jnp.where(do_sample, drawn,
                         jnp.argmax(logits, axis=-1)).astype(jnp.int32)
    return parent


@pytest.mark.parametrize("form", ["search32", "sort"])
def test_batcher_bf16_tokens_are_the_parent_forms(form, monkeypatch):
    """A tiny bf16 model behind the batcher at the cells' sampling
    (temperature 0.7, top-k off, top-p 0.9; chunks of up to 8 passes):
    the admit program's first token and every chunk's tokens, drawn with
    the head's bf16 logits handed to the sampler as they are, are the
    tokens of the same seeds with the logits cast to float32 first, by
    the 32-bit search and by the sort."""
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.runtime import batcher

    cfg = get_config("tiny-llama").replace(dtype="bfloat16",
                                           attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    seen = []

    def serve():
        b = batcher.ContinuousBatcher(cfg, params, num_blocks=64,
                                      block_size=8, slots=4, max_seq=128,
                                      decode_chunk_cap=8)
        rng = np.random.default_rng(4)
        reqs = [b.submit(rng.integers(0, cfg.vocab_size, 5 + 3 * i).tolist(),
                         max_new_tokens=28, seed=40 + i,
                         sampling=SamplingParams(temperature=0.7, top_k=0,
                                                 top_p=0.9))
                for i in range(3)]
        for _ in range(200):
            b.step()
            if all(r.done.is_set() for r in reqs):
                break
        assert 8 in {key[0] for key in b._decode_fns}
        return [r.wait() for r in reqs]

    def spy(logits, *rest):
        seen.append(logits.dtype)
        return sample_batch(logits, *rest)

    monkeypatch.setattr(sampling, "sample_batch", spy)
    monkeypatch.setattr(batcher, "sample_batch", spy)
    got = serve()
    assert seen and set(seen) == {jnp.dtype(jnp.bfloat16)}
    assert all(len(t) == 28 for t in got)
    parent = _parent_sample_batch(form)
    monkeypatch.setattr(sampling, "sample_batch", parent)
    monkeypatch.setattr(batcher, "sample_batch", parent)
    assert serve() == got
