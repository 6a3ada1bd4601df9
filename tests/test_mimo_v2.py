"""MiMo-V2 (windowed and full attention layers of different shapes in one
stack, a ring a slot for the windowed layers beside the full layers'
block pool, a share of the experts held) at toy widths, `tiny-mimo-v2`:
hidden 64, 8 query heads over 2 full / 4 windowed K/V heads, heads of 24
with values of 16 and 8 rotated columns, window 8, pattern
[0,1,1,1,1,1,0] with layer 0 dense, 32 experts top-4, value scale 0.707,
rotary bases 1e4 / 1e2. The float32 reference is
models/reference/mimo_v2_ref.py (no cache, no ring), which imports
nothing from the package."""

import functools
import importlib.util
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import convert, transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.reference import mimo_v2_ref
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    init_paged_cache, ring_positions, ring_read)
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime import batcher as batcher_mod
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher

BS = 4
R = 3            # serving slots of the hand-driven pool; row R is the dummy
MB = 16          # blocks a slot's table holds: contexts to 64, ring 8
# float32 against float32 on the CPU: the two sum in another order. The
# limit is on the largest error of a position's logits over the spread of
# the reference's logits; what is found is 1e-6..1e-5, the controls (the
# window off by one, the sink or the value scale left out, the bases
# swapped, a stale ring) read 1e-3..1.
TOL = 1e-4


def cfg32(**kw):
    return get_config("tiny-mimo-v2").replace(dtype="float32",
                                              attn_backend="xla", **kw)


# value heads of whole lanes: what lets a decode chunk's full layers read
# the pool by the paged kernel (transformer._pool_kernel), interpreted
# here; K rows stay 2 x 24 -> 128 columns, V rows become 2 x 128 = 256
KERNEL = dict(v_head_dim=128, pool_kernel="pallas_interpret")


def _params(cfg):
    """Seeded random weights; the norms' scales too, or a norm left out
    would go unseen behind ones (sinks and the router's bias are drawn
    by init_params)."""
    p = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def jitter(a):
        return a * (1.0 + 0.3 * jax.random.normal(next(keys), a.shape))
    for stack in ("layers", "layers_full", "layers_dense"):
        for name in ("attn_norm", "mlp_norm"):
            p[stack][name]["scale"] = jitter(p[stack][name]["scale"])
    p["final_norm"]["scale"] = jitter(p["final_norm"]["scale"])
    return p


@pytest.fixture(scope="module")
def params():
    return _params(cfg32())


@pytest.fixture(scope="module")
def kernel_params():
    return _params(cfg32(**KERNEL))


@pytest.fixture(params=["gather", "kernel"])
def model(request, params):
    """(cfg, params) a hand-driven pool is run with: the toy model, whose
    full layers gather the pool in XLA, and the same with value heads of
    128, whose full layers read it by the paged kernel (interpreted)."""
    if request.param == "gather":
        return cfg32(), params
    return cfg32(**KERNEL), request.getfixturevalue("kernel_params")


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, n).astype(np.int32)


def ref_logits(cfg, params, toks, experts_held=None, **arch_kw):
    arch = dict(mimo_v2_ref.arch_of(cfg), **arch_kw)
    return np.asarray(mimo_v2_ref.forward(
        params, arch, jnp.asarray(toks), experts_held=experts_held))


def err(got, ref):
    return float(np.abs(np.asarray(got) - ref).max() / ref.std())


@functools.partial(jax.jit, static_argnums=(0,))
def _prefill(cfg, params, toks, lengths, cache):
    return transformer.prefill(params, cfg, toks, lengths, cache)


def dense_logits(cfg, params, toks, pad=0):
    cache = init_cache(cfg, 1, 64, dtype=jnp.float32)
    padded = np.concatenate([toks, np.zeros(pad, np.int32)])
    logits, cache = _prefill(cfg, params, jnp.asarray(padded[None]),
                             jnp.asarray([len(toks)]), cache)
    return np.asarray(logits[0, :len(toks)], np.float32), cache


# ---- (a) forward ---------------------------------------------------------

@pytest.mark.parametrize("n,pad", [(5, 27), (21, 11), (32, 0), (19, 13)])
def test_forward_matches_the_reference(params, n, pad):
    """The dense cache is as wide as the wider kind's K/V heads and holds
    every position: both kinds, the sink, the partial rotation and the
    value scale against the reference, past the window (8)."""
    cfg = cfg32()
    toks = tokens(n)
    got, cache = dense_logits(cfg, params, toks, pad)
    assert err(got, ref_logits(cfg, params, toks)) < TOL
    assert cache.k.shape[-2:] == (4, 24) and cache.v.shape[-2:] == (4, 16)


# what the reference computes when one thing is changed on its side alone
CONTROLS = {
    "window_127": {"sliding_window": 7},
    "window_129": {"sliding_window": 9},
    "no_sink": {"add_swa_attention_sink_bias": False},
    "no_value_scale": {"attention_value_scale": None},
    "bases_swapped": {"rope_theta": 1e2, "swa_rope_theta": 1e4},
    "rotate_all": {"partial_rotary_factor": 1.0},
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_one_side_changed_fails_the_tolerance(params, control):
    cfg = cfg32()
    toks = tokens(32)
    got, _ = dense_logits(cfg, params, toks)
    assert err(got, ref_logits(cfg, params, toks, **CONTROLS[control])) \
        > 10 * TOL


def test_the_windowed_heads_read_as_pairs_fail_the_tolerance(params):
    """The windowed layers' 4 K/V heads read as 2 pairs (the full
    layers' grouping) is another model."""
    cfg = cfg32()
    toks = tokens(32)
    got, _ = dense_logits(cfg, params, toks)
    want = np.asarray(mimo_v2_ref.forward(
        params, mimo_v2_ref.arch_of(cfg), jnp.asarray(toks), kv_pairs=True))
    assert err(got, want) > 10 * TOL


def test_the_benchmarks_copy_of_the_reference_is_byte_equal():
    root = Path(__file__).resolve().parents[1]
    a = root / "distributed_llm_inferencing_tpu/models/reference/mimo_v2_ref.py"
    b = root / "benchmarks/chip/reference/mimo_v2_ref.py"
    assert a.read_bytes() == b.read_bytes()


# ---- (b) the share of the experts ----------------------------------------

def moe_layer(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def share_of(lp, first, count):
    return dict(lp, experts=jax.tree.map(
        lambda a: a[first:first + count], lp["experts"]))


def test_four_shares_add_up_to_the_whole_layer(params):
    """32 experts held 8 at a time: each share routes over all 32, keeps
    the weights normalised over all four chosen, and sums the chosen
    experts it holds; the four partial outputs add up to the uncut
    reference's whole layer, and every choice is some share's."""
    cfg = cfg32().kind_cfg("swa", 1)
    lp = moe_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 11, 64), jnp.float32)
    arch = mimo_v2_ref.arch_of(cfg32())
    with jax.default_matmul_precision("highest"):
        want = np.asarray(mimo_v2_ref.moe(lp, arch, x.reshape(-1, 64)))
        total, away = 0.0, 0
        for s in range(4):
            held = cfg.replace(experts_held=(8 * s, 8))
            out, stats = transformer._moe(x, share_of(lp, 8 * s, 8), held)
            assert stats.shape == (len(transformer.MOE_STATS),)
            total = total + np.asarray(out).reshape(-1, 64)
            away += int(stats[-1])
            part = np.asarray(mimo_v2_ref.moe(
                share_of(lp, 8 * s, 8), arch, x.reshape(-1, 64),
                experts_held=(8 * s, 8)))
            assert np.abs(np.asarray(out).reshape(-1, 64) - part).max() \
                < 1e-5
    assert np.abs(total - want).max() / want.std() < TOL
    assert away == 3 * 22 * 4         # each choice is away in 3 of 4 shares


@pytest.mark.parametrize("favour", [0.0, 10.0])
def test_a_share_is_walked_as_far_as_its_pairs_reach(params, favour):
    """A held share's sorted rows go through the experts a piece at a
    time (8 of 32 held: 2 pieces of 44 rows); a router that sends the
    share every choice (a bias of 10 on its experts: 88 real rows) makes
    the walk take both, and the layer still is the reference's."""
    cfg = cfg32().kind_cfg("swa", 1).replace(experts_held=(8, 8))
    assert transformer._held_pieces(cfg, 88) == 2
    assert transformer._held_pieces(cfg, 87) == 1
    assert transformer._held_pieces(cfg.replace(experts_held=None), 88) == 1
    lp = moe_layer(params)
    lp = dict(lp, router=dict(lp["router"], bias=lp["router"]["bias"]
                              .at[8:16].add(favour)))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 11, 64), jnp.float32)
    arch = mimo_v2_ref.arch_of(cfg32())
    with jax.default_matmul_precision("highest"):
        out, stats = transformer._moe(x, share_of(lp, 8, 8), cfg)
        want = np.asarray(mimo_v2_ref.moe(
            share_of(lp, 8, 8), arch, x.reshape(-1, 64),
            experts_held=(8, 8)))
    assert np.abs(np.asarray(out).reshape(-1, 64) - want).max() < 1e-5
    rows = dict(zip(transformer.MOE_STATS, np.asarray(stats)))["rows"]
    assert (rows == 88) if favour else (0 < rows <= 44)


def test_a_model_that_holds_all_its_experts_runs_the_call_it_ran(params):
    """experts_held None is the parent's code path (six counters, no
    compare against a held range), and gives bit-equal output to the
    held path given every expert."""
    cfg = cfg32().kind_cfg("swa", 1)
    lp = moe_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 7, 64), jnp.float32)
    valid = jnp.arange(7)[None, :] < jnp.asarray([7, 4, 1])[:, None]
    out, stats = transformer._moe(x, lp, cfg, valid=valid)
    out_all, stats_all = transformer._moe(
        x, lp, cfg.replace(experts_held=(0, 32)), valid=valid)
    assert np.array_equal(np.asarray(out), np.asarray(out_all))
    assert stats.shape == (6,) and int(stats_all[-1]) == 0
    assert np.array_equal(np.asarray(stats), np.asarray(stats_all[:6]))


def test_a_held_share_through_the_whole_model(params):
    """forward with 8 of 32 experts held, against the reference leaving
    the absent experts' terms out; adding them back fails."""
    cfg = cfg32(experts_held=(8, 8))
    held = dict(params)
    for stack in ("layers", "layers_full"):
        held[stack] = dict(params[stack], experts=jax.tree.map(
            lambda a: a[:, 8:16], params[stack]["experts"]))
    toks = tokens(21)
    got, _ = dense_logits(cfg, held, toks, 11)
    assert err(got, ref_logits(cfg, held, toks, experts_held=(8, 8))) < TOL
    assert err(got, ref_logits(cfg32(), params, toks)) > 10 * TOL


# ---- (c) prefill then decode through pool and ring, on logits ------------

def table(slot):
    return 1 + slot * MB + np.arange(MB)


# one compile a shape for the whole file (cfg is hashable: static)
@functools.partial(jax.jit, static_argnums=(0,))
def admit_fn(cfg, params, *a):
    return transformer.paged_prefill_tail(params, cfg, *a[:-1], slots=a[-1])


@functools.partial(jax.jit, static_argnums=(0, 1))
def chunk_fn(cfg, k, params, *a):
    return transformer.decode_chunk_with_logits(params, cfg, k, *a, 0)


class Sim:
    """The pool and the ring driven by hand: admission waves
    (transformer.paged_prefill_tail) and greedy decode chunks
    (decode_chunk_with_logits), each slot's tokens and the logits that
    chose them kept for the comparison."""

    def __init__(self, cfg, params, spoil=False):
        self.cfg, self.params = cfg, params
        self.paged = init_paged_cache(cfg, 1 + R * MB, BS,
                                      dtype=jnp.float32, slots=R)
        if spoil:   # whatever the slots' last tenants left
            self.paged = self.paged._replace(
                ring_k=self.paged.ring_k + 7.0,
                ring_v=self.paged.ring_v - 3.0,
                k=self.paged.k + 5.0, v=self.paged.v - 2.0)
        self.cl = np.zeros(R, np.int32)
        self.seq = [None] * R      # prompt + emitted tokens
        self.n0 = [0] * R          # prompt length
        self.logits = [[] for _ in range(R)]

    def admit(self, rows, t, b, pb=8):
        """rows: (slot, tokens of this chunk, positions before it)."""
        toks = np.zeros((b, t), np.int32)
        tl = np.ones(b, np.int32)
        tb = np.zeros((b, t // BS), np.int32)
        pfb = np.zeros((b, pb), np.int32)
        pfl = np.zeros(b, np.int32)
        slots = np.full(b, R, np.int32)
        for j, (slot, tk, pre) in enumerate(rows):
            toks[j, :len(tk)], tl[j], pfl[j], slots[j] = tk, len(tk), pre, slot
            tb[j] = table(slot)[pre // BS:pre // BS + t // BS]
            pfb[j, :pre // BS] = table(slot)[:pre // BS]
        last, self.paged = admit_fn(
            self.cfg, self.params,
            *map(jnp.asarray, (toks, tl, tb, pfb, pfl)), self.paged,
            jnp.asarray(slots))
        return np.asarray(last)

    def start(self, rows, t, b):
        """Whole prompts, one wave; the first token is the argmax."""
        last = self.admit([(s, p, 0) for s, p in rows], t, b)
        for j, (slot, prompt) in enumerate(rows):
            self.seq[slot] = list(prompt) + [int(np.argmax(last[j]))]
            self.n0[slot], self.cl[slot] = len(prompt), len(prompt)
            self.logits[slot] = [last[j]]

    def decode(self, k, budget):
        z = jnp.zeros((R,), jnp.int32)
        bt = np.stack([table(s) for s in range(R)])
        cur = [0 if s is None else s[-1] for s in self.seq]
        toks, emits, *_, self.paged, lg = chunk_fn(
            self.cfg, k, self.params,
            jnp.asarray(cur, jnp.int32), self.paged, jnp.asarray(bt),
            jnp.asarray(self.cl), z, z, jnp.ones((R,), jnp.float32), z,
            jnp.ones((R,), jnp.float32), jnp.zeros((R,), bool),
            jnp.asarray(budget, jnp.int32), z - 1)
        toks, emits, lg = map(np.asarray, (toks, emits, lg))
        for s in range(R):
            n = int(emits[:, s].sum())
            assert n == min(k, budget[s])
            if self.seq[s] is None:
                continue
            self.seq[s] = self.seq[s] + toks[:n, s].tolist()
            self.logits[s] += list(lg[:n, s])
            self.cl[s] += n

    def check(self, slot, tol=TOL, **arch_kw):
        """Every kept logit against the reference's full forward over
        the slot's own sequence."""
        seq = np.asarray(self.seq[slot][:-1], np.int32)
        ref = ref_logits(self.cfg, self.params, seq, **arch_kw)
        n0 = self.n0[slot]
        assert len(self.logits[slot]) == len(seq) - n0 + 1
        return max(err(got, ref[n0 - 1 + i])
                   for i, got in enumerate(self.logits[slot]))


def test_slots_that_join_at_different_times_match_the_reference(
        model, monkeypatch):
    """Two prompts in one wave (a padded tail bucket: 9 and 14 of 16,
    both past the window), decode, a third joins in a wave with a padded
    row while the others are mid-way, chunks of 4 through side buffers,
    ring and pool, every slot over a ring and blocks that held something
    else before. The pool is read by the in-loop gather under the
    ladder's switch, or by the paged kernel, as the chip reads it."""
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    sim = Sim(*model, spoil=True)
    sim.start([(0, tokens(9, 1)), (1, tokens(14, 2))], 16, 2)
    sim.decode(4, [4, 4, 0])
    sim.start([(2, tokens(6, 3))], 8, 2)          # one real row of two
    for _ in range(3):
        sim.decode(4, [4, 4, 4])
    assert max(sim.check(s) for s in range(R)) < TOL
    # ... and the window one off, the sink or the scale left out, fails
    for control in ("window_127", "window_129", "no_sink"):
        assert sim.check(1, **CONTROLS[control]) > 10 * TOL, control


def test_a_context_that_wraps_the_ring_twice(model):
    """Ring 8: a prompt of 13 wraps it once at admission, 12 decoded
    positions wrap it again and more; the pre-gathered pool (or the
    kernel's walk)."""
    sim = Sim(*model)
    assert sim.paged.ring_k.shape[2] == 8
    sim.start([(1, tokens(13, 5))], 16, 2)
    for _ in range(3):
        sim.decode(4, [0, 4, 0])
    assert sim.check(1) < TOL


def test_a_decode_chunk_of_8_across_the_windows_edge(model):
    """A prompt of 5 (inside the window), then one chunk of 8 passes:
    the window's edge falls inside the chunk, its first passes see ring
    rows the later ones must not, side buffers and ring in one softmax
    (and the full layers' eight passes of side rows beside a pool context
    of less than a page and a half)."""
    sim = Sim(*model, spoil=True)
    sim.start([(0, tokens(5, 6))], 8, 2)
    sim.decode(8, [8, 0, 0])
    sim.decode(8, [8, 0, 0])
    assert sim.check(0) < TOL
    assert sim.check(0, sliding_window=7) > 10 * TOL


def test_a_prompt_in_chunks_goes_on_from_its_slots_ring(model):
    """A prompt of 27 in chunks of 8 (the last one 3 of a bucket of 8):
    a later chunk's windowed layers see the ring the earlier ones left,
    across every chunk boundary and the ring's wrap; its full layers
    gather the earlier chunks' blocks."""
    sim = Sim(*model, spoil=True)
    prompt = tokens(27, 7)
    for pre in (0, 8, 16, 24):
        last = sim.admit([(2, prompt[pre:pre + 8], pre)], 8, 2)
    sim.seq[2] = list(prompt) + [int(np.argmax(last[0]))]
    sim.n0[2], sim.cl[2], sim.logits[2] = 27, 27, [last[0]]
    sim.decode(4, [0, 0, 4])
    assert sim.check(2) < TOL


def test_a_reused_slot_sees_nothing_of_its_last_tenant(model):
    """A slot's second tenant (shorter than the ring: most rows still
    hold the first one's positions) reads what a fresh slot reads, bit
    for bit."""
    sim = Sim(*model)
    sim.start([(0, tokens(14, 8))], 16, 2)
    sim.decode(4, [4, 0, 0])
    sim.start([(0, tokens(5, 9))], 8, 2)
    sim.decode(4, [4, 0, 0])
    fresh = Sim(*model)
    fresh.start([(0, tokens(5, 9))], 8, 2)
    fresh.decode(4, [4, 0, 0])
    assert sim.check(0) < TOL
    assert all(np.array_equal(a, b)
               for a, b in zip(sim.logits[0], fresh.logits[0]))


def test_padded_rows_and_padded_positions_change_no_live_ring(params):
    """A wave of one real row and one padded row, the real tail 5 of a
    bucket of 16: the other slots' ring rows stay bit for bit, the
    admitted slot's rows past its 5 positions too; only the dummy row
    takes the rest."""
    sim = Sim(cfg32(), params, spoil=True)
    before = np.asarray(sim.paged.ring_k)
    sim.start([(1, tokens(5, 10))], 16, 2)
    after = np.asarray(sim.paged.ring_k)
    assert np.array_equal(after[:, [0, 2]], before[:, [0, 2]])
    assert np.array_equal(after[:, 1, 5:], before[:, 1, 5:])
    assert not np.array_equal(after[:, 1, :5], before[:, 1, :5])


def test_a_slot_that_ends_inside_a_chunk_stops_writing_its_ring(model):
    """Budget 2 in a chunk of 4: the slot's ring takes two rows, its
    later passes' rows go to the dummy row, and the slots beside it are
    what they would be alone."""
    sim = Sim(*model)
    sim.start([(0, tokens(9, 11)), (1, tokens(6, 12))], 16, 2)
    before = np.asarray(sim.paged.ring_v)
    sim.decode(4, [2, 4, 0])
    after = np.asarray(sim.paged.ring_v)
    changed = [j for j in range(8)
               if not np.array_equal(after[:, 0, j], before[:, 0, j])]
    assert changed == [(9 + 0) % 8, (9 + 1) % 8]
    assert np.array_equal(after[:, 2], before[:, 2])
    assert sim.check(0) < TOL and sim.check(1) < TOL


def test_a_stale_ring_fails_the_tolerance(params):
    """The ring's rows count: with them spoiled after admission the
    decoded logits leave the reference's."""
    sim = Sim(cfg32(), params)
    sim.start([(0, tokens(13, 13))], 16, 2)
    sim.paged = sim.paged._replace(ring_k=sim.paged.ring_k * 0.5)
    sim.decode(4, [4, 0, 0])
    assert sim.check(0) > 10 * TOL


def test_the_rings_rule():
    """The window in whole blocks, from the configuration; what each row
    holds below a horizon; a window a ring cannot hold is refused."""
    cfg = cfg32()
    assert ring_positions(cfg, 4) == 8 and ring_positions(cfg, 16) == 16
    assert ring_positions(get_config("mimo-v2.5"), 16) == 128
    pos, valid = ring_read(8, jnp.asarray([0, 5, 8, 21]))
    assert not np.asarray(valid[0]).any()
    assert np.asarray(pos[1])[:5].tolist() == [0, 1, 2, 3, 4]
    assert np.asarray(valid[1]).tolist() == [True] * 5 + [False] * 3
    assert sorted(np.asarray(pos[3]).tolist()) == list(range(13, 21))
    assert all(int(p) % 8 == j for j, p in enumerate(np.asarray(pos[3])))
    with pytest.raises(ValueError, match="ring"):
        ring_positions(cfg.replace(sliding_window=2048), 16)
    paged = init_paged_cache(get_config("tiny-mimo-v2"), 9, 4, slots=3)
    assert paged.k.shape == (2, 9, 4, 1, 128)       # 2 x 24 -> a tile
    assert paged.ring_k.shape == (5, 4, 8, 1, 128)  # 4 x 24
    assert paged.ring_bytes_per_slot == 5 * 8 * (128 + 128) * 2


# ---- (d) the batcher ------------------------------------------------------

def serve(cfg, prompts, new=10, cap=8, blocks=128, slots=4, **kw):
    b = ContinuousBatcher(cfg, None, seed=0, num_blocks=blocks,
                          block_size=BS, slots=slots, max_seq=64,
                          decode_chunk_cap=cap, **kw)
    reqs = [b.submit(list(map(int, p)), max_new_tokens=new,
                     sampling=SamplingParams.greedy(), seed=0)
            for p in prompts]
    while b.inflight():
        b.step()
    return b, reqs


def served_right(b, reqs, new):
    """Each request's tokens are the reference's greedy continuation of
    its prompt (float32: the argmax is the reference's own)."""
    arch = mimo_v2_ref.arch_of(b.cfg)
    params = dict(b.params)
    for req in reqs:
        assert req.error is None and len(req.tokens) == new
        seq = list(req.prompt) + req.tokens
        lg = np.asarray(mimo_v2_ref.forward(params, arch,
                                            jnp.asarray(seq[:-1])))
        want = np.argmax(lg[len(req.prompt) - 1:], axis=-1)
        assert req.tokens == want.tolist()


def test_the_batcher_serves_what_the_reference_computes(monkeypatch):
    """submit / step, chunks capped at 8: prompts of 5, 13 and 30 (the
    last in chunks of 16: prefill_chunk 4 blocks, its slot held between
    them), three requests on two slots so that one waits and a slot is
    reused; counters and gauge of the ring."""
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    prompts = [tokens(n, 20 + n) for n in (5, 13, 30)]
    b, reqs = serve(cfg32(), prompts, new=9, slots=2, prefill_chunk=4)
    served_right(b, reqs, 9)
    snap = b.metrics.snapshot()
    counters, gauges = snap["counters"], snap["gauges"]
    assert b._chunked_admissions >= 1 and not b._holds
    assert gauges["batcher_kv_ring_bytes_per_slot"] \
        == b.paged.ring_bytes_per_slot > 0
    assert gauges["batcher_kv_bytes_per_token"] == 2 * (128 + 128) * 4
    assert counters["batcher_decode_ring_positions"] \
        == 8 * counters["batcher_weight_passes"]
    assert counters["batcher_moe_rows_away"] == 0
    assert counters["batcher_moe_experts_held"] \
        == 32 * counters["batcher_moe_layer_passes"]
    assert counters.get("prefix_hits", 0) == 0


def test_the_batcher_counts_the_passes_its_full_layers_read_by_the_kernel(
        monkeypatch):
    """With value heads of whole lanes and the batcher's pin interpreted
    (a one-device TPU pins "pallas") every decode pass reads the full
    layers' pool by the paged kernel: ``batcher_pool_kernel_passes`` is
    the weight passes, the span says so, the pool's extent a pass is the
    longest live context in whole blocks and not the table's 64, and the
    tokens are the reference's. Where the pin is a mesh's ("xla") the
    count stays 0 and the in-loop gather serves the same tokens; value
    heads of the toy's 16 columns (served off the kernel in
    tests/test_pallas_parity.py), planes out of the compute dtype (an
    int8 pool; the batcher refuses one by name) or a sink on the full
    layers are turned away by transformer._pool_kernel."""
    from distributed_llm_inferencing_tpu.utils import trace
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    prompts = [tokens(n, 60 + n) for n in (5, 13)]
    mesh_pin = batcher_mod._expert_backend(2, "tpu")   # "xla"

    def passes(cfg, pin):
        monkeypatch.setattr(batcher_mod, "_expert_backend",
                            lambda *a, **k: pin)
        b, reqs = serve(cfg, prompts, new=9, slots=2)
        counters = b.metrics.snapshot()["counters"]
        span = [s_ for s_ in trace.get_tracer().spans()
                if s_.name == "batcher.decode_chunk"][-1]
        assert span.attrs["pool_kernel"] == int(b.pool_kernel)
        if pin != mesh_pin:
            served_right(b, reqs, 9)
        return ([r.tokens for r in reqs],
                counters["batcher_pool_kernel_passes"],
                counters["batcher_weight_passes"],
                counters["batcher_decode_pool_positions"])
    wide = cfg32(v_head_dim=128)
    toks, kernel, weight, positions = passes(wide, "pallas_interpret")
    assert kernel == weight > 0
    # contexts reach 13 + 9: six blocks of 4 at most, of the table's 16
    assert 0 < positions <= 24 * weight
    assert passes(wide, mesh_pin)[:2] == (toks, 0)
    cfg = wide.replace(pool_kernel="pallas_interpret")
    paged = init_paged_cache(cfg, 9, BS, dtype=jnp.float32, slots=2)
    assert transformer._pool_kernel(cfg, paged) == "pallas_interpret"
    assert transformer._pool_kernel(
        cfg32(pool_kernel="pallas_interpret"),
        init_paged_cache(cfg32(), 9, BS, dtype=jnp.float32, slots=2)) is None
    assert transformer._pool_kernel(cfg, paged._replace(
        k=paged.k.astype(jnp.bfloat16))) is None
    assert transformer._pool_kernel(
        cfg.replace(attn_sinks=True), paged) is None


def test_the_batcher_serves_a_held_share():
    """8 of 32 experts held: the served tokens are the reference's with
    the absent experts' terms left out, and the choices that fell on
    them are counted."""
    b, reqs = serve(cfg32(experts_held=(8, 8)), [tokens(11, 31)], new=6)
    arch = mimo_v2_ref.arch_of(b.cfg)
    seq = list(reqs[0].prompt) + reqs[0].tokens
    lg = np.asarray(mimo_v2_ref.forward(
        dict(b.params), arch, jnp.asarray(seq[:-1]), experts_held=(8, 8)))
    assert reqs[0].tokens == np.argmax(lg[10:], axis=-1).tolist()
    counters = b.metrics.snapshot()["counters"]
    assert counters["batcher_moe_rows_away"] > 0
    assert counters["batcher_moe_experts_held"] \
        == 8 * counters["batcher_moe_layer_passes"]


def test_the_same_prompt_twice_hits_no_prefix():
    """A model with ring layers matches and inserts nothing in the radix
    cache: the second request prefills every position again."""
    prompt = tokens(24, 40)
    b, reqs = serve(cfg32(), [prompt], new=3)
    reqs += [b.submit(list(map(int, prompt)), max_new_tokens=3,
                      sampling=SamplingParams.greedy(), seed=0)]
    while b.inflight():
        b.step()
    assert reqs[0].tokens == reqs[1].tokens
    counters = b.metrics.snapshot()["counters"]
    assert counters.get("prefill_cached_tokens", 0) == 0
    assert counters["prefill_uncached_tokens"] == 2 * 24
    assert b.pool.match_prefix(list(map(int, prompt)))[1] == 0


def test_a_preempted_request_is_prefilled_again_from_its_first_token():
    """A pool too small for both: the younger slot is preempted, and on
    its return it is prefilled from position 0 (its ring was another
    tenant's meanwhile), prompt and tokens so far; both still serve the
    reference's tokens."""
    b, reqs = serve(cfg32(), [tokens(14, 41), tokens(15, 42)], new=9,
                    blocks=10, slots=2)
    assert b.metrics.snapshot()["counters"]["batcher_preemptions"] >= 1
    served_right(b, reqs, 9)


def test_a_cancelled_chunked_prompt_gives_its_slot_and_blocks_back():
    b = ContinuousBatcher(cfg32(), None, seed=0, num_blocks=64,
                          block_size=BS, slots=2, max_seq=64,
                          prefill_chunk=2)
    free = b.pool.free_count()
    req = b.submit(list(map(int, tokens(30, 43))), max_new_tokens=4,
                   sampling=SamplingParams.greedy(), seed=0)
    b.step()
    assert req._held_slot is not None and b._holds
    b.cancel(req) if hasattr(b, "cancel") else setattr(req, "_cancelled",
                                                       True)
    while b.inflight():
        b.step()
    assert not b._holds and b.pool.free_count() == free


def test_a_model_without_a_ring_registers_zeros():
    b = ContinuousBatcher(get_config("tiny-afmoe").replace(dtype="float32"),
                          None, num_blocks=16, block_size=BS, slots=2,
                          max_seq=32)
    snap = b.metrics.snapshot()
    assert snap["gauges"]["batcher_kv_ring_bytes_per_slot"] == 0
    for name in ("batcher_decode_ring_positions", "batcher_moe_rows_away",
                 "batcher_moe_experts_held"):
        assert snap["counters"][name] == 0
    assert b.paged.ring_k is None and b.paged.ring_bytes_per_slot == 0


def test_the_wave_bounds_come_from_the_configuration():
    """Bytes, not elements: 64 query heads take half the score elements
    a head that 32 take, and a model with a ring has the token bound of
    its widest per-token transient; the sized models keep theirs."""
    mimo = get_config("mimo-v2.5")
    assert batcher_mod._wave_score_budget(mimo) \
        == batcher_mod.WAVE_SCORE_BUDGET / 2
    assert batcher_mod._wave_token_budget(mimo) == 6826
    for name in ("mistral-7b", "kanana-2-30b-a3b", "trinity-mini",
                 "ouro-2.6b", "falcon-h1-34b"):
        cfg = get_config(name)
        assert batcher_mod._wave_score_budget(cfg) \
            == batcher_mod.WAVE_SCORE_BUDGET, name
    assert batcher_mod._wave_token_budget(get_config("falcon-h1-34b")) == 4746
    assert batcher_mod._wave_token_budget(get_config("trinity-mini")) \
        == float("inf")

    def rows(t):   # the widest wave of tails t that one program carries
        b = object.__new__(ContinuousBatcher)
        b.cfg, b.block_size = mimo, 16
        b.mesh_spec = types.SimpleNamespace(pp=1)
        b._wave_token_budget = batcher_mod._wave_token_budget(mimo)
        n = 1
        while not b._past_score_budget(
                [{"t": t, "pb": 1}] * n, {"t": t, "pb": 1}):
            n += 1
        return n
    assert [rows(t) for t in (2048, 1024, 512, 256)] == [2, 4, 8, 16]


@pytest.mark.parametrize("kw,env,match", [
    ({"speculative": "ngram"}, {}, "speculative"),
    ({"mesh_spec": "tp2"}, {}, "mesh of more than one device"),
    ({"kv_host_mb": 64}, {}, "host arena"),
    ({}, {"DLI_KV_HOST_MB": "64"}, "host arena"),
    ({"cfg": {"kv_quant": "int8"}}, {}, "kv_quant"),
])
def test_what_carries_no_ring_is_refused_by_name(kw, env, match,
                                                 monkeypatch):
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    for key, val in env.items():
        monkeypatch.setenv(key, val)
    kw = dict(kw)
    cfg = cfg32(**kw.pop("cfg", {}))
    if kw.get("mesh_spec") == "tp2":
        kw["mesh_spec"] = MeshSpec(tp=2)
    with pytest.raises(ValueError, match=match):
        ContinuousBatcher(cfg, None, num_blocks=32, block_size=BS, slots=2,
                          max_seq=64, **kw)


def test_the_paths_without_a_ring_refuse_by_name(params):
    cfg = cfg32()
    paged = init_paged_cache(cfg, 9, BS, dtype=jnp.float32, slots=2)
    z = jnp.zeros((2,), jnp.int32)
    bt = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="window ring"):
        transformer.paged_decode_step(params, cfg, z, paged, bt, z)
    with pytest.raises(NotImplementedError, match="window ring"):
        transformer.paged_speculative_chunk(
            params, cfg, 2, 2, z, jnp.zeros((2, 8), jnp.int32), paged, bt,
            z, z, z, jnp.ones((2,)), z, jnp.ones((2,)),
            jnp.zeros((2,), bool), z, z, 0)
    b = ContinuousBatcher(cfg, None, num_blocks=32, block_size=BS, slots=2,
                          max_seq=64)
    with pytest.raises(ValueError, match="ring"):
        b.migrate_out(types.SimpleNamespace())


def test_the_engine_serves_both_kinds_from_a_dense_cache(params):
    """The single-stream engine's cache is full-length for every layer,
    as wide as the wider kind: greedy tokens are the reference's."""
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)
    cfg = cfg32()
    eng = InferenceEngine(cfg, params)
    prompt = tokens(12, 50).tolist()
    out = eng.generate([prompt], max_new_tokens=6,
                       sampling=SamplingParams.greedy())
    got = list(out.tokens[0]) if hasattr(out, "tokens") else list(out[0])
    got = [int(t) for t in got][-6:]
    seq = prompt + got
    lg = ref_logits(cfg, params, np.asarray(seq[:-1], np.int32))
    assert got == np.argmax(lg[11:], axis=-1).tolist()


# ---- (e) the source's config and weight names ----------------------------

def hf_config(**kw):
    c = dict(
        model_type="mimo_v2", vocab_size=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=7, num_attention_heads=8, num_key_value_heads=2,
        head_dim=24, v_head_dim=16, swa_num_attention_heads=8,
        swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
        attention_value_scale=0.707, partial_rotary_factor=0.334,
        rope_theta=1e4, swa_rope_theta=1e2, sliding_window=8,
        sliding_window_size=8, hybrid_layer_pattern=[0, 1, 1, 1, 1, 1, 0],
        moe_layer_freq=[0, 1, 1, 1, 1, 1, 1],
        add_swa_attention_sink_bias=True,
        add_full_attention_sink_bias=False, attention_bias=False,
        attention_chunk_size=8, attention_projection_layout="fused_qkv",
        hidden_act="silu", layernorm_epsilon=1e-5,
        max_position_embeddings=256, n_routed_experts=32,
        n_shared_experts=None, num_experts_per_tok=4, n_group=1,
        topk_group=1, topk_method="noaux_tc", scoring_func="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=None,
        rope_scaling={"rope_type": "default", "type": "default"},
        tie_word_embeddings=False, name_or_path="tiny-mimo-v2")
    c.update(kw)
    return types.SimpleNamespace(**c)


def hf_state_dict(cfg, params):
    """The tree under the names convert.py assumes (a fused qkv_proj,
    torch's [out, in] weights)."""
    arch = mimo_v2_ref.arch_of(cfg)
    sd = {"model.embed_tokens.weight": params["embed"]["tokens"],
          "model.norm.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"]["w"].T}
    for i in range(cfg.num_layers):
        lp = mimo_v2_ref.layer_params(params, arch, i)
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = lp["attn_norm"]["scale"]
        sd[p + "post_attention_layernorm.weight"] = lp["mlp_norm"]["scale"]
        sd[p + "self_attn.qkv_proj.weight"] = jnp.concatenate(
            [lp[n]["w"] for n in "qkv"], axis=1).T
        sd[p + "self_attn.o_proj.weight"] = lp["o"]["w"].T
        if "sinks" in lp:
            sd[p + "self_attn.attention_sink_bias"] = lp["sinks"]
        if "experts" not in lp:
            for n in ("gate", "up", "down"):
                sd[p + f"mlp.{n}_proj.weight"] = lp[n]["w"].T
            continue
        sd[p + "mlp.gate.weight"] = lp["router"]["w"].T
        sd[p + "mlp.gate.e_score_correction_bias"] = lp["router"]["bias"]
        for e in range(cfg.num_experts):
            for n in ("gate", "up", "down"):
                sd[p + f"mlp.experts.{e}.{n}_proj.weight"] = \
                    lp["experts"][n]["w"][e].T
    return {k: np.asarray(v) for k, v in sd.items()}


def test_the_sources_config_and_state_dict_convert(params):
    cfg = convert.config_from_hf(hf_config())
    want = get_config("tiny-mimo-v2")
    for field in ("swa", "num_kv_heads", "head_dim", "v_head_dim",
                  "attn_value_scale", "rope_pct", "rope_theta",
                  "sliding_window", "num_experts", "num_experts_per_tok",
                  "moe_router", "dense_prefix_layers", "attn_sinks",
                  "moe_intermediate_size", "norm_eps"):
        assert getattr(cfg, field) == getattr(want, field), field
    back = convert.convert_state_dict(
        cfg.replace(dtype="float32"), hf_state_dict(cfg, params),
        dtype=jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(back),
                               jax.tree.leaves(params)))
    with pytest.raises(NotImplementedError, match="scoring_func"):
        convert.config_from_hf(hf_config(scoring_func="softmax"))


def test_the_registry_has_the_source_sizes():
    c = get_config("mimo-v2.5")
    assert (c.num_layers, c.hidden_size, c.num_heads, c.num_kv_heads,
            c.head_dim, c.v_head_dim, c.vocab_size, c.intermediate_size,
            c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
            c.sliding_window, c.rope_theta, c.swa.rope_theta,
            c.swa.num_kv_heads) == (
        48, 4096, 64, 4, 192, 128, 152576, 16384, 2048, 256, 8, 128, 1e7,
        1e4, 8)
    assert int(c.head_dim * c.rope_pct) == 64
    assert c.kind_layers("full") == (0, 5, 11, 17, 23, 29, 35, 41, 47)
    assert c.swa.sinks and not c.attn_sinks and c.dense_prefix_layers == 1
    assert c.slot_cache and not get_config("trinity-mini").slot_cache
    cut = c.replace(num_layers=7, swa={"pattern": (0, 1, 1, 1, 1, 1, 0)},
                    experts_held=(0, 16), vocab_size=19072)
    shapes = jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == 3_429_893_952 + 7 * 2 * 4096 + 4096   # ... and the norms
    paged = jax.eval_shape(lambda: init_paged_cache(cut, 8, 16, slots=2))
    assert paged.k.shape[-1] == 768 and paged.v.shape[-1] == 512
    assert paged.ring_k.shape == (5, 3, 128, 1, 1536)


# ---- (f) tracing ---------------------------------------------------------

def _profile_summary():
    path = Path(__file__).resolve().parents[1] / "scripts/profile_summary.py"
    spec = importlib.util.spec_from_file_location("profile_summary", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("op_name,scope", [
    ("jit(chunk)/while/body/attention/attention_swa/dot_general",
     "attention/attention_swa"),
    ("jit(chunk)/while/body/attention/attention_full/dot_general",
     "attention/attention_full"),
    ("jit(admit)/ring_write/scatter", "ring_write"),
])
def test_profile_summary_names_the_kinds_scopes(op_name, scope):
    assert _profile_summary().scope_of(op_name) == scope


def test_the_kinds_scopes_are_in_the_programs(params):
    cfg = cfg32()
    paged = init_paged_cache(cfg, 1 + R * MB, BS, dtype=jnp.float32,
                             slots=R)
    z = jnp.zeros((R,), jnp.int32)
    text = chunk_fn.lower(
        cfg, 2, params, z, paged, jnp.zeros((R, MB), jnp.int32), z, z, z,
        jnp.ones((R,)), z, jnp.ones((R,)), jnp.zeros((R,), bool), z + 2,
        z - 1).as_text(debug_info=True)
    for scope in ("attention/attention_swa", "attention/attention_full",
                  "ring_write", "kv_write", "kv_gather",
                  "moe_route", "moe_experts", "moe_combine"):
        assert scope in text, scope
