"""Falcon-H1 (a Mamba-2 state-space mixer beside the attention heads of
every block) at toy widths, `tiny-falcon-h1`: 2 layers, head_dim 24 !=
64 / 4, 2 groups, a scan chunk of 8, every multiplier away from 1. The
float32 reference is models/reference/falcon_h1_ref.py (the recurrence
token by token), which imports nothing from the package."""

import dataclasses
import functools
import importlib.util
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import convert, lora, transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.reference import falcon_h1_ref
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops import ssm
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    init_paged_cache)
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime import batcher as batcher_mod
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher
from distributed_llm_inferencing_tpu.utils import trace
from conftest import jitted

BS = 4
R = 3            # serving slots of the hand-driven pool; row R is the dummy
MB = 16          # blocks a slot's table holds
# float32 against float32 on the CPU: the two sum in another order (the
# chunked scan against the recurrence). The limit is on the largest
# error of a position's logits over the spread of the reference's
# logits; what is found is 1e-6..1e-5, the controls (a multiplier set to
# 1, D or the conv bias left out, a stale state) read 1e-2..1.
TOL = 1e-4


def cfg32(**kw):
    return get_config("tiny-falcon-h1").replace(dtype="float32",
                                                attn_backend="xla", **kw)


@pytest.fixture(scope="module")
def params():
    """Seeded random weights; the norms' scales, D and the conv bias too,
    or one left out would go unseen behind ones and zeros."""
    p = init_params(cfg32(), jax.random.PRNGKey(0), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def jitter(a):
        return a * (1.0 + 0.3 * jax.random.normal(next(keys), a.shape))
    lay = p["layers"]
    for name in ("attn_norm", "mlp_norm", "ssm_norm"):
        lay[name]["scale"] = jitter(lay[name]["scale"])
    lay["D"] = jitter(lay["D"])
    lay["conv"]["b"] = 0.3 * jax.random.normal(next(keys),
                                               lay["conv"]["b"].shape)
    p["final_norm"]["scale"] = jitter(p["final_norm"]["scale"])
    return p


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, n).astype(np.int32)


def ref_logits(cfg, params, toks, **controls):
    return np.asarray(falcon_h1_ref.forward(
        params, falcon_h1_ref.arch_of(cfg), jnp.asarray(toks), **controls))


def ref_states(cfg, params, toks):
    st, cw = falcon_h1_ref.final_states(
        params, falcon_h1_ref.arch_of(cfg), jnp.asarray(toks))
    return np.asarray(st), np.asarray(cw).reshape(cw.shape[0], -1)


def err(got, ref):
    return float(np.abs(np.asarray(got) - ref).max() / ref.std())


def dense_logits(cfg, params, toks, pad=0):
    cache = init_cache(cfg, 1, 64, dtype=jnp.float32)
    padded = np.concatenate([toks, np.zeros(pad, np.int32)])
    logits, cache = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(padded[None]), jnp.asarray([len(toks)]),
        cache)
    return np.asarray(logits[0, :len(toks)], np.float32), cache


# ---- (a) forward ---------------------------------------------------------

@pytest.mark.parametrize("n,pad", [(5, 0), (21, 0), (24, 0), (19, 13)])
def test_forward_matches_the_reference(params, n, pad):
    """Lengths that are no multiple of the scan's chunk of 8, and a
    right-padded prompt: the state and the window are those after the
    last real position."""
    cfg = cfg32()
    toks = tokens(n, seed=n)
    ref = ref_logits(cfg, params, toks)
    got, cache = dense_logits(cfg, params, toks, pad)
    assert err(got, ref) < TOL
    st, cw = ref_states(cfg, params, toks)
    assert cache.ssm.dtype == jnp.float32
    assert np.abs(np.asarray(cache.ssm[:, 0]) - st).max() < 1e-5
    assert np.abs(np.asarray(cache.conv[:, 0]) - cw).max() < 1e-5
    # then one decode step through the dense cache and the state
    cache = cache._replace(lengths=jnp.asarray([n]))
    nxt = int(np.argmax(ref[-1]))
    logits, _ = jitted(transformer.decode_step)(
        params, cfg, jnp.asarray([[nxt]]), cache)
    ref2 = ref_logits(cfg, params, np.append(toks, nxt))
    assert err(logits[0, 0], ref2[-1]) < TOL


MULTIPLIERS = [f.name for f in dataclasses.fields(cfg32().ssm)
               if "multiplier" in f.name]


@pytest.mark.parametrize("which", ["embed_scale", "logit_scale"]
                         + [f"{n}[{i}]" if n.endswith("multipliers") else n
                            for n in MULTIPLIERS
                            for i in range(len(getattr(cfg32().ssm, n))
                                           if n.endswith("multipliers")
                                           else 1)])
def test_a_multiplier_dropped_fails_the_tolerance(params, which):
    """Each of the fourteen multipliers set to 1 in the system alone."""
    cfg = cfg32()
    if which in ("embed_scale", "logit_scale"):
        bad = cfg.replace(**{which: 1.0})
    elif "[" in which:
        name, i = which[:-1].split("[")
        vals = list(getattr(cfg.ssm, name))
        vals[int(i)] = 1.0
        bad = cfg.replace(ssm=dataclasses.replace(cfg.ssm,
                                                  **{name: tuple(vals)}))
    else:
        bad = cfg.replace(ssm=dataclasses.replace(cfg.ssm, **{which: 1.0}))
    assert len(MULTIPLIERS) == 7
    toks = tokens(24, seed=1)
    ref = ref_logits(cfg, params, toks)
    assert err(dense_logits(cfg, params, toks)[0], ref) < TOL
    # the weakest (B's 0.9, dt's 0.75, the keys' 0.6) read 50 x TOL
    assert err(dense_logits(bad, params, toks)[0], ref) > 10 * TOL


@pytest.mark.parametrize("control", [{"skip_d": True}, {"conv_bias": False}])
def test_d_or_the_conv_bias_left_out_fails_the_tolerance(params, control):
    toks = tokens(24, seed=1)
    ref = ref_logits(cfg32(), params, toks)
    assert err(ref_logits(cfg32(), params, toks, **control), ref) > 100 * TOL


# ---- (b) the chunked scan against the recurrence --------------------------

@pytest.mark.parametrize("t,valid", [(1, 1), (7, 7), (8, 8), (19, 19),
                                     (24, 13), (16, 0)])
def test_the_chunked_scan_is_the_recurrence(t, valid):
    """scan_chunks at chunk 8 against the token loop, from a state that
    is not zero, positions past `valid` with dt = 0 (a padded tail
    bucket's): they advance nothing."""
    h, p, g, n, bsz = 4, 16, 2, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(ks[0], (bsz, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, h)))
    dt = jnp.where(jnp.arange(t)[None, :, None] < valid, dt, 0.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (bsz, t, g, n))
    c = jax.random.normal(ks[4], (bsz, t, g, n))
    s0 = jax.random.normal(ks[5], (bsz, h, p, n))
    with jax.default_matmul_precision("highest"):
        y, s = ssm.scan_chunks(x, dt, a, b, c, s0, 8)
    grp = np.arange(h) // (h // g)
    want_s, want_y = np.asarray(s0, np.float64), []
    for i in range(t):
        decay = np.exp(np.asarray(dt[:, i] * a, np.float64))
        want_s = decay[..., None, None] * want_s + (
            np.asarray(dt[:, i])[..., None] * np.asarray(x[:, i])
        )[..., None] * np.asarray(b[:, i])[:, grp][:, :, None, :]
        want_y.append(np.einsum("bhpn,bhn->bhp", want_s,
                                np.asarray(c[:, i])[:, grp]))
    assert np.abs(np.asarray(s) - want_s).max() < 1e-4
    want_y = np.stack(want_y, 1)
    if valid:
        assert np.abs(np.asarray(y)[:, :valid]
                      - want_y[:, :valid]).max() < 1e-4
    else:
        assert np.array_equal(np.asarray(s), np.asarray(s0))


# ---- (c) prefill then decode through the pool and the state, on logits ----

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
    """The pool and the state planes driven by hand: admission waves
    (transformer.paged_prefill_tail) and greedy decode chunks
    (decode_chunk_with_logits), each slot's tokens and the logits that
    chose them kept for the comparison."""

    def __init__(self, cfg, params, spoil=False):
        self.cfg, self.params = cfg, params
        self.paged = init_paged_cache(cfg, 1 + R * MB, BS,
                                      dtype=jnp.float32, slots=R)
        if spoil:   # whatever a slot held before
            self.paged = self.paged._replace(
                ssm=self.paged.ssm + 7.0, conv=self.paged.conv - 3.0)
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

    def check(self, slot, tol=TOL):
        """Every kept logit against the reference's full forward over
        the slot's own sequence, and the slot's state row and window
        against the reference's after the last position it consumed."""
        seq = np.asarray(self.seq[slot][:-1], np.int32)
        ref = ref_logits(self.cfg, self.params, seq)
        n0 = self.n0[slot]
        assert len(self.logits[slot]) == len(seq) - n0 + 1
        worst = max(err(got, ref[n0 - 1 + i])
                    for i, got in enumerate(self.logits[slot]))
        st, cw = ref_states(self.cfg, self.params, seq)
        worst_st = float(np.abs(np.asarray(self.paged.ssm[:, slot]) - st)
                         .max() / st.std())
        assert np.abs(np.asarray(self.paged.conv[:, slot]) - cw).max() < 1e-4
        assert worst < tol and worst_st < tol, (worst, worst_st)
        return worst


def test_slots_that_join_at_different_times_match_the_reference(
        params, monkeypatch):
    """Two prompts in one wave (a padded tail bucket: 9 and 14 of 16),
    decode, a third joins in a wave with a padded row while the others
    are mid-way, decode chunks of 4 through the side buffers and
    the state planes, every slot from planes that held something else
    before: logits and the timed state against the reference. The pool
    is read by the in-loop gather under the ladder's switch, as the
    chip's cell reads it."""
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    sim = Sim(cfg32(), params, spoil=True)
    sim.start([(0, tokens(9, 1)), (2, tokens(14, 2))], 16, 2)
    dummy = np.asarray(sim.paged.ssm[:, R]).copy()
    sim.decode(4, [4, 0, 4])
    assert np.array_equal(np.asarray(sim.paged.ssm[:, R]), dummy)
    sim.start([(1, tokens(6, 3))], 16, 2)       # one real row, one padded
    sim.decode(4, [4, 4, 4])
    sim.decode(4, [1, 3, 2])
    for slot in range(R):
        sim.check(slot)


def test_a_reused_slot_starts_from_zero(params):
    """A second request in a slot that a first one left its state in."""
    sim = Sim(cfg32(), params)
    sim.start([(1, tokens(11, 4))], 16, 2)
    sim.decode(4, [0, 4, 0])
    sim.check(1)
    left = np.asarray(sim.paged.ssm[:, 1]).copy()
    sim.seq[1] = None
    sim.start([(1, tokens(7, 5))], 16, 2)
    assert np.abs(left).max() > 1e-3
    sim.decode(4, [0, 4, 0])
    sim.check(1)


def test_a_stale_state_fails_the_tolerance(params):
    """The control of the test above: the same second request over the
    first one's state (its first chunk passed off as a later one)."""
    sim = Sim(cfg32(), params, spoil=True)
    toks = tokens(12, 6)
    ref = ref_logits(cfg32(), params, toks)
    # positions 8.. as a later chunk of a prompt whose first chunk never
    # ran: K and V of positions 0..7 are zeros, the state what was there
    last = sim.admit([(0, toks[8:], 8)], 4, 1)
    assert err(last[0], ref[-1]) > 100 * TOL


def test_padded_rows_and_padded_positions_advance_no_live_state(params):
    """A wave's padding row writes the dummy row alone; positions past
    tail_len in the bucket leave the state where the last real position
    left it (the same prompt in a bucket of 8 and of 32)."""
    cfg = cfg32()
    sim = Sim(cfg, params)
    sim.start([(0, tokens(6, 7))], 8, 1)
    before = [np.asarray(p).copy() for p in (sim.paged.ssm, sim.paged.conv)]
    sim.start([(2, tokens(6, 7))], 32, 2)       # and a padding row
    after = [np.asarray(p) for p in (sim.paged.ssm, sim.paged.conv)]
    for b4, af in zip(before, after):
        assert np.array_equal(b4[:, :2], af[:, :2])      # slots 0 and 1
        assert np.abs(af[:, 2] - b4[:, 0]).max() < 1e-5  # bucket 32 == 8
    sim.check(0)
    sim.check(2)


def test_a_slot_that_ends_inside_a_chunk_stops_its_state(params):
    """Budget 2 of a chunk of 4: the state is the one after its second
    pass, and a chunk later finds it there (the dead passes wrote
    nothing, and the slot's neighbour went on)."""
    sim = Sim(cfg32(), params)
    sim.start([(0, tokens(10, 8)), (1, tokens(5, 9))], 16, 2)
    sim.decode(4, [2, 4, 0])
    sim.check(0)
    sim.check(1)
    sim.decode(4, [4, 0, 0])
    sim.check(0)


def test_a_prompt_in_chunks_goes_on_from_its_slots_state(params):
    """A prompt of 22 as chunks of 8, 8 and 6, the later ones over the
    earlier ones' blocks and from the slot's state."""
    sim = Sim(cfg32(), params, spoil=True)
    toks = tokens(22, 10)
    sim.admit([(1, toks[:8], 0)], 8, 1)
    sim.admit([(1, toks[8:16], 8)], 8, 1)
    last = sim.admit([(1, toks[16:], 16)], 8, 1)
    sim.seq[1] = toks.tolist() + [int(np.argmax(last[0]))]
    sim.n0[1], sim.cl[1], sim.logits[1] = 22, 22, [last[0]]
    sim.decode(4, [0, 4, 0])
    sim.check(1)


@pytest.mark.parametrize("form", ["in-loop", "kernel"])
def test_flat_rows_serve_what_the_heads_axis_serves(form, monkeypatch):
    """tiny-falcon-h1 with falcon-h1-34b's head shape at 2 K/V heads (10
    query heads of 128: 5 a K/V head, no power of two), the state planes
    beside the pool: a one-device pool stores a position's heads side by
    side (rows of 256; ops/paged_kvcache.heads_in_rows), a mesh's keeps
    them as an axis. conftest.flat_rows_scenario over both (a wave, a
    chunked prompt's next chunk from its slot's state, a decode chunk)
    by the in-loop gather and by the kernel, interpreted, which reads
    the flat rows by p @ V a K/V head at a time (5 rows of p) where it
    read the heads' own rows behind a mask: the same tokens, logits,
    planes and states to a float32's rounding."""
    from conftest import (
        assert_flat_rows_serve_the_same, flat_rows_scenario)
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    cfg = cfg32(num_heads=10, head_dim=128,
                pool_kernel="pallas_interpret" if form == "kernel" else "xla")
    p = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    flat, by_heads = (flat_rows_scenario(p, cfg, devices)
                      for devices in (1, 4))
    assert_flat_rows_serve_the_same(flat, by_heads)


def test_the_step_kernel_is_the_numpy_form(params):
    """ops/pallas/ssm_step.py, interpreted, against the jax.numpy update
    at a state of whole tiles (d_state 128): a prompt, then chunks of 4
    in which one slot ends early and one is dead: logits, the state
    plane (the dead rows and the dummy row bit for bit) and the tokens."""
    cfg = cfg32(ssm=dataclasses.replace(cfg32().ssm, d_state=128))
    wide = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    sims = [Sim(cfg.replace(pool_kernel=kind), wide, spoil=True)
            for kind in ("xla", "pallas_interpret")]
    assert transformer._ssm_kernel(sims[0].cfg, sims[0].paged) is None
    assert transformer._ssm_kernel(sims[1].cfg, sims[1].paged) \
        == "pallas_interpret"
    # the toy preset's 16 x 16 state is no whole tile: the numpy form
    assert transformer._ssm_kernel(
        cfg32(pool_kernel="pallas_interpret"),
        Sim(cfg32(), params).paged) is None
    for sim in sims:
        sim.start([(0, tokens(9, 1)), (2, tokens(14, 2))], 16, 2)
        sim.decode(4, [4, 0, 2])
        sim.decode(4, [4, 0, 4])
    a, b = sims
    assert a.seq == b.seq
    for got, want in zip(a.logits[0] + a.logits[2],
                         b.logits[0] + b.logits[2]):
        assert err(got, want) < 1e-5
    sa, sb = np.asarray(a.paged.ssm), np.asarray(b.paged.ssm)
    assert np.abs(sa - sb).max() < 1e-5 * np.abs(sa).max()
    assert np.array_equal(sa[:, [1, R]], sb[:, [1, R]])
    b.check(0)
    b.check(2)


# ---- (d) the batcher ------------------------------------------------------

def serve(cfg, prompts, new=10, cap=8, blocks=128, slots=4, **kw):
    b = ContinuousBatcher(cfg, None, seed=0, slots=slots, num_blocks=blocks,
                          block_size=BS, max_seq=128, prefill_chunk=4,
                          decode_chunk_cap=cap, **kw)
    greedy = SamplingParams.greedy()
    reqs = [b.submit(p, max_new_tokens=new, sampling=greedy, seed=0)
            for p in prompts]
    while b.inflight():
        b.step()
    return b, reqs


def served_right(b, reqs, new):
    for r in reqs:
        assert r.error is None and len(r.tokens) == new
        ref = ref_logits(b.cfg, b.params, (r.prompt + r.tokens)[:-1])
        assert np.argmax(ref[len(r.prompt) - 1:], -1).tolist() == r.tokens


def test_the_batcher_serves_what_the_reference_computes(monkeypatch):
    """submit -> waves, a prompt longer than prefill_chunk (16 tokens: it
    takes its slot at its first chunk and keeps it), six requests over
    four slots (slots reused), decode chunks of 8 over the in-loop
    gather: greedy tokens are the reference's argmax everywhere; the
    gauge, the counters and the spans' attribute say what a slot holds."""
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    cfg = cfg32()
    prompts = [tokens(n, seed=n).tolist() for n in (9, 37, 13, 21, 12, 10)]
    b, reqs = serve(cfg, prompts, new=9)
    served_right(b, reqs, 9)
    assert not b._holds and b.pool.free_count() == 128
    c, g = (b.metrics.snapshot()[k] for k in ("counters", "gauges"))
    # 2 layers x (4 x 16 x 16 float32 + 3 x (64 + 2 x 2 x 16) float32)
    per_slot = 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert g["batcher_ssm_state_bytes_per_slot"] == per_slot
    assert c["batcher_ssm_scan_positions"] == sum(map(len, prompts))
    # every emitted token but a request's first came from a live pass
    assert c["batcher_ssm_step_slot_passes"] == 6 * 8
    assert b._chunked_admissions == 2 + 1    # 37 = 16 + 16 + 5, 21 = 16 + 5
    for name in ("batcher.decode_chunk", "batcher.admit_wave"):
        last = [s for s in trace.get_tracer().spans() if s.name == name][-1]
        assert last.attrs["ssm_state_bytes_per_slot"] == per_slot


def test_the_same_prompt_twice_hits_no_prefix():
    """K and V without the state at their end are of no use: nothing is
    matched in the radix cache and nothing inserted."""
    prompt = tokens(15, seed=3).tolist()
    b, reqs = serve(cfg32(), [prompt, prompt[:12] + [9, 9]], slots=1, new=9)
    served_right(b, reqs, 9)
    c = b.metrics.snapshot()["counters"]
    assert c["prefill_cached_tokens"] == 0
    assert c["prefill_uncached_tokens"] == 15 + 14
    assert b.pool.stats()["prefix_hits"] == 0


def test_a_preempted_request_is_prefilled_again_from_its_first_token():
    """A pool too small for both: the younger is preempted while
    decoding, keeps nothing, and resumes with the reference's tokens."""
    prompts = [tokens(n, seed=n).tolist() for n in (14, 13)]
    b, reqs = serve(cfg32(), prompts, new=17, blocks=13, slots=2)
    assert b.metrics.snapshot()["counters"]["batcher_preemptions"] >= 1
    served_right(b, reqs, 17)
    assert b.pool.free_count() == 13 and not b._holds


def test_a_cancelled_chunked_prompt_gives_its_slot_and_blocks_back():
    b = ContinuousBatcher(cfg32(), None, seed=0, slots=2, num_blocks=64,
                          block_size=BS, max_seq=128, prefill_chunk=4)
    req = b.submit(tokens(40, 1).tolist(), max_new_tokens=4,
                   sampling=SamplingParams.greedy())
    b.step()
    assert b._holds == {0} and req._held_slot == 0 and len(req._blocks) == 4
    req.cancel()
    b.step()
    assert req.error == "cancelled" and not b._holds
    assert b.pool.free_count() == 64


def test_a_model_without_state_layers_registers_zeros():
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    b = ContinuousBatcher(cfg, None, slots=2, num_blocks=16, block_size=BS,
                          max_seq=32, kv_host_mb=0)
    snap = b.metrics.snapshot()
    assert snap["gauges"]["batcher_ssm_state_bytes_per_slot"] == 0
    assert snap["counters"]["batcher_ssm_scan_positions"] == 0
    assert snap["counters"]["batcher_ssm_step_slot_passes"] == 0
    assert b.paged.ssm is None and b._wave_token_budget == float("inf")
    assert len(b.paged.planes()) == 2 and not b._holds


def test_the_wave_bound_comes_from_the_configuration():
    """Falcon-H1-34B's 129 KB of MLP rows a token: 4,096 tokens a wave
    as bucketed; a model without state layers is cut by the score
    budget alone, as it was."""
    big = get_config("falcon-h1-34b")
    budget = batcher_mod._wave_token_budget(big)
    assert 4096 <= budget < 8192
    assert batcher_mod._wave_token_budget(get_config("mistral-7b")) \
        == float("inf")
    b = ContinuousBatcher(cfg32(), None, slots=4, num_blocks=64,
                          block_size=BS, max_seq=64)
    b._wave_token_budget = 48
    wave = [{"t": 16, "pb": 1}] * 3
    assert not b._past_score_budget(wave[:1], wave[0])    # 2 rows x 16
    assert b._past_score_budget(wave, wave[0])            # 4 rows x 16
    assert not b._past_score_budget([], {"t": 128, "pb": 1})   # alone


@pytest.mark.parametrize("kw,env,match", [
    ({"speculative": "ngram"}, {}, "speculative decoding"),
    ({"kv_host_mb": 8}, {}, "kv_host_mb > 0"),
    ({}, {"DLI_KV_HOST_MB": "8"}, "the host arena"),
    ({"cfg_kw": {"kv_quant": "int8"}}, {}, "kv_quant"),
    ({"mesh": {"pp": 2}}, {}, "pp > 1 or any mesh"),
    ({"mesh": {"tp": 2}}, {}, "more than one device"),
])
def test_what_does_not_carry_a_state_is_refused_by_name(kw, env, match,
                                                        monkeypatch):
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    kw = dict(kw)
    cfg = cfg32(**kw.pop("cfg_kw", {}))
    if "mesh" in kw:
        kw["mesh_spec"] = MeshSpec(**kw.pop("mesh"))
    with pytest.raises(ValueError, match=match):
        ContinuousBatcher(cfg, None, slots=2, num_blocks=16,
                          block_size=BS, max_seq=32, **kw)


@pytest.mark.parametrize("asked,env", [
    ("auto", {"DLI_ATTENTION": "pallas"}),
    ("pallas", {}),
])
def test_a_request_for_pallas_attention_serves_as_auto_does(asked, env,
                                                            monkeypatch):
    from conftest import served_as_under_auto
    served_as_under_auto(lambda attn_backend: ContinuousBatcher(
        cfg32().replace(attn_backend=attn_backend), None, slots=2,
        num_blocks=16, block_size=BS, max_seq=32),
        asked, env, monkeypatch)


def test_the_paths_without_a_state_refuse_by_name(params):
    cfg = cfg32()
    with pytest.raises(NotImplementedError, match="speculative"):
        transformer.paged_speculative_chunk(
            params, cfg, 1, 2, *([None] * 14), 0)
    with pytest.raises(NotImplementedError, match="paged_decode_step"):
        transformer.paged_decode_step(params, cfg, *([None] * 4))
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine)
    with pytest.raises(ValueError, match="continuous batcher"):
        InferenceEngine(cfg, params)
    b = ContinuousBatcher(cfg, None, slots=2, num_blocks=16, block_size=BS,
                          max_seq=32)
    req = b.submit([5, 6, 7], max_new_tokens=2)
    with pytest.raises(ValueError, match="migrate_out"):
        b.migrate_out(req)
    # LoRA names attention's and the MLP's projections; the mixer's are
    # no target
    assert "in_proj" not in lora.lora_targets(cfg)
    with pytest.raises(ValueError, match="unknown LoRA target"):
        lora.synthesize(cfg, "a", rank=2, targets=("in_proj",))


# ---- (e) the source's names through convert.py ---------------------------

def hf_config(**kw):
    c = cfg32().ssm
    base = dict(
        model_type="falcon_h1", name_or_path="tiny-falcon-h1",
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24, max_position_embeddings=256, rms_norm_eps=1e-5,
        hidden_act="silu", rope_theta=1e6, rope_scaling=None,
        attention_bias=False, mlp_bias=False, projectors_bias=False,
        attn_layer_indices=None, tie_word_embeddings=False,
        mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
        mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
        mamba_expand=2, mamba_conv_bias=True, mamba_proj_bias=False,
        mamba_rms_norm=True, mamba_norm_before_gate=False,
        embedding_multiplier=2.5, lm_head_multiplier=0.4,
        attention_in_multiplier=c.attn_in_multiplier,
        attention_out_multiplier=c.attn_out_multiplier,
        key_multiplier=c.key_multiplier,
        mlp_multipliers=list(c.mlp_multipliers),
        ssm_in_multiplier=c.in_multiplier,
        ssm_out_multiplier=c.out_multiplier,
        ssm_multipliers=list(c.multipliers))
    return types.SimpleNamespace(**{**base, **kw})


def hf_state_dict(cfg, params):
    """The tree under the source's names (modeling_falcon_h1.py), linear
    weights transposed to torch's [out, in], the filter to [C, 1, K]."""
    sd = {"model.embed_tokens.weight": params["embed"]["tokens"],
          "model.final_layernorm.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"]["w"].T}
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = lp["attn_norm"]["scale"]
        sd[p + "pre_ff_layernorm.weight"] = lp["mlp_norm"]["scale"]
        for nm in ("q", "k", "v", "o"):
            sd[p + f"self_attn.{nm}_proj.weight"] = lp[nm]["w"].T
        for nm in ("gate", "up", "down"):
            sd[p + f"feed_forward.{nm}_proj.weight"] = lp[nm]["w"].T
        sd[p + "mamba.in_proj.weight"] = lp["in_proj"]["w"].T
        sd[p + "mamba.out_proj.weight"] = lp["out_proj"]["w"].T
        sd[p + "mamba.conv1d.weight"] = lp["conv"]["w"].T[:, None, :]
        sd[p + "mamba.conv1d.bias"] = lp["conv"]["b"]
        sd[p + "mamba.norm.weight"] = lp["ssm_norm"]["scale"]
        for nm in ("A_log", "D", "dt_bias"):
            sd[p + "mamba." + nm] = lp[nm]
    return {k: np.asarray(v) for k, v in sd.items()}


def test_the_sources_config_and_state_dict_convert(params):
    cfg = cfg32()
    got_cfg = convert.config_from_hf(hf_config())
    assert got_cfg.replace(dtype="float32", attn_backend="xla") == cfg
    got = convert.convert_state_dict(got_cfg, hf_state_dict(cfg, params),
                                     dtype=jnp.float32)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    toks = tokens(20, seed=8)
    ref = ref_logits(got_cfg, got, toks)
    assert err(dense_logits(cfg, got, toks)[0], ref) < TOL
    assert err(ref, ref_logits(cfg, params, toks)) < 1e-6
    for key, value in (("mamba_norm_before_gate", True),
                       ("mamba_rms_norm", False), ("mamba_proj_bias", True),
                       ("attn_layer_indices", [0])):
        with pytest.raises(NotImplementedError, match=key):
            convert.config_from_hf(hf_config(**{key: value}))


def test_transformers_own_falcon_h1_gives_the_same_logits():
    """A random FalconH1ForCausalLM of the installed transformers (its
    torch_forward: the chunked SSD scan) through convert.py: its logits
    are the reference's and the system's. No published weights."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    if not hasattr(tf, "FalconH1ForCausalLM"):
        pytest.skip("the installed transformers has no falcon_h1")
    keys = {k: v for k, v in vars(hf_config()).items()
            if k not in ("model_type", "name_or_path", "attn_layer_indices")}
    torch.manual_seed(0)
    model = tf.FalconH1ForCausalLM(tf.FalconH1Config(**keys)).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith(("conv1d.bias", ".D", "norm.weight")):
                p.add_(torch.randn_like(p) * 0.2)
        toks = tokens(21, seed=9)
        want = model(torch.tensor(toks[None].astype(np.int64))) \
            .logits[0].numpy()
    cfg, got = convert.load_hf_model(model, dtype=jnp.float32)
    cfg = cfg.replace(dtype="float32", attn_backend="xla")
    assert cfg == cfg32().replace(name=cfg.name)
    assert err(ref_logits(cfg, got, toks), want) < TOL
    assert err(dense_logits(cfg, got, toks)[0], want) < TOL


# ---- (f) sizes, and the operator's table ---------------------------------

def test_the_registry_has_the_source_sizes():
    cfg = get_config("falcon-h1-34b")
    c = cfg.ssm
    assert (cfg.num_layers, cfg.hidden_size, cfg.intermediate_size,
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size,
            cfg.rope_theta, cfg.norm_eps, cfg.max_position_embeddings) == (
        72, 5120, 21504, 20, 4, 128, 261120, 1e11, 1e-5, 262144)
    assert (c.d_ssm, c.n_heads, c.d_head, c.d_state, c.n_groups, c.d_conv,
            c.chunk_size, c.conv_dim, c.proj_dim) == (
        4096, 32, 128, 256, 2, 4, 128, 5120, 9248)
    assert cfg.embed_scale == 5.656854249492381
    assert cfg.logit_scale == 0.0078125 and not cfg.tie_word_embeddings
    # a layer's parameters, from the leaves init_params makes
    shapes = jax.eval_shape(
        lambda: init_params(cfg.replace(num_layers=1, vocab_size=8),
                            jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes["layers"])) \
        == 430_120_032
    # float32 state + bf16 conv window of one slot over 6 layers
    assert 6 * (c.state_elems * 4 + c.conv_elems * 2) == 25_350_144
    # the checkpoint round trip gives the nested dataclass back
    assert type(cfg)(**dataclasses.asdict(cfg)) == cfg


def test_the_published_initial_values(params):
    p = init_params(cfg32(), jax.random.PRNGKey(3), dtype=jnp.float32)
    lay = p["layers"]
    assert np.allclose(np.exp(lay["A_log"][0]), [1, 2, 3, 4])
    assert np.array_equal(lay["D"], np.ones((2, 4)))
    dt = np.asarray(jax.nn.softplus(lay["dt_bias"]))
    assert (dt >= 1e-3 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    assert np.abs(lay["conv"]["w"]).max() <= 0.5


@pytest.mark.parametrize("op_name,scope", [
    ("jit(admit)/while/body/ssm_scan/while/body/dot_general", "ssm_scan"),
    ("jit(chunk)/while/body/while/body/ssm_step/mul", "ssm_step"),
    ("jit(chunk)/while/body/while/body/ssm_in_proj/dot_general",
     "ssm_in_proj"),
    ("jit(admit)/while/body/ssm_conv/add", "ssm_conv"),
    ("jit(chunk)/while/body/while/body/ssm_gate_norm/rsqrt",
     "ssm_gate_norm"),
    ("jit(chunk)/while/body/while/body/ssm_out_proj/dot_general",
     "ssm_out_proj"),
])
def test_profile_summary_names_the_mixers_scopes(op_name, scope):
    spec = importlib.util.spec_from_file_location(
        "profile_summary", Path(__file__).resolve().parents[1]
        / "scripts" / "profile_summary.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.scope_of(op_name) == scope


def test_the_mixers_scopes_are_in_the_programs(params):
    """The lowered admit and chunk programs carry the six scopes."""
    cfg = cfg32()
    sim = Sim(cfg, params)
    z = jnp.zeros((R,), jnp.int32)
    text = jax.jit(lambda pg: transformer.decode_chunk_with_logits(
        params, cfg, 1, z, pg, jnp.zeros((R, MB), jnp.int32), z, z, z,
        jnp.ones((R,)), z, jnp.ones((R,)), jnp.zeros((R,), bool), z + 1,
        z - 1, 0)).lower(sim.paged).as_text(debug_info=True)
    for scope in ("ssm_in_proj", "ssm_conv", "ssm_step", "ssm_gate_norm",
                  "ssm_out_proj"):
        assert scope in text, scope
    b, t = 1, 8
    text = jax.jit(lambda pg: transformer.paged_prefill_tail(
        params, cfg, jnp.zeros((b, t), jnp.int32), jnp.ones((b,), jnp.int32),
        jnp.zeros((b, 2), jnp.int32), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b,), jnp.int32), pg, slots=jnp.zeros((b,), jnp.int32))
    ).lower(sim.paged).as_text(debug_info=True)
    assert "ssm_scan" in text and "ssm_conv" in text
