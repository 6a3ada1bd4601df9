#!/usr/bin/env python3
"""Hold a looped cell's serving programs (Ouro: the layer stack run
`total_ut_steps` times a token, a K and V plane a (step, layer) pair) to
the plain float32 reference (reference/ouro_ref.py) at the cell's own
widths, pool and context lengths, on the pool's planes and on logits.

    python3 benchmarks/chip/compare_reference_loop.py [--config NAME|PATH]
        [--seed N] [--steps 376] [--wave 4]

Builds the configuration's batcher (run.build_batcher: the cell's weights,
pool and mesh) and serves one prompt a slot, lengths as the cell's mix
draws them (65 to 256; the longest in the last slot, whose block table
ends in the pool's last block), two ways from the same weights:

- the TIMED programs, as the window runs them: the prompts through the
  batcher's admit program in waves of --wave rows a tail bucket
  (`_run_admit`), then --steps decode steps in chunks of the cell's
  largest size (`_run_decode`, every slot live, greedy), to contexts of
  prompt + steps: the last slot's reaches max_seq - 8. They return
  tokens, not logits.
- a LOGITS path through the SAME pool (at 1.5 MiB a token the pool is
  8 GB of the chip's 16 and cannot be copied): `paged_prefill_tail`
  jitted here at the timed wave's shape so that it returns logits, then
  `transformer.decode_chunk_with_logits` at k = 1 (the timed chunk's own
  code, which drops the logits: the ladder's switch, the in-loop gather
  by (plane, block), the side buffer, the one write), fed the tokens the
  timed chunks chose. It writes every position again; a sample of the
  timed pool's rows is kept first.

Three comparisons, each with its limits and a control that must fail
them:

1. THE TIMED POOL against the reference's K and V, a (step, layer) pair
   at a time (POOL below): the sampled rows of every one of the 192
   planes, as the admit programs and the chunks of 8 left them, against
   `ouro_ref.keys_values` at the same positions of the reference's full
   forward pass; a row's relative difference, the median over a loop
   step's planes. This is where the measure has power: a loop step's
   planes carry the error of the layers before them alone, so the first
   step's read hundredths where the logits read a fifth, and a fault in
   a step (a norm, the rotation, the plane's index) shows in that step's
   planes at once. Controls: the same rows against the reference's
   planes of the step BEFORE (planes told apart by step), and the
   int8-rounded run's pool.
2. THE TIMED PROGRAMS against the logits path (TIE below): the sampled
   rows must agree and the timed tokens must be the logits path's argmax
   at seven eighths of the positions or more. Control: the last timed
   chunk run again with every slot's block table rolled by one slot,
   whose tokens must fail that share.
3. THE LOGITS PATH against the reference's logits (LIMITS below) for
   every slot: the prompt's last position and every decode step, after
   4 x 48 layer applications. Controls: int8-rounded weights, and the
   reference a loop step short.

The reference is a full forward pass (no cache, every position under the
causal mask, a jitted layer at a time).

Error of a position, the phases' quantiles: compare_reference.py's
docstring. This model is dense, so there are no positions of a second
kind (no expert choice to flip): the error is smooth, and the median is
held in both phases.

Exit code 0 if every reading is under its limit AND every control is
over one; and the last slot's last block, where plane 191's rows lie at
94 % of 2^31 elements, must hold what was written (its steps' error is
held to the decode limit on its own, and its rows must not be zero).
Last stdout line: JSON, also appended to
chiprun_out/compare_reference.json.
Off a TPU it fails, unless the configuration file says `"rehearsal":
true`.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "reference"))
sys.path.insert(2, str(ROOT))

import numpy as np                      # noqa: E402

import compare_reference as base        # noqa: E402
import run as harness                   # noqa: E402

# Limits on a phase's quantiles of the per-position error, with their
# reasons. Readings on the v5e at published widths, 48 layers x 4 steps,
# contexts to 632, seeds 0, 1, 2 (my chip runs, PR 39; PERF.md section 6):
# the system in bf16 (weights as stored; the residual stream, every norm's
# and projection's output, K and V rounded to bf16; float32 accumulation,
# softmax and norm statistics) reads p50 0.207-0.248, p90 0.259-0.290 and
# max 0.277-0.318; the same with every linear weight rounded to int8 reads
# p50 0.552-0.601, p90 0.599-0.722 and max 0.635-0.733; the reference
# stopped a step short reads p50 1.157-1.180. That is ten times what
# kanana's 7 layers (0.014) and trinity's 5 (0.009) read, and it is the
# depth: with random weights and norm scales of one the residual stream
# grows to an rms near 10 over a pass's 96 adds, each add is rounded to 8
# bits, and a pass's error goes through every later pass whole. The same
# model at hidden 512 on the CPU reads 0.014, 0.021, 0.051, 0.169 and
# 0.358 at 12, 24, 48, 96 and 192 layer applications (PERF.md section 6).
# The limits sit between the bf16 and the int8 readings, about 1.45 times
# from either: p50 0.36 (0.248 below, 0.552 above), p90 0.42 (0.290,
# 0.599). `max` (bf16 0.318, int8 0.635) is held at 0.45: no expert
# choice can flip in a dense model, so there is no position of a second
# kind. These limits have little power on their own (a fault that adds
# 0.2 passes them): POOL below holds each loop step's planes, and is
# where a fault in one step shows.
LIMITS = {"p50": 0.36, "p90": 0.42, "max": 0.45}
# The timed pool against the reference's K and V: the median over a loop
# step's planes (K and V, 48 planes, every sampled row: 604 a plane) of a
# row's relative difference, a limit a loop step. Read on the v5e, seed 4
# (my chip run, PR 39; PERF.md section 6): the timed pool 0.0157, 0.0329,
# 0.0837, 0.2236 for steps 0-3 (a step's planes carry the roundings of
# the layers before them: the last step's read what the logits read, the
# first step's a fourteenth of it); the pool of the int8-rounded run
# 0.0508, 0.1147, 0.2518, 0.5616; the reference's planes of the step
# before 1.26, 1.21, 1.18 (unrelated rows). Each limit is the geometric
# mean of the bf16 and the int8 reading, 1.6 to 1.9 times from either:
# a fault that adds 0.03 in the first step's planes, 0.06 in the
# second's or 0.12 in the third's fails, where the logits' limits let
# 0.2 pass; in the last step's planes the room is the logits' own (what
# adds under 0.27 there passes), and what takes one step's planes for
# another's reads 1.2 at every step.
POOL = {"rows_rel_diff_p50_by_step": [0.028, 0.061, 0.145, 0.35]}
# The timed programs against the logits path (both bf16, other orders of
# summation: a chunk of 8 reads its own tokens from the side buffer, the
# chunks of 1 from the pool). The first loop step's planes agree to a
# bf16 rounding (median relative difference under 1 %, as in
# compare_reference.py: read 0.0, seeds 0-3); every later step's rows
# inherit the earlier steps' differences through 48 more layers (read,
# seeds 0-3: steps 1-3 0.015-0.017, 0.022-0.024, 0.025-0.029, all steps
# 0.016-0.018), so all planes together are held under 2.5 %, and the
# tokens to agree at 88 % of the positions (read: 0.91-0.94). The
# control, a chunk whose slots read each other's blocks, shares no
# token with the logits path (read: 0.0 of 64).
TIE = {"pool_rows_rel_diff_p50_first_step": 0.01,
       "pool_rows_rel_diff_p50": 0.025, "tokens_equal_share": 0.88}
ARCH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
             "num_hidden_layers", "total_ut_steps", "early_exit_threshold",
             "vocab_size")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="ouro-2.6b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=376)
    ap.add_argument("--wave", type=int, default=4)
    ap.add_argument("--min-prompt", type=int, default=65)
    ap.add_argument("--max-prompt", type=int, default=256)
    args = ap.parse_args()
    t_start = time.time()
    config = harness.load_json("configs", args.config)
    devices = harness.check_device(config, 1)

    import jax
    import jax.numpy as jnp
    import ouro_ref as ref
    from distributed_llm_inferencing_tpu.models import transformer

    b = harness.build_batcher(config)
    cfg, vocab, bs, R, mb = b.cfg, config["vocab_size"], b.block_size, \
        b.slots, b.max_blocks
    arch = ref.arch_of(cfg)
    for key in ARCH_KEYS:
        assert arch[key] == config[key], (key, arch[key], config[key])
    T_steps, L = arch["total_ut_steps"], arch["num_hidden_layers"]
    planes = int(cfg.cache_planes)
    steps, wave = args.steps, args.wave
    k = max(b.decode_chunks)
    lo, hi = args.min_prompt, args.max_prompt
    assert steps % k == 0 and R % wave == 0 and hi + steps + 1 <= b.max_seq \
        and 1 + R * mb <= b.paged.num_blocks
    rng = np.random.default_rng(args.seed)
    # half the slots in each of the mix's two tail buckets, the shortest
    # prompt first and the longest last
    mid = b._bucket_tail(lo)
    assert b._bucket_tail(hi) > mid and R % 2 == 0 and (R // 2) % wave == 0
    lengths = np.concatenate([
        [lo], rng.integers(lo, mid + 1, R // 2 - 1),
        rng.integers(mid + 1, hi + 1, R // 2 - 1), [hi]]).astype(int)
    prompts = [rng.integers(3, vocab, int(n)).tolist() for n in lengths]
    # slot i owns blocks 1 + i * mb ..: the last slot's table ends in the
    # pool's last block
    tables = np.stack([1 + i * mb + np.arange(mb) for i in range(R)]) \
        .astype(np.int32)
    assert tables[-1, -1] == b.paged.num_blocks - 1
    context = lengths.astype(np.int32)
    zeros = np.zeros((R,), np.int32)
    waves = [(b._bucket_tail(int(lengths[w0])), list(range(w0, w0 + wave)))
             for w0 in range(0, R, wave)]

    def pack(T, rows):
        toks = np.zeros((len(rows), T), np.int32)
        for j, i in enumerate(rows):
            toks[j, :lengths[i]] = prompts[i]
        return toks, tables[rows, :T // bs], \
            np.zeros((len(rows), 0), np.int32)

    prefill_logits = jax.jit(
        lambda p, toks, tl, tb, pfb, pfl, pg: transformer.paged_prefill_tail(
            p, cfg, toks, tl, tb, pfb, pfl, pg), donate_argnums=(6,))
    step_logits = jax.jit(
        lambda p, t, pg, bt, cl, budget: transformer.decode_chunk_with_logits(
            p, cfg, 1, t, pg, bt, cl, zeros, zeros,
            jnp.ones((R,), jnp.float32), zeros, jnp.ones((R,), jnp.float32),
            jnp.zeros((R,), bool), budget, zeros - 1, b._dummy),
        donate_argnums=(2,))

    def admit(params, pool, timed):
        """Every slot's prompt, a wave a tail bucket. timed: through the
        batcher's admit program into b.paged (first tokens); else
        through the logits jit into `pool` (last-position logits)."""
        out = []
        for T, rows in waves:
            toks, tb, pfb = pack(T, rows)
            n = len(rows)
            if timed:
                out.append(b._run_admit({
                    "toks": toks, "tail_alloc": tb, "pfb": pfb,
                    "tail_len": [int(lengths[i]) for i in rows],
                    "cached": [0] * n, "seeds": [0] * n, "steps": [0] * n,
                    "tks": [0] * n, "ds": [0] * n, "temps": [1.0] * n,
                    "tps": [1.0] * n}))
            else:
                lg, pool = prefill_logits(
                    params, jnp.asarray(toks),
                    jnp.asarray(lengths[rows], jnp.int32), jnp.asarray(tb),
                    jnp.asarray(pfb), jnp.zeros((n,), jnp.int32), pool)
                out.append(np.asarray(lg))
        return np.concatenate(out), pool

    def decode_logits(params, pool, first, forced):
        """`steps` decode steps of every slot through the k = 1 chunk,
        fed the tokens the timed chunks chose. Returns logits
        [R, steps, V], their argmax [steps, R], and the pool."""
        got, arg = [], []
        bt = jnp.asarray(tables)
        for t in range(steps):
            cur = first if t == 0 else forced[t - 1]
            *_, pool, lg = step_logits(
                params, jnp.asarray(cur, jnp.int32), pool, bt,
                jnp.asarray(context + t), jnp.asarray(zeros + 1))
            got.append(np.asarray(lg[0]))
            arg.append(np.argmax(got[-1], -1))
        return np.stack(got, 1), np.stack(arg), pool

    # a sample of the pool's rows, every plane: each slot's first block,
    # the block its decode began in, and its last written one
    cols = np.stack([zeros, context // bs, (context + steps - 1) // bs], 1)
    sample = np.unique(tables[np.arange(R)[:, None], cols])
    last_block = int(tables[-1, cols[-1, -1]])
    # their positions in the slot's sequence, [R, 3 * bs], and which of
    # them were written (the last block's only as far as the last step)
    at = (cols[:, :, None] * bs + np.arange(bs)).reshape(R, -1)
    at_written = at < (context + steps)[:, None]

    take = jax.jit(lambda plane, planes: plane[planes[:, None],
                                               jnp.asarray(sample)[None, :]])

    def rows_of(pool, step=24):
        """The sampled blocks of every plane, on the host, float32; a few
        planes a gather: beside the weights and the pool the device has
        a few hundred MB to spare."""
        return tuple(np.concatenate([
            np.asarray(take(plane, jnp.arange(p0, min(p0 + step, planes)))
                       .astype(jnp.float32))
            for p0 in range(0, planes, step)]) for plane in (pool.k, pool.v))

    def slot_rows(rows, i):
        """Slot i's sampled rows of rows_of's planes: [K and V, planes,
        3 * bs positions, Hkv * hd]."""
        idx = np.searchsorted(sample, tables[i, cols[i]])
        return np.stack([r[:, idx].reshape(planes, at.shape[1], -1)
                         for r in rows])

    def rel_rows(rows, want, shift=0):
        """A row's relative difference from `want` (a slot's [K and V,
        planes, 3 * bs, Hkv * hd]), every slot's written sampled rows:
        [K and V, planes, rows]; with `shift` the pool's loop step u
        against `want`'s step u - shift."""
        rel = []
        for i in range(R):
            got, want_i = slot_rows(rows, i)[:, shift * L:], \
                want[i][:, :planes - shift * L]
            rel.append((np.linalg.norm(got - want_i, axis=-1)
                        / np.maximum(np.linalg.norm(want_i, axis=-1), 1e-6)
                        )[:, :, at_written[i]])
        return np.concatenate(rel, axis=-1)

    def by_step(rel):
        """The median over each loop step's planes."""
        return [float(np.median(rel[:, p0:p0 + L]))
                for p0 in range(0, rel.shape[1], L)]

    def memory(where):
        st = devices[0].memory_stats() or {}
        print(f"memory {where}: in use {st.get('bytes_in_use', 0) / 2**30:.2f}"
              f" GiB, peak {st.get('peak_bytes_in_use', 0) / 2**30:.2f}, "
              f"limit {st.get('bytes_limit', 0) / 2**30:.2f}",
              file=sys.stderr, flush=True)

    # ---- the timed programs: admit waves, decode chunks -----------------
    memory("built")
    first, _ = admit(b.params, None, timed=True)
    def timed_chunk(c, cur, bt):
        toks, emits = b._run_decode({
            "bt": bt, "cl": context + c * k, "seeds": zeros,
            "steps": zeros + c * k, "tks": zeros,
            "budget": zeros + k, "eos": zeros - 1, "ds": zeros,
            "temps": np.ones((R,), np.float32),
            "tps": np.ones((R,), np.float32), "k": k, "tokens": cur})
        assert np.asarray(emits).all()
        return np.asarray(toks)

    cur, forced, extents = first.astype(np.int32), [], set()
    for c in range(steps // k):
        last_in = cur
        forced.append(timed_chunk(c, cur, tables))
        extents.add(int(b._pool_positions))
        cur = forced[-1][-1]
    forced = np.concatenate(forced)                       # [steps, R]
    memory("after the timed programs")
    timed_rows = rows_of(b.paged)
    # plane u * L + l of the pool's last block, as the timed chunks left it
    last_plane = np.asarray(
        b.paged.k[planes - 1, last_block].astype(jnp.float32))
    written = (context[-1] + steps - 1) % bs + 1
    # the tie's control: the last chunk again, every slot reading (and
    # writing) the next slot's blocks. What it spoils the logits path
    # writes again before it reads it.
    rolled = timed_chunk(steps // k - 1, last_in, np.roll(tables, 1, axis=0))

    # ---- the logits path, through the same pool ---------------------------
    pool, b.paged = b.paged, None
    lg_prefill, pool = admit(b.params, pool, timed=False)
    lg_decode, arg, pool = decode_logits(b.params, pool, first, forced)
    # a row's relative difference, all planes and a loop step at a time
    logit_rows = rows_of(pool)
    rel = rel_rows(timed_rows, [slot_rows(logit_rows, i) for i in range(R)])
    tie_by_step = by_step(rel)
    differ = arg != forced
    tie = {
        "first_tokens_equal": int((first == np.argmax(lg_prefill, -1)).sum()),
        "of_rows": R,
        "tokens_equal_share": float(1.0 - differ.mean()),
        "of_decode_tokens": int(differ.size),
        "pool_rows_rel_diff_p50_by_step": tie_by_step,
        "pool_rows_rel_diff_p50": float(np.percentile(rel, 50)),
        "pool_rows_rel_diff_p90": float(np.percentile(rel, 90)),
        "pool_rows_rel_diff_max": float(rel.max()),
        "pool_rows_sampled": int(rel.size),
        "pool_positions_read": sorted(extents),
        "control_rolled_tables_tokens_equal_share": float(
            (rolled == arg[-k:]).mean()),
    }
    del pool, logit_rows, rel
    memory("after the logits path")

    # ---- the reference, a jitted layer at a time --------------------------
    layer = jax.jit(lambda lp, x, pos: ref.layer(lp, arch, x, pos))
    between = jax.jit(lambda p, x: ref.between(p, arch, x))
    head = jax.jit(lambda p, x: ref.logits(p, arch, x))
    # a layer's K and V at the sampled positions, [K and V, 3 * bs, Hkv * hd]
    keys_values = jax.jit(lambda lp, x, pos, rows: jnp.stack([
        t[rows].reshape(rows.shape[0], -1) for t in ref.keys_values(
            lp, arch, ref.rms_norm(x, lp["attn_norm"]["scale"],
                                   arch["rms_norm_eps"]), pos)]))
    lps = [ref.layer_params(b.params, i) for i in range(L)]

    def ref_forward(seq, rows, kv_rows):
        """Logits at `rows` after the last pass, and after the pass
        before it (a model of total_ut_steps - 1); every (step, layer)
        pair's K and V at `kv_rows`, [K and V, planes, rows, Hkv * hd]."""
        with jax.default_matmul_precision("highest"):
            tokens = jnp.asarray(seq, jnp.int32)
            positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
            kv_rows = jnp.asarray(kv_rows, jnp.int32)
            x = ref.embed(b.params, arch, tokens)
            out, kv = [], []
            for u in range(T_steps):
                for lp in lps:
                    kv.append(np.asarray(keys_values(lp, x, positions,
                                                     kv_rows)))
                    x = layer(lp, x, positions)
                x = between(b.params, x)
                if u >= T_steps - 2:
                    out.append(np.asarray(head(b.params,
                                               x[jnp.asarray(rows)])))
            return out[-1], out[0], np.stack(kv, 1)

    want_prefill, want_decode, short_prefill, short_decode = [], [], [], []
    want_kv = []
    for i in range(R):
        n = int(context[i])
        seq = prompts[i] + [int(first[i])] + forced[:steps - 1, i].tolist()
        full, short, kv = ref_forward(
            seq, range(n - 1, n + steps), np.minimum(at[i], len(seq) - 1))
        want_prefill.append(full[0]), want_decode.append(full[1:])
        short_prefill.append(short[0]), short_decode.append(short[1:])
        want_kv.append(kv)
    del layer, lps
    want = (np.stack(want_prefill), np.stack(want_decode))
    pool_readings = {
        "rows_rel_diff_p50_by_step": by_step(rel_rows(timed_rows, want_kv)),
        "control_step_before_p50_by_step": by_step(
            rel_rows(timed_rows, want_kv, 1)),
        "rows_sampled_a_plane": int(2 * at_written.sum())}
    del timed_rows
    readings = {"prefill": base.errors(lg_prefill, want[0]),
                "decode": base.errors(lg_decode, want[1])}
    # the last slot's last chunk: positions that live in the last block
    last_rows = base.errors(lg_decode[-1, -k:], want[1][-1, -k:])
    one_step_short = {
        "prefill": base.errors(lg_prefill, np.stack(short_prefill)),
        "decode": base.errors(lg_decode, np.stack(short_decode))}
    del short_prefill, short_decode

    # ---- teeth: the same with int8-rounded weights ------------------------
    q = base.int8_roundtrip(b.params)
    b.params = q
    pool = empty_pool(cfg, config)
    lq_prefill, pool = admit(q, pool, timed=False)
    lq_decode, _, pool = decode_logits(q, pool, first, forced)
    pool_readings["control_int8_p50_by_step"] = by_step(
        rel_rows(rows_of(pool), want_kv))
    del pool
    int8 = {"prefill": base.errors(lq_prefill, want[0]),
            "decode": base.errors(lq_decode, want[1])}

    def over(reading):         # a control fails by its median or its p90
        return any(reading[ph][m] > LIMITS[m] for ph in reading
                   for m in ("p50", "p90"))
    under = all(readings[ph][m] < LIMITS[m] for ph in readings
                for m in LIMITS)
    last_block_ok = bool(
        np.abs(last_plane[:written]).max() > 0
        and last_rows["p50"] < LIMITS["p50"])
    tied = (tie_by_step[0] < TIE["pool_rows_rel_diff_p50_first_step"]
            and tie["pool_rows_rel_diff_p50"] < TIE["pool_rows_rel_diff_p50"]
            and tie["tokens_equal_share"] > TIE["tokens_equal_share"])
    tie_control_fails = tie["control_rolled_tables_tokens_equal_share"] \
        < TIE["tokens_equal_share"]
    pool_limits = POOL["rows_rel_diff_p50_by_step"]
    pool_under = all(r < m for r, m in zip(
        pool_readings["rows_rel_diff_p50_by_step"], pool_limits))
    # a control fails by any loop step's planes
    pool_controls_fail = all(
        any(r > m for r, m in zip(pool_readings[c], pool_limits[-len(
            pool_readings[c]):]))
        for c in ("control_step_before_p50_by_step",
                  "control_int8_p50_by_step"))
    out = {"ok": bool(under and over(int8) and over(one_step_short)
                      and last_block_ok and tied and tie_control_fails
                      and pool_under and pool_controls_fail),
           "limits": LIMITS, "tie_limits": TIE, "pool_limits": POOL,
           "timed_pool_vs_reference": pool_readings,
           "timed_pool_under_limits": bool(pool_under),
           "pool_controls_over_a_limit": bool(pool_controls_fail),
           "tie_control_fails": bool(tie_control_fails),
           "system_vs_reference": readings, "int8_vs_reference": int8,
           "system_vs_reference_one_step_short": one_step_short,
           "system_under_limits": bool(under),
           "int8_over_a_limit": bool(over(int8)),
           "one_step_short_over_a_limit": bool(over(one_step_short)),
           "last_block": {"plane": planes - 1, "block": last_block,
                          "rows_written": int(written),
                          "rows_abs_max": float(np.abs(
                              last_plane[:written]).max()),
                          "last_chunk_vs_reference": last_rows,
                          "ok": last_block_ok},
           "timed_programs_vs_logits_path": tie, "tied": bool(tied),
           "config": args.config, "seed": args.seed, "rows": R,
           "prompt_lengths": lengths.tolist(),
           "contexts": [int(context.min()), int(context.max()) + steps],
           "steps": steps, "wave": wave, "decode_chunk": k,
           "loop_steps": T_steps, "planes": planes,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind},
           "memory_peak_bytes": int((devices[0].memory_stats() or {}).get(
               "peak_bytes_in_use", 0)),
           "seconds": time.time() - t_start}
    (ROOT / "chiprun_out").mkdir(exist_ok=True)
    with open(ROOT / "chiprun_out" / "compare_reference.json", "a") as f:
        f.write(json.dumps(out) + "\n")
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


def empty_pool(cfg, config):
    """A pool of the configuration's shape (the batcher's, dummy block
    included)."""
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        init_paged_cache)
    return init_paged_cache(cfg, config["batcher"]["num_blocks"] + 1,
                            config["batcher"]["block_size"])


if __name__ == "__main__":
    sys.exit(main())
