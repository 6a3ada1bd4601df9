#!/usr/bin/env python3
"""Hold an afmoe-shaped cell's serving programs to the plain float32
reference (reference/afmoe_ref.py) at the cell's own widths and context
lengths, on logits.

    python3 benchmarks/chip/compare_reference_afmoe.py [--config NAME|PATH]
        [--seed N] [--prefix 7680] [--groups 4] [--steps 72] [--wave 2]
        [--checked 8]

Builds the configuration's batcher (run.build_batcher: the cell's weights,
pool and mesh) and serves, as the cell's mix does, --groups shared
prefixes of --prefix tokens and behind them one private prompt part a slot
(the checked rows' as long as the mix allows, the others' seeded), two
ways from the same weights:

- the TIMED programs, as the window runs them: each prefix through the
  batcher's admit program a chunk at a time (`_run_admit`, one row, the
  tail bucket of `prefill_chunk`, the prefix bucket the batcher would
  choose for what is cached so far: from 256 blocks up a windowed layer
  reads its window's columns only), the private parts as waves of --wave
  rows over the whole cached prefix, then decode chunks (`_run_decode`,
  k = the cell's largest, every slot live, a windowed layer reading its
  window's columns), greedy. They return tokens, not logits.
- a LOGITS path over a copy of the pool the timed chunks left:
  `paged_prefill_tail` jitted here at the timed wave's shape so that it
  returns logits, and `paged_decode_step`, which gathers and reads every
  layer's whole block table under the layer's mask (no bounded read),
  decoding the tokens the timed chunks chose.

The two are tied together as in compare_reference.py: the private blocks
they leave must agree row by row and the timed tokens must be the logits
path's argmax at three quarters of the positions or more. The logits path
is then compared with the reference's full forward pass (no cache, every
position under the full mask) for the first --checked rows: prefill alone
(a prefix's first chunk), a tail over a cached prefix of --prefix tokens
(so the windowed prefix read and a full layer without rotation are both
under test at the cell's 8k), and --steps decode steps at contexts past
it. The reference runs a jitted layer at a time.

Error of a position, the phases' quantiles and why they and not the
mean: compare_reference.py's docstring. Exit code 0 if every reading is
under its limit AND the same run with every linear weight rounded to int8
(the prefixes built again with those weights) is over at least one. Last
stdout line: JSON, also appended to chiprun_out/compare_reference.json.
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
# reasons. Two readings on the v5e at published widths, 5 layers, contexts
# of 8k (my chip runs, PR 31; PERF.md section 6): the system in bf16
# (weights as stored; activations, K and V rounded to bf16 at every layer;
# float32 accumulation, softmax, gate and routing) reads p25 0.0089-0.0113
# over its phases and seeds 0, 1, 2 and a decode p50 of 0.0091-0.0092; the
# same with every linear weight rounded to int8 reads p25 0.0304-0.147 and
# a decode p50 of 0.105-0.108.
# Both quantiles split in two at this model: where the system and the
# float32 reference choose the same experts the error is the precision's
# own (0.009 in bf16, 0.03 with int8 weights), where they do not it is
# 0.1-0.3 whatever the precision, and int8 weights move more than half of
# the positions there, bf16 about a tenth. So the lower quartile gets a
# limit between the two smooth errors, 0.018 (1.6 times above the largest
# bf16 reading, 1.7 times below the smallest int8 one), in every phase,
# and the decode phase's median (576 positions; the prefill phases have 4
# and 8) one between 0.0092 and 0.105, 0.03. `max` is loose: the largest
# bf16 reading was 0.36, at a position of the second kind.
LIMITS = {"p25": 0.018, "p50": 0.03, "max": 1.0}
TIE = base.TIE


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="trinity-mini-l5")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix", type=int, default=7680)
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--steps", type=int, default=72)
    ap.add_argument("--wave", type=int, default=2)
    ap.add_argument("--checked", type=int, default=8)
    args = ap.parse_args()
    t_start = time.time()
    config = harness.load_json("configs", args.config)
    devices = harness.check_device(config, 1)

    import jax
    import jax.numpy as jnp
    import afmoe_ref as ref
    from distributed_llm_inferencing_tpu.models import transformer

    b = harness.build_batcher(config)
    cfg, vocab, bs, R, mb = b.cfg, config["vocab_size"], b.block_size, \
        b.slots, b.max_blocks
    arch = ref.arch_of(cfg)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "rope_theta", "rms_norm_eps", "num_hidden_layers",
                "num_dense_layers", "layer_types", "sliding_window",
                "num_experts", "num_experts_per_tok", "route_norm",
                "route_scale", "num_shared_experts", "mup_enabled"):
        assert arch[key] == config[key], (key, arch[key], config[key])
    PFX, G, steps, wave, checked = (args.prefix, args.groups, args.steps,
                                    args.wave, args.checked)
    T = b.prefill_chunk * bs              # a chunk, and the tail's bucket
    k = max(b.decode_chunks)
    longest = T - bs                      # the mix's longest private part
    assert PFX % T == 0 and steps % k == 0 and R % wave == 0 \
        and checked <= R and R % G == 0 and b._bucket_tail(T) == T
    rng = np.random.default_rng(args.seed)
    prefixes = [rng.integers(3, vocab, PFX).tolist() for _ in range(G)]
    lengths = [longest] * checked + rng.integers(
        T // 2 + bs, longest + 1, R - checked).tolist()
    tails = [rng.integers(3, vocab, n).tolist() for n in lengths]
    group = [i % G for i in range(R)]
    npb = PFX // bs                               # blocks of a prefix
    need = -(-(longest + steps + 1) // bs)        # private blocks a slot
    assert 1 + G * npb + R * need <= b.paged.num_blocks \
        and npb + need <= mb
    prefix_blocks = [1 + g * npb + np.arange(npb) for g in range(G)]
    private = [1 + G * npb + i * need + np.arange(need) for i in range(R)]
    tables = np.full((R, mb), b._dummy, np.int32)
    for i in range(R):
        tables[i, :npb] = prefix_blocks[group[i]]
        tables[i, npb:npb + need] = private[i]
    context = np.asarray([PFX + n for n in lengths], np.int32)
    zeros = np.zeros((R,), np.int32)

    def admit_args(rows, toks, tail_blocks, pfb, cached):
        n = len(rows)
        return {"toks": toks, "tail_alloc": tail_blocks, "pfb": pfb,
                "tail_len": [len(t) for t in rows], "cached": cached,
                "seeds": [0] * n, "steps": [0] * n, "tks": [0] * n,
                "ds": [0] * n, "temps": [1.0] * n, "tps": [1.0] * n}

    def pack(rows, blocks, prefix, width):
        """Token rows, their tail blocks and cached prefix blocks as the
        batcher packs a wave (`width` prefix columns, dummy-padded)."""
        toks = np.zeros((len(rows), T), np.int32)
        tb = np.full((len(rows), T // bs), b._dummy, np.int32)
        pfb = np.full((len(rows), width), b._dummy, np.int32)
        for i, (t, bl, pf) in enumerate(zip(rows, blocks, prefix)):
            toks[i, :len(t)] = t
            tb[i, :len(bl)] = bl[:T // bs]
            pfb[i, :len(pf)] = pf
        return toks, tb, pfb

    prefill_logits = jax.jit(
        lambda p, toks, tl, tb, pfb, pfl, pg: transformer.paged_prefill_tail(
            p, cfg, toks, tl, tb, pfb, pfl, pg), donate_argnums=(6,))
    step_logits = jax.jit(
        lambda p, t, pg, bt, cl: transformer.paged_decode_step(
            p, cfg, t, pg, bt, cl), donate_argnums=(2,))

    def build_prefixes():
        """Each group's prefix into b.paged through the batcher's own
        admit program, a chunk a call, as its chunked prefill runs it."""
        for g in range(G):
            for c in range(PFX // T):
                done = prefix_blocks[g][:c * T // bs]
                toks, tb, pfb = pack(
                    [prefixes[g][c * T:(c + 1) * T]],
                    [prefix_blocks[g][c * T // bs:]], [done],
                    max(b._bucket_prefix(len(done)), 1))
                b._run_admit(admit_args([toks[0]], toks, tb, pfb, [c * T]))

    def first_chunks(params):
        """Prefill alone: each prefix's first chunk over no prefix,
        through the logits jit into a pool of its own."""
        toks, tb, pfb = pack([p[:T] for p in prefixes],
                             [bl[:T // bs] for bl in prefix_blocks],
                             [[]] * G, 0)
        lg, _ = prefill_logits(
            params, jnp.asarray(toks), jnp.full((G,), T, jnp.int32),
            jnp.asarray(tb), jnp.asarray(pfb), jnp.zeros((G,), jnp.int32),
            jax.tree.map(jnp.zeros_like, b.paged))
        return np.asarray(lg)

    def tail_waves(params, pool, timed):
        """Every slot's private part over its group's cached prefix, in
        waves. timed: through the batcher's admit program into b.paged
        (first tokens); else through the logits jit into `pool` (logits
        of the first `checked` rows)."""
        out = []
        width = b._bucket_prefix(npb)
        for w0 in range(0, R, wave):
            rows = range(w0, w0 + wave)
            toks, tb, pfb = pack(
                [tails[i] for i in rows], [private[i] for i in rows],
                [prefix_blocks[group[i]] for i in rows], width)
            if timed:
                out.append(b._run_admit(admit_args(
                    [tails[i] for i in rows], toks, tb, pfb, [PFX] * wave)))
            else:
                lg, pool = prefill_logits(
                    params, jnp.asarray(toks),
                    jnp.asarray([lengths[i] for i in rows], jnp.int32),
                    jnp.asarray(tb), jnp.asarray(pfb),
                    jnp.full((wave,), PFX, jnp.int32), pool)
                if w0 < checked:
                    out.append(np.asarray(lg[:checked - w0]))
        return np.concatenate(out), pool

    def decode_logits(params, pool, first, forced):
        """`steps` decode steps of every slot through paged_decode_step,
        fed the tokens the timed chunks chose. Returns the checked rows'
        logits [checked, steps, V], every row's argmax and top-2 margin
        over its logits' spread [steps, R], and the pool."""
        got, arg, margin = [], [], []
        bt = jnp.asarray(tables)
        for t in range(steps):
            cur = first if t == 0 else forced[t - 1]
            lg, pool = step_logits(params, jnp.asarray(cur, jnp.int32),
                                   pool, bt, jnp.asarray(context + t))
            top = jax.lax.top_k(lg, 2)[0]
            got.append(np.asarray(lg[:checked]))
            arg.append(np.asarray(jnp.argmax(lg, -1)))
            margin.append(np.asarray((top[:, 0] - top[:, 1])
                                     / jnp.std(lg, axis=-1)))
        return np.stack(got, 1), np.stack(arg), np.stack(margin), pool

    # ---- the timed programs: prefixes, tails, decode chunks ------------
    lg_first = first_chunks(b.params)
    build_prefixes()
    pool = jax.tree.map(jnp.copy, b.paged)        # the logits path's
    first, _ = tail_waves(b.params, None, timed=True)
    cur, forced, window_positions = first.astype(np.int32), [], set()
    for c in range(steps // k):
        toks, emits = b._run_decode({
            "bt": tables, "cl": context + c * k, "seeds": zeros,
            "steps": zeros + c * k, "tks": zeros,
            "budget": zeros + k, "eos": zeros - 1, "ds": zeros,
            "temps": np.ones((R,), np.float32),
            "tps": np.ones((R,), np.float32), "k": k, "tokens": cur})
        assert np.asarray(emits).all()
        window_positions.add((b._pool_positions, b._window_positions))
        forced.append(np.asarray(toks))
        cur = np.asarray(toks)[-1]
    forced = np.concatenate(forced)                       # [steps, R]

    # ---- the logits path over the same prefixes ------------------------
    lg_tail, pool = tail_waves(b.params, pool, timed=False)
    lg_decode, arg, margin, pool = decode_logits(b.params, pool, first,
                                                 forced)

    # ---- timed against logits path --------------------------------------
    differ = arg != forced
    rows_used = np.concatenate(
        [private[i][:-(-(lengths[i] + steps) // bs)] for i in range(R)])
    ours = b.paged.k[:, rows_used].astype(jnp.float32)
    theirs = pool.k[:, rows_used].astype(jnp.float32)
    norm = np.asarray(jnp.linalg.norm(theirs, axis=-1))
    rel = np.asarray(jnp.linalg.norm(ours - theirs, axis=-1)) \
        / np.maximum(norm, 1e-6)
    rel = rel[norm > 0]
    tie = {
        "first_tokens_equal": int(
            (first[:checked] == np.argmax(lg_tail, -1)).sum()),
        "of_checked": checked,
        "tokens_equal_share": float(1.0 - differ.mean()),
        "of_decode_tokens": int(differ.size),
        "pool_rows_rel_diff_p50": float(np.percentile(rel, 50)),
        "pool_rows_rel_diff_p90": float(np.percentile(rel, 90)),
        "pool_rows_rel_diff_max": float(rel.max()),
        "median_margin_where_differing": float(
            np.median(margin[differ])) if differ.any() else 0.0,
        # (pool extent, a windowed layer's) the timed chunks read a slot
        "pool_and_window_positions": sorted(window_positions),
    }
    del ours, theirs, pool
    b.paged = None                  # the timed pool's memory goes back

    # ---- the reference, a jitted layer at a time ------------------------
    layers = [jax.jit(lambda p, x, pos, i=i: ref.layer(p, arch, i, x, pos))
              for i in range(arch["num_hidden_layers"])]

    def ref_forward(seq, rows):
        tokens = jnp.asarray(seq, jnp.int32)
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = ref.embed(b.params, arch, tokens)
        for layer in layers:
            x = layer(b.params, x, positions)
        return np.asarray(ref.logits(b.params, arch, x[jnp.asarray(rows)]))

    want_first = np.stack([ref_forward(p[:T], [T - 1])[0]
                           for p in prefixes])
    want_tail, want_decode = [], []
    for c in range(checked):
        n = int(context[c])
        seq = prefixes[group[c]] + tails[c] + [int(first[c])] \
            + forced[:steps - 1, c].tolist()
        lg = ref_forward(seq, range(n - 1, n + steps))
        want_tail.append(lg[0])
        want_decode.append(lg[1:])
    want = (want_first, np.stack(want_tail), np.stack(want_decode))
    del layers
    readings = {"prefill": base.errors(lg_first, want[0]),
                "prefix": base.errors(lg_tail, want[1]),
                "decode": base.errors(lg_decode, want[2])}

    # ---- teeth: the same with int8-rounded weights ----------------------
    q = base.int8_roundtrip(b.params)
    b.params = q
    b.paged = empty_pool(cfg, config)
    lq_first = first_chunks(q)
    build_prefixes()
    pool, b.paged = b.paged, None
    lq_tail, pool = tail_waves(q, pool, timed=False)
    lq_decode, _, _, pool = decode_logits(q, pool, first, forced)
    int8 = {"prefill": base.errors(lq_first, want[0]),
            "prefix": base.errors(lq_tail, want[1]),
            "decode": base.errors(lq_decode, want[2])}

    def held(ph):
        return ("p25", "p50", "max") if ph == "decode" else ("p25", "max")
    under = all(readings[ph][m] < LIMITS[m]
                for ph in readings for m in held(ph))
    int8_over = any(int8[ph][m] > LIMITS[m]
                    for ph in int8 for m in held(ph) if m != "max")
    tied = (tie["pool_rows_rel_diff_p50"] < TIE["pool_rows_rel_diff_p50"]
            and tie["tokens_equal_share"] > TIE["tokens_equal_share"])
    out = {"ok": bool(under and int8_over and tied), "limits": LIMITS,
           "tie_limits": TIE,
           "system_vs_reference": readings, "int8_vs_reference": int8,
           "system_under_limits": bool(under),
           "int8_over_a_limit": bool(int8_over),
           "timed_programs_vs_logits_path": tie, "tied": bool(tied),
           "config": args.config, "seed": args.seed, "rows": R,
           "prefix": PFX, "groups": G, "tail_bucket": T,
           "contexts": [int(context.min()), int(context.max()) + steps],
           "steps": steps, "wave": wave, "checked": checked,
           "decode_chunk": k,
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
