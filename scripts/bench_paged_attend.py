#!/usr/bin/env python3
"""Device time of a decode pass's attention over the paged pool at the
benchmark cells' shapes, in the two forms models/transformer.py has
(PERF.md section 6, PRs 40, 42 and 46):

- ``rung``: the XLA form, ``_attend_pool_rung``'s taken branch: gather
  every slot's block-table columns as far as the rung of
  ``_pool_ladder`` that holds the longest live context, then the
  two-segment ``attend`` over (the gathered copy, the side rows);
- ``kernel``: ops/pallas/paged_attention.paged_attend, which walks each
  live slot's block table to its own length and reads the pages where
  they lie.

One timed call is a ``lax.scan`` over every plane of the stacked pool
(mistral-7b: 32, Ouro-2.6B: 192, falcon-h1 at 6 layers: 6, kanana at 7
layers: 7), as a decode pass makes them, so a call is milliseconds and
the dispatch cost is out of the number. Shapes: mistral 16 slots x 32
query heads over 8 K/V heads, 1025 blocks, 128 block-table columns,
window 4096; Ouro 8 slots x 16 heads, 321 blocks, 40 columns; falcon-h1
at 6 layers (12 planes: K and V) 64 slots x 20 query heads over 4 K/V
heads (a group of 5, half a tile of heads a position), 4097 blocks, 64
columns, and the same as its one-device pool lies since PR 49, flat rows
of 512 (``falcon-h1-34b-l6.flat``); kanana 64 slots x 32 heads over ONE plane of shared rows 640
wide (a latent pool: K and V at once, the query 576 wide), 10,241
blocks, 160 columns, its layers held one by one (the XLA form's ladder
is the full extent alone); mimo-v2.5 at its cell's 2 full layers (2
planes each of K and of V), 64 slots x 64 query heads of 192 over flat
rows (a position's 4 K/V heads side by side: 768 columns of K, 512 of
V), 20,481 blocks, 320 columns, held one by one (the XLA form gathers
the whole table and reads the rows as they lie, ``_attend_flat_rows``).
Lengths: every slot at the table's end; the
cells' own ragged draws (mistral ``decode-sat``: 16 live contexts of
81-768; Ouro ``cot-sat``: 8 of 249-576; falcon-h1 ``chat-sat``: 64 of
130-1024; kanana ``reason-sat``: 64 of 65-1600; mimo ``longmix-sat``:
64 of 512-5000); mistral
``chat-steady``: one live slot of 16. Each row gives the time, the live
K and V bytes (every live slot's context once; a latent row once for
both) and their share of the HBM peak, and the kernel's largest
difference from the rung form.

On the chip: ``python scripts/bench_paged_attend.py`` (~4 min; the last
stdout line is JSON, the table goes to chiprun_out/microbench.json).
``--only kanana`` times one model; ``--sweep step:tail:item,...`` (KiB)
the kernel alone under other step plans. ``--small`` rehearses the
control flow on the CPU with the kernel interpreted (its times mean
nothing).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

HBM_GBPS = 819.0      # TPU v5e (benchmarks/chip/peaks.json)
SIDE = 8              # decode_chunk_cap: the side rows of a chunk
SCALE = 192 ** -0.5   # kanana's qk_head_dim; near enough 128's for all


def timed(fn, args, calls, repeats):
    out = jax.block_until_ready(fn(*args))
    sets = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        sets.append((time.perf_counter() - t0) / calls * 1e3)
    return out, min(sets), statistics.median(sets)


def forms(bs, mb, window, interpret, latent=False, flat=None):
    """name -> jitted f(q, k, v, bt, cl, live, side_k, side_v, t): every
    plane's attention output summed in float32. ``latent``: f(q, rows,
    bt, cl, live, side_rows, t) over a latent pool's one plane (K and V
    at once, ``q`` as wide as the rows' own columns), its layers held
    one by one, so the XLA form's ladder is the full extent alone.
    ``flat`` = (K/V heads a row, a value head's width): flat rows, K's
    and V's of different widths, held one by one too."""
    from distributed_llm_inferencing_tpu.models.transformer import (
        _attend_flat_rows, _flat_rows_q, _pool_ladder, _pool_rung)
    from distributed_llm_inferencing_tpu.ops.attention import attend
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import gather_seq
    from distributed_llm_inferencing_tpu.ops.pallas import (
        paged_attention as pa)
    ladder = _pool_ladder(mb, scanned=not (latent or flat))

    def over_planes(one_plane, q, k):
        def body(acc, plane):
            return acc + one_plane(plane).astype(jnp.float32), None
        out = q.shape[:-1] + (flat[1],) if flat else q.shape
        return jax.lax.scan(body, jnp.zeros(out, jnp.float32),
                            jnp.arange(k.shape[0], dtype=jnp.int32))[0]

    def rung(q, k, v, bt, cl, live, side_k, side_v, t):
        r = q.shape[0]
        idx, _ = _pool_rung(ladder, bs, cl, live)
        side_pos = cl[:, None] + jnp.arange(SIDE, dtype=jnp.int32)[None, :]
        side_valid = jnp.broadcast_to(
            jnp.arange(SIDE, dtype=jnp.int32)[None, :] <= t, (r, SIDE))

        def branch(m):
            def run(plane):
                pos = jnp.broadcast_to(
                    jnp.arange(m * bs, dtype=jnp.int32), (r, m * bs))
                if flat:
                    return _attend_flat_rows(
                        q, (gather_seq(k, bt[:, :m], plane), side_k[plane]),
                        (gather_seq(v, bt[:, :m], plane), side_v[plane]),
                        *flat, (cl + t)[:, None], (pos, side_pos),
                        (pos < cl[:, None], side_valid))
                w = q.shape[-1]
                got_k = gather_seq(k, bt[:, :m], plane)[..., :w]
                got_v = (got_k if v is k
                         else gather_seq(v, bt[:, :m], plane))
                return attend(
                    q, (got_k, side_k[plane][..., :w]),
                    (got_v, side_v[plane][..., :w]),
                    (cl + t)[:, None], (pos, side_pos),
                    (pos < cl[:, None], side_valid), sliding_window=window,
                    scale=SCALE)
            return run
        return over_planes(
            lambda plane: jax.lax.switch(idx, [branch(m) for m in ladder],
                                         plane), q, k)

    def kernel(q, k, v, bt, cl, live, side_k, side_v, t):
        walk = pa.pool_walk(cl, live, k, mb, sliding_window=window,
                            n_planes=1 if v is k else 2, v_planes=v)

        def one_plane(plane):
            sk = side_k[plane]
            if flat:
                return pa.paged_attend(
                    _flat_rows_q(q, flat[0], k), k, v, plane, bt, cl,
                    cl + t, walk, (sk, side_v[plane], t),
                    scale=q.shape[-1] ** -0.5,   # _attend_flat_rows'
                    v_head_dim=flat[1], interpret=interpret)
            return pa.paged_attend(
                q, k, v, plane, bt, cl, cl + t, walk,
                (sk, sk if v is k else side_v[plane], t),
                sliding_window=window, scale=SCALE,
                interpret=interpret)[..., :q.shape[-1]]
        return over_planes(one_plane, q, k)

    if not latent:
        return {"rung": jax.jit(rung), "kernel": jax.jit(kernel)}

    # a latent pool goes in once: inside a jit ``v is k`` would be lost
    def shared(f):
        return jax.jit(lambda q, k, bt, cl, live, side_k, t: f(
            q, k, k, bt, cl, live, side_k, side_k, t))
    return {"rung": shared(rung), "kernel": shared(kernel)}


def kernel_plans(sweep):
    """(label, (_STEP_BYTES, _TAIL_BYTES, _ITEM_BYTES)) of the kernel's
    own plan and of each ``step:tail:item`` (KiB) of ``--sweep``. A plan
    is read when a form is traced, so each gets its own jit (forms) and
    is set (use_plan) before that jit's first call."""
    from distributed_llm_inferencing_tpu.ops.pallas import (
        paged_attention as pa)
    plans = [("", (pa._STEP_BYTES, pa._TAIL_BYTES, pa._ITEM_BYTES))]
    for spec in filter(None, sweep.split(",")):
        plans.append(("@" + spec,
                      tuple(int(x) * 1024 for x in spec.split(":"))))
    return plans


def use_plan(plan):
    from distributed_llm_inferencing_tpu.ops.pallas import (
        paged_attention as pa)
    pa._STEP_BYTES, pa._TAIL_BYTES, pa._ITEM_BYTES = plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/microbench.json")
    ap.add_argument("--only", default="", help="a model's name: time it "
                    "alone")
    ap.add_argument("--sweep", default="", help="step:tail:item,... in "
                    "KiB: time the kernel alone at these (_STEP_BYTES, "
                    "_TAIL_BYTES, _ITEM_BYTES) too")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if not args.small and dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no TPU: {dev.platform}"}))
        return 2
    bs = 16
    # (name, planes, slots, query heads, K/V heads, (row width, the
    #  query's), blocks, columns, window, cases of (name, live slots,
    #  shortest, longest context)); one K/V head: a latent pool; the
    #  row width a pair (a K head's, a V head's): flat rows of K/V heads
    models = [
        ("mistral-7b", 32, 16, 32, 8, (128, 128), 1025, 128, 4096,
         [("full", 16, 2048, 2048), ("decode-sat", 16, 81, 768),
          ("chat-steady", 1, 81, 768)]),
        ("ouro-2.6b", 192, 8, 16, 16, (128, 128), 321, 40, None,
         [("full", 8, 640, 640), ("cot-sat", 8, 249, 576)]),
        ("falcon-h1-34b-l6", 6, 64, 20, 4, (128, 128), 4097, 64, None,
         [("full", 64, 1024, 1024), ("chat-sat", 64, 130, 1024)]),
        # ... and as its one-device pool lies since PR 49: flat rows of 512
        ("falcon-h1-34b-l6.flat", 6, 64, 20, 4, ((128, 128), 128), 4097, 64,
         None, [("full", 64, 1024, 1024), ("chat-sat", 64, 130, 1024)]),
        ("kanana-2-30b-a3b-l7", 7, 64, 32, 1, (640, 576), 10241, 160, None,
         [("full", 64, 2560, 2560), ("reason-sat", 64, 65, 1600)]),
        ("mimo-v2.5-l7", 2, 64, 64, 4, ((192, 128), 192), 20481, 320, None,
         [("full", 64, 5120, 5120), ("longmix-sat", 64, 512, 5000)]),
    ]
    if args.small:
        models = [(name, 2, 4, h, hkv, (256, 200) if hkv == 1 else hd, 33,
                   8, 24 if window else None,
                   [("full", 4, 128, 128), ("ragged", 3, 5, 100)])
                  for name, _, _, h, hkv, hd, _, _, window, _ in models]
        args.calls, args.repeats = 1, 1
    models = [m for m in models if args.only in m[0]]
    rng = np.random.default_rng(args.seed)
    plans = kernel_plans(args.sweep)
    table = []
    for name, planes, r, h, hkv, (hd, qw), nb, mb, window, cases in models:
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
        latent = hkv == 1
        flat = (hkv, hd[1]) if isinstance(hd, tuple) else None
        # a latent pool's columns past the query's are zeros, as stored

        def rows(key, *lead, v=False):
            if flat:
                return jax.random.normal(
                    key, lead + (1, hkv * hd[v]), jnp.bfloat16)
            x = jax.random.normal(key, lead + (hkv, hd), jnp.bfloat16)
            return x * (jnp.arange(hd) < qw).astype(jnp.bfloat16)
        pool = tuple(rows(kk, planes, nb, bs, v=bool(i))
                     for i, kk in enumerate(keys[:1 if latent else 2]))
        q = jax.random.normal(keys[2], (r, 1, h, qw), jnp.bfloat16)
        side = tuple(rows(kk, planes, r, SIDE, v=bool(i))
                     for i, kk in enumerate(keys[3:4 if latent else 5]))
        # the rung form once, the kernel under every plan
        runs = [(form + label, fn, plan)
                for label, plan in plans
                for form, fn in forms(bs, mb, window, args.small,
                                      latent, flat).items()
                if form == "kernel" or not label]
        for case, n_live, lo, hi in cases:
            lens = np.zeros(r, np.int64)
            lens[rng.permutation(r)[:n_live]] = rng.integers(
                lo, hi + 1, n_live)
            bt = np.zeros((r, mb), np.int32)
            bt.reshape(-1)[:] = rng.permutation(r * mb) % (nb - 1)
            call = (q, *pool, jnp.asarray(bt), jnp.asarray(lens, jnp.int32),
                    jnp.asarray(lens > 0), *side, jnp.int32(3))
            gb = planes * int(lens.sum()) * hkv * 2 * (
                sum(hd) if flat else hd * len(pool)) / 1e9
            ref = None
            for form, fn, plan in runs:
                use_plan(plan)
                y, best, med = timed(fn, call, args.calls, args.repeats)
                y = np.asarray(y)[lens > 0]
                ref = y if ref is None else ref
                row = {"model": name, "case": case, "form": form,
                       "live_slots": n_live, "longest": int(lens.max()),
                       "mean_live": round(float(lens.sum()) / n_live, 1),
                       "live_gb": round(gb, 4), "ms_min": round(best, 4),
                       "ms_median": round(med, 4),
                       "us_a_plane": round(best / planes * 1e3, 2),
                       "hbm_share_of_live_bytes": round(
                           gb / HBM_GBPS / (best / 1e3) * 100, 1),
                       "max_abs_diff_vs_rung": float(np.max(np.abs(y - ref))),
                       "sum_scale": float(np.max(np.abs(ref)))}
                table.append(row)
                print(json.dumps(row), flush=True)
        use_plan(plans[0][1])
        del pool
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "rows": table}, f, indent=1)
    print(json.dumps({"ok": True, "device": dev.device_kind,
                      "n": len(table), "out": args.out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
