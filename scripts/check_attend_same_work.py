#!/usr/bin/env python3
"""Same work, on the chip: the grouped, segmented ``ops.attention.attend``
against the head-expanded f32 formulation it replaced (PR 25), at the
decode chunk's shapes in the benchmark's cells.

    python scripts/check_attend_same_work.py            # on the chip
    JAX_PLATFORMS=cpu python scripts/check_attend_same_work.py --small

Random bf16 q, pool K/V [16, 2048, 8, 128] and side K/V [16, 8, 8, 128],
contexts drawn as the cells draw them (65..768 of 2048). Both outputs are
taken in f32, before the cast to q's dtype, and compared on the scale of
the output (its largest magnitude); a float64 NumPy reference stands
beside both. A probability rounded to bf16 on its way into ``p @ V`` shows
as about 4e-3 here, which the last line demonstrates; the limit is 1e-5.
Also prints the wall time of one call of each (jitted alone, K and V as
arguments: a time of this program, not of the decode pass).

Last stdout line: one JSON object; exit code 1 if the limit is passed.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_llm_inferencing_tpu.ops.attention import (  # noqa: E402
    NEG_INF, attend, repeat_kv)

LIMIT = 1e-5


def expanded_f32_attend(q, k, v, q_pos, kv_pos, kv_valid, round_probs=False):
    """The parent's ``attend`` (commit 47ab366), window-free: K and V
    repeated over the query heads of a group, everything cast to f32, one
    concatenated KV set. Returns f32. ``round_probs`` rounds the
    probabilities to bf16 first: the fault the limit has to catch."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = (kv_pos[:, None, :] <= q_pos[:, :, None]) & kv_valid[:, None, :]
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    if round_probs:
        # (a convert pair would be simplified away on the TPU)
        probs = jax.lax.reduce_precision(probs, exponent_bits=8,
                                         mantissa_bits=7)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def float64_attend(q, k, v, q_pos, kv_pos, kv_valid):
    q, k, v = (np.asarray(x.astype(jnp.float32), np.float64)
               for x in (q, k, v))
    g = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    mask = ((kv_pos[:, None, :] <= q_pos[:, :, None])
            & kv_valid[:, None, :])[:, None]
    logits = np.where(mask, logits, -np.inf)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, v)


def wall_ms(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="rehearsal shapes for a CPU")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    R, S, K, H, HKV, HD = ((4, 64, 8, 8, 2, 16) if a.small
                           else (16, 2048, 8, 32, 8, 128))
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(a.seed), 6)
    q = jax.random.normal(ks[0], (R, 1, H, HD), bf)
    pool_k = jax.random.normal(ks[1], (R, S, HKV, HD), bf)
    pool_v = jax.random.normal(ks[2], (R, S, HKV, HD), bf)
    side_k = jax.random.normal(ks[3], (R, K, HKV, HD), bf)
    side_v = jax.random.normal(ks[4], (R, K, HKV, HD), bf)
    hi = min(768, S - K)
    cl = jax.random.randint(ks[5], (R,), min(65, hi - 1), hi, jnp.int32)
    t = K // 2                                     # a step mid-chunk
    pool_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (R, S))
    pool_valid = pool_pos < cl[:, None]
    side_pos = cl[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
    side_valid = jnp.broadcast_to(
        jnp.arange(K, dtype=jnp.int32)[None] <= t, (R, K))
    q_pos = (cl + t)[:, None]

    @jax.jit
    def grouped(q, pk, pv, sk, sv):
        return attend(q, (pk, sk), (pv, sv), q_pos, (pool_pos, side_pos),
                      (pool_valid, side_valid), out_dtype=jnp.float32)

    @functools.partial(jax.jit, static_argnames="round_probs")
    def expanded(q, pk, pv, sk, sv, round_probs=False):
        return expanded_f32_attend(
            q, jnp.concatenate([pk, sk], 1), jnp.concatenate([pv, sv], 1),
            q_pos, jnp.concatenate([pool_pos, side_pos], 1),
            jnp.concatenate([pool_valid, side_valid], 1),
            round_probs=round_probs)

    args = (q, pool_k, pool_v, side_k, side_v)
    new = np.asarray(grouped(*args), np.float64)
    old = np.asarray(expanded(*args), np.float64)
    rounded = np.asarray(expanded(*args, round_probs=True), np.float64)
    ref = float64_attend(
        q, jnp.concatenate([pool_k, side_k], 1),
        jnp.concatenate([pool_v, side_v], 1), np.asarray(q_pos),
        np.asarray(jnp.concatenate([pool_pos, side_pos], 1)),
        np.asarray(jnp.concatenate([pool_valid, side_valid], 1)))
    out_scale = float(np.abs(old).max())
    dev = jax.devices()[0]
    res = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "shapes": {"slots": R, "pool": S, "side": K, "heads": H,
                   "kv_heads": HKV, "head_dim": HD},
        "out_scale": out_scale,
        "grouped_vs_expanded": float(np.abs(new - old).max()) / out_scale,
        "grouped_vs_float64": float(np.abs(new - ref).max()) / out_scale,
        "expanded_vs_float64": float(np.abs(old - ref).max()) / out_scale,
        "bf16_probs_vs_expanded":
            float(np.abs(rounded - old).max()) / out_scale,
        "grouped_call_ms": wall_ms(grouped, *args),
        "expanded_call_ms": wall_ms(expanded, *args),
        "limit": LIMIT,
    }
    res["ok"] = res["grouped_vs_expanded"] <= LIMIT
    print(json.dumps(res))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
