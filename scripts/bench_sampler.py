"""Time the sampler's full tier on the chip at the cells' decode shapes.

    python scripts/bench_sampler.py            # on the chip
    python scripts/bench_sampler.py --small    # rehearsal on the CPU

One JSON line a (rows, vocabulary): device milliseconds a run (the median
of the profiler's ``XLA Modules`` events, and beside it the part spent in
the search's steps) of ``ops/sampling.sample_batch`` (top-k off, top-p 0.9, temperature 0.7: the
benchmark's traffic) for float32 logits (a 32-bit search, 16 steps) and
for bfloat16 logits (16 bits, 8 steps), and of the nucleus search alone in
the forms PR 47 weighed: which array a step compares (``f32``: the float32
copy of the 16-bit logits; ``bf16``: the logits as they are), where its
weights come from (``mass``: the float32 ``exp(scaled - top)`` computed
once; ``recompute``: from the logits inside the step) and the bits a step
decides. The forms return the same keys; the script checks that they do.
"""

import argparse
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inferencing_tpu.ops import sampling

SHAPES = {"mistral": (16, 32000), "mimo": (64, 19072), "ouro": (8, 49152),
          "kanana": (64, 128256), "trinity": (64, 200192),
          "falcon-h1": (64, 261120)}


def _search(x, weigh, target, bits, step_bits):
    """ops/sampling._largest_key_reaching with the key's width and the
    step's bits as arguments and the weights an array or a function of
    the step's own read of ``x``."""
    fields = jnp.arange(1, 1 << step_bits, dtype=jnp.uint32)
    key_dtype = jnp.bfloat16 if bits == 16 else jnp.float32
    least = sampling._key_neg_inf(key_dtype)

    def step(i, t):
        lo = (bits - step_bits * (i + 1)).astype(jnp.uint32)
        cand = t[None, :] | (fields[:, None] << lo)
        cut = sampling._keys_to_float(jnp.maximum(cand, least),
                                      key_dtype).astype(x.dtype)
        w = weigh(x) if callable(weigh) else weigh
        reached = jnp.sum(
            jnp.where(x[None] >= cut[:, :, None], w[None], 0.0), axis=-1)
        taken = jnp.sum(reached >= target[None, :], axis=0)
        return t | (taken.astype(jnp.uint32) << lo)

    return jax.lax.fori_loop(0, bits // step_bits, step,
                             jnp.zeros(x.shape[:1], jnp.uint32))


def _nucleus(compare, weights, step_bits):
    def run(logits, logits32, temps, top_ps):
        t = jnp.maximum(temps, 1e-6)[:, None]
        scaled = logits.astype(jnp.float32) / t
        top = jnp.max(scaled, axis=-1)
        mass = jnp.exp(scaled - top[:, None])
        target = top_ps * jnp.sum(mass, axis=-1)
        x = logits32 if compare == "f32" else logits
        if weights == "recompute":
            def weigh(v):
                return jnp.exp(v.astype(jnp.float32) / t - top[:, None])
        else:
            weigh = mass
        return _search(x, weigh, target, 16, step_bits)
    run.__name__ = f"search_{compare}_{weights}_{step_bits}bit"
    return jax.jit(run)


def _device_ms(trace_dir):
    """{program: (median device ms a run, ms a run in the search's steps:
    its ``select_reduce_fusion`` ops with a [C, R] result)} from the
    trace's ``XLA Modules`` and ``XLA Ops`` lines."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        steps = [(ev.start_ns, ev.duration_ns)
                 for ev in lines.get("XLA Ops", [])
                 if ev.name.lstrip("%").startswith("select_reduce_fusion")
                 and re.search(r"= f32\[\d+,\d+\]\{", ev.name)]
        runs = {}
        for ev in lines.get("XLA Modules", []):
            inside = sum(d for s, d in steps if ev.start_ns <= s
                         < ev.start_ns + ev.duration_ns)
            runs.setdefault(ev.name.split("(")[0], []).append(
                (ev.duration_ns * 1e-6, inside * 1e-6))
        for name, r in runs.items():
            out[name] = (statistics.median(a for a, _ in r),
                         statistics.median(b for _, b in r))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not args.small and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: --small rehearses on the CPU")
    dev = jax.devices()[0]
    for name, (r, v) in SHAPES.items():
        if args.only and name not in args.only.split(","):
            continue
        if args.small:
            v = v // 64
        rng = np.random.default_rng([args.seed, r, v])
        logits = jnp.asarray(rng.normal(size=(r, v)) * 2.5, jnp.bfloat16)
        logits32 = logits.astype(jnp.float32)
        i = jnp.arange(r, dtype=jnp.int32)
        temps = jnp.full((r,), 0.7, jnp.float32)
        top_ps = jnp.full((r,), 0.9, jnp.float32)
        rest = (i, i, temps, jnp.zeros((r,), jnp.int32), top_ps,
                jnp.ones((r,), bool))

        def sample_f32(*a):
            return sampling.sample_batch(*a)

        def sample_bf16(*a):
            return sampling.sample_batch(*a)

        programs = {"sample_f32": (jax.jit(sample_f32), (logits32,) + rest),
                    "sample_bf16": (jax.jit(sample_bf16), (logits,) + rest)}
        for compare, weights, step_bits in [
                ("bf16", "mass", 2), ("f32", "mass", 2),
                ("bf16", "recompute", 2), ("bf16", "mass", 4),
                ("f32", "mass", 4), ("bf16", "mass", 1),
                ("f32", "mass", 1)]:
            programs[f"search_{compare}_{weights}_{step_bits}bit"] = (
                _nucleus(compare, weights, step_bits),
                (logits, logits32, temps, top_ps))
        outs = {tag: jax.block_until_ready(fn(*a))
                for tag, (fn, a) in programs.items()}
        trace_dir = tempfile.mkdtemp(prefix="bench_sampler_")
        jax.profiler.start_trace(trace_dir)
        for fn, a in programs.values():
            for _ in range(3 if args.small else args.calls):
                jax.block_until_ready(fn(*a))
        jax.profiler.stop_trace()
        device = _device_ms(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        line = {"shape": name, "rows": r, "vocab": v,
                "device": dev.device_kind,
                "tokens_equal": bool(
                    (outs["sample_f32"] == outs["sample_bf16"]).all()),
                "keys_differ": [
                    tag for tag, out in outs.items()
                    if tag.startswith("search_") and not bool(
                        (out == outs["search_bf16_mass_2bit"]).all())]}
        for tag in programs:
            whole, steps = device.get("jit_" + tag, (None, None))
            line[tag + "_ms"], line[tag + "_steps_ms"] = whole, steps
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
