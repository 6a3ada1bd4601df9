#!/usr/bin/env python3
"""Run one cell several times, each run a process of its own, and print
each metric's median and spread (distance between the first and third
quartile of `statistics.quantiles(values, n=4)`, as a share of the
median): what a builder sets a bound from. This parent never imports
JAX, so each child has the chip to itself.

    python3 benchmarks/chip/sets.py --workload <name> [--sets 2] [--runs 6]
        [--seconds S] [--trace 0|1] [--seed0 N] [--out chiprun_out/bench]
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed0", type=int, default=2147483700)
    ap.add_argument("--out", default="chiprun_out/bench")
    args = ap.parse_args()
    out_dir = ROOT / args.out
    out_dir.mkdir(parents=True, exist_ok=True)
    log = out_dir / f"{args.workload}.trace{args.trace}.jsonl"
    sets = []
    for s in range(args.sets):
        rows = []
        for r in range(args.runs):
            cmd = [sys.executable, str(HERE / "run.py"), "--workload",
                   args.workload, "--seed", str(args.seed0 + r),
                   "--trace", str(args.trace)]
            if args.seconds is not None:
                cmd += ["--seconds", str(args.seconds)]
            t0 = time.time()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            last = (p.stdout.strip().splitlines() or [""])[-1]
            if p.returncode != 0 or not last.startswith("{"):
                print(f"set {s} run {r}: exit {p.returncode}\n"
                      f"{p.stderr[-3000:]}", flush=True)
                continue
            row = json.loads(last)
            row["wall_s"] = time.time() - t0
            row["set"], row["run"] = s, r
            rows.append(row)
            with open(log, "a") as f:
                f.write(json.dumps(row) + "\n")
            print(f"set {s} run {r} seed {args.seed0 + r} "
                  f"correct={row['correct']} attempted={row['attempted']} "
                  f"failed={row['failed']} wall={row['wall_s']:.0f}s "
                  + " ".join(f"{k}={v['value']:.4f}"
                             for k, v in row["metrics"].items()),
                  flush=True)
        sets.append(rows)
    names = sorted({k for rows in sets for row in rows for k in row["metrics"]})
    for name in names:
        per_set = []
        for rows in sets:
            vals = [row["metrics"][name]["value"] for row in rows
                    if name in row["metrics"]]
            # the first run of the first set compiles: its set-up is apart
            if name == "setup_s" and rows is sets[0]:
                vals = vals[1:]
            if vals:
                per_set.append((statistics.median(vals), spread(vals),
                                len(vals)))
        print(name, " | ".join(
            f"median {m:.4f} spread {('%.4f' % sp) if sp is not None else 'n/a'}"
            f" n={n}" for m, sp, n in per_set), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
