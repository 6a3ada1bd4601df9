#!/usr/bin/env python3
"""Find an open-loop mix's knee once, in one warm process: offer the mix
at rates in steps of 1.25x and report, for each, what failed and whether
the queue grew (median TTFT of the window's last third against its first
third). The knee is the highest rate at which nothing failed and that
ratio stayed within 1.5. The cell's traffic file then fixes its rate at
four fifths of the knee; this tool is never part of a measured run.

    python3 benchmarks/chip/sweep.py --config mistral-7b-int8 \
        --traffic chat-steady --start-rps 1.0 --steps 8 --seconds 40 \
        --out chiprun_out/bench/sweep.json
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench
import stats


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--start-rps", type=float, required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2147483801)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    config = bench.load_json("configs", args.config)
    traffic = bench.load_json("traffic", args.traffic)
    devices = bench.check_device(config, 1)
    b = bench.build_batcher(config)
    vocab = config["vocab_size"]
    bench.warm(b, traffic, vocab)
    b.start()
    rows, knee, rate = [], None, args.start_rps
    for step in range(args.steps):
        mix = dict(traffic, rate_rps=rate, drain_s=60)
        r = bench.drive(b, mix, args.seed + step, args.seconds, vocab)
        v = bench.judge(r, mix, vocab)
        reqs = bench.request_rows(r)

        def third(lo, hi):
            return stats.percentile(stats.ttfts_ms(
                [q for q in reqs if lo <= q["due"] < hi]), 50)
        s = args.seconds
        first, last = third(0, s / 3), third(2 * s / 3, s)
        times = [t for q in reqs for t in q["times"]]
        ttft = stats.ttfts_ms(reqs)
        row = {
            "rate_rps": rate, "sent": len(reqs), "failed": v["failed"],
            "unfinished_60s_after": r["unfinished"],
            "ttft_p50_first_third_ms": first, "ttft_p50_last_third_ms": last,
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "out_tok_s": stats.between_events_rate(times, 0.0, s),
        }
        row["steady"] = bool(v["failed"] == 0 and first and last
                             and last <= 1.5 * first)
        if row["steady"]:
            knee = rate
        rows.append(row)
        print(json.dumps(row), flush=True)
        while b.inflight():
            time.sleep(0.1)
        if not row["steady"] and knee is not None:
            break
        rate *= 1.25
    b.stop()
    out = {"config": args.config, "traffic": args.traffic,
           "seconds_per_rate": args.seconds, "device": devices[0].device_kind,
           "rule": "knee = highest rate with no failure and median TTFT of "
                   "the last third within 1.5x that of the first third",
           "rates": rows, "knee_rps": knee,
           "cell_rate_rps": None if knee is None else round(0.8 * knee, 2)}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
