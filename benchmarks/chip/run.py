#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are files found by the
names in BENCHMARK.json (README.md beside this file). The process builds
the configuration's ContinuousBatcher, warms the traffic file's declared
shapes, measures for --seconds, and prints one JSON object as the last
line of its standard output. Off a TPU it fails without a result line,
unless the configuration file says `"rehearsal": true`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                        # noqa: E402
import json                            # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402
import threading                       # noqa: E402
from pathlib import Path               # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT))

import loadgen                         # noqa: E402
import stats                           # noqa: E402
from readers import load_reader         # noqa: E402
import xplane                          # noqa: E402

# source key -> ModelConfig attribute: the configuration file states the
# sizes as the source names them, and the run checks the program agrees
SOURCE_KEYS = {
    "hidden_size": "hidden_size", "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "sliding_window": "sliding_window",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
}
POLL_S = 0.002
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(kind: str, name: str) -> dict:
    """configs/<name>.json or traffic/<name>.json; a path is taken as is
    (tests keep a rehearsal mix outside the cells' directories)."""
    path = Path(name) if name.endswith(".json") else HERE / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


# ---- the system under test -------------------------------------------

def check_device(config: dict, chips: int):
    """The devices JAX found, or exit: a measurement off the chip, or on
    fewer chips than the cell asks for, gives no result."""
    from distributed_llm_inferencing_tpu.utils import platform
    platform.enable_compilation_cache()    # <checkout>/.jax_cache, or the env's
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not config.get("rehearsal"):
        sys.exit(f"error: this cell measures a TPU and JAX found "
                 f"{devices[0].platform}; no result")
    if len(devices) < chips:
        sys.exit(f"error: the cell asks for {chips} chips and JAX found "
                 f"{len(devices)}; no result")
    return devices


def build_batcher(config: dict):
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    cfg = get_config(config["registry"]).replace(**config["overrides"])
    for key, attr in SOURCE_KEYS.items():
        if key in config and getattr(cfg, attr) != config[key]:
            sys.exit(f"error: {config['registry']} runs {attr}="
                     f"{getattr(cfg, attr)!r}, the configuration file says "
                     f"{key}={config[key]!r}")
    kw = dict(config["batcher"])
    mesh = MeshSpec(**kw.pop("mesh"))
    return ContinuousBatcher(cfg, None, seed=int(config["weight_seed"]),
                             mesh_spec=mesh, **kw)


def step_until_idle(b, limit_s: float = 900.0):
    t_end = time.perf_counter() + limit_s
    while b.inflight():
        b.step()
        if time.perf_counter() > t_end:
            sys.exit("error: warm-up did not drain")


def warm(b, traffic: dict, vocab: int) -> int:
    """Run every program the traffic file declares once, by stepping the
    batcher by hand before its thread starts: one wave per (prefix bucket,
    tail bucket, wave bucket) of prompts that finish at their first token,
    and one request per decode-chunk size. A prefix bucket above 0 is
    reached by first serving that many blocks alone, so that the wave's
    prompts find them in the radix cache. Returns the waves run."""
    import numpy as np
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    shapes = traffic["warm_shapes"]
    greedy = SamplingParams.greedy()
    rng = np.random.default_rng(12345)
    bs = b.block_size

    def tokens(n):
        return rng.integers(3, vocab, n).tolist()
    n = 0
    for pb in shapes.get("prefix_blocks", [0]):
        prefix = tokens(pb * bs)
        if prefix:
            b.submit(prefix + tokens(1), max_new_tokens=1, sampling=greedy,
                     seed=0)
            step_until_idle(b)
        for t in shapes["tail_buckets"]:
            for w in shapes["wave_buckets"]:
                if w > b.slots:
                    continue
                for _ in range(w):
                    b.submit(prefix + tokens(t - bs // 2), max_new_tokens=1,
                             sampling=greedy, seed=0)
                step_until_idle(b)
                n += 1
    b.warm_decode_programs()
    for k in shapes["decode_chunks"]:
        b.submit(tokens(min(shapes["tail_buckets"]) - bs // 2),
                 max_new_tokens=k + 1, sampling=greedy, seed=0)
        step_until_idle(b)
        n += 1
    return n


def probe(b, vocab: int) -> dict:
    """Greedy first tokens of the fixed probe prompts, served alone and
    then all at once (the second pass finds the prompts' blocks in the
    radix cache, so it checks the prefix path against full prefill).
    probes.json says what share must agree, and why not all."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    import numpy as np
    with open(HERE / "probes.json") as f:
        spec = json.load(f)
    greedy = SamplingParams.greedy()
    prompts = [np.random.default_rng(p["seed"]).integers(
        3, vocab, p["length"]).tolist() for p in spec["probes"]
        if p["length"] + 1 <= b.max_seq][:b.slots]
    alone = []
    for p in prompts:
        r = b.submit(p, max_new_tokens=1, sampling=greedy, seed=0)
        step_until_idle(b)
        alone.append(list(r.tokens))
    reqs = [b.submit(p, max_new_tokens=1, sampling=greedy, seed=0)
            for p in prompts]
    step_until_idle(b)
    together = [list(r.tokens) for r in reqs]
    agree = sum(a == t and len(a) == 1 for a, t in zip(alone, together))
    return {"alone": alone, "together": together, "agree": agree,
            "of": len(prompts),
            "ok": agree >= spec["min_agree_share"] * len(prompts)}


# ---- the load --------------------------------------------------------

class Rec:
    """One request as the benchmark saw it, on the benchmark's clock."""
    __slots__ = ("spec", "due", "submitted", "times", "req")

    def __init__(self, spec, due):
        self.spec, self.due, self.times = spec, due, []
        self.submitted, self.req = None, None


def drive(b, traffic: dict, seed: int, seconds: float, vocab: int,
          at_mark=None) -> dict:
    """Offer the mix to a started batcher for `seconds`. The stream
    callback runs on the scheduler's thread, so it only appends a time.
    `at_mark` is (seconds from start, callable): run once from this
    thread's loop (it starts the tracer's thread)."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    now = time.perf_counter
    sampling = SamplingParams(**traffic["sampling"])
    eos = traffic.get("eos_token_id")
    recs = []

    def build(spec, due):
        rec = Rec(spec, due)

        def cb(_tok, _append=rec.times.append, _now=now):
            _append(_now())
        recs.append(rec)
        return rec, dict(prompt=spec.prompt(vocab),
                         max_new_tokens=spec.out_len, sampling=sampling,
                         eos_token_id=eos, stream_cb=cb,
                         seed=spec.sample_seed)

    def submit(spec, due):
        rec, kw = build(spec, due)
        rec.submitted = now()
        rec.req = b.submit(**kw)
        return rec

    def mark(t_rel):
        nonlocal at_mark
        if at_mark and t_rel >= at_mark[0]:
            at_mark[1]()
            at_mark = None

    counters0 = b.metrics.snapshot()["counters"]
    t0 = now()
    t1 = t0 + seconds
    if traffic["loop"] == "closed":
        specs = loadgen.closed_loop(traffic, seed)
        # every caller's first request in ONE enqueue: sent one by one, the
        # scheduler wakes at the first and its first wave and chunk run
        # nearly empty, or not, as the threads happen to race
        first = [build(next(specs), t0) for _ in range(traffic["callers"])]
        reqs = b.submit_many([kw for _, kw in first])
        live = [rec for rec, _ in first]
        for rec, req in zip(live, reqs):
            rec.submitted, rec.req = now(), req
        while now() < t1:
            for i, rec in enumerate(live):
                if rec.req.done.is_set():
                    live[i] = submit(next(specs), now())
            mark(now() - t0)
            time.sleep(POLL_S)
    elif traffic["loop"] == "open":
        for spec in loadgen.open_loop(traffic, seed, seconds):
            while True:
                wait = t0 + spec.due - now()
                if wait <= 0:
                    break
                mark(now() - t0)
                time.sleep(min(wait, 0.05))
            submit(spec, t0 + spec.due)
        while now() < t1:
            mark(now() - t0)
            time.sleep(min(max(t1 - now(), 0), 0.05))
    else:
        sys.exit(f"error: unknown loop kind {traffic['loop']!r}")
    counters1 = b.metrics.snapshot()["counters"]

    unfinished = 0
    if traffic["at_window_end"] == "drain":
        t_drain = t1 + float(traffic["drain_s"])
        for rec in recs:
            rec.req.done.wait(max(0.0, t_drain - now()))
    for rec in recs:
        if not rec.req.done.is_set():
            unfinished += 1
            rec.req.cancel()
    for rec in recs:
        if not rec.req.done.wait(120):
            sys.exit("error: a cancelled request never ended")
    return {"t0": t0, "t1": t1, "recs": recs, "unfinished": unfinished,
            "counters": {k: counters1.get(k, 0) - counters0.get(k, 0)
                         for k in counters1}}


def request_rows(run: dict) -> list:
    """Plain dicts for the metric readers, times in seconds from the
    window's start."""
    t0 = run["t0"]
    rows = []
    for rec in run["recs"]:
        r = rec.req
        rows.append({
            "due": rec.due - t0, "submitted": rec.submitted - t0,
            "times": [t - t0 for t in rec.times],
            "prompt_len": rec.spec.prompt_len, "max_new": rec.spec.out_len,
            "tokens": len(r.tokens), "error": r.error,
            "cost": dict(r.cost or {}),
        })
    return rows


def judge(run: dict, traffic: dict, vocab: int) -> dict:
    """attempted / failed / per-request output checks. Closed loop:
    requests cancelled at the window's end are neither failed nor
    counted. Open loop: every request due in the window is attempted, and
    one that failed or was unfinished drain_s after the window failed."""
    drain = traffic["at_window_end"] == "drain"
    attempted = failed = bad = 0
    for rec in run["recs"]:
        r = rec.req
        cancelled = r.error == "cancelled"
        if cancelled and not drain:
            continue
        attempted += 1
        if r.error:
            failed += 1
            continue
        n = len(r.tokens)
        if not (0 <= n <= rec.spec.out_len and len(rec.times) == n
                and (n > 0 or r.first_token_at is not None)
                and all(0 <= t < vocab for t in r.tokens)):
            bad += 1
    return {"attempted": attempted, "failed": failed, "bad_outputs": bad}


def max_event_gap(record: dict):
    """Longest time between two emission events inside the window."""
    t0, t1 = record["window"]
    ev = stats.events([t for r in record["requests"] for t in r["times"]
                       if t0 <= t <= t1])
    return max((b[0] - a[1] for a, b in zip(ev, ev[1:])), default=None)


# ---- the traced interval ----------------------------------------------

class Tracer(threading.Thread):
    """Take a profiler trace of `seconds` from the middle of the window,
    on a thread of its own (starting and stopping block for a while)."""

    def __init__(self, out_dir: Path, seconds: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.out_dir, self.seconds = out_dir, seconds
        self.result = None

    def run(self):
        import jax
        from distributed_llm_inferencing_tpu.utils import trace as dli_trace
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        ta = time.time()
        time.sleep(self.seconds)
        tb = time.time()
        jax.profiler.stop_trace()
        # decode chunks that began and ended between start and stop are
        # wholly in the trace; the batcher's own span names their size
        chunks = [[s.start, s.end, int(s.attrs.get("k", 0))]
                  for s in dli_trace.get_tracer().spans()
                  if s.name == "batcher.decode_chunk"
                  and s.start >= ta and s.end <= tb]
        self.result = {"seconds": tb - ta, "chunks": sorted(chunks)}


# ---- main -----------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--config", help="configuration name or .json path "
                    "(with --traffic, instead of --workload)")
    ap.add_argument("--traffic", help="traffic name or .json path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def resolve(args):
    """(BENCHMARK.json, the cell to run): a cell of the manifest, or an
    explicit configuration and traffic file (rehearsal), which reports
    every metric that has a reader."""
    with open(ROOT / "BENCHMARK.json") as f:
        manifest = json.load(f)
    if args.workload:
        cells = {w["name"]: w for w in manifest["workloads"]}
        if args.workload not in cells:
            sys.exit(f"error: no cell {args.workload!r} in BENCHMARK.json; "
                     f"cells: {sorted(cells)}")
        cell = dict(cells[args.workload])
        for section in ("end_to_end", "per_layer"):
            cell[section] = [m["name"] for m in manifest[section]
                             if "workloads" not in m
                             or cell["name"] in m["workloads"]]
        return manifest, cell
    if not (args.config and args.traffic):
        sys.exit("error: give --workload, or --config and --traffic")
    return manifest, {
        "name": f"{Path(args.config).stem}.{Path(args.traffic).stem}",
        "chips": 1, "config": args.config, "traffic": args.traffic,
        "end_to_end": sorted(p.stem for p in
                             (HERE / "e2e_metrics").glob("*.py")),
        "per_layer": sorted(p.stem for p in
                            (HERE / "layer_metrics").glob("*.py"))}


def device_record(devices, trace: dict) -> dict:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": max(peaks)}
    if trace:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    manifest, cell = resolve(args)
    name = cell["name"]
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    seconds = float(args.seconds if args.seconds is not None
                    else manifest["run_seconds"])
    devices = check_device(config, cell["chips"])
    with open(HERE / "peaks.json") as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if kind not in peaks and not config.get("rehearsal"):
        sys.exit(f"error: no peaks for device kind {kind!r} in peaks.json")

    import jax
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, dur, **kw: compiles.append(time.perf_counter())
        if event == COMPILE_EVENT else None)

    b = build_batcher(config)
    vocab = config["vocab_size"]
    t_built = time.perf_counter()
    programs = warm(b, traffic, vocab)
    t_warm = time.perf_counter()
    probes = probe(b, vocab)
    b.start()
    tracer = None
    at_mark = None
    if args.trace:
        b.profiler.configure(enabled=True, sample_every=1, reset=True)
        tcfg = traffic["trace"]
        tracer = Tracer(ROOT / "chiprun_out" / "bench_trace" / name,
                        min(float(tcfg["seconds"]), seconds / 2))
        at_mark = (float(tcfg["start_frac"]) * seconds, tracer.start)
    setup_s = time.perf_counter() - T_START
    n_compiled_setup = len(compiles)

    run = drive(b, traffic, args.seed, seconds, vocab, at_mark)
    if tracer is not None:
        tracer.join(300)
    phases = b.profiler.summary() if args.trace else None
    b.stop()
    # a program compiled, or read from the cache, after the window began
    first_run_in_window = sum(1 for t in compiles if t >= run["t0"])

    trace = {}
    traced = None
    if tracer is not None and tracer.result is not None:
        traced = tracer.result
        path = xplane.find_xplane(str(tracer.out_dir))
        if path:
            trace = xplane.reduce(xplane.read_events(path))
        shutil.rmtree(tracer.out_dir, ignore_errors=True)   # tens of MB

    verdict = judge(run, traffic, vocab)
    record = {
        "cell": name, "seconds": seconds, "setup_s": setup_s,
        "window": [0.0, run["t1"] - run["t0"]],
        "requests": request_rows(run),
        "counters": run["counters"], "phases": phases,
        "trace": trace, "traced": traced,
        "config": config, "traffic": traffic, "peaks": peaks.get(kind),
    }
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    kind_dir, section = (("layer_metrics", "per_layer") if args.trace
                         else ("e2e_metrics", "end_to_end"))
    metrics = {}
    for metric in cell[section]:
        value = load_reader(kind_dir, metric)(record)
        if value is not None:
            metrics[metric] = {"value": value, "unit": units.get(metric, "")}

    correct = (probes["ok"] and verdict["bad_outputs"] == 0
               and first_run_in_window == 0)
    out = {
        "correct": bool(correct), "attempted": verdict["attempted"],
        "failed": verdict["failed"] , "metrics": metrics,
        "device": device_record(devices, trace),
        "cell": name, "seed": args.seed, "seconds": seconds,
        "checks": {"probes": probes, "bad_outputs": verdict["bad_outputs"],
                   "programs_first_run_in_window": first_run_in_window,
                   "unfinished_at_end": run["unfinished"],
                   # a run that reads far off: did the stream stall?
                   "max_event_gap_s": max_event_gap(record),
                   # a starved generator must not be read as a fast server
                   "gen_lag_p95_ms": load_reader(
                       "layer_metrics", "gen_lag_p95_ms")(record)},
        "setup": {"build_s": t_built - T_START, "warm_s": t_warm - t_built,
                  "probe_and_start_s": setup_s - (t_warm - T_START),
                  "programs_warmed": programs,
                  "compile_events_in_setup": n_compiled_setup},
        "requests_sent": len(run["recs"]),
        # for whoever reads the ledger: what the window's work was
        "counters": {k: v for k, v in run["counters"].items()
                     if v and k.startswith(("batcher_", "prefill_"))},
    }
    if trace:
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
