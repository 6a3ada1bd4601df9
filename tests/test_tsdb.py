"""Telemetry plane: TSDB ring/rate/downsample semantics, SLO evaluator,
tolerant exposition parsing, decode-profiler sampling, trace
tail-retention, and the live master e2e (/api/timeseries +
/api/requests/<id>/cost) over a real batched worker."""

import math
import time

import pytest
import requests

from distributed_llm_inferencing_tpu.runtime import tsdb
from distributed_llm_inferencing_tpu.utils import trace as trace_mod
from distributed_llm_inferencing_tpu.utils.metrics import parse_prometheus
from distributed_llm_inferencing_tpu.utils.profiler import PhaseProfiler
from conftest import stop_worker

T0 = 1_700_000_000.0


# ---- TSDB ------------------------------------------------------------

def test_series_ring_bounds_and_eviction():
    db = tsdb.TSDB(window_s=100, step_s=1)
    for i in range(5000):
        db.record("n", "g", float(i), t=T0 + i)
    [s] = db.query("g", now=T0 + 5000)
    # bounded: fine ring caps at window/step buckets; nothing older than
    # the window survives
    assert len(s["points"]) <= 100 + 2
    assert s["points"][-1][1] == 4999.0
    assert all(t >= T0 + 5000 - 100 for t, _ in s["points"])


def test_counter_rate_and_reset_monotonicity():
    db = tsdb.TSDB(window_s=600, step_s=1)
    # steady 100 tokens/s...
    for i in range(10):
        db.record("w", "tokens_generated", i * 100.0, kind="counter",
                  t=T0 + i)
    # ...then the worker restarts: the cumulative counter resets to a
    # small value. The rate series must never go negative.
    db.record("w", "tokens_generated", 40.0, kind="counter", t=T0 + 10)
    db.record("w", "tokens_generated", 140.0, kind="counter", t=T0 + 11)
    [s] = db.query("tokens_generated", now=T0 + 12)
    vals = [v for _, v in s["points"]]
    assert all(v >= 0 for v in vals), vals
    assert vals[0] == 100.0
    # post-reset sample treats the new cumulative as growth-since-restart
    assert 40.0 in vals and vals[-1] == 100.0


def test_downsampling_serves_history_past_the_fine_ring():
    # window larger than the fine ring's span: old points must come from
    # the 8x-downsampled coarse ring, in time order, without overlap
    db = tsdb.TSDB(window_s=10_000, step_s=1)   # fine capped at 512
    for i in range(5000):
        db.record("n", "g", float(i % 7), t=T0 + i)
    [s] = db.query("g", window=10_000, now=T0 + 5000)
    ts = [t for t, _ in s["points"]]
    assert ts == sorted(ts)
    assert len(ts) == len(set(ts))
    span = ts[-1] - ts[0]
    assert span > 4000, span            # history beyond the 512-pt fine ring
    assert len(ts) < 1500               # ...but downsampled, not dense


def test_staleness_is_a_gap_not_a_flatline():
    db = tsdb.TSDB(window_s=600, step_s=1)
    for i in range(5):
        db.record("n", "g", 1.0, t=T0 + i)
    # the node goes silent for 100s, then returns
    db.record("n", "g", 2.0, t=T0 + 105)
    [s] = db.query("g", now=T0 + 106)
    ts = [t for t, _ in s["points"]]
    # no synthetic samples were invented inside the silence
    assert not any(T0 + 5 < t < T0 + 105 for t in ts), ts


def test_series_cap_and_catalog_and_nonfinite():
    db = tsdb.TSDB(window_s=60, step_s=1, max_series_per_node=3)
    for i in range(10):
        db.record("n", f"m{i}", 1.0, t=T0)
    assert db.series_count() == 3        # cap: new names dropped
    db.record("n", "m0", float("nan"), t=T0 + 1)
    db.record("n", "m0", float("inf"), t=T0 + 2)
    [s] = db.query("m0", now=T0 + 3)
    assert all(math.isfinite(v) for _, v in s["points"])
    assert db.catalog() == {"n": ["m0", "m1", "m2"]}


def test_ingest_prometheus_strips_and_classifies():
    db = tsdb.TSDB(window_s=60, step_s=1)
    samples = [("dli_tokens_generated_total", {}, 100.0),
               ("dli_batcher_queue_depth", {}, 4.0),
               ("dli_x_seconds_bucket", {"le": "1"}, 3.0),   # skipped
               ("dli_x_seconds_sum", {}, 1.0),               # skipped
               ("dli_x_seconds_count", {}, 3.0)]             # skipped
    db.ingest_prometheus("w0", samples, t=T0)
    db.ingest_prometheus("w0", [("dli_tokens_generated_total", {}, 150.0),
                                ("dli_batcher_queue_depth", {}, 2.0)],
                         t=T0 + 1)
    assert db.catalog() == {"w0": ["batcher_queue_depth",
                                   "tokens_generated"]}
    [s] = db.query("tokens_generated", now=T0 + 2)
    assert s["kind"] == "counter" and s["points"][-1][1] == 50.0
    [s] = db.query("batcher_queue_depth", node="w0", now=T0 + 2)
    assert s["points"][-1][1] == 2.0


# ---- tolerant exposition parsing (satellite) -------------------------

def test_parse_prometheus_tolerates_malformed_lines():
    text = "\n".join([
        "good_total 3",
        "this is : not a sample",          # malformed — must be skipped
        'labeled{a="x",b="y"} 2',
        "exp_v 1.5e-3",
        "neg_inf -Inf",
        "nan_v NaN",
        'escaped{msg="a\\"b\\\\c\\nd"} 1',
        "{} 5",                             # malformed
        "trailing_ts 7 1700000000000",      # exposition timestamp ok
    ])
    out = parse_prometheus(text)
    names = [n for n, _, _ in out]
    assert names == ["good_total", "labeled", "exp_v", "neg_inf", "nan_v",
                     "escaped", "trailing_ts"]
    d = {n: (l, v) for n, l, v in out}
    assert d["labeled"][0] == {"a": "x", "b": "y"}
    assert d["escaped"][0]["msg"] == 'a"b\\c\nd'
    assert d["exp_v"][1] == 1.5e-3
    assert d["neg_inf"][1] == float("-inf")
    assert math.isnan(d["nan_v"][1])
    assert d["trailing_ts"][1] == 7.0
    # strict mode still raises for format checkers
    try:
        parse_prometheus("not a sample !!", strict=True)
        assert False, "strict must raise"
    except ValueError:
        pass


# ---- SLO evaluator ---------------------------------------------------

def test_slo_evaluator_windows_and_burn_rate():
    ev = tsdb.SLOEvaluator(targets={"ttft_ms": 100, "itl_p95_ms": 50,
                                    "availability": 0.9},
                           fast_window_s=10, slow_window_s=100)
    now = T0 + 1000
    for i in range(90):                      # old window: all good
        ev.record(True, t=now - 100 + i)
    for i in range(10):                      # recent: half bad
        ev.record(i % 2 == 0, t=now - 10 + i)
    assert ev.attainment(10, now=now) == 0.5
    assert ev.attainment(100, now=now) == 0.95
    # budget is 10%: burning 50% of requests = 5x budget on the fast
    # window, 0.5x on the slow — the classic page-vs-wait split
    assert abs(ev.burn_rate(10, now=now) - 5.0) < 1e-6
    assert abs(ev.burn_rate(100, now=now) - 0.5) < 1e-6
    snap = ev.snapshot(now=now)
    assert snap["requests_total"] == 100 and snap["violations_total"] == 5
    assert tsdb.SLOEvaluator().attainment(10) is None


def test_cost_within_slo():
    t = {"ttft_ms": 100.0, "itl_p95_ms": 50.0, "availability": 0.99}
    assert tsdb.cost_within_slo(
        {"queue_ms": 30, "prefill_ms": 40, "itl_p95_ms": 10}, t) is True
    assert tsdb.cost_within_slo(
        {"queue_ms": 80, "prefill_ms": 40, "itl_p95_ms": 10}, t) is False
    assert tsdb.cost_within_slo(
        {"queue_ms": 1, "prefill_ms": 1, "itl_p95_ms": 90}, t) is False
    assert tsdb.cost_within_slo(None, t) is None
    assert tsdb.cost_within_slo({"queue_ms": "garbage"}, t) is None
    # schema drift (no phase keys at all) is unevaluable, not a free pass
    assert tsdb.cost_within_slo({}, t) is None
    assert tsdb.cost_within_slo({"decode_ms": 5.0}, t) is None


# ---- decode profiler -------------------------------------------------

def test_profiler_disabled_records_nothing():
    p = PhaseProfiler(enabled=False)
    rec = p.step_begin()
    assert rec is None
    with p.phase("dispatch"):
        pass
    p.step_end(rec)
    assert p.samples() == []
    assert p.summary()["steps_sampled"] == 0


def test_profiler_phases_ring_and_sampling():
    p = PhaseProfiler(capacity=16, sample_every=2, enabled=True)
    for i in range(50):
        rec = p.step_begin()
        with p.phase("dispatch"):
            time.sleep(0.0005)
        with p.phase("emit"):
            pass
        p.step_end(rec, keep=True, active=1)
    # every other step sampled, ring bounded at its capacity
    assert len(p.samples()) == 16
    summ = p.summary()
    assert summ["steps_sampled"] == 16 and summ["steps_seen"] == 50
    assert summ["phases"]["dispatch"]["s"] > 0
    # unattributed time is conserved into "other", so fractions sum ~1
    total_frac = sum(v["frac"] for v in summ["phases"].values())
    assert 0.99 <= total_frac <= 1.01, summ
    ev = p.chrome_events(pid=1)
    assert ev and all(e["ph"] == "X" for e in ev)
    # runtime toggle clears and disarms
    cfg = p.configure(enabled=False, reset=True)
    assert cfg["enabled"] is False and p.samples() == []
    # keep=False discards (idle polls)
    p.configure(enabled=True)
    p.step_end(p.step_begin(), keep=False)
    assert p.samples() == []


# ---- trace tail-retention (satellite) --------------------------------

def test_trace_retention_survives_ring_eviction():
    tr = trace_mod.Tracer(service="t", capacity=64)
    bad = tr.record("req.bad", T0, T0 + 1, attrs={"error": "boom"})
    tr.retain(bad.trace_id)
    # flood the main ring far past capacity
    for i in range(500):
        tr.record(f"noise{i}", T0 + 2, T0 + 3)
    assert not any(s.trace_id == bad.trace_id for s in tr.spans())
    kept = [s for s in tr.retained_spans() if s.trace_id == bad.trace_id]
    assert kept and kept[0].name == "req.bad"
    # spans recorded AFTER the flag are captured too
    tr.record("req.bad.child", T0 + 4, T0 + 5,
              parent=trace_mod.SpanCtx(bad.trace_id, bad.span_id))
    names = {s.name for s in tr.retained_spans()
             if s.trace_id == bad.trace_id}
    assert names == {"req.bad", "req.bad.child"}
    # retained spans reach the chrome export exactly once
    events = tr.chrome_trace()["traceEvents"]
    assert sum(1 for e in events if e["name"] == "req.bad") == 1
    # retain is idempotent
    tr.retain(bad.trace_id)
    assert sum(1 for s in tr.retained_spans()
               if s.span_id == bad.span_id) == 1


# ---- batcher cost ledger: exact phase partition ----------------------

def test_batcher_cost_record_partitions_e2e_exactly():
    import numpy as np
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)

    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    b = ContinuousBatcher(cfg, num_blocks=64, block_size=8, slots=2,
                          max_seq=64, seed=0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, 20).tolist()
    reqs = [b.submit(prompt, max_new_tokens=8,
                     sampling=SamplingParams.greedy()),
            b.submit(prompt, max_new_tokens=8,
                     sampling=SamplingParams.greedy())]
    for _ in range(200):
        b.step()
        if all(r.done.is_set() for r in reqs):
            break
    for r in reqs:
        assert not r.error
        c = r.cost
        assert c is not None
        # the three phases partition [submitted, finished) exactly
        e2e_ms = (r.finished_at - r.submitted_at) * 1e3
        phase_sum = c["queue_ms"] + c["prefill_ms"] + c["decode_ms"]
        assert abs(phase_sum - e2e_ms) < 1.0, (c, e2e_ms)
        assert c["decode_tokens"] == 8
        assert c["weight_passes"] >= 1
        assert c["kv_blocks_peak"] >= len(prompt) // 8
    # identical prompts in one wave: the second leg's prefix came from
    # the radix cache, and the ledger reconciles with the counters
    cached_total = sum(r.cost["prefill_cached_tokens"] for r in reqs)
    uncached_total = sum(r.cost["prefill_uncached_tokens"] for r in reqs)
    counters = b.metrics.snapshot()["counters"]
    assert counters.get("prefill_cached_tokens", 0) == cached_total
    assert counters["prefill_uncached_tokens"] == uncached_total
    assert cached_total >= 16   # two full 8-token blocks reused


# ---- live master e2e: /api/timeseries + cost endpoint ----------------

@pytest.mark.slow   # ~1 min (two live services + model load); always
                    # runs in check.sh's dedicated telemetry step and in
                    # scripts/telemetry_smoke.py — 'not slow' tier-1
                    # sweeps keep their 870s budget for the wide suite
def test_master_timeseries_and_cost_endpoint_live():
    from distributed_llm_inferencing_tpu.runtime.master import Master
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent

    agent = WorkerAgent()
    wsrv = agent.serve("127.0.0.1", 0, background=True)
    wport = wsrv.server_address[1]
    r = requests.post(f"http://127.0.0.1:{wport}/load_model", json={
        "model_name": "tiny-llama", "allow_random_init": True,
        "dtype": "float32", "serving": "batched", "slots": 2,
        "kv_blocks": 64, "kv_block_size": 8, "max_seq": 64}, timeout=600)
    assert r.status_code == 200, r.text
    m = Master(":memory:", health_interval=1.0, tsdb_step_s=0.3)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        r = requests.post(f"{base}/api/nodes/add", json={
            "name": "w0", "host": "127.0.0.1", "port": wport}).json()
        assert r["status"] == "success", r
        m.start_background()
        rid = requests.post(f"{base}/api/inference/submit", json={
            "model_name": "tiny-llama", "prompt": "hello telemetry",
            "max_new_tokens": 6,
            "sampling": {"do_sample": False,
                         "allow_random_init": True}}).json()["request_id"]
        deadline = time.time() + 300
        while time.time() < deadline:
            st = requests.get(
                f"{base}/api/inference/status/{rid}").json()["request"]
            if st["status"] in ("completed", "failed"):
                break
            time.sleep(0.1)
        assert st["status"] == "completed", st
        # the completed row itself carries the parsed cost record
        assert isinstance(st["cost"], dict) and "decode_ms" in st["cost"]

        # two scrape intervals -> multi-sample series for the node
        time.sleep(1.0)
        ts = requests.get(f"{base}/api/timeseries",
                          params={"metric": "batcher_queue_depth"}).json()
        [s] = [x for x in ts["series"] if x["node"] == "w0"]
        assert len(s["points"]) >= 2, ts
        ts = requests.get(f"{base}/api/timeseries",
                          params={"metric": "tokens_generated",
                                  "node": "w0"}).json()
        assert ts["series"] and ts["series"][0]["kind"] == "counter"
        # catalog mode + breaker series exist
        cat = requests.get(f"{base}/api/timeseries").json()
        assert "w0" in cat["metrics"] and "master" in cat["metrics"]
        assert "breaker_state" in cat["metrics"]["w0"]

        # cost endpoint: phases sum close to the master-observed e2e
        c = requests.get(f"{base}/api/requests/{rid}/cost").json()
        assert c["status"] == "success", c
        phase_sum = (c["cost"]["queue_ms"] + c["cost"]["prefill_ms"]
                     + c["cost"]["decode_ms"])
        assert c["e2e_ms"] and phase_sum <= c["e2e_ms"] * 1.02
        assert c["within_slo"] in (True, False)
        # SLO evaluator recorded the completion; /api/slo reports it
        slo = requests.get(f"{base}/api/slo").json()
        assert slo["requests_total"] >= 1
        # unknown id -> 404
        assert requests.get(
            f"{base}/api/requests/999999/cost").status_code == 404

        # runtime profiler toggle through the worker + master scrape
        pr = requests.post(f"http://127.0.0.1:{wport}/api/profile",
                           json={"enabled": True}).json()
        assert pr["profilers"]["tiny-llama"]["enabled"] is True
        rid2 = requests.post(f"{base}/api/inference/submit", json={
            "model_name": "tiny-llama", "prompt": "profile me",
            "max_new_tokens": 6,
            "sampling": {"do_sample": False,
                         "allow_random_init": True}}).json()["request_id"]
        deadline = time.time() + 300
        while time.time() < deadline:
            st = requests.get(
                f"{base}/api/inference/status/{rid2}").json()["request"]
            if st["status"] in ("completed", "failed"):
                break
            time.sleep(0.1)
        assert st["status"] == "completed", st
        prof = requests.get(f"{base}/api/profile").json()
        summ = prof["nodes"]["w0"]["tiny-llama"]["summary"]
        assert summ["steps_sampled"] >= 1, prof
        assert "dispatch" in summ["phases"], prof
        # profiler spans merge into the worker's chrome-trace export
        tr = requests.get(f"http://127.0.0.1:{wport}/api/trace").json()
        assert any(e.get("name", "").startswith("profile.")
                   for e in tr["traceEvents"]), "no profiler trace spans"
    finally:
        m.stop()
        stop_worker(agent)
