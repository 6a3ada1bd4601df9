"""Prometheus exposition-format correctness (strict regex checker, no new
deps) and live batcher gauges/histograms moving during a batched run.
"""

import math
import re

import numpy as np
import requests

from distributed_llm_inferencing_tpu.utils.metrics import (
    HIST_BUCKETS, Metrics, hist_quantile, parse_prometheus, sanitize_name)
from conftest import stop_worker

NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
SAMPLE_RE = re.compile(
    rf"^({NAME})"
    rf'(\{{[a-zA-Z_][a-zA-Z0-9_]*="[^"\\\n]*"'
    rf'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"\\\n]*")*\}})?'
    r" [-+]?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][-+]?[0-9]+)?|\+?Inf|NaN)$")
COMMENT_RE = re.compile(rf"^# (HELP|TYPE) ({NAME}) .+$")


def check_exposition(text: str):
    """Strict text-format checker: every line is a valid sample or
    HELP/TYPE comment; TYPE precedes its family's samples; histograms
    have cumulative le= buckets ending at +Inf with matching _count."""
    types = {}
    samples = []
    for line in text.splitlines():
        if not line:
            continue
        mc = COMMENT_RE.match(line)
        if mc:
            if mc.group(1) == "TYPE":
                types[mc.group(2)] = line.split()[-1]
            continue
        ms = SAMPLE_RE.match(line)
        assert ms, f"invalid exposition line: {line!r}"
        samples.append(line)
        name = ms.group(1)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert base in types or name in types, \
            f"sample {name!r} has no preceding # TYPE"
    # histogram structure
    hists = {}
    for name, labels, value in parse_prometheus(text):
        if name.endswith("_bucket"):
            hists.setdefault(name[:-7], []).append(
                (float(labels["le"]), value))
    for base, buckets in hists.items():
        assert types.get(base) == "histogram"
        les = [le for le, _ in buckets]
        counts = [c for _, c in buckets]
        assert les == sorted(les) and les[-1] == math.inf, \
            f"{base}: buckets not cumulative-ordered with +Inf"
        assert counts == sorted(counts), f"{base}: non-monotone buckets"
    flat = {(n, tuple(sorted(l.items()))): v
            for n, l, v in parse_prometheus(text)}
    for base, buckets in hists.items():
        inf_count = dict(buckets)[math.inf]
        assert flat[(base + "_count", ())] == inf_count
        assert (base + "_sum", ()) in flat
    return samples


def test_prometheus_strict_format_and_collisions():
    m = Metrics()
    # dots/dashes in names must sanitize; counter vs gauge sharing a name
    # must NOT collide into one exposition line
    m.inc("requests.completed-ok", 3)
    m.gauge("requests.completed-ok", 7)
    m.inc("tokens_generated", 120)
    m.gauge("queue depth", 4)   # space needs sanitizing too
    for v in (0.002, 0.004, 0.03, 0.3, 2.0, 80.0):
        m.observe("load model", v)
    text = m.prometheus()
    check_exposition(text)
    flat = {n: v for n, l, v in parse_prometheus(text) if not l}
    assert flat["dli_requests_completed_ok_total"] == 3
    assert flat["dli_requests_completed_ok"] == 7
    assert flat["dli_queue_depth"] == 4
    assert flat["dli_load_model_seconds_count"] == 6
    assert abs(flat["dli_load_model_seconds_sum"] - 82.336) < 1e-6
    # real cumulative buckets, not two quantile samples
    b = {l["le"]: v for n, l, v in parse_prometheus(text)
         if n == "dli_load_model_seconds_bucket"}
    assert b["+Inf"] == 6
    assert b["0.005"] == 2 and b["0.05"] == 3
    assert b["60"] == 5 and b["120"] == 6   # 80s lands between


def test_sanitize_name():
    assert sanitize_name("a.b-c d") == "a_b_c_d"
    assert re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", sanitize_name("9lives"))


def test_hist_quantile_interpolation():
    # 10 observations uniform in (0, 1]: p50 lands mid-scale
    buckets = [(0.1, 1), (0.5, 5), (1.0, 10), (math.inf, 10)]
    p50 = hist_quantile(buckets, 0.5)
    assert 0.4 <= p50 <= 0.5
    p95 = hist_quantile(buckets, 0.95)
    assert 0.5 < p95 <= 1.0
    assert hist_quantile([], 0.5) is None
    assert hist_quantile([(math.inf, 0)], 0.5) is None


def test_snapshot_has_p95():
    m = Metrics()
    for i in range(100):
        m.observe("t", i / 100)
    snap = m.snapshot()["timings"]["t"]
    assert snap["p50"] <= snap["p95"] <= snap["p99"]
    assert snap["count"] == 100


def test_worker_metrics_endpoint_parses_strict():
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
    agent = WorkerAgent()
    srv = agent.serve(host="127.0.0.1", port=0, background=True)
    port = srv.server_address[1]
    try:
        agent.metrics.inc("requests_completed")
        agent.metrics.observe("inference", 0.123)
        r = requests.get(f"http://127.0.0.1:{port}/metrics")
        assert r.status_code == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        check_exposition(r.text)
        names = {n for n, _, _ in parse_prometheus(r.text)}
        assert "dli_requests_completed_total" in names
        assert "dli_inference_seconds_bucket" in names
    finally:
        stop_worker(agent)


def test_master_cluster_metrics_aggregation():
    """The master scrapes each worker's /metrics exposition and serves one
    parsed cluster snapshot (counters summed, histogram p50/p95 derived
    from the cumulative buckets)."""
    from distributed_llm_inferencing_tpu.runtime.master import Master
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
    agent = WorkerAgent()
    wsrv = agent.serve(host="127.0.0.1", port=0, background=True)
    wport = wsrv.server_address[1]
    m = Master(":memory:", dispatcher_threads=1, health_interval=30)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    mport = msrv.server_address[1]
    try:
        agent.metrics.inc("tokens_generated", 42)
        for v in (0.01, 0.02, 0.04, 0.08):
            agent.metrics.observe("batcher_ttft", v)
        r = requests.post(f"http://127.0.0.1:{mport}/api/nodes/add",
                          json={"name": "mw", "host": "127.0.0.1",
                                "port": wport})
        assert r.status_code == 200, r.text
        cm = requests.get(
            f"http://127.0.0.1:{mport}/api/cluster_metrics").json()
        assert cm["status"] == "success"
        (node,) = cm["nodes"]
        assert node["scraped"], node
        assert node["counters"]["tokens_generated"] == 42
        h = node["histograms"]["batcher_ttft_seconds"]
        assert h["count"] == 4 and 0.01 <= h["p50"] <= 0.08
        assert cm["cluster"]["counters"]["tokens_generated"] == 42
        assert cm["cluster"]["workers_scraped"] == 1
        assert "counters" in cm["master"]
    finally:
        m.stop()
        stop_worker(agent)


def test_batcher_gauges_and_histograms_move():
    """Queue-depth/active-slot/free-block gauges and TTFT / inter-token
    histograms must move during a real batched run."""
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)

    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    met = Metrics()
    b = ContinuousBatcher(cfg, num_blocks=64, block_size=8, slots=2,
                          max_seq=64, seed=0, metrics=met)
    rng = np.random.default_rng(0)
    reqs = [b.submit(rng.integers(0, cfg.vocab_size, 5 + i).tolist(),
                     max_new_tokens=8, sampling=SamplingParams.greedy())
            for i in range(4)]
    # 4 submissions into 2 slots: the queue-depth gauge saw the backlog
    assert met.snapshot()["gauges"]["batcher_queue_depth"] >= 2
    for _ in range(200):
        b.step()
        if all(r.done.is_set() for r in reqs):
            break
    assert all(r.done.is_set() for r in reqs)
    assert not any(r.error for r in reqs)

    snap = met.snapshot()
    g = snap["gauges"]
    assert g["batcher_queue_depth"] == 0          # drained
    assert g["batcher_active_slots"] == 0
    assert g["batcher_free_kv_blocks"] == b.pool.free_count() > 0
    c = snap["counters"]
    assert c["batcher_requests_submitted"] == 4
    assert c["batcher_requests_completed"] == 4
    t = snap["timings"]
    assert t["batcher_ttft"]["count"] == 4
    assert t["batcher_e2e_latency"]["count"] == 4
    # per-GAP histogram: one observation per token after each request's
    # first -> 4 requests x 7 gaps
    assert t["batcher_inter_token"]["count"] == 4 * 7
    assert t["batcher_ttft"]["p50"] > 0
    assert t["batcher_decode_chunk"]["count"] >= 1
    assert t["batcher_admit_wave"]["count"] >= 1
    # and the whole thing round-trips through strict exposition
    check_exposition(met.prometheus())
