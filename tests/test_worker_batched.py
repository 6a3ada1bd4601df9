"""Worker agent in batched serving mode over localhost HTTP.

The reference worker serialized all inference behind one sync gunicorn
worker (reference: worker/Dockerfile:47). Batched mode instead runs the
continuous batcher (runtime/batcher.py) behind the same /inference API:
concurrent requests share decode steps.
"""

import json
import threading
import time

import pytest
import requests

from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
from conftest import stop_worker


@pytest.fixture(scope="module")
def worker():
    agent = WorkerAgent()
    srv = agent.serve(host="127.0.0.1", port=0, background=True)
    port = srv.server_address[1]
    r = requests.post(f"http://127.0.0.1:{port}/load_model", json={
        "model_name": "tiny-llama", "allow_random_init": True,
        "serving": "batched", "kv_blocks": 64, "kv_block_size": 8,
        "slots": 4, "max_seq": 128, "dtype": "float32",
    }, timeout=300)
    assert r.status_code == 200, r.text
    yield agent, port
    stop_worker(agent)


def _url(port, path):
    return f"http://127.0.0.1:{port}{path}"


def test_health_reports_scheduler(worker):
    _, port = worker
    h = requests.get(_url(port, "/health")).json()
    [m] = h["loaded_models"]
    assert m["serving"] == "batched"
    assert m["scheduler"]["slots"] == 4


def test_concurrent_inference_shares_batch(worker):
    agent, port = worker
    results = {}

    def go(i):
        r = requests.post(_url(port, "/inference"), json={
            "model_name": "tiny-llama",
            "prompt_tokens": [3, 5, 7, 11 + i],
            "max_new_tokens": 16,
            "sampling": {"do_sample": False},
        }, timeout=300)
        results[i] = r.json()

    threads = [threading.Thread(target=go, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert len(results) == 6
    for i, r in results.items():
        assert r["status"] == "success", r
        assert len(r["tokens"]) == 16
        assert r["ttft_ms"] is not None
        # cost ledger rides every completed response: phases partition
        # the e2e span (queue+prefill+decode ≈ execution_time, which
        # adds only handler overhead around the batcher span)
        c = r["cost"]
        phase_sum_ms = c["queue_ms"] + c["prefill_ms"] + c["decode_ms"]
        assert 0 < phase_sum_ms <= r["execution_time"] * 1e3 * 1.02, r
        assert c["decode_tokens"] == 16
        assert c["weight_passes"] >= 1
        assert c["kv_blocks_peak"] >= 1
    # identical prompts -> identical greedy outputs
    r_a = requests.post(_url(port, "/inference"), json={
        "model_name": "tiny-llama", "prompt_tokens": [3, 5, 7, 11],
        "max_new_tokens": 16, "sampling": {"do_sample": False}},
        timeout=300).json()
    assert r_a["tokens"] == results[0]["tokens"]
    # the scheduler actually ran these (prefix cache saw the repeats)
    assert r_a["scheduler"]["tokens_out"] >= 7 * 16


def test_cost_ledger_cached_tokens_match_kvtier_counters(worker):
    """The cost record's cached/uncached prefill tokens use the exact
    expressions behind the cluster ``dli_prefill_{cached,uncached}_
    tokens_total`` counters, so per-request ledgers reconcile with the
    fleet metrics (the acceptance contract of the telemetry PR)."""
    agent, port = worker
    prompt = list(range(101, 121))    # 20 tokens: 2 full 8-token blocks
    before = dict(agent.metrics.snapshot()["counters"])
    costs = []
    for _ in range(2):
        r = requests.post(_url(port, "/inference"), json={
            "model_name": "tiny-llama", "prompt_tokens": prompt,
            "max_new_tokens": 4, "sampling": {"do_sample": False},
        }, timeout=300)
        assert r.status_code == 200, r.text
        costs.append(r.json()["cost"])
    after = agent.metrics.snapshot()["counters"]

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    # the second identical prompt hit the radix cache for its two full
    # prefix blocks (the first may also hit KV left by earlier tests)
    assert costs[1]["prefill_cached_tokens"] >= 16, costs
    assert sum(c["prefill_cached_tokens"] for c in costs) == \
        delta("prefill_cached_tokens")
    assert sum(c["prefill_uncached_tokens"] for c in costs) == \
        delta("prefill_uncached_tokens")


def test_streaming_batched(worker):
    _, port = worker
    with requests.post(_url(port, "/inference_stream"), json={
        "model_name": "tiny-llama", "prompt_tokens": [2, 4, 6, 8],
        "max_new_tokens": 8, "sampling": {"do_sample": False},
    }, stream=True, timeout=300) as r:
        assert r.status_code == 200
        events = []
        for line in r.iter_lines():
            if line.startswith(b"data: "):
                events.append(json.loads(line[6:]))
    kinds = [e["event"] for e in events]
    assert kinds.count("token") == 8
    assert kinds[-1] == "done"
    streamed = [e["token"] for e in events if e["event"] == "token"]
    done = [e for e in events if e["event"] == "done"][0]
    assert done["result"]  # decoded text present


def test_stream_validation_is_http_400(worker):
    """Bad stream requests fail with a status code, not a 200+SSE error —
    same contract as /inference."""
    _, port = worker
    r = requests.post(_url(port, "/inference_stream"), json={
        "model_name": "tiny-llama", "prompt_tokens": [],
        "max_new_tokens": 4}, timeout=60)
    assert r.status_code == 400
    r = requests.post(_url(port, "/inference_stream"), json={
        "model_name": "no-such-model", "prompt_tokens": [1]}, timeout=60)
    assert r.status_code == 400


def test_profiler_endpoints(worker, tmp_path):
    _, port = worker
    d = str(tmp_path / "trace")
    r = requests.post(_url(port, "/profile/start"), json={"trace_dir": d})
    assert r.status_code == 200
    # double-start is rejected
    assert requests.post(_url(port, "/profile/start"), json={}).status_code == 409
    requests.post(_url(port, "/inference"), json={
        "model_name": "tiny-llama", "prompt_tokens": [1, 2, 3],
        "max_new_tokens": 2, "sampling": {"do_sample": False}}, timeout=300)
    r = requests.post(_url(port, "/profile/stop"), json={})
    assert r.status_code == 200
    import glob
    assert glob.glob(d + "/**/*.xplane.pb", recursive=True), \
        "trace produced no xplane"
    assert requests.post(_url(port, "/profile/stop"), json={}).status_code == 409
    m = requests.get(_url(port, "/memory_profile"))
    assert m.status_code == 200 and len(m.content) > 0


def test_unload_stops_batcher(worker):
    agent, port = worker
    # load a second batched model and unload it; its batcher thread stops
    r = requests.post(_url(port, "/load_model"), json={
        "model_name": "tiny-gpt2", "allow_random_init": True,
        "serving": "batched", "kv_blocks": 32, "kv_block_size": 8,
        "slots": 2, "max_seq": 64, "dtype": "float32"}, timeout=300)
    assert r.status_code == 200, r.text
    b = agent.models["tiny-gpt2"].batcher
    assert b._thread is not None
    r = requests.post(_url(port, "/unload_model"),
                      json={"model_name": "tiny-gpt2"}, timeout=60)
    assert r.status_code == 200
    assert b._thread is None


def test_batched_with_tp_mesh():
    """Round-2 lift: batched serving accepts a tp mesh (the old 400 is
    gone); dp/pp/sp on the batcher still 400s before any restore."""
    agent = WorkerAgent()
    srv = agent.serve(host="127.0.0.1", port=0, background=True)
    port = srv.server_address[1]
    try:
        r = requests.post(_url(port, "/load_model"), json={
            "model_name": "tiny-llama", "allow_random_init": True,
            "serving": "batched", "kv_blocks": 32, "kv_block_size": 8,
            "slots": 2, "max_seq": 64, "dtype": "float32",
            "mesh": {"tp": 2},
        }, timeout=300)
        assert r.status_code == 200, r.text
        h = requests.get(_url(port, "/health")).json()
        [m] = h["loaded_models"]
        assert m["scheduler"]["mesh"]["tp"] == 2
        r = requests.post(_url(port, "/inference"), json={
            "model_name": "tiny-llama", "prompt_tokens": [2, 4, 6],
            "max_new_tokens": 5, "sampling": {"do_sample": False},
        }, timeout=300)
        assert r.status_code == 200, r.text
        assert len(r.json()["tokens"]) == 5

        r = requests.post(_url(port, "/load_model"), json={
            "model_name": "tiny-gpt2", "allow_random_init": True,
            "serving": "batched", "mesh": {"dp": 2}, "dtype": "float32",
        }, timeout=60)
        assert r.status_code == 400
        assert "tp/ep" in r.json()["message"]
    finally:
        stop_worker(agent)


def test_timeout_and_cancel_free_slots():
    """A request that exceeds its budget 408s AND releases its batcher
    slot; a tagged in-flight request can be cancelled via /cancel
    (round-2 master↔worker timeout/cancel story)."""
    agent = WorkerAgent()
    srv = agent.serve(host="127.0.0.1", port=0, background=True)
    port = srv.server_address[1]
    try:
        r = requests.post(_url(port, "/load_model"), json={
            "model_name": "tiny-llama", "allow_random_init": True,
            "serving": "batched", "kv_blocks": 64, "kv_block_size": 8,
            "slots": 2, "max_seq": 512, "dtype": "float32",
        }, timeout=300)
        assert r.status_code == 200, r.text

        # 1) worker-side budget: long generation, tiny timeout -> 408
        r = requests.post(_url(port, "/inference"), json={
            "model_name": "tiny-llama", "prompt_tokens": [1, 2, 3],
            "max_new_tokens": 120, "timeout": 0.5,
        }, timeout=60)
        assert r.status_code == 408, r.text
        deadline = time.time() + 30
        while time.time() < deadline:   # cancel lands at the next step
            st = requests.get(_url(port, "/health")).json()[
                "loaded_models"][0]["scheduler"]
            if st["active"] == 0:
                break
            time.sleep(0.2)
        assert st["active"] == 0, st

        # 2) tagged cancel: kick off a long request, cancel it mid-flight
        results = {}

        def go():
            results["r"] = requests.post(_url(port, "/inference"), json={
                "model_name": "tiny-llama", "prompt_tokens": [5, 6, 7],
                "max_new_tokens": 120, "request_tag": "req-42",
            }, timeout=120)

        t = threading.Thread(target=go)
        t.start()
        deadline = time.time() + 30
        cancelled = False
        while time.time() < deadline and not cancelled:
            c = requests.post(_url(port, "/cancel"),
                              json={"request_tag": "req-42"}, timeout=10)
            cancelled = c.status_code == 200
            time.sleep(0.1)
        assert cancelled
        t.join(timeout=60)
        r = results["r"]
        assert r.status_code == 400 and "cancel" in r.json()["message"]
        deadline = time.time() + 30
        while time.time() < deadline:
            st = requests.get(_url(port, "/health")).json()[
                "loaded_models"][0]["scheduler"]
            if st["active"] == 0:
                break
            time.sleep(0.2)
        assert st["active"] == 0, st

        # unknown tag -> 404
        c = requests.post(_url(port, "/cancel"),
                          json={"request_tag": "nope"}, timeout=10)
        assert c.status_code == 404
    finally:
        stop_worker(agent)
