"""Chaos suite: master + worker in-process under injected faults.

Drives the fault-injection harness (utils/faults.py, armed over
``POST /api/faults``) against the self-healing dispatch path and asserts
the robustness invariants the reference system violated (SURVEY.md §3.4,
§5.3 — one strike deactivated a node forever; a timed-out generation
kept running for nobody; a requeue could double-generate a prompt):

- every submitted request reaches exactly one terminal state
- no prompt is ever generated twice (idempotency cache hit observable
  in metrics)
- a node whose fault clears is rescheduled via the breaker's half-open
  probe without operator action
- drain finishes in-flight work, 503s new work, and costs no strike

Reproduce any failure locally with the same schedule:

    DLI_FAULTS_SEED=<seed> JAX_PLATFORMS=cpu \
        python -m pytest tests/test_chaos.py -q
"""

import os
import threading
import time

import pytest
import requests

# The fault-admin surface only registers when injection is explicitly
# enabled at service construction (it includes a remote kill switch);
# must be set before any fixture builds a worker/master.
os.environ.setdefault("DLI_FAULTS_ENABLE", "1")

from distributed_llm_inferencing_tpu.runtime.master import (
    FAILURE_STRIKES, MAX_ATTEMPTS, Master)
from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
from distributed_llm_inferencing_tpu.utils.faults import FaultInjector
from conftest import stop_worker


def _url(port, path):
    return f"http://127.0.0.1:{port}{path}"


def _load_tiny(port, name="tiny-gpt2", **kw):
    body = {"model_name": name, "allow_random_init": True,
            "dtype": "float32", "max_seq": 64, **kw}
    r = requests.post(_url(port, "/load_model"), json=body, timeout=300)
    assert r.status_code == 200, r.text


def _warm(port, name="tiny-gpt2"):
    r = requests.post(_url(port, "/inference"), json={
        "model_name": name, "prompt": "hi", "max_new_tokens": 4,
        "sampling": {"do_sample": False}}, timeout=300)
    assert r.status_code == 200, r.text


@pytest.fixture(scope="module")
def worker():
    """Standing worker with a preloaded + jit-warmed tiny engine."""
    agent = WorkerAgent()
    srv = agent.serve("127.0.0.1", 0, background=True)
    port = srv.server_address[1]
    _load_tiny(port)
    _warm(port)
    yield agent, port
    stop_worker(agent)


@pytest.fixture()
def clean_worker(worker):
    """Per-test guard: faults cleared and drain lifted on teardown."""
    agent, port = worker
    yield agent, port
    agent.service.faults.clear()
    agent._draining = False


@pytest.fixture()
def master():
    m = Master(":memory:", dispatcher_threads=2, health_interval=0.3,
               infer_timeout=15, retry_backoff_base=0.05)
    m.start_background()
    srv = m.service.serve("127.0.0.1", 0, background=True)
    port = srv.server_address[1]
    yield m, port
    m.stop()


def _add_node(mport, wport, name="w1"):
    r = requests.post(_url(mport, "/api/nodes/add"), json={
        "name": name, "host": "127.0.0.1", "port": wport}).json()
    assert r["status"] == "success", r
    return r["node_id"]


def _submit(mport, **kw):
    body = {"model_name": "tiny-gpt2", "prompt": "hi", "max_new_tokens": 4,
            "sampling": {"do_sample": False, "allow_random_init": True}}
    body.update(kw)
    r = requests.post(_url(mport, "/api/inference/submit"), json=body).json()
    assert r["status"] == "success", r
    return r["request_id"]


def _wait_terminal(mport, rid, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        r = requests.get(
            _url(mport, f"/api/inference/status/{rid}")).json()["request"]
        if r["status"] in ("completed", "failed"):
            return r
        time.sleep(0.1)
    raise TimeoutError(f"request {rid} never reached a terminal state")


def _node(mport, node_id):
    ns = requests.get(_url(mport, "/api/nodes/status")).json()["nodes"]
    return next(n for n in ns if n["id"] == node_id)


def _wait_breaker(mport, node_id, states, timeout=15):
    deadline = time.time() + timeout
    while time.time() < deadline:
        n = _node(mport, node_id)
        if n["breaker"] in states:
            return n
        time.sleep(0.1)
    raise TimeoutError(f"breaker never reached {states}: {n}")


def _arm(port, faults, seed=None):
    body = {"faults": faults}
    if seed is not None:
        body["seed"] = seed
    r = requests.post(_url(port, "/api/faults"), json=body).json()
    assert r["status"] == "success", r


# ---- injector unit behavior ------------------------------------------

def test_fault_injector_deterministic_and_bounded():
    mk = lambda: FaultInjector("w", seed=7)
    a, b = mk(), mk()
    # synthetic point: this test drives inj.intercept("/x") directly
    # dlilint: disable=rpc-fault-unknown
    spec = [{"point": "/x", "mode": "error", "p": 0.5, "after": 2,
             "times": 4}]
    a.arm(spec)
    b.arm(spec)
    fa = [a.intercept("/x") is not None for _ in range(40)]
    fb = [b.intercept("/x") is not None for _ in range(40)]
    assert fa == fb                      # seeded: replayable schedule
    assert not any(fa[:2])               # 'after' skips the first hits
    assert sum(fa) == 4                  # 'times' bounds total firings
    assert a.intercept("/y") is None     # point is matched
    st = a.state()["faults"][0]
    assert st["fired"] == 4 and st["hits"] == 40


def test_fault_injector_env_arming(monkeypatch):
    monkeypatch.setenv(
        "DLI_FAULTS",
        '[{"point": "/inference", "mode": "latency", "delay_s": 0.5}]')
    monkeypatch.setenv("DLI_FAULTS_SEED", "9")
    inj = FaultInjector.from_env("worker")
    assert inj.state()["seed"] == 9
    f = inj.intercept("/inference")
    assert f is not None and f.mode == "latency" and f.delay_s == 0.5
    with pytest.raises(ValueError):
        # dlilint: disable=rpc-fault-unknown
        inj.arm([{"point": "/x", "mode": "no-such-mode"}])


def test_fault_admin_api(clean_worker):
    _, port = clean_worker
    # deliberately-unmatched points: the admin API must round-trip them
    # dlilint: disable=rpc-fault-unknown
    _arm(port, [{"point": "/never", "mode": "error"}], seed=3)
    st = requests.get(_url(port, "/api/faults")).json()
    assert st["seed"] == 3 and len(st["faults"]) == 1
    r = requests.post(_url(port, "/api/faults"),
                      # dlilint: disable=rpc-fault-unknown
                      json={"faults": [{"point": "/x"}]})
    assert r.status_code == 400          # mode missing -> rejected
    requests.post(_url(port, "/api/faults/clear"), json={})
    assert requests.get(_url(port, "/api/faults")).json()["faults"] == []


# ---- retry / failover under response faults --------------------------

def test_corrupt_response_is_retried_to_completion(clean_worker, master):
    _, wport = clean_worker
    m, mport = master
    nid = _add_node(mport, wport)
    _arm(wport, [{"point": "/inference", "mode": "corrupt", "times": 1}])
    done = _wait_terminal(mport, _submit(mport))
    assert done["status"] == "completed", done
    assert done["attempts"] >= 1         # the corrupt attempt was retried
    assert _node(mport, nid)["is_active"]  # one strike != deactivation


def test_mid_response_disconnect_is_retried(clean_worker, master):
    _, wport = clean_worker
    m, mport = master
    _add_node(mport, wport)
    _arm(wport, [{"point": "/inference", "mode": "disconnect", "times": 1}])
    done = _wait_terminal(mport, _submit(mport))
    assert done["status"] == "completed", done
    assert done["attempts"] >= 1


def test_injected_500_is_retried(clean_worker, master):
    _, wport = clean_worker
    m, mport = master
    _add_node(mport, wport)
    _arm(wport, [{"point": "/inference", "mode": "error", "times": 1}])
    done = _wait_terminal(mport, _submit(mport))
    assert done["status"] == "completed", done
    assert done["attempts"] >= 1


# ---- idempotent dispatch: exactly-once execution ---------------------

def test_duplicate_dispatch_replays_cached_result(clean_worker):
    agent, wport = clean_worker
    body = {"model_name": "tiny-gpt2", "prompt_tokens": [5, 6, 7],
            "max_new_tokens": 4, "sampling": {"do_sample": False},
            "request_tag": "chaos-dup-1"}
    before = agent.metrics.snapshot()["timings"].get(
        "inference", {}).get("count", 0)
    r1 = requests.post(_url(wport, "/inference"), json=body).json()
    r2 = requests.post(_url(wport, "/inference"), json=body).json()
    assert r1["status"] == r2["status"] == "success"
    assert r2["tokens"] == r1["tokens"]
    assert r2.get("idempotent") is True and not r1.get("idempotent")
    after = agent.metrics.snapshot()["timings"]["inference"]["count"]
    assert after - before == 1           # the generation ran exactly once
    assert agent.metrics.snapshot()["counters"]["idempotent_hits"] >= 1


def test_concurrent_same_tag_joins_single_execution(clean_worker):
    agent, wport = clean_worker
    body = {"model_name": "tiny-gpt2", "prompt_tokens": [9, 8, 7, 6],
            "max_new_tokens": 4, "sampling": {"do_sample": False},
            "request_tag": "chaos-join-1"}
    before = agent.metrics.snapshot()["timings"].get(
        "inference", {}).get("count", 0)
    results = []

    def post():
        results.append(
            requests.post(_url(wport, "/inference"), json=body).json())

    threads = [threading.Thread(target=post) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r["status"] == "success" for r in results)
    assert len({tuple(r["tokens"]) for r in results}) == 1
    after = agent.metrics.snapshot()["timings"]["inference"]["count"]
    assert after - before == 1           # 3 dispatches, one execution


def test_timeout_retry_does_not_regenerate(clean_worker):
    """Master-side timeout + retry loop against a slow worker: the
    prompt is generated exactly once; the master's eventual success is
    an idempotency-cache replay, visible in both sides' metrics."""
    agent, wport = clean_worker
    m = Master(":memory:", dispatcher_threads=2, health_interval=0.5,
               infer_timeout=2.5, retry_backoff_base=0.05)
    m.start_background()
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    mport = msrv.server_address[1]
    try:
        _add_node(mport, wport)
        before = agent.metrics.snapshot()["timings"].get(
            "inference", {}).get("count", 0)
        # first two dispatches stall 4s in the HTTP layer — the master
        # (2.5s timeout) gives up on both; the generation itself runs
        # (once) and lands in the worker's completed-result cache
        _arm(wport, [{"point": "/inference", "mode": "latency",
                      "delay_s": 4.0, "times": 2}])
        done = _wait_terminal(mport, _submit(mport), timeout=40)
        assert done["status"] == "completed", done
        assert done["attempts"] >= 1
        deadline = time.time() + 10      # late replays may still be landing
        while time.time() < deadline:
            after = agent.metrics.snapshot()["timings"]["inference"]["count"]
            hits = agent.metrics.snapshot()["counters"].get(
                "idempotent_hits", 0)
            if after - before == 1 and hits >= 1:
                break
            time.sleep(0.2)
        assert after - before == 1, "prompt was generated more than once"
        assert hits >= 1
        assert m.metrics.snapshot()["counters"].get(
            "requests_idempotent_replayed", 0) >= 1
    finally:
        m.stop()


# ---- circuit breaker: partition opens, recovery closes ---------------

def test_partition_opens_breaker_then_recovers(clean_worker, master):
    m, mport = master
    _, wport = clean_worker
    nid = _add_node(mport, wport)
    # partition: every master->worker RPC fails at the client side
    m.service.faults.arm([{"point": "rpc:*", "mode": "reset"}])
    rid = _submit(mport)
    done = _wait_terminal(mport, rid, timeout=30)
    assert done["status"] == "failed"    # exactly one terminal state
    n = _wait_breaker(mport, nid, ("open",))
    assert not n["is_active"]
    # fault clears -> health probe flips the breaker half-open with no
    # operator involvement, and real traffic closes it
    m.service.faults.clear()
    n = _wait_breaker(mport, nid, ("half_open", "closed"))
    assert n["is_active"]
    done = _wait_terminal(mport, _submit(mport))
    assert done["status"] == "completed", done
    assert _wait_breaker(mport, nid, ("closed",))["strikes"] == 0


def test_worker_crash_fails_over_to_peer(worker, master):
    """Crash-on-Nth-request: the struck node's breaker opens, the
    request fails over to the surviving peer, and still reaches exactly
    one terminal state."""
    m, mport = master
    _, bport = worker                    # surviving peer (standing worker)
    agent_a = WorkerAgent()
    asrv = agent_a.serve("127.0.0.1", 0, background=True)
    aport = asrv.server_address[1]
    try:
        _load_tiny(aport)
        aid = _add_node(mport, aport, name="doomed")
        bid = _add_node(mport, bport, name="survivor")
        _arm(aport, [{"point": "/inference", "mode": "crash", "times": 1}])
        done = _wait_terminal(mport, _submit(mport), timeout=60)
        assert done["status"] == "completed", done
        assert done["node_id"] == bid    # failover excluded the crasher
        n = _wait_breaker(mport, aid, ("open",))
        assert not n["is_active"]
        assert _node(mport, bid)["is_active"]
    finally:
        stop_worker(agent_a)


# ---- graceful drain ---------------------------------------------------

def test_drain_finishes_inflight_and_rejects_new():
    # dedicated master: the long batched generation needs the full
    # production inference budget, not this module's fast-retry fixture
    m = Master(":memory:", dispatcher_threads=2, health_interval=0.3,
               retry_backoff_base=0.05)
    m.start_background()
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    mport = msrv.server_address[1]
    agent = WorkerAgent()
    srv = agent.serve("127.0.0.1", 0, background=True)
    wport = srv.server_address[1]
    try:
        _load_tiny(wport, name="tiny-llama", serving="batched",
                   kv_blocks=64, kv_block_size=8, slots=2, max_seq=128)
        nid = _add_node(mport, wport)
        rid = _submit(mport, model_name="tiny-llama", prompt="hello world",
                      max_new_tokens=110)
        # wait until the request is actually running in the batcher
        deadline = time.time() + 60
        while time.time() < deadline:
            st = requests.get(_url(wport, "/health")).json()[
                "loaded_models"][0]["scheduler"]
            if st["active"] > 0:
                break
            time.sleep(0.05)
        assert st["active"] > 0, "request never started"
        d = requests.post(_url(wport, "/drain"), json={"timeout": 120},
                          timeout=300).json()
        assert d["drained"] is True and d["in_flight"] == 0, d
        # zero in-flight loss: the admitted request finished normally
        done = _wait_terminal(mport, rid, timeout=30)
        assert done["status"] == "completed", done
        assert len(done["result"]) > 0
        # new work is refused with Retry-After
        r = requests.post(_url(wport, "/inference"), json={
            "model_name": "tiny-llama", "prompt": "x"})
        assert r.status_code == 503 and r.headers.get("Retry-After")
        assert r.json().get("draining") is True
        # the master sees draining: unschedulable, but NOT struck
        deadline = time.time() + 10
        while time.time() < deadline:
            n = _node(mport, nid)
            if n["draining"]:
                break
            time.sleep(0.1)
        assert n["draining"] and n["strikes"] == 0 and \
            n["breaker"] == "closed", n
        assert m._pick_node("tiny-llama") is None
        # undrain -> schedulable again, still no strikes
        requests.post(_url(wport, "/undrain"), json={})
        done = _wait_terminal(mport, _submit(
            mport, model_name="tiny-llama", max_new_tokens=4), timeout=60)
        assert done["status"] == "completed", done
        assert _node(mport, nid)["strikes"] == 0
    finally:
        m.stop()
        stop_worker(agent)


# ---- relayed worker responses (satellite: structured 502) ------------

def test_corrupt_load_relay_returns_structured_502(clean_worker, master):
    _, wport = clean_worker
    m, mport = master
    nid = _add_node(mport, wport)
    _arm(wport, [{"point": "/load_model", "mode": "corrupt", "times": 1}])
    r = requests.post(_url(mport, "/api/models/load"), json={
        "model_name": "tiny-gpt2", "node_id": nid,
        "allow_random_init": True})
    assert r.status_code == 502
    body = r.json()
    assert body["status"] == "error" and "unparseable" in body["message"]


def test_corrupt_deploy_relay_returns_structured_502(clean_worker, master):
    _, wport = clean_worker
    m, mport = master
    _add_node(mport, wport)
    p = requests.post(_url(mport, "/api/plans/create"), json={
        "model_name": "tiny-gpt2", "mesh": {"tp": 1},
        "max_seq": 64}).json()
    _arm(wport, [{"point": "/load_shard", "mode": "corrupt", "times": 1}])
    r = requests.post(_url(mport, f"/api/plans/deploy/{p['plan_id']}"),
                      json={"allow_random_init": True})
    assert r.status_code == 502
    assert "unparseable" in r.json()["message"]


# ---- multiplexed batch dispatch under faults -------------------------

def test_mid_batch_disconnect_recovers_each_subrequest_exactly_once(
        clean_worker):
    """A batch RPC dies mid-stream (disconnect fault on
    /inference_batch): the master requeues every unanswered sub-request
    individually, strikes the node at most once for the shared socket
    fault, and the retries land each prompt exactly once — no
    double-generation, no lost request."""
    agent, wport = clean_worker
    m = Master(":memory:", dispatcher_threads=1, health_interval=0.3,
               infer_timeout=15, retry_backoff_base=0.05,
               dispatch_batch=4)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    mport = msrv.server_address[1]
    try:
        nid = _add_node(mport, wport)
        before = agent.metrics.snapshot()["timings"].get(
            "inference", {}).get("count", 0)
        _arm(wport, [{"point": "/inference_batch", "mode": "disconnect",
                      "times": 1}])
        # submit before the dispatcher starts so one claim batches all 4
        rids = [_submit(mport) for _ in range(4)]
        m.start_background()
        finals = {rid: _wait_terminal(mport, rid, timeout=90)
                  for rid in rids}
        assert all(r["status"] == "completed" for r in finals.values()), \
            finals
        # each sub-request burned the failed batch attempt, exactly once
        assert all(r["attempts"] >= 1 for r in finals.values())
        deadline = time.time() + 10
        while time.time() < deadline:
            after = agent.metrics.snapshot()["timings"]["inference"]["count"]
            if after - before == len(rids):
                break
            time.sleep(0.2)
        assert after - before == len(rids), \
            "a sub-request was generated more than once (or lost)"
        # one socket fault = one strike, not four: breaker still closed
        n = _node(mport, nid)
        assert n["breaker"] == "closed" and n["strikes"] <= 1, n
    finally:
        m.stop()


# ---- barrage: every request ends in exactly one terminal state -------

def test_mixed_fault_barrage_all_requests_terminal(clean_worker, master):
    _, wport = clean_worker
    m, mport = master
    _add_node(mport, wport)
    _arm(wport, [
        {"point": "/inference", "mode": "corrupt", "p": 0.5, "times": 3},
        {"point": "/inference", "mode": "disconnect", "p": 0.3, "times": 2},
        {"point": "/inference", "mode": "latency", "delay_s": 0.1,
         "p": 0.5},
    ], seed=1234)
    rids = [_submit(mport) for _ in range(6)]
    finals = {rid: _wait_terminal(mport, rid, timeout=90) for rid in rids}
    states = {rid: r["status"] for rid, r in finals.items()}
    assert all(s in ("completed", "failed") for s in states.values())
    assert sum(s == "completed" for s in states.values()) >= 1
    # terminal means terminal: statuses never change afterwards
    time.sleep(0.5)
    for rid in rids:
        r = requests.get(
            _url(mport, f"/api/inference/status/{rid}")).json()["request"]
        assert r["status"] == states[rid]
    counts = requests.get(_url(mport, "/api/inference/recent")).json()[
        "counts"]
    assert counts.get("pending", 0) == 0 and counts.get("processing", 0) == 0
