"""Integration tests: worker agent + master control plane over localhost HTTP.

Reproduces the reference's primary call stack (SURVEY.md §3.1) — submit →
queue → dispatch → worker load+infer → poll result — against real sockets,
plus the failure-handling upgrades (retry/failover, strikes, reactivation).
"""

import json
import time

import pytest
import requests

from distributed_llm_inferencing_tpu.runtime.master import Master
from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
from conftest import stop_worker


@pytest.fixture(scope="module")
def worker():
    agent = WorkerAgent()
    srv = agent.serve(host="127.0.0.1", port=0, background=True)
    port = srv.server_address[1]
    yield agent, port
    stop_worker(agent)


@pytest.fixture()
def master():
    m = Master(":memory:", dispatcher_threads=2, health_interval=0.5)
    m.start_background()
    srv = m.service.serve("127.0.0.1", 0, background=True)
    port = srv.server_address[1]
    yield m, port
    m.stop()


def _url(port, path):
    return f"http://127.0.0.1:{port}{path}"


def _wait_status(port, req_id, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        r = requests.get(_url(port, f"/api/inference/status/{req_id}")).json()
        if r["request"]["status"] in ("completed", "failed"):
            return r["request"]
        time.sleep(0.2)
    raise TimeoutError("request never finished")


# ---- worker alone ----------------------------------------------------

def test_worker_health(worker):
    _, port = worker
    r = requests.get(_url(port, "/health")).json()
    assert r["status"] == "online"
    assert r["resources"]["devices"]
    assert isinstance(r["loaded_models"], list)


def test_worker_load_requires_checkpoint_or_optin(worker):
    _, port = worker
    r = requests.post(_url(port, "/load_model"),
                      json={"model_name": "tiny-gpt2"})
    assert r.status_code == 400
    assert "allow_random_init" in r.json()["message"]


def test_worker_load_infer_unload(worker):
    _, port = worker
    r = requests.post(_url(port, "/load_model"), json={
        "model_name": "tiny-gpt2", "allow_random_init": True,
        "dtype": "float32", "max_seq": 64})
    assert r.status_code == 200, r.text
    # idempotent second load (reference worker/app.py:106-110)
    r2 = requests.post(_url(port, "/load_model"), json={
        "model_name": "tiny-gpt2", "allow_random_init": True})
    assert "already loaded" in r2.json()["message"]

    r = requests.post(_url(port, "/inference"), json={
        "model_name": "tiny-gpt2", "prompt_tokens": [1, 2, 3],
        "max_new_tokens": 5, "sampling": {"do_sample": False}})
    assert r.status_code == 200, r.text
    data = r.json()
    assert data["status"] == "success"
    assert len(data["tokens"]) == 5
    assert data["execution_time"] > 0

    r = requests.post(_url(port, "/unload_model"),
                      json={"model_name": "tiny-gpt2"})
    assert r.json()["status"] == "success"
    r = requests.post(_url(port, "/unload_model"),
                      json={"model_name": "tiny-gpt2"})
    assert r.status_code == 404


def test_worker_streaming(worker):
    _, port = worker
    requests.post(_url(port, "/load_model"), json={
        "model_name": "tiny-gpt2", "allow_random_init": True,
        "dtype": "float32", "max_seq": 64})
    with requests.post(_url(port, "/inference_stream"), json={
            "model_name": "tiny-gpt2", "prompt_tokens": [4, 5],
            "max_new_tokens": 4, "sampling": {"do_sample": False}},
            stream=True) as r:
        assert r.status_code == 200
        events = []
        for line in r.iter_lines():
            if line.startswith(b"data: "):
                events.append(json.loads(line[6:]))
    kinds = [e["event"] for e in events]
    assert kinds.count("token") == 4
    assert kinds[-1] == "done"
    requests.post(_url(port, "/unload_model"), json={"model_name": "tiny-gpt2"})


def test_worker_auth():
    agent = WorkerAgent(auth_key="sekrit")
    srv = agent.serve("127.0.0.1", 0, background=True)
    port = srv.server_address[1]
    try:
        assert requests.get(_url(port, "/health")).status_code == 401
        r = requests.get(_url(port, "/health"),
                         headers={"Authorization": "Bearer sekrit"})
        assert r.status_code == 200
    finally:
        stop_worker(agent)


# ---- master + worker end-to-end --------------------------------------

def test_end_to_end_submit_poll(worker, master):
    _, wport = worker
    m, mport = master
    r = requests.post(_url(mport, "/api/nodes/add"), json={
        "name": "w1", "host": "127.0.0.1", "port": wport}).json()
    assert r["status"] == "success", r

    req = requests.post(_url(mport, "/api/inference/submit"), json={
        "model_name": "tiny-gpt2", "prompt": "hi",
        "max_new_tokens": 4,
        "sampling": {"do_sample": False, "allow_random_init": True},
    }).json()
    assert req["status"] == "success"
    done = _wait_status(mport, req["request_id"])
    assert done["status"] == "completed", done
    assert done["node_id"] is not None
    assert done["execution_time"] > 0

    recent = requests.get(_url(mport, "/api/inference/recent")).json()
    assert recent["counts"]["completed"] >= 1

    # pages render
    for path in ("/", "/nodes", "/inference"):
        page = requests.get(_url(mport, path))
        assert page.status_code == 200
        assert "<html" in page.text

    # node status shows the worker with the loaded model
    ns = requests.get(_url(mport, "/api/nodes/status")).json()
    assert ns["nodes"][0]["is_active"]


def test_master_rejects_unreachable_node(master):
    _, mport = master
    r = requests.post(_url(mport, "/api/nodes/add"), json={
        "name": "ghost", "host": "127.0.0.1", "port": 1})
    assert r.status_code == 502


def test_master_plan_api(master):
    _, mport = master
    r = requests.post(_url(mport, "/api/plans/create"), json={
        "model_name": "llama-3-8b", "mesh": {"tp": 4}}).json()
    assert r["status"] == "success"
    assert r["plan"]["num_devices"] == 4
    plans = requests.get(_url(mport, "/api/plans")).json()
    assert len(plans["plans"]) == 1


def test_plan_create_and_deploy_ui_flow(worker, master):
    """The nodes page's plan mutation surface end-to-end: the same
    create → deploy POSTs the dashboard form/button issue (the reference
    kept this mutation surface in Django admin only, admin.py:4-19, and
    never actually called /load_shard, SURVEY.md §3.2)."""
    _, wport = worker
    m, mport = master
    requests.post(_url(mport, "/api/nodes/add"), json={
        "name": "wplan", "host": "127.0.0.1", "port": wport})
    r = requests.post(_url(mport, "/api/plans/create"), json={
        "model_name": "tiny-gpt2", "mesh": {"tp": 1}, "max_seq": 64}).json()
    assert r["status"] == "success", r
    pid = r["plan_id"]
    d = requests.post(_url(mport, f"/api/plans/deploy/{pid}"), json={
        "allow_random_init": True, "dtype": "float32"}).json()
    assert d["status"] == "success", d
    plans = requests.get(_url(mport, "/api/plans")).json()["plans"]
    mine = [p for p in plans if p["id"] == pid]
    assert mine and mine[0]["is_loaded"] and mine[0]["node_id"] is not None
    # the worker really holds the model now
    h = requests.get(_url(wport, "/health")).json()
    assert any(mdl["name"] == "tiny-gpt2" for mdl in h["loaded_models"])
    requests.post(_url(wport, "/unload_model"),
                  json={"model_name": "tiny-gpt2"})
    # the page ships the mutation form + deploy wiring
    page = requests.get(_url(mport, "/nodes")).text
    assert "Create Placement Plan" in page
    assert "deployPlan" in page and "/api/plans/deploy/" in page
    assert "/api/plans/create" in page


def test_user_error_does_not_strike_node(worker, master):
    """An unknown model name must fail the request immediately without
    deactivating the (healthy) node."""
    _, wport = worker
    m, mport = master
    requests.post(_url(mport, "/api/nodes/add"), json={
        "name": "w1", "host": "127.0.0.1", "port": wport})
    req = requests.post(_url(mport, "/api/inference/submit"), json={
        "model_name": "no-such-model", "prompt": "x",
        "sampling": {"allow_random_init": True}}).json()
    done = _wait_status(mport, req["request_id"], timeout=20)
    assert done["status"] == "failed"
    assert "rejected" in done["error"]
    ns = requests.get(_url(mport, "/api/nodes/status")).json()
    assert ns["nodes"][0]["is_active"], "healthy node was struck offline"


def test_max_length_reference_semantics(worker, master):
    """max_length counts prompt+new tokens (reference views.py:351)."""
    _, wport = worker
    m, mport = master
    requests.post(_url(mport, "/api/nodes/add"), json={
        "name": "w1", "host": "127.0.0.1", "port": wport})
    # ByteTokenizer: "hello" -> BOS + 5 bytes = 6 tokens; max_length=10 -> 4 new
    req = requests.post(_url(mport, "/api/inference/submit"), json={
        "model_name": "tiny-gpt2", "prompt": "hello", "max_length": 10,
        "sampling": {"do_sample": False, "allow_random_init": True}}).json()
    done = _wait_status(mport, req["request_id"])
    assert done["status"] == "completed", done
    assert done["max_length"] == 10


def test_failed_request_after_node_death(worker, master):
    """Kill the only node → request fails with a real error after retries
    (reference: mark_failed with no retry, views.py:364-378)."""
    m, mport = master
    # add a node then kill it by pointing at a dead port
    agent = WorkerAgent()
    srv = agent.serve("127.0.0.1", 0, background=True)
    dead_port = srv.server_address[1]
    requests.post(_url(mport, "/api/nodes/add"), json={
        "name": "dying", "host": "127.0.0.1", "port": dead_port})
    agent.service.shutdown()  # node is now dead

    req = requests.post(_url(mport, "/api/inference/submit"), json={
        "model_name": "tiny-gpt2", "prompt": "x",
        "sampling": {"allow_random_init": True}}).json()
    done = _wait_status(mport, req["request_id"], timeout=30)
    assert done["status"] == "failed"
    assert done["error"]


def test_ssh_setup_parity(worker):
    """Reference worker/app.py:374-413: /ssh_setup probes a connection.
    paramiko is optional here (the reference used-but-never-declared it,
    SURVEY.md §5.9), and the endpoint refuses to exist without worker
    auth — it is an SSRF primitive otherwise."""
    _, port = worker
    # unauthenticated worker: hard 403 regardless of body
    r = requests.post(_url(port, "/ssh_setup"),
                      json={"host": "127.0.0.1", "username": "u",
                            "password": "p", "port": 1})
    assert r.status_code == 403

    agent = WorkerAgent(auth_key="s3")
    srv = agent.serve("127.0.0.1", 0, background=True)
    aport = srv.server_address[1]
    try:
        hdr = {"Authorization": "Bearer s3"}
        r = requests.post(_url(aport, "/ssh_setup"), headers=hdr,
                          json={"host": "127.0.0.1", "username": "u",
                                "password": "p", "port": 1})
        try:
            import paramiko  # noqa: F401
            assert r.status_code == 502      # closed port -> connect fails
            r2 = requests.post(_url(aport, "/ssh_setup"), headers=hdr,
                               json={"host": "x"})
            assert r2.status_code == 400     # missing username
        except ImportError:
            assert r.status_code == 501
            assert "paramiko" in r.json()["message"]
    finally:
        stop_worker(agent)


def test_admin_cli(worker, master):
    """The admin CLI drives the master API end-to-end (≙ Django admin)."""
    import io
    from contextlib import redirect_stdout
    from distributed_llm_inferencing_tpu.__main__ import main as cli

    _, wport = worker
    _, mport = master
    base = f"http://127.0.0.1:{mport}"

    def run(*argv):
        buf = io.StringIO()
        with redirect_stdout(buf):
            cli(["admin", "--master", base, *argv])
        return json.loads(buf.getvalue())

    out = run("add-node", "--name", "adm1", "--node_host", "127.0.0.1",
              "--node_port", str(wport))
    assert out["status"] == "success"
    nodes = run("nodes")
    assert any(n["name"] == "adm1" for n in nodes["nodes"])
    out = run("load-model", "--model_name", "tiny-gpt2",
              "--allow_random_init")
    assert out["status"] == "success", out
    reqs = run("requests")
    assert "counts" in reqs
    node_id = [n["id"] for n in nodes["nodes"] if n["name"] == "adm1"][0]
    out = run("remove-node", "--node_id", str(node_id))
    assert out["status"] == "success"


def test_master_cancel_frees_worker_slot(master):
    """Master-side cancel reaches the worker's batcher and frees the slot."""
    m, mport = master
    agent = WorkerAgent()
    srv = agent.serve(host="127.0.0.1", port=0, background=True)
    wport = srv.server_address[1]
    try:
        r = requests.post(_url(wport, "/load_model"), json={
            "model_name": "tiny-llama", "allow_random_init": True,
            "serving": "batched", "kv_blocks": 64, "kv_block_size": 8,
            "slots": 2, "max_seq": 128, "dtype": "float32",
        }, timeout=300)
        assert r.status_code == 200, r.text
        r = requests.post(_url(mport, "/api/nodes/add"), json={
            "name": "cancel-node", "host": "127.0.0.1", "port": wport,
        }, timeout=30)
        assert r.status_code == 200, r.text

        r = requests.post(_url(mport, "/api/inference/submit"), json={
            "model_name": "tiny-llama", "prompt": "hello world",
            "max_new_tokens": 110,
        }, timeout=30)
        req_id = r.json()["request_id"]

        # wait until it's actually running on the worker, then cancel (a
        # cancel that catches it still pending — a slow dispatcher on a
        # loaded host — fails it at the master: the other path, not this
        # test's)
        deadline = time.time() + 60
        while time.time() < deadline:
            sch = requests.get(_url(wport, "/health")).json()[
                "loaded_models"][0]["scheduler"]
            if sch["active"] or sch["queued"]:
                break
            time.sleep(0.05)
        cancelled = False
        while time.time() < deadline and not cancelled:
            c = requests.post(
                _url(mport, f"/api/inference/cancel/{req_id}"), timeout=30)
            if c.status_code == 200 and "relayed" in c.json()["message"]:
                cancelled = True
            elif c.status_code == 409 and "already" in c.json()["message"]:
                raise AssertionError(f"finished before cancel: {c.json()}")
            time.sleep(0.1)
        assert cancelled

        req = _wait_status(mport, req_id)
        assert req["status"] == "failed"
        assert "cancel" in req["error"]

        deadline = time.time() + 30
        while time.time() < deadline:
            st = requests.get(_url(wport, "/health")).json()[
                "loaded_models"][0]["scheduler"]
            if st["active"] == 0:
                break
            time.sleep(0.2)
        assert st["active"] == 0, st
    finally:
        stop_worker(agent)


def test_dashboard_pages_surface_serving_internals(master):
    """The three pages render, and the round-2 additions are present:
    batcher stats on the dashboard, placement plans on the nodes page
    (≙ reference node_management.html:154-171 shard table)."""
    _, mport = master
    dash = requests.get(_url(mport, "/")).text
    assert "Batched Serving" in dash and "Prefix hit rate" in dash
    nodes = requests.get(_url(mport, "/nodes")).text
    assert "Placement Plans" in nodes and "/api/plans" in nodes
    inf = requests.get(_url(mport, "/inference")).text
    assert "Run Inference" in inf


def test_worker_streaming_speculative(worker):
    """SSE streaming with speculative decoding on: every token arrives as
    its own event (chunk-verified tokens are re-serialized per token) and
    the stream matches the non-streaming result."""
    _, wport = worker
    requests.post(_url(wport, "/load_model"), json={
        "model_name": "tiny-gpt2", "allow_random_init": True,
        "dtype": "float32", "max_seq": 128})
    body = {"model_name": "tiny-gpt2", "prompt_tokens": [7, 3] * 6,
            "max_new_tokens": 18, "sampling": {"do_sample": False},
            "speculative": "ngram", "spec_gamma": 4}
    import json as _json
    with requests.post(_url(wport, "/inference_stream"), json=body,
                       stream=True, timeout=300) as r:
        assert r.status_code == 200
        events = [_json.loads(l[6:]) for l in r.iter_lines()
                  if l.startswith(b"data: ")]
    toks = [e["token"] for e in events if e["event"] == "token"]
    assert events[-1]["event"] == "done"
    plain = requests.post(_url(wport, "/inference"), json=body,
                          timeout=300).json()
    assert toks == plain["tokens"] and len(toks) == 18
    requests.post(_url(wport, "/unload_model"),
                  json={"model_name": "tiny-gpt2"})


def test_worker_serves_deepseek_moe(worker):
    """The flagship MLA + MoE family through the worker's HTTP surface:
    load (random-init registry model), infer, unload — the same wire
    protocol the reference exposes for any model (reference
    worker/app.py:49-330), exercised on a mixed dense-prefix MLA stack
    with the latent KV cache auto-enabled by the engine underneath."""
    _, port = worker
    r = requests.post(_url(port, "/load_model"), json={
        "model_name": "tiny-deepseek", "allow_random_init": True,
        "dtype": "float32", "max_seq": 64})
    assert r.status_code == 200, r.text

    r = requests.post(_url(port, "/inference"), json={
        "model_name": "tiny-deepseek", "prompt_tokens": [4, 9, 2, 7],
        "max_new_tokens": 6, "sampling": {"do_sample": False}})
    assert r.status_code == 200, r.text
    data = r.json()
    assert data["status"] == "success" and len(data["tokens"]) == 6

    r = requests.post(_url(port, "/unload_model"),
                      json={"model_name": "tiny-deepseek"})
    assert r.json()["status"] == "success"
