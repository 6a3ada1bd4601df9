"""Multiplexed batched dispatch + pooled keep-alive RPC (ISSUE 4).

Covers the control-plane pipeline end to end: the single-transaction
multi-claim, FIFO order through batcher.submit_many and across master
dispatch batches, per-sub-request failure isolation (a poisoned
sub-request requeues alone while its batch siblings complete),
idempotent replay of a timed-out batch member, and connection reuse
through the per-node keep-alive sessions.

Reproduce any failure locally:

    JAX_PLATFORMS=cpu python -m pytest tests/test_dispatch_batch.py -q
"""

import os
import threading
import time

import pytest
import requests

os.environ.setdefault("DLI_FAULTS_ENABLE", "1")

import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher
from distributed_llm_inferencing_tpu.runtime.master import Master
from distributed_llm_inferencing_tpu.runtime.state import Store
from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
from conftest import stop_worker


def _url(port, path):
    return f"http://127.0.0.1:{port}{path}"


# ---- store: single-transaction multi-claim ---------------------------

def test_claim_many_order_limit_and_due_filter():
    s = Store(":memory:")
    ids = [s.submit_request("m", f"p{i}") for i in range(5)]
    # park one behind backoff: invisible to the claim until due
    s.claim_next_pending()                      # ids[0] -> processing
    s.requeue(ids[0], delay_s=60.0)             # parked
    got = s.claim_next_pending_many(3)
    assert [r["id"] for r in got] == ids[1:4]   # FIFO, limit respected
    assert all(r["status"] == "pending" for r in got)  # snapshot pre-flip
    for r in got:
        assert s.get_request(r["id"])["status"] == "processing"
        assert r["started_at"] is not None
    rest = s.claim_next_pending_many(10)
    assert [r["id"] for r in rest] == ids[4:]   # parked id stays invisible
    assert s.claim_next_pending_many(10) == []


def test_group_commit_store_reads_its_own_writes(tmp_path):
    """Barriered group commit: a requeue/terminal write is visible (and
    on disk) the moment the call returns, even with the write-behind
    flusher in between."""
    db = str(tmp_path / "gc.sqlite3")
    s = Store(db, group_commit=True)
    rid = s.submit_request("m", "p")
    assert s.claim_next_pending()["id"] == rid
    s.requeue(rid, excluded_node_id=3, delay_s=0.0)
    assert s.claim_next_pending()["id"] == rid  # read-your-writes
    s.mark_completed(rid, "out", 1, 0.1, 2.0)
    # durability barrier: a fresh connection (separate Store) sees the
    # terminal status immediately — it was committed before return
    assert Store(db).get_request(rid)["status"] == "completed"
    s.close()


# ---- batcher: multi-submit entry -------------------------------------

def test_submit_many_preserves_order_and_validates_all_first():
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = ContinuousBatcher(cfg, params, num_blocks=64, block_size=8,
                          slots=4, max_seq=64)
    specs = [{"prompt": [1 + i, 2, 3], "max_new_tokens": 4,
              "sampling": SamplingParams.greedy()} for i in range(5)]
    reqs = b.submit_many(specs)
    assert [r.prompt[0] for r in reqs] == [1, 2, 3, 4, 5]
    assert [q.prompt[0] for q in b.queue] == [1, 2, 3, 4, 5]  # FIFO queue
    # all-or-nothing: one invalid spec enqueues nothing new
    bad = specs[:2] + [{"prompt": [1], "max_new_tokens": 999,
                        "sampling": SamplingParams.greedy()}]
    with pytest.raises(ValueError):
        b.submit_many(bad)
    assert len(b.queue) == 5


# ---- end-to-end: master + worker over /inference_batch ---------------

@pytest.fixture(scope="module")
def batched_worker():
    """Standing worker serving tiny-llama through the continuous
    batcher with ONE slot, so completion order proves admission order."""
    agent = WorkerAgent()
    srv = agent.serve("127.0.0.1", 0, background=True)
    port = srv.server_address[1]
    r = requests.post(_url(port, "/load_model"), json={
        "model_name": "tiny-llama", "allow_random_init": True,
        "dtype": "float32", "serving": "batched", "slots": 1,
        "kv_blocks": 64, "kv_block_size": 8, "max_seq": 64}, timeout=300)
    assert r.status_code == 200, r.text
    # jit-warm one generation so timed tests don't pay compilation
    r = requests.post(_url(port, "/inference"), json={
        "model_name": "tiny-llama", "prompt": "hi", "max_new_tokens": 2,
        "sampling": {"do_sample": False}}, timeout=300)
    assert r.status_code == 200, r.text
    yield agent, port
    stop_worker(agent)


def _mk_master(**kw):
    kw.setdefault("dispatcher_threads", 1)
    kw.setdefault("health_interval", 0.3)
    kw.setdefault("retry_backoff_base", 0.05)
    m = Master(":memory:", **kw)
    srv = m.service.serve("127.0.0.1", 0, background=True)
    return m, srv.server_address[1]


def _add_node(mport, wport, name="w1"):
    r = requests.post(_url(mport, "/api/nodes/add"), json={
        "name": name, "host": "127.0.0.1", "port": wport}).json()
    assert r["status"] == "success", r
    return r["node_id"]


def _submit(mport, prompt="hi", **kw):
    body = {"model_name": "tiny-llama", "prompt": prompt,
            "max_new_tokens": 3,
            "sampling": {"do_sample": False, "allow_random_init": True}}
    body.update(kw)
    r = requests.post(_url(mport, "/api/inference/submit"), json=body).json()
    assert r["status"] == "success", r
    return r["request_id"]


def _wait_terminal(mport, rid, timeout=90):
    deadline = time.time() + timeout
    while time.time() < deadline:
        r = requests.get(
            _url(mport, f"/api/inference/status/{rid}")).json()["request"]
        if r["status"] in ("completed", "failed"):
            return r
        time.sleep(0.1)
    raise TimeoutError(f"request {rid} never reached a terminal state")


def test_fifo_order_within_and_across_batches(batched_worker):
    """6 requests, dispatch batch 3, single dispatcher, single batcher
    slot: completions must land in submission order — within one
    multiplexed batch (submit_many preserves wire order) and across
    consecutive batches (claim_next_pending_many is id-ordered)."""
    _, wport = batched_worker
    m, mport = _mk_master(dispatch_batch=3)
    try:
        _add_node(mport, wport)
        # submit before the dispatcher starts so batches form
        rids = [_submit(mport, prompt=f"request number {i}")
                for i in range(6)]
        m.start_background()
        finals = [_wait_terminal(mport, rid) for rid in rids]
        assert all(f["status"] == "completed" for f in finals), finals
        completed_at = [f["completed_at"] for f in finals]
        assert completed_at == sorted(completed_at), completed_at
        assert all(f["attempts"] == 0 for f in finals)
        # the multiplexed path actually ran: fewer RPC batches than reqs
        snap = m.metrics.snapshot()
        assert snap["timings"]["master_dispatch_batch_size"]["count"] >= 1
    finally:
        m.stop()


def test_poisoned_subrequest_requeues_alone(batched_worker):
    """One sub-request of a batch joins a wedged execution (its tag is
    registered in-flight on the worker) and times out into a per-sub
    408; the master requeues JUST that request — its two batch siblings
    complete on the first attempt. Releasing the wedge lets the retry
    take ownership and complete."""
    agent, wport = batched_worker
    # infer_timeout=8 -> worker join budget 3s: the poisoned sub answers
    # its 408 line well inside the master's read timeout
    m, mport = _mk_master(dispatch_batch=3, infer_timeout=8)
    try:
        _add_node(mport, wport)
        # the fixture-warmed prompt shape: no fresh prefill-bucket
        # compile may eat the 3s worker budget the 408 path relies on
        rids = [_submit(mport, prompt="hi") for _ in range(3)]
        poison = rids[1]
        tag = m._tag(poison)
        wedge = threading.Event()
        with agent._idem_lock:
            agent._inflight_tags[tag] = wedge   # simulate a stuck owner
        m.start_background()
        sib_finals = [_wait_terminal(mport, rid)
                      for rid in rids if rid != poison]
        assert all(f["status"] == "completed" and f["attempts"] == 0
                   for f in sib_finals), sib_finals
        # the poisoned member burned (at least) one attempt alone
        deadline = time.time() + 30
        while time.time() < deadline:
            st = requests.get(_url(
                mport, f"/api/inference/status/{poison}")).json()["request"]
            if st["attempts"] >= 1:
                break
            time.sleep(0.1)
        assert st["attempts"] >= 1, st
        assert st["status"] != "completed"
        # release the wedge exactly like _idem_release on a failed owner:
        # drop the in-flight registration, then wake joiners — the retry
        # re-claims ownership and runs the generation
        with agent._idem_lock:
            agent._inflight_tags.pop(tag, None)
            wedge.set()
        done = _wait_terminal(mport, poison)
        assert done["status"] == "completed", done
        assert done["attempts"] >= 1
    finally:
        m.stop()


def test_idempotent_replay_of_timed_out_batch_member(batched_worker):
    """The whole batch stalls past the master's timeout (latency fault
    on /inference_batch); every member requeues sticky, the worker
    finishes the generations anyway, and the retries replay from the
    idempotency cache — each prompt generated exactly once."""
    agent, wport = batched_worker
    m, mport = _mk_master(dispatch_batch=3, infer_timeout=7.5)
    try:
        _add_node(mport, wport)
        before = agent.metrics.snapshot()["timings"].get(
            "inference", {}).get("count", 0)
        r = requests.post(_url(wport, "/api/faults"), json={"faults": [
            {"point": "/inference_batch", "mode": "latency",
             "delay_s": 4.0, "times": 1}]}).json()
        assert r["status"] == "success", r
        # warmed prompt shape (see the poison test): the 2.5s worker
        # budget must cover generation, not a fresh bucket compile
        rids = [_submit(mport, prompt="hi") for _ in range(3)]
        m.start_background()
        finals = [_wait_terminal(mport, rid) for rid in rids]
        assert all(f["status"] == "completed" for f in finals), finals
        deadline = time.time() + 10     # late replays may still be landing
        while time.time() < deadline:
            after = agent.metrics.snapshot()["timings"]["inference"]["count"]
            if after - before == len(rids):
                break
            time.sleep(0.2)
        assert after - before == len(rids), \
            "a batch member was generated more than once"
    finally:
        agent.service.faults.clear()
        m.stop()


def test_connection_reuse_counter_climbs_under_sustained_load(
        batched_worker):
    """Pooled keep-alive sessions: sustained dispatch + health sweeps
    ride a handful of connections; the reuse counter climbs while the
    created counter stays near the pool's floor."""
    _, wport = batched_worker
    m, mport = _mk_master(dispatch_batch=4)
    try:
        _add_node(mport, wport)
        m.start_background()
        for i in range(12):
            _wait_terminal(mport, _submit(mport, prompt=f"reuse {i}"))
        c = m.metrics.snapshot()["counters"]
        created = c.get("master_rpc_conns_created", 0)
        reused = c.get("master_rpc_conns_reused", 0)
        assert reused >= 12, c
        assert reused / max(1.0, created + reused) > 0.6, c
    finally:
        m.stop()
