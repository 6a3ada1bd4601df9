"""Flight recorder suite (runtime/events.py, docs/observability.md
"Flight recorder").

Covers the acceptance-critical invariants:
- the declared registry is self-consistent and the journal enforces it
  at emit time (undeclared types raise; the module helper never does),
- the in-memory ring is bounded and the durable half persists through
  the Store group-commit path with working type/node/request/since
  filters and retention pruning,
- events AND TSDB series survive a master restart on the same sqlite
  file (a series queried after restart spans samples from before it),
- TSDB snapshot/restore serves byte-equivalent points and continues
  counter rates across the restart without a spike,
- the journey endpoint merges lifecycle + events + node-scoped context
  + cost phases into one connected, time-ordered view — over a LIVE
  disagg + chaos run, the persisted journal alone reconstructs the
  recovery (breaker open -> requeue -> resume) linked to the affected
  request's journey,
- decision-site units: breaker transitions, drain changes, parks, and
  SLO burn crossings each journal exactly once per transition.
"""

import json
import os
import time

import pytest
import requests as rq

from distributed_llm_inferencing_tpu.runtime import events as events_mod
from distributed_llm_inferencing_tpu.runtime.master import (
    MAX_ATTEMPTS, Master)
from distributed_llm_inferencing_tpu.runtime.state import Store
from distributed_llm_inferencing_tpu.runtime.tsdb import TSDB
from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
from conftest import stop_worker

# char-level tiny-llama tokenizer + the workers' max_seq=128: the
# prompt must stay under ~98 tokens with 30 new, while clearing the
# 64-char disagg floor
LONG_PROMPT = "The quick brown fox jumps over the lazy dog. " * 2 + "Go."


# ---- registry + journal units ------------------------------------------

def test_registry_is_self_consistent():
    reg = events_mod.registry()
    assert len(reg) == len(events_mod.EVENT_TYPES)
    for t in events_mod.EVENT_TYPES:
        assert t.severity in events_mod.SEVERITIES
        assert t.doc.strip(), t.name
        assert isinstance(t.fields, tuple)
        assert len(t.fields) == len(set(t.fields)), t.name
    assert events_mod.get("breaker-open").severity == "warning"


def test_emit_validates_and_shapes():
    j = events_mod.EventJournal(ring=8)
    ev = j.emit("breaker-open", node_id=3, strikes=2,
                prev_state="closed", ghost=None)
    assert ev["type"] == "breaker-open" and ev["severity"] == "warning"
    assert ev["node_id"] == 3 and ev["request_id"] is None
    assert ev["data"] == {"strikes": 2, "prev_state": "closed"}
    ev2 = j.emit("migrate-anomaly", severity="info", status=409)
    assert ev2["severity"] == "info"
    with pytest.raises(ValueError):
        j.emit("not-a-declared-type")
    with pytest.raises(ValueError):
        j.emit("breaker-open", severity="fatal")


def test_ring_is_bounded():
    j = events_mod.EventJournal(ring=4)
    for i in range(10):
        j.emit("node-drain", node_id=i, draining=True)
    tail = j.tail(100)
    assert len(tail) == 4
    assert [e["node_id"] for e in tail] == [6, 7, 8, 9]
    c = j.counts()
    assert c["emitted"] == 10 and c["ring_cap"] == 4


def test_module_helper_never_raises():
    j = events_mod.EventJournal(ring=4)
    assert events_mod.emit("node-drain") is None   # none installed
    events_mod.set_journal(j)
    try:
        assert events_mod.emit("node-drain", draining=True) is not None
        # an undeclared type through the helper logs, never raises
        assert events_mod.emit("definitely-not-declared") is None
        other = events_mod.EventJournal(ring=4)
        events_mod.clear_journal(other)   # not installed: no-op
        assert events_mod.get_journal() is j
    finally:
        events_mod.clear_journal(j)
    assert events_mod.get_journal() is None


# ---- durable persistence through the Store -----------------------------

def test_store_persistence_and_filters():
    st = Store(":memory:", group_commit=True)
    try:
        j = events_mod.EventJournal(store=st, ring=64)
        t0 = time.time()
        j.emit("breaker-open", node_id=1, strikes=3, prev_state="closed")
        j.emit("breaker-open", node_id=2, strikes=3, prev_state="closed")
        j.emit("request-requeued", node_id=1, request_id=7,
               error="boom", attempts=0)
        j.emit("node-drain", node_id=1, draining=True, t=t0 + 100)
        st.flush()
        assert st.count_events() == 4
        rows = st.query_events()
        assert [r["type"] for r in rows] == [
            "breaker-open", "breaker-open", "request-requeued",
            "node-drain"]
        assert [r["type"] for r in st.query_events(etype="breaker-open")
                ] == ["breaker-open"] * 2
        assert [r["node_id"] for r in st.query_events(node_id=1)
                ] == [1, 1, 1]
        byreq = st.query_events(request_id=7)
        assert len(byreq) == 1 and byreq[0]["data"]["error"] == "boom"
        assert [r["type"] for r in st.query_events(since=t0 + 50)
                ] == ["node-drain"]
        # bounded window: BOTH ends are server-side filters, so the
        # newest-N page can never cut in-window rows (the journey's
        # node-context merge depends on this)
        assert [r["type"] for r in st.query_events(until=t0 + 50)] == [
            "breaker-open", "breaker-open", "request-requeued"]
        assert [r["type"] for r in st.query_events(
            since=t0 + 50, until=t0 + 200)] == ["node-drain"]
        # limit keeps the NEWEST matches, served oldest-first
        assert [r["type"] for r in st.query_events(limit=2)] == [
            "request-requeued", "node-drain"]
    finally:
        st.close()


def test_retention_prunes_the_table():
    st = Store(":memory:", group_commit=True)
    try:
        j = events_mod.EventJournal(store=st, ring=8, retain=10)
        n = events_mod.EventJournal._PRUNE_EVERY + 8
        for i in range(n):
            j.emit("node-drain", node_id=i, draining=bool(i % 2))
        st.flush()
        # prune fired once at _PRUNE_EVERY: the table holds the retained
        # window plus whatever landed after the prune op in the buffer
        assert st.count_events() <= 10 + 8
        newest = st.query_events(limit=1)[0]
        assert newest["node_id"] == n - 1
    finally:
        st.close()


def test_events_survive_store_restart(tmp_path):
    db = str(tmp_path / "m.sqlite3")
    st = Store(db, group_commit=True)
    j = events_mod.EventJournal(store=st)
    j.emit("role-flip", node_id=4, role="decode", prev_role="prefill",
           reason="divergence")
    st.flush()
    st.close()
    st2 = Store(db)
    try:
        rows = st2.query_events(etype="role-flip")
        assert len(rows) == 1
        assert rows[0]["data"] == {"role": "decode",
                                   "prev_role": "prefill",
                                   "reason": "divergence"}
        assert rows[0]["node_id"] == 4
    finally:
        st2.close()


# ---- TSDB snapshot/restore ---------------------------------------------

def _filled_tsdb(now):
    t = TSDB(window_s=40.0, step_s=0.5)
    for i in range(120):   # long enough that history downsampled into
        ts = now - 60 + i * 0.5   # the coarse ring is exercised too
        t.record("w0", "tok", 50.0 * i, kind="counter", t=ts)
        t.record("w0", "q", float(i % 7), kind="gauge", t=ts)
        t.record("w1", "q", float(i % 3), kind="gauge", t=ts)
    return t


def test_tsdb_snapshot_restore_byte_equivalent():
    now = time.time()
    t = _filled_tsdb(now)
    snap = json.loads(json.dumps(t.dump()))   # through the wire format
    t2 = TSDB(window_s=40.0, step_s=0.5)
    assert t2.restore(snap) == 3
    for metric in ("tok", "q"):
        for window in (5.0, 40.0):
            a = json.dumps(t.query(metric, window=window, now=now))
            b = json.dumps(t2.query(metric, window=window, now=now))
            assert a == b, (metric, window)
    assert t2.catalog() == t.catalog()


def test_tsdb_restore_continues_counter_rate_without_spike():
    now = time.time()
    t = _filled_tsdb(now)
    t2 = TSDB(window_s=40.0, step_s=0.5)
    t2.restore(t.dump())
    # next cumulative sample after the "restart": the restored baseline
    # keeps rating from the pre-restart value — a fresh series would
    # need two samples, and a zeroed baseline would spike to v/dt
    t2.record("w0", "tok", 50.0 * 121, kind="counter", t=now + 0.5)
    pts = [p for s in t2.query("tok", now=now + 1.0) for p in s["points"]]
    assert pts, "restored counter series vanished"
    last = pts[-1][1]
    assert 0 < last < 1000, last


def test_tsdb_restore_refuses_step_mismatch():
    t = _filled_tsdb(time.time())
    other = TSDB(window_s=40.0, step_s=1.0)
    assert other.restore(t.dump()) == 0
    assert other.restore({"v": 2}) == 0
    assert other.restore("garbage") == 0


# ---- master decision-site units ----------------------------------------

def _types(m, **kw):
    m.store.flush()
    return [e["type"] for e in m.store.query_events(**kw)]


def test_master_breaker_and_park_events():
    m = Master(":memory:", rebalance=False)
    try:
        nid = m.store.add_node("w0", "127.0.0.1", 1, is_active=True)
        node = m.store.get_node(nid)
        for _ in range(3):
            m._node_failure(node)
        assert _types(m, etype="breaker-open", node_id=nid) == [
            "breaker-open"]
        ev = m.store.query_events(etype="breaker-open")[0]
        assert ev["data"]["strikes"] == 3
        # half-open probe success closes -> breaker-closed event
        m.store.update_node(nid, breaker_state="half_open")
        m._node_success(m.store.get_node(nid))
        assert _types(m, etype="breaker-closed", node_id=nid) == [
            "breaker-closed"]

        # no schedulable node: park (non-terminal), then terminal fail
        rid = m.store.submit_request("tiny-llama", "p")
        m.store.update_node(nid, is_active=0)
        req = m.store.claim_next_pending()
        assert m._reserve_node_for(req) is None
        m.store.flush()
        parks = m.store.query_events(etype="request-park",
                                     request_id=rid)
        assert len(parks) == 1 and parks[0]["data"]["terminal"] is False
        req["attempts"] = MAX_ATTEMPTS - 1
        assert m._reserve_node_for(req) is None
        m.store.flush()
        parks = m.store.query_events(etype="request-park",
                                     request_id=rid)
        assert [p["data"]["terminal"] for p in parks] == [False, True]
        assert parks[-1]["severity"] == "error"
    finally:
        m.stop()


class _Resp:
    def __init__(self, body):
        self._body = body

    def json(self):
        return self._body


def test_master_drain_transition_events():
    m = Master(":memory:", rebalance=False)
    try:
        nid = m.store.add_node("w0", "127.0.0.1", 1, is_active=True)

        def sweep(status):
            node = m.store.get_node(nid)
            m._scrape_workers = lambda path, nodes=None: [
                (node, _Resp({"status": status}), None)]
            m._health_sweep()

        sweep("online")                    # no change: no event
        sweep("draining")                  # off -> on
        sweep("draining")                  # steady: no event
        sweep("online")                    # on -> off
        m.store.flush()
        evs = m.store.query_events(etype="node-drain", node_id=nid)
        assert [e["data"]["draining"] for e in evs] == [True, False]
    finally:
        m.stop()


def test_master_burn_crossing_hysteresis():
    m = Master(":memory:", rebalance=False)
    try:
        m._note_burn(0.5)
        m._note_burn(2.0)     # crossing up
        m._note_burn(5.0)     # still above: silent
        m._note_burn(0.3)     # crossing down
        m._note_burn(0.1)     # still below: silent
        m.store.flush()
        evs = m.store.query_events(etype="slo-burn")
        assert [e["data"]["direction"] for e in evs] == ["above",
                                                         "below"]
        assert evs[0]["severity"] == "warning"
        assert evs[1]["severity"] == "info"
    finally:
        m.stop()


def test_fault_arm_emits_event():
    m = Master(":memory:", rebalance=False)
    try:
        m.service.faults.arm([{"point": "/inference", "mode": "error",
                               "times": 1}])
        assert _types(m, etype="fault-armed") == ["fault-armed"]
        ev = m.store.query_events(etype="fault-armed")[0]
        assert ev["data"]["points"] == ["/inference"]
        assert ev["data"]["service"] == "master"
    finally:
        m.stop()


def test_api_events_filters_and_validation():
    m = Master(":memory:", rebalance=False)
    try:
        nid = m.store.add_node("w0", "127.0.0.1", 1, is_active=True)
        m.events.emit("breaker-open", node_id=nid, strikes=3,
                      prev_state="closed")
        m.events.emit("node-drain", node_id=nid, draining=True)
        out = m.api_events({})
        assert out["count"] == 2
        assert out["events"][0].get("node") == "w0"
        out = m.api_events({"type": "node-drain"})
        assert [e["type"] for e in out["events"]] == ["node-drain"]
        status, body = m.api_events({"type": "no-such-type"})
        assert status == 400, body
        status, body = m.api_events({"node": "notanint"})
        assert status == 400, body
    finally:
        m.stop()


def test_journey_merges_events_phases_and_node_context():
    m = Master(":memory:", rebalance=False)
    try:
        nid = m.store.add_node("w0", "127.0.0.1", 1, is_active=True)
        rid = m.store.submit_request("tiny-llama", "p")
        req = m.store.claim_next_pending()
        assert req["id"] == rid
        # node-scoped context inside the window (no request id)...
        m.events.emit("breaker-open", node_id=nid, strikes=3,
                      prev_state="closed")
        # ...a request-tagged event on the same node...
        m.events.emit("request-requeued", request_id=rid, node_id=nid,
                      error="boom", attempts=0)
        # ...and an unrelated node's event that must NOT merge
        other = m.store.add_node("w9", "127.0.0.1", 2, is_active=True)
        m.events.emit("breaker-open", node_id=other, strikes=3,
                      prev_state="closed")
        cost = {"queue_ms": 10.0, "prefill_ms": 30.0, "decode_ms": 60.0}
        m.store.mark_completed(rid, "out", nid, 0.1, 80.0, cost=cost)
        out = m.api_request_journey({}, str(rid))
        assert out["status"] == "success" and out["connected"], out
        names = [(e["kind"], e["name"]) for e in out["entries"]]
        assert ("lifecycle", "submitted") in names
        assert ("lifecycle", "claimed") in names
        assert ("lifecycle", "completed") in names
        assert ("event", "request-requeued") in names
        assert ("node-event", "breaker-open") in names
        # the unrelated node's trip stays out
        merged_nodes = {e.get("node_id") for e in out["entries"]
                        if e["name"] == "breaker-open"}
        assert merged_nodes == {nid}
        ts = [e["t"] for e in out["entries"]]
        assert ts == sorted(ts)
        # phases partition backward from completion and abut exactly
        assert [p["phase"] for p in out["phases"]] == [
            "queue", "prefill", "decode"]
        q, pf, dc = out["phases"]
        assert q["end"] == pf["start"] and pf["end"] == dc["start"]
        # epoch-magnitude floats: ~1e-7 s absolute precision, so gate
        # the 100ms span at 0.01 ms
        assert abs((dc["end"] - q["start"]) * 1e3 - 100.0) < 0.01
        # 404/400 shapes
        assert m.api_request_journey({}, "999999")[0] == 404
        assert m.api_request_journey({}, "notanint")[0] == 400
    finally:
        m.stop()


# ---- master restart: TSDB + journal durability -------------------------

def test_master_restart_restores_tsdb_and_journal(tmp_path):
    db = str(tmp_path / "m.sqlite3")
    m = Master(db, rebalance=False, tsdb_step_s=0.2, tsdb_snapshot_s=0.1)
    m.metrics.inc("requests_submitted", 5)
    for _ in range(3):
        m._telemetry_sweep()
        time.sleep(0.25)
    m.events.emit("node-drain", node_id=1, draining=True)
    before = m.tsdb.query("requests_submitted", node="master")
    assert before and len(before[0]["points"]) >= 2, before
    m.stop()   # final snapshot + flush

    m2 = Master(db, rebalance=False, tsdb_step_s=0.2, tsdb_snapshot_s=0)
    try:
        # restored series serves the pre-restart points...
        after = m2.tsdb.query("requests_submitted", node="master")
        assert after and after[0]["points"] == before[0]["points"]
        # ...and a post-restart sweep extends the SAME series: one
        # query spans samples from both runs. The restored fine-ring
        # samples survive verbatim; only the in-progress coarse
        # accumulator's preview may re-average as new samples join its
        # bucket — exactly as it would WITHOUT a restart.
        pre_fine = [tuple(p) for p in m.tsdb.dump()["nodes"]["master"]
                    ["requests_submitted"]["fine"]]
        time.sleep(0.25)
        m2.metrics.inc("requests_submitted", 2)
        m2._telemetry_sweep()
        s2 = m2.tsdb._series["master"]["requests_submitted"]
        assert list(s2.fine)[:len(pre_fine)] == pre_fine
        spanned = m2.tsdb.query("requests_submitted", node="master")
        assert len(spanned[0]["points"]) > len(before[0]["points"])
        pre_last = max(t for t, _ in before[0]["points"])
        assert spanned[0]["points"][-1][0] > pre_last
        # the journal survived too
        evs = m2.store.query_events(etype="node-drain")
        assert len(evs) == 1 and evs[0]["data"]["draining"] is True
    finally:
        m2.stop()


def test_master_snapshot_disabled_writes_nothing(tmp_path):
    db = str(tmp_path / "m.sqlite3")
    m = Master(db, rebalance=False, tsdb_step_s=0.2, tsdb_snapshot_s=0)
    m._telemetry_sweep()
    m.stop()
    st = Store(db)
    try:
        assert st.get_meta("tsdb_snapshot") is None
    finally:
        st.close()


# ---- live e2e: the chaos gate ------------------------------------------

def _mk_worker(role="mixed", **load_kw):
    agent = WorkerAgent(role=role)
    srv = agent.serve("127.0.0.1", 0, background=True)
    port = srv.server_address[1]
    body = {"model_name": "tiny-llama", "allow_random_init": True,
            "dtype": "float32", "serving": "batched", "slots": 4,
            "kv_blocks": 64, "kv_block_size": 8, "max_seq": 128,
            "decode_chunk_cap": 4}
    body.update(load_kw)
    r = rq.post(f"http://127.0.0.1:{port}/load_model", json=body,
                timeout=600)
    assert r.status_code == 200, r.text
    return agent, port


def _cluster(roles, **master_kw):
    workers = [_mk_worker(role=r) for r in roles]
    master_kw.setdefault("health_interval", 0.5)
    master_kw.setdefault("rebalance", False)
    m = Master(":memory:", **master_kw)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    for i, (_, port) in enumerate(workers):
        r = rq.post(f"{base}/api/nodes/add",
                    json={"name": f"w{i}", "host": "127.0.0.1",
                          "port": port}, timeout=30).json()
        assert r["status"] == "success", r
    m.start_background()
    return m, base, workers


def _wait_req(base, rid, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline:
        st = rq.get(f"{base}/api/inference/status/{rid}",
                    timeout=30).json()["request"]
        if st["status"] in ("completed", "failed"):
            return st
        time.sleep(0.05)
    raise TimeoutError(f"request {rid} never finished")


def test_chaos_kill_decode_node_journal_reconstructs_recovery():
    """The ISSUE 13 chaos gate: kill a decode worker mid-request and
    reconstruct the whole recovery from the persisted journal alone —
    disagg verdict -> breaker open -> failover requeue -> recovery —
    with every event linked into the affected request's journey, which
    shows one connected cross-node timeline."""
    m, base, workers = _cluster(["prefill", "decode", "decode"],
                                disagg=True, disagg_min_prompt=64,
                                infer_timeout=20)
    (pre, _), (d1, p1), (d2, p2) = workers
    try:
        time.sleep(0.8)   # one health sweep: runtime roles fresh
        ref = _wait_req(base, rq.post(
            f"{base}/api/inference/submit", json={
                "model_name": "tiny-llama", "prompt": LONG_PROMPT,
                "max_new_tokens": 30,
                "sampling": {"do_sample": False,
                             "allow_random_init": True}},
            timeout=30).json()["request_id"])
        assert ref["status"] == "completed", ref

        rid = rq.post(f"{base}/api/inference/submit", json={
            "model_name": "tiny-llama", "prompt": LONG_PROMPT,
            "max_new_tokens": 30,
            "sampling": {"do_sample": False,
                         "allow_random_init": True}},
            timeout=30).json()["request_id"]
        victim = None
        deadline = time.time() + 30
        while time.time() < deadline and victim is None:
            node = m._processing.get(rid)
            if node is not None and node["port"] in (p1, p2):
                victim = node
            time.sleep(0.002)
        assert victim is not None, "request never landed on decode"
        killed = d1 if victim["port"] == p1 else d2
        killed.service.shutdown()
        st = _wait_req(base, rid, timeout=120)
        assert st["status"] == "completed", st
        assert st["result"] == ref["result"]
        assert st["attempts"] >= 1

        # ---- the journal alone reconstructs the recovery ----
        m.store.flush()
        plan = m.store.query_events(etype="disagg-plan", request_id=rid)
        assert plan and plan[0]["data"]["verdict"] == "transfer", plan
        assert plan[0]["data"]["prefill_pool"] == 1
        assert plan[0]["data"]["est_tokens"] > 0
        trips = m.store.query_events(etype="breaker-open",
                                     node_id=victim["id"])
        assert trips, "victim's breaker trip not journaled"
        requeues = m.store.query_events(etype="request-requeued",
                                        request_id=rid)
        assert requeues and requeues[0]["node_id"] == victim["id"]
        # chronology: verdict -> trip/requeue -> completion
        assert plan[0]["ts"] <= requeues[0]["ts"]
        assert requeues[0]["ts"] <= st["completed_at"]

        # ---- and every event links into the request's journey ----
        jr = rq.get(f"{base}/api/requests/{rid}/journey",
                    timeout=30).json()
        assert jr["status"] == "success" and jr["connected"], jr
        names = [(e["kind"], e["name"]) for e in jr["entries"]]
        assert ("event", "disagg-plan") in names
        assert ("event", "request-requeued") in names
        assert ("node-event", "breaker-open") in names
        ts = [e["t"] for e in jr["entries"]]
        assert ts == sorted(ts)
        # cross-node: the journey's records name BOTH sides of the
        # disagg split (prefill node + the decode nodes involved)
        nodes_seen = {e.get("node_id") for e in jr["entries"]
                      if e.get("node_id") is not None}
        assert victim["id"] in nodes_seen
        assert plan[0]["data"]["prefill_node"] in nodes_seen \
            or len(nodes_seen) >= 2
        assert jr["trace_id"], jr
    finally:
        m.stop()
        # the batchers' scheduler threads too: the killed worker's keeps
        # decoding for nobody otherwise
        for agent, _ in workers:
            stop_worker(agent)
