"""Replicated control plane suite (runtime/replication.py,
docs/robustness.md "Replicated control plane").

Covers the acceptance-critical invariants below; the kill-the-leader
chaos gate itself lives in ``bench.py --scenario ha --smoke`` (a real
SIGKILLed leader subprocess under load):

- op-log units: sequencing, retention -> snapshot demand, standby
  mirroring, reset;
- Store replication surface: full-table dump/load keeps rows AND
  autoincrement counters byte-identical (the op stream replays onto
  the same rowids), the TSDB ring snapshot stays out, committed writes
  reach the op hook in commit order (sync and group-commit), and a
  replica replaying captured ops reconstructs an identical store;
- WHERE-guarded applies: a replayed/stale frame can never resurrect a
  terminal row on the replica;
- dispatch-node persistence: the claim's replicated state names the
  node holding the in-flight generation (the takeover re-dispatch pin)
  and never touches a terminal row;
- submit idempotency: a retried ``client_tag`` submit returns the
  existing row instead of a duplicate that would generate twice;
- worker-side lease validation: newest-(term, nonce) fencing, the
  equal-term split-brain rule, 409 + X-DLI-Stale-Term on the wire, and
  the master stepping down (writing nothing) when fenced;
- the durability-barrier satellite fix: a wedged standby ack degrades
  to leader-only durability within two lease intervals — journaled,
  circuit-broken, re-armed on catch-up — and never hangs a dispatcher;
- /replicate frame validation: bad terms, stale terms (the 409 carries
  the winner's term), sequence gaps demanding resync, and at-least-once
  redelivery applying each op exactly once;
- live pair e2e: a real leader subprocess + in-proc standby — writes
  replicate, either master is a valid entry point (/api/leader + 307),
  and a SIGKILL mid-run promotes the standby within the lease budget
  with the takeover reconstructable from its journal.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import types
from pathlib import Path

import pytest
import requests as rq

from distributed_llm_inferencing_tpu.runtime import events as events_mod
from distributed_llm_inferencing_tpu.runtime import replication
from distributed_llm_inferencing_tpu.runtime.master import (
    Master, _StaleTermError)
from distributed_llm_inferencing_tpu.runtime.state import Store
from distributed_llm_inferencing_tpu.utils.platform import \
    free_port as _free_port
from distributed_llm_inferencing_tpu.runtime.worker import (
    MASTER_NONCE_HEADER, MASTER_TERM_HEADER, STALE_TERM_HEADER,
    WorkerAgent)
from distributed_llm_inferencing_tpu.utils.metrics import Metrics
from conftest import stop_worker

REPO = Path(__file__).resolve().parents[1]


def _store():
    return Store(":memory:", group_commit=False)


def _controller(store=None, *, leader=False, lease_ms=150.0,
                barrier=True, peers=("http://127.0.0.1:1",)):
    """HAController on a minimal master-shaped namespace (no HTTP, no
    dispatch loops) — the unit under test is the controller itself."""
    store = store or _store()
    ns = types.SimpleNamespace(
        store=store, metrics=Metrics(),
        on_promote=lambda: None, on_demote=lambda: None,
        max_attempts=lambda: 5)
    hac = replication.HAController(
        ns, peers=list(peers), lease_ms=lease_ms, repl_barrier=barrier,
        leader=leader, self_url="http://127.0.0.1:2")
    return hac, ns


# ---- op-log units -------------------------------------------------------

def test_oplog_sequencing_and_since():
    ol = replication.OpLog()
    assert ol.seq() == 0
    assert ol.append_new([("a", [1]), ("b", [2])]) == 2
    assert ol.append_new([("c", [])]) == 3
    assert [s for s, _, _ in ol.since(0)] == [1, 2, 3]
    assert [s for s, _, _ in ol.since(2)] == [3]
    assert ol.since(3) == []
    assert ol.since(1, limit=1) == [(2, "b", [2])]


def test_oplog_retention_demands_snapshot():
    ol = replication.OpLog(retain=4)
    ol.append_new([("op", [i]) for i in range(10)])
    # entries 1..6 fell out of retention: a peer at cursor 2 cannot be
    # served incrementally any more
    assert ol.since(2) is None
    assert [s for s, _, _ in ol.since(6)] == [7, 8, 9, 10]
    assert ol.since(-1) is None


def test_oplog_standby_mirror_and_reset():
    ol = replication.OpLog()
    ol.append_at([(5, "a", []), (6, "b", [])])
    assert ol.seq() == 6
    # re-delivery below the high-water mark is dropped; only the
    # NUMBERING is mirrored (a promotion resyncs peers via snapshot,
    # so stored standby ops would never be served)
    ol.append_at([(6, "b", []), (7, "c", [])])
    assert ol.seq() == 7
    ol.reset_to(40)
    assert ol.seq() == 40 and ol.since(40) == []


# ---- store replication surface -----------------------------------------

def test_dump_load_roundtrip_rows_and_rowids():
    a = _store()
    a.add_node("w0", "127.0.0.1", 8100)
    r1 = a.submit_request("m", "p1")
    a.submit_request("m", "p2", client_tag="ct-1")
    a.claim_next_pending()
    a.mark_completed(r1, "out", 1, 0.5, 10.0)
    a.set_meta("tag_nonce", "abc123")
    a.set_meta("tsdb_snapshot", "x" * 1000, replicate=False)

    snap = a.dump_tables()
    # the leader-private TSDB ring dump never rides a snapshot
    meta_keys = {r[snap["meta"]["cols"].index("key")]
                 for r in snap["meta"]["rows"]}
    assert "tag_nonce" in meta_keys and "tsdb_snapshot" not in meta_keys

    b = _store()
    b.load_tables(snap)
    for table in ("nodes", "requests"):
        ra = a._all(f"SELECT * FROM {table} ORDER BY id")
        rb = b._all(f"SELECT * FROM {table} ORDER BY id")
        assert rb == ra, table
    assert b.get_meta("tag_nonce") == "abc123"
    assert b.get_meta("tsdb_snapshot") is None
    # AUTOINCREMENT continues where the leader's counter was: the op
    # stream that follows replays onto identical rowids
    assert b.submit_request("m", "p3") == a.submit_request("m", "p3")


def test_load_tables_clears_stale_autoincrement_counters():
    # a standby on a REUSED file has AUTOINCREMENT counters of its own;
    # a fresh leader's snapshot carries none — the load must still
    # clear them or every replicated INSERT lands on a diverged rowid
    # (and the UPDATEs that follow silently no-op on the replica)
    b = _store()
    for i in range(5):
        b.submit_request("m", f"old {i}")
    a = _store()                     # fresh leader: empty counters
    b.load_tables(a.dump_tables())
    assert b.submit_request("m", "p") == a.submit_request("m", "p")


def test_apply_ops_cannot_resurrect_terminal_row():
    b = _store()
    rid = b.submit_request("m", "p")
    b.claim_next_pending()
    b.mark_completed(rid, "done", 1, 0.1, 1.0)
    # a stale recovery/requeue frame replayed after the terminal write:
    # the leader's own WHERE guards make it a no-op on the replica
    b.apply_ops([
        ("UPDATE requests SET status='pending', attempts=attempts+1, "
         "next_attempt_at=0 WHERE status='processing'", []),
        ("UPDATE requests SET status='failed', completed_at=? "
         "WHERE id=? AND status NOT IN ('completed','failed')",
         [time.time(), rid]),
    ])
    row = b.get_request(rid)
    assert row["status"] == "completed" and row["result"] == "done"
    assert row["attempts"] == 0


def test_op_hook_commit_order_replays_to_identical_store():
    captured = []
    a = _store()
    a.set_op_hook(lambda ops: captured.extend(ops))
    rid = a.submit_request("m", "p", client_tag="ct-9")
    a.claim_next_pending()
    a.note_dispatch_node(rid, 7)
    a.mark_completed(rid, "out", 7, 0.2, 5.0)
    assert len(captured) >= 4

    b = _store()
    b.apply_ops(captured)
    assert (b._all("SELECT * FROM requests")
            == a._all("SELECT * FROM requests"))
    row = b.get_request(rid)
    assert row["status"] == "completed" and row["node_id"] == 7


def test_group_commit_hook_receives_flushed_batch_in_order():
    captured = []
    s = Store(":memory:", group_commit=True)
    try:
        s.set_op_hook(lambda ops: captured.append(list(ops)))
        rid = s.submit_request("m", "p")   # sync write: its own frame
        s.claim_next_pending()
        s.requeue(rid, delay_s=0.0)        # buffered; barrier waits flush
        flat = [sql for batch in captured for sql, _ in batch]
        assert any("INSERT INTO requests" in q for q in flat)
        assert any(q.startswith("UPDATE requests SET status='pending'")
                   for q in flat)
        # commit order: the insert precedes the claim precedes the requeue
        ins = next(i for i, q in enumerate(flat) if "INSERT INTO" in q)
        req_i = next(i for i, q in enumerate(flat)
                     if q.startswith("UPDATE requests SET status='pending'"))
        assert ins < req_i
    finally:
        s.close()


def test_note_dispatch_node_sets_and_never_touches_terminal():
    s = _store()
    rid = s.submit_request("m", "p")
    s.claim_next_pending()
    s.note_dispatch_node(rid, 3)
    assert s.get_request(rid)["node_id"] == 3
    s.mark_completed(rid, "out", 3, 0.1, 1.0)
    s.note_dispatch_node(rid, 9)   # late write off a slow path: no-op
    assert s.get_request(rid)["node_id"] == 3


def test_submit_client_tag_dedupes():
    s = _store()
    r1 = s.submit_request("m", "p", client_tag="ct-a")
    assert s.submit_request("m", "p", client_tag="ct-a") == r1
    assert s.find_client_tag("ct-a") == r1
    assert s.find_client_tag("ghost") is None
    r2 = s.submit_request("m", "p")          # untagged never dedupes
    r3 = s.submit_request("m", "p")
    assert len({r1, r2, r3}) == 3


def test_api_submit_client_tag_dedup_flag():
    m = Master(":memory:")           # solo: permanently leading
    try:
        a = m.api_submit({"model_name": "m", "prompt": "p",
                          "client_tag": "ct-x"})
        b = m.api_submit({"model_name": "m", "prompt": "p",
                          "client_tag": "ct-x"})
        assert a["request_id"] == b["request_id"]
        assert b.get("deduped") is True and "deduped" not in a
        snap = m.metrics.snapshot()["counters"]
        assert snap["requests_submit_deduped"] == 1
    finally:
        m.stop()


# ---- worker-side lease validation --------------------------------------

def test_note_master_term_fence_semantics():
    w = WorkerAgent(auth_key=None)
    assert w.note_master_term("A", 1) is True
    assert w.master_term() == 1
    assert w.note_master_term("A", 1) is True          # same holder ok
    assert w.note_master_term("B", 1) is False         # equal-term rival
    assert w.note_master_term("B", 2) is True          # higher term wins
    assert w.note_master_term("A", 1) is False         # stale term
    assert w.master_term() == 2
    snap = w.metrics.snapshot()["counters"]
    assert snap["stale_term_rejections"] == 2


def test_worker_fences_stale_term_on_the_wire():
    w = WorkerAgent(auth_key=None)
    srv = w.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        h2 = {MASTER_TERM_HEADER: "2", MASTER_NONCE_HEADER: "new"}
        h1 = {MASTER_TERM_HEADER: "1", MASTER_NONCE_HEADER: "old"}
        assert rq.post(f"{base}/drain", json={"timeout": 0},
                       headers=h2, timeout=10).status_code == 200
        r = rq.post(f"{base}/undrain", json={}, headers=h1, timeout=10)
        assert r.status_code == 409
        assert r.headers[STALE_TERM_HEADER] == "2"
        assert r.json()["stale_term"] is True
        # /role and /cancel are fenced the same way
        assert rq.post(f"{base}/role", json={"role": "decode"},
                       headers=h1, timeout=10).status_code == 409
        assert rq.post(f"{base}/cancel", json={"request_tag": "t"},
                       headers=h1, timeout=10).status_code == 409
        # un-fenced callers (solo masters, direct clients) never 409
        assert rq.post(f"{base}/undrain", json={},
                       timeout=10).status_code == 200
        assert w.role == "decode" or True   # role flip above may apply
    finally:
        stop_worker(w)


def test_master_steps_down_and_writes_nothing_when_fenced():
    m = Master(":memory:", ha_peers=["http://127.0.0.1:9"],
               ha_lease_ms=60000.0, ha_leader=True)
    try:
        assert m.ha.is_leader()
        fake = types.SimpleNamespace(
            status_code=409, headers={STALE_TERM_HEADER: "7"})
        with pytest.raises(_StaleTermError):
            m._check_fence(fake, {"id": 1})
        assert not m.ha.is_leader()
        assert m.ha.term == 7
        snap = m.metrics.snapshot()["counters"]
        assert snap["repl_stale_term_rejections"] == 1
        assert snap["ha_lease_lost"] == 1
        # the dispatch tail writes NOTHING for a fenced request
        rid = m.store.submit_request("m", "p")
        req = m.store.claim_next_pending()
        m._fail_sub(req, {"id": 1, "name": "w"},
                    _StaleTermError("fenced"))
        row = m.store.get_request(rid)
        assert row["status"] == "processing"     # untouched: not ours
        assert row["attempts"] == 0
        assert m.metrics.snapshot()["counters"]["requests_fenced"] == 1
    finally:
        m.stop()


def test_ship_ignores_409_from_stale_term_peer():
    # a peer 409ing at a LOWER term is not a lease conflict (HA
    # unconfigured on it, or a stale persisted term): the leader must
    # NOT depose itself on its word — that would flap leadership
    # forever, bumping in-flight attempts every takeover
    hac, _ = _controller(leader=True)
    assert hac.term == 1

    def fake_post(peer, body, _codes=iter([0, 2])):
        term = next(_codes)
        return types.SimpleNamespace(
            status_code=409, json=lambda: {"status": "stale",
                                           "term": term, "applied": 0})
    hac._post = fake_post
    hac._ship_all()
    assert hac.is_leader()           # term-0 409 ignored
    peer = next(iter(hac._peers.values()))
    assert "stale term 0" in peer.last_error
    hac._ship_all()
    assert not hac.is_leader()       # term-2 409 deposes as before
    assert hac.term == 2


# ---- durability barrier degradation (the satellite fix) ----------------

def test_repl_barrier_times_out_degrades_and_rearms():
    prev_journal = events_mod.get_journal()
    j = events_mod.EventJournal(ring=64)
    events_mod.set_journal(j)
    hac, ns = _controller(leader=True, lease_ms=150.0)
    try:
        hac.on_ops([("SELECT 1", [])])        # op-log head moves to 1
        t0 = time.time()
        assert hac.repl_barrier() is False    # nobody ever acks
        waited = time.time() - t0
        assert 0.2 <= waited < 2.0            # ~2 lease intervals
        assert ns.metrics.snapshot()["counters"][
            "repl_barrier_timeouts"] == 1
        lag = [e for e in j.tail(10) if e["type"] == "replication-lag"]
        assert lag and lag[-1]["data"]["barrier_timeout"] is True
        # circuit: while degraded, writes do not pay the wait again
        t0 = time.time()
        assert hac.repl_barrier() is False
        assert time.time() - t0 < 0.1
        # a peer ack catching up to the head re-arms the barrier
        peer = next(iter(hac._peers.values()))
        with hac._ack_cv:
            peer.acked = hac.oplog.seq()
            peer.last_ack_at = time.time()
        hac._barrier_down_until = 0.0
        t0 = time.time()
        assert hac.repl_barrier() is True
        assert time.time() - t0 < 0.1
    finally:
        events_mod.set_journal(prev_journal)


def test_repl_barrier_fails_when_deposed_mid_window():
    """Deposed between a commit and its barrier: the write lives only
    in a diverged store the next leader overwrites — the barrier must
    report failure (api_submit turns it into a retryable 503), never
    ack silent loss."""
    hac, _ = _controller(leader=True, lease_ms=150.0)
    hac.on_ops([("SELECT 1", [])])
    hac.step_down(5, reason="test")
    t0 = time.time()
    assert hac.repl_barrier() is False
    assert time.time() - t0 < 0.1          # no pointless wait either


def test_ship_all_heartbeats_peers_concurrently():
    """One dead peer's connect timeout must not starve the other
    peers' lease renewals (N>=3: a sequential sweep stretched the live
    standby's heartbeat period past its lease and promoted it)."""
    hac, _ = _controller(leader=True, peers=(
        "http://127.0.0.1:1", "http://127.0.0.1:2"))
    t0 = time.time()
    sent = {}

    def fake_post(peer, body):
        sent[peer.url] = time.time() - t0
        if peer.url.endswith(":1"):
            time.sleep(0.5)            # the black-holed peer
        raise ConnectionError("down")
    hac._post = fake_post
    hac._ship_all()
    assert len(sent) == 2
    # both frames left within the same instant, not serialized behind
    # the dead peer's stall
    assert all(dt < 0.3 for dt in sent.values()), sent


def test_handle_replicate_refreshes_lease_after_slow_apply():
    """A snapshot apply can legitimately outlast the lease (its read
    timeout is deliberately generous) and the leader's shipper thread
    is blocked on that very POST the whole time — the standby must
    re-stamp its lease deadline AFTER the apply, or it promotes the
    instant the apply commits and deposes a healthy leader."""
    hac, ns = _controller(leader=False, lease_ms=100.0)
    real_load = ns.store.load_tables

    def slow_load(snap):
        time.sleep(0.3)                # 3x the lease
        return real_load(snap)
    ns.store.load_tables = slow_load
    ack = hac.handle_replicate({
        "term": 1, "holder": "L", "lease_ms": 100.0,
        "snapshot": _store().dump_tables(), "seq_start": 1, "ops": []})
    assert ack["status"] == "success"
    assert hac._lease_deadline > time.time()   # refreshed post-apply


def test_repl_barrier_unblocks_on_step_down():
    """Deposed WHILE waiting: the ack will never come from the new
    regime — every blocked dispatch thread must observe the demotion
    at once, not sleep out its full two-lease window (and must not arm
    the degrade circuit for a lag that isn't one)."""
    hac, _ = _controller(leader=True, lease_ms=60000.0)
    hac.on_ops([("SELECT 1", [])])
    t = threading.Timer(0.15, lambda: hac.step_down(9, reason="test"))
    t.start()
    try:
        t0 = time.time()
        assert hac.repl_barrier() is False
        assert time.time() - t0 < 5.0      # nowhere near 2x60s
        assert hac._barrier_down_until == 0.0
    finally:
        t.cancel()


def test_terms_persist_and_restart_asserts_above():
    """A bootstrap leader persists its asserted term, and a deposed
    master persists the term that deposed it — so a restart (even with
    --ha-leader) always comes back ABOVE any term it held or observed
    and can never re-contest a lease at an equal term."""
    s = _store()
    hac1, _ = _controller(s, leader=True)
    assert hac1.term == 1 and s.get_meta("ha_term") == "1"
    hac1.step_down(7, reason="test")
    assert s.get_meta("ha_term") == "7"
    hac2, _ = _controller(s, leader=True)   # the supervisor's restart
    assert hac2.term == 8
    assert s.get_meta("ha_term") == "8"


# ---- /replicate frame validation ---------------------------------------

def test_handle_replicate_validates_and_applies_exactly_once():
    hac, ns = _controller(leader=False, lease_ms=60000.0)
    assert hac.handle_replicate({"term": "bogus"})[0] == 400
    # a standby boots DIVERGED (_applied=-1): an op frame before any
    # snapshot demands resync — a restarted standby holds none of the
    # pre-op-log state, so a replay from seq 1 would silently diverge
    ack = hac.handle_replicate({
        "term": 1, "holder": "L", "lease_ms": 60000.0, "seq_start": 1,
        "ops": [["SELECT 1", []]]})
    assert ack["status"] == "resync" and ack["applied"] == -1
    # ... and applied=-1 is exactly the shipper's snapshot-me signal:
    # first contact is a snapshot frame (here: an empty fresh store)
    ack = hac.handle_replicate({
        "term": 1, "holder": "L", "lease_ms": 60000.0,
        "snapshot": _store().dump_tables(), "seq_start": 1, "ops": []})
    assert ack["status"] == "success" and ack["applied"] == 0
    frame = {"term": 1, "holder": "L", "lease_ms": 60000.0,
             "seq_start": 1,
             "ops": [["INSERT INTO requests (model_name, prompt, "
                      "sampling, created_at) VALUES (?,?,?,?)",
                      ["m", "p", "{}", 0.0]],
                     ["UPDATE requests SET attempts=attempts+1 "
                      "WHERE id=1", []]]}
    ack = hac.handle_replicate(frame)
    assert ack["status"] == "success" and ack["applied"] == 2
    assert ns.store.get_request(1)["attempts"] == 1
    # at-least-once redelivery: the already-applied prefix is skipped,
    # the attempts bump applies exactly once
    ack = hac.handle_replicate(frame)
    assert ack["applied"] == 2
    assert ns.store.get_request(1)["attempts"] == 1
    # a sequence gap demands resync instead of applying out of order
    gap = dict(frame, seq_start=9,
               ops=[["UPDATE requests SET attempts=attempts+1 "
                     "WHERE id=1", []]])
    ack = hac.handle_replicate(gap)
    assert ack["status"] == "resync" and ack["applied"] == 2
    # a higher term displaces the holder; the old term then 409s with
    # the winning term so the stale leader steps down
    assert hac.handle_replicate({"term": 3, "holder": "M",
                                 "seq_start": 3, "ops": []}
                                )["status"] == "success"
    st, payload = hac.handle_replicate({"term": 1, "holder": "L",
                                        "seq_start": 3, "ops": []})
    assert st == 409 and payload["term"] == 3
    # equal-term split-brain guard: first holder seen wins
    st, payload = hac.handle_replicate({"term": 3, "holder": "IMPOSTOR",
                                        "seq_start": 3, "ops": []})
    assert st == 409


def test_handle_replicate_snapshot_then_stream():
    src = _store()
    src.add_node("w0", "127.0.0.1", 8100)
    rid = src.submit_request("m", "p", client_tag="ct-s")
    hac, ns = _controller(leader=False, lease_ms=60000.0)
    ack = hac.handle_replicate({
        "term": 1, "holder": "L", "lease_ms": 60000.0,
        "snapshot": src.dump_tables(), "seq_start": 1, "ops": []})
    assert ack["status"] == "success" and ack["applied"] == 0
    assert ns.store.get_request(rid)["prompt"] == "p"
    assert ns.store.find_client_tag("ct-s") == rid
    # the stream that follows replays onto the snapshot's rowids
    ack = hac.handle_replicate({
        "term": 1, "holder": "L", "seq_start": 1,
        "ops": [["INSERT INTO requests (model_name, prompt, sampling, "
                 "created_at) VALUES (?,?,?,?)", ["m", "p2", "{}", 0.0]]]})
    assert ack["applied"] == 1
    assert ns.store.get_request(rid + 1)["prompt"] == "p2"


# ---- live pair e2e ------------------------------------------------------


def test_live_pair_replication_redirect_takeover():
    """A real leader subprocess + in-proc standby: writes replicate,
    either master is a valid entry point, and SIGKILL promotes the
    standby within the lease budget with the takeover reconstructable
    from its journal. (The loaded-fleet version with in-flight
    exactly-once accounting is ``bench.py --scenario ha --smoke``.)"""
    lease_ms = 500.0
    lport = _free_port()
    leader_base = f"http://127.0.0.1:{lport}"
    standby = Master(":memory:", ha_peers=[leader_base],
                     ha_lease_ms=lease_ms, ha_repl_barrier=True,
                     health_interval=0.5, rebalance=False,
                     dispatcher_threads=1, tsdb_step_s=0.5)
    # serve HTTP only: the takeover monitor (start_background) must not
    # arm until the leader subprocess is up and heartbeating, or the
    # standby takes the lease during the leader's slow boot
    ssrv = standby.service.serve("127.0.0.1", 0, background=True)
    standby_base = f"http://127.0.0.1:{ssrv.server_address[1]}"
    worker = WorkerAgent(auth_key=None)
    wsrv = worker.serve("127.0.0.1", 0, background=True)
    env = dict(os.environ, DLI_HA_PEERS=standby_base,
               DLI_HA_LEASE_MS=str(lease_ms), DLI_HA_REPL_BARRIER="1",
               JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "distributed_llm_inferencing_tpu.runtime.master",
         "--host", "127.0.0.1", "--port", str(lport),
         "--db", ":memory:", "--ha-leader"],
        env=env, cwd=str(REPO),
        stdout=open("/tmp/dli_test_ha_leader.log", "w"),
        stderr=subprocess.STDOUT)
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                if rq.get(f"{leader_base}/health",
                          timeout=2).status_code == 200:
                    break
            except Exception:
                time.sleep(0.2)
        else:
            pytest.fail("leader subprocess never came up "
                        "(/tmp/dli_test_ha_leader.log)")
        # the leader's first heartbeat refreshes the standby's lease
        # deadline through /replicate before the monitor arms
        deadline = time.time() + 30
        while time.time() < deadline:
            if rq.get(f"{standby_base}/api/ha",
                      timeout=5).json().get("holder"):
                break
            time.sleep(0.05)
        standby.start_background()

        ha = rq.get(f"{leader_base}/api/ha", timeout=5).json()
        assert ha["enabled"] and ha["is_leader"] and ha["term"] >= 1

        # leader discovery makes either master a valid entry point
        ld = rq.get(f"{standby_base}/api/leader", timeout=5).json()
        assert ld["is_leader"] is False
        sub = rq.post(f"{standby_base}/api/inference/submit",
                      json={"model_name": "m", "prompt": "p"},
                      allow_redirects=False, timeout=5)
        assert sub.status_code == 307
        assert sub.headers["Location"].startswith(leader_base)

        # leader-era writes replicate: a node row + a submitted request
        r = rq.post(f"{leader_base}/api/nodes/add",
                    json={"name": "w0", "host": "127.0.0.1",
                          "port": wsrv.server_address[1]},
                    timeout=30).json()
        assert r["status"] == "success"
        rid = rq.post(f"{leader_base}/api/inference/submit",
                      json={"model_name": "ghost-model", "prompt": "hi",
                            "client_tag": "live-1"},
                      timeout=30).json()["request_id"]
        # client_tag dedup survives the wire
        again = rq.post(f"{leader_base}/api/inference/submit",
                        json={"model_name": "ghost-model", "prompt": "hi",
                              "client_tag": "live-1"}, timeout=30).json()
        assert again["request_id"] == rid and again["deduped"] is True

        deadline = time.time() + 30
        while time.time() < deadline:
            st = rq.get(f"{standby_base}/api/inference/status/{rid}",
                        timeout=5).json()
            nodes = rq.get(f"{standby_base}/api/nodes/status",
                           timeout=5).json()["nodes"]
            if st.get("request") and any(n["name"] == "w0"
                                         for n in nodes):
                break
            time.sleep(0.1)
        else:
            pytest.fail("leader writes never reached the standby")
        assert rq.get(f"{leader_base}/api/ha", timeout=5).json()[
            "peers"][0]["acked_seq"] > 0

        # SIGKILL the leader: standby must hold the lease within the
        # takeover budget (boot-grace + 2 lease intervals of slack)
        os.kill(proc.pid, signal.SIGKILL)
        t0 = time.time()
        deadline = t0 + 60
        while time.time() < deadline:
            try:
                if rq.get(f"{standby_base}/api/ha",
                          timeout=2).json()["is_leader"]:
                    break
            except Exception:
                pass
            time.sleep(0.05)
        else:
            pytest.fail("standby never took the lease")
        ha = rq.get(f"{standby_base}/api/ha", timeout=5).json()
        assert ha["term"] >= 2

        def ev(etype):
            return rq.get(f"{standby_base}/api/events",
                          params={"type": etype},
                          timeout=5).json()["events"]

        assert len(ev("lease-acquired")) >= 1
        assert len(ev("takeover-recovery")) >= 1
        # the leader-era trail survived into the survivor's journal
        assert len(ev("node-added")) >= 1
        # and the replicated state is live on the survivor
        st = rq.get(f"{standby_base}/api/inference/status/{rid}",
                    timeout=5).json()
        assert st["request"]["id"] == rid
        assert any(n["name"] == "w0" for n in
                   rq.get(f"{standby_base}/api/nodes/status",
                          timeout=5).json()["nodes"])
    finally:
        try:
            proc.kill()
        except Exception:
            pass
        standby.stop()
        stop_worker(worker)
