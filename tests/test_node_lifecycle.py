"""Node lifecycle: breaker state machine, reactivation, accounting.

Covers what the HTTP suites never did: strike accumulation to the
FAILURE_STRIKES trip point, the half-open probe edges in both
directions, reactivation of a dead-then-revived worker via the health
loop, bounded crash-loop recovery, and the master's in-flight counter
staying non-negative under concurrent failures.
"""

import threading
import time

import pytest
import requests

from distributed_llm_inferencing_tpu.runtime.master import (
    FAILURE_STRIKES, MAX_ATTEMPTS, Master)
from distributed_llm_inferencing_tpu.runtime.state import Store
from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
from conftest import stop_worker


def _url(port, path):
    return f"http://127.0.0.1:{port}{path}"


@pytest.fixture
def quiet_master():
    """``Master(...)`` that is never started: its store's write-behind
    flusher is the one thread it has, closed and joined at teardown."""
    made = []

    def make(*args, **kw):
        made.append(Master(*args, **kw))
        return made[-1]
    yield make
    for m in made:
        m.store.close()


# ---- breaker state machine (no sockets) ------------------------------

def test_strikes_accumulate_then_open_at_threshold(quiet_master):
    m = quiet_master(":memory:")           # no background threads started
    nid = m.store.add_node("n1", "127.0.0.1", 1, is_active=True)
    node = m.store.get_node(nid)
    for i in range(FAILURE_STRIKES - 1):
        m._node_failure(node)
        n = m.store.get_node(nid)
        assert n["consecutive_failures"] == i + 1
        assert n["is_active"] == 1 and n["breaker_state"] == "closed"
    m._node_failure(node)
    n = m.store.get_node(nid)
    assert n["is_active"] == 0 and n["breaker_state"] == "open"
    assert n["breaker_opened_at"] is not None
    assert m.metrics.snapshot()["counters"]["breaker_opened"] == 1


def test_half_open_probe_failure_reopens_immediately(quiet_master):
    m = quiet_master(":memory:")
    nid = m.store.add_node("n1", "127.0.0.1", 1, is_active=True)
    m.store.update_node(nid, breaker_state="half_open", is_active=1,
                        consecutive_failures=FAILURE_STRIKES)
    m._node_failure(m.store.get_node(nid))
    n = m.store.get_node(nid)
    assert n["breaker_state"] == "open" and n["is_active"] == 0


def test_success_closes_half_open_and_clears_strikes(quiet_master):
    m = quiet_master(":memory:")
    nid = m.store.add_node("n1", "127.0.0.1", 1, is_active=True)
    m.store.update_node(nid, breaker_state="half_open", is_active=1,
                        consecutive_failures=FAILURE_STRIKES)
    m._node_success(m.store.get_node(nid))
    n = m.store.get_node(nid)
    assert n["breaker_state"] == "closed"
    assert n["consecutive_failures"] == 0 and n["is_active"] == 1
    assert m.metrics.snapshot()["counters"]["breaker_closed"] == 1


def test_pick_node_skips_open_draining_and_limits_half_open(quiet_master):
    m = quiet_master(":memory:")
    a = m.store.add_node("a", "127.0.0.1", 1, is_active=True)
    b = m.store.add_node("b", "127.0.0.1", 2, is_active=True)
    # open breaker on a -> only b schedulable
    m.store.update_node(a, breaker_state="open", is_active=0)
    assert m._pick_node(None)["id"] == b
    # draining b too -> nothing schedulable
    m.store.update_node(b, draining=1)
    assert m._pick_node(None) is None
    # half-open a admits exactly one in-flight probe
    m.store.update_node(a, breaker_state="half_open", is_active=1)
    assert m._pick_node(None)["id"] == a
    m._inflight[a] = 1
    assert m._pick_node(None) is None
    # exclusion falls back to the excluded node rather than failing
    m._inflight[a] = 0
    m.store.update_node(b, draining=0)
    assert m._pick_node(None, exclude={b})["id"] == a
    assert m._pick_node(None, exclude={a, b}) is not None


def test_timeout_retry_prefers_node_holding_the_generation(quiet_master):
    """A timeout requeue records the node and does not exclude it; the
    retry pins back to that node (its idempotency cache / in-flight
    join has the generation) instead of re-generating on a peer."""
    m = quiet_master(":memory:")
    a = m.store.add_node("a", "127.0.0.1", 1, is_active=True)
    b = m.store.add_node("b", "127.0.0.1", 2, is_active=True)
    rid = m.store.submit_request("x", "p", 3, {})
    assert m.store.claim_next_pending()["id"] == rid
    m.store.requeue(rid, excluded_node_id=None, delay_s=0.0, last_node_id=b)
    req = m.store.claim_next_pending()
    assert req["node_id"] == b and req["excluded_nodes"] == []
    # plain least-loaded would tie-break to node a; prefer pins b
    assert m._pick_node("x", exclude=set())["id"] == a
    assert m._pick_node("x", exclude=set(), prefer=b)["id"] == b
    # an excluded (faulted) node is never pinned
    m.store.requeue(rid, excluded_node_id=b, delay_s=0.0, last_node_id=b)
    req = m.store.get_request(rid)
    assert req["excluded_nodes"] == [b]
    assert m._pick_node("x", exclude={b}, prefer=None)["id"] == a


# ---- reactivation via the health loop --------------------------------

def test_dead_node_reactivates_via_health_probe():
    """Worker dies -> breaker opens; worker comes back on the same port
    -> health probe half-opens; real traffic closes. The reference
    deactivated forever on one strike (SURVEY.md §3.4)."""
    agent = WorkerAgent()
    srv = agent.serve("127.0.0.1", 0, background=True)
    port = srv.server_address[1]
    m = Master(":memory:", dispatcher_threads=2, health_interval=0.2,
               retry_backoff_base=0.05)
    m.start_background()
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    mport = msrv.server_address[1]
    revived = None
    try:
        r = requests.post(_url(mport, "/api/nodes/add"), json={
            "name": "lazarus", "host": "127.0.0.1", "port": port}).json()
        nid = r["node_id"]
        agent.service.shutdown()          # node dies
        deadline = time.time() + 15
        while time.time() < deadline:
            n = m.store.get_node(nid)
            if n["breaker_state"] == "open":
                break
            time.sleep(0.1)
        assert n["breaker_state"] == "open" and not n["is_active"]

        revived = WorkerAgent()           # same address, new process-alike
        revived.serve("127.0.0.1", port, background=True)
        deadline = time.time() + 15
        while time.time() < deadline:
            n = m.store.get_node(nid)
            if n["breaker_state"] == "half_open":
                break
            time.sleep(0.1)
        assert n["breaker_state"] == "half_open" and n["is_active"]

        # a real request through the half-open probe closes the breaker
        rid = requests.post(_url(mport, "/api/inference/submit"), json={
            "model_name": "tiny-gpt2", "prompt": "hi", "max_new_tokens": 3,
            "sampling": {"do_sample": False, "allow_random_init": True},
        }).json()["request_id"]
        deadline = time.time() + 90
        while time.time() < deadline:
            st = requests.get(_url(
                mport, f"/api/inference/status/{rid}")).json()["request"]
            if st["status"] in ("completed", "failed"):
                break
            time.sleep(0.2)
        assert st["status"] == "completed", st
        n = m.store.get_node(nid)
        assert n["breaker_state"] == "closed"
        assert n["consecutive_failures"] == 0
    finally:
        m.stop()
        if revived is not None:
            stop_worker(revived)
        stop_worker(agent)


# ---- in-flight accounting under concurrent failures ------------------

def test_inflight_never_negative_under_concurrent_failures(quiet_master):
    m = quiet_master(":memory:", retry_backoff_base=0.01)
    m.store.add_node("dead", "127.0.0.1", 1, is_active=True)  # refused port
    for _ in range(8):
        m.store.submit_request("x", "p", 3, {})

    def run():
        req = m.store.claim_next_pending()
        while req is not None:
            m._execute_on_node(req)
            req = m.store.claim_next_pending()

    threads = [threading.Thread(target=run) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert all(v >= 0 for v in m._inflight.values()), m._inflight


# ---- bounded crash-loop recovery (satellite) -------------------------

def test_recover_stale_counts_attempts_and_bounds_poison_requests():
    s = Store(":memory:")
    rid = s.submit_request("m", "p")
    assert s.claim_next_pending()["id"] == rid
    assert s.recover_stale_processing(max_attempts=MAX_ATTEMPTS) == 1
    r = s.get_request(rid)
    assert r["status"] == "pending" and r["attempts"] == 1
    # a poison request that kills its worker on every dispatch stops
    # being requeued once recovery has consumed the attempt budget
    while True:
        r = s.get_request(rid)
        if r["status"] == "failed":
            break
        assert r["attempts"] < MAX_ATTEMPTS
        assert s.claim_next_pending() is not None
        s.recover_stale_processing(max_attempts=MAX_ATTEMPTS)
    assert "crash recovery" in r["error"]
    assert r["attempts"] == MAX_ATTEMPTS - 1   # the final one failed, not ran


def test_requeue_records_exclusion_and_backoff():
    s = Store(":memory:")
    rid = s.submit_request("m", "p")
    s.claim_next_pending()
    s.requeue(rid, excluded_node_id=7, delay_s=5.0)
    r = s.get_request(rid)
    assert r["status"] == "pending" and r["attempts"] == 1
    assert r["excluded_nodes"] == [7]
    assert r["next_attempt_at"] > time.time() + 3
    # parked behind backoff: invisible to the dispatcher until due
    assert s.claim_next_pending() is None
    s.requeue(rid, excluded_node_id=7, delay_s=0.0)   # no duplicate entry
    r = s.get_request(rid)
    assert r["excluded_nodes"] == [7] and r["attempts"] == 2
    assert s.claim_next_pending()["id"] == rid


def test_schema_migration_adds_new_columns(tmp_path):
    """A pre-PR2 on-disk DB (no breaker/backoff columns) upgrades in
    place at open instead of crashing the master."""
    import sqlite3
    db = str(tmp_path / "old.sqlite3")
    conn = sqlite3.connect(db)
    conn.executescript("""
        CREATE TABLE nodes (
            id INTEGER PRIMARY KEY AUTOINCREMENT, name TEXT UNIQUE NOT NULL,
            host TEXT NOT NULL, port INTEGER NOT NULL,
            is_active INTEGER DEFAULT 0, consecutive_failures INTEGER
            DEFAULT 0, last_heartbeat REAL, added_at REAL,
            info TEXT DEFAULT '{}');
        CREATE TABLE requests (
            id INTEGER PRIMARY KEY AUTOINCREMENT, model_name TEXT NOT NULL,
            prompt TEXT NOT NULL, status TEXT DEFAULT 'pending',
            result TEXT, error TEXT, node_id INTEGER,
            attempts INTEGER DEFAULT 0, max_new_tokens INTEGER,
            max_length INTEGER, sampling TEXT DEFAULT '{}', created_at REAL,
            started_at REAL, completed_at REAL, execution_time REAL,
            tokens_per_s REAL);
        INSERT INTO nodes (name, host, port, is_active)
            VALUES ('old', 'h', 1, 1);
        INSERT INTO requests (model_name, prompt, status)
            VALUES ('m', 'p', 'pending');
    """)
    conn.commit()
    conn.close()
    s = Store(db)
    n = s.list_nodes()[0]
    assert n["breaker_state"] == "closed" and n["draining"] == 0
    r = s.claim_next_pending()
    assert r is not None and r["excluded_nodes"] == []
