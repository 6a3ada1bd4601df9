"""Live in-flight migration + elastic rebalancing.

Covers the acceptance-critical invariants:
- a mid-generation migration is BITWISE invisible to the client: the
  resumed continuation emits exactly the tokens the unmigrated run
  would have (greedy AND sampled — the position-keyed PRNG continues
  the same stream), with zero duplicated and zero lost stream tokens
  (the source's stream cursor + the destination's new tokens partition
  the full output exactly),
- the migrate-vs-complete race is safe at every layer: the batcher
  answers None/409 when the request finished first, and a handoff can
  never resurrect a terminal row (the dliverify ``migrate_vs_complete``
  scenario model-checks the store's side),
- role is mutable worker state: POST /role flips it, /health and the
  numeric ``dli_worker_role`` gauge re-advertise it,
- master-driven migration end-to-end: draining a node live-migrates
  its in-flight request (303 handoff -> requeue_migrated -> resume on
  a peer with a real cross-node KV transfer) with an identical result,
- chaos: killing a worker mid-stream loses nothing — the failover
  retry completes the request with identical output, and a
  disaggregated request's persisted kv_source makes the recovery a
  re-fetch, not a re-prefill (FailSafe),
- the rebalancer's decision function: flips toward the starving pool
  on sustained TSDB divergence, honors the per-node cooldown, never
  empties the decode-capable pool, and migrates in-flight work off
  draining nodes.
"""

import json
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import requests as rq

from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime.master import Master
from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
from conftest import shared_batcher as Batcher, stop_worker

CFG = get_config("tiny-llama").replace(dtype="float32", attn_backend="xla")
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

LONG_PROMPT = "The quick brown fox jumps over the lazy dog. " * 2 + "Go."
PROMPT_TOKS = list(range(7, 7 + 21))   # 21 tokens: several full 8-blocks


# ---- batcher-level: snapshot + resume ----------------------------------

def _mk_batcher(**kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq", 128)
    kw.setdefault("kv_host_mb", 8)
    # small decode chunks so a migration request lands mid-stream, not
    # after the whole budget ran inside one chunk
    kw.setdefault("decode_chunk_cap", 4)
    return Batcher(CFG, PARAMS, **kw)


def _wait_tokens(req, n, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if len(req.tokens) >= n or req.done.is_set():
            return
        time.sleep(0.002)
    raise AssertionError(f"never reached {n} tokens "
                         f"({len(req.tokens)} emitted)")


@pytest.mark.parametrize("do_sample", [False, True],
                         ids=["greedy", "sampled"])
def test_batcher_migrate_stream_zero_dup_zero_loss(do_sample):
    """The headline guarantee at the batcher layer: source stream +
    destination stream partition the unmigrated run's exact token
    sequence — nothing duplicated, nothing lost, bitwise identical."""
    sp = SamplingParams(temperature=0.8, top_k=20, do_sample=do_sample)
    ref_b = _mk_batcher()
    ref_b.start()
    try:
        ref = ref_b.submit(PROMPT_TOKS, max_new_tokens=40, sampling=sp,
                           seed=5).wait(timeout=120)
    finally:
        ref_b.stop()

    src = _mk_batcher()
    src.start()
    s1 = []
    req = src.submit(PROMPT_TOKS, max_new_tokens=40, sampling=sp,
                     stream_cb=s1.append, seed=5)
    _wait_tokens(req, 6)
    rec = src.migrate_out(req)
    src.stop()
    assert rec is not None and req._migrated
    # the resume record IS the stream cursor: exactly what streamed
    assert rec["tokens"] == s1 and 0 < len(s1) < 40
    assert rec["seed"] == 5 and rec["steps"] == len(s1)

    dst = _mk_batcher()
    dst.start()
    try:
        # host-arena handover (the HTTP twin — /kv_fetch — is pinned in
        # test_disagg and the worker-level test below)
        for d in list(src.kvtier.arena._entries):
            dst.kvtier.arena.put(d, src.kvtier.arena.peek_pages(d),
                                 count_offload=False)
        s2 = []
        req2 = dst.submit(rec["prompt_tokens"],
                          max_new_tokens=rec["max_new_tokens"],
                          sampling=sp, stream_cb=s2.append,
                          eos_token_id=rec["eos_token_id"], resume=rec)
        full = req2.wait(timeout=120)
    finally:
        dst.stop()
    assert s1 + s2 == full == ref
    # the snapshot was actually used: the destination restored blocks
    # from the migrated KV instead of re-prefilling everything
    c = dst.metrics.snapshot()["counters"]
    assert c.get("kvtier_restored_blocks", 0) > 0


def test_batcher_migrate_races_completion_returns_none():
    b = _mk_batcher()
    b.start()
    try:
        req = b.submit(PROMPT_TOKS, max_new_tokens=2,
                       sampling=SamplingParams.greedy())
        req.wait(timeout=60)
        assert b.migrate_out(req, timeout=2.0) is None
        assert not req._migrated and not req.error
    finally:
        b.stop()


def test_batcher_migrate_queued_request():
    """A request still in the queue migrates by resume record alone
    (nothing on device yet)."""
    b = _mk_batcher(slots=1)
    b.start()
    try:
        hog = b.submit(PROMPT_TOKS, max_new_tokens=60,
                       sampling=SamplingParams.greedy())
        _wait_tokens(hog, 2)
        queued = b.submit(list(range(40, 55)), max_new_tokens=20,
                          sampling=SamplingParams.greedy(), seed=3)
        rec = b.migrate_out(queued, timeout=30)
        assert rec is not None and rec["tokens"] == []
        assert rec["prompt_tokens"] == list(range(40, 55))
        hog.cancel()
    finally:
        b.stop()


def test_migrated_accounting_not_failed():
    """A handoff is not a failure: it lands in
    batcher_requests_migrated, and submitted reconciles with
    completed + failed + migrated."""
    b = _mk_batcher()
    b.start()
    try:
        req = b.submit(PROMPT_TOKS, max_new_tokens=40,
                       sampling=SamplingParams.greedy())
        _wait_tokens(req, 4)
        assert b.migrate_out(req) is not None
        c = b.metrics.snapshot()["counters"]
        assert c["batcher_requests_migrated"] == 1
        assert c["batcher_requests_submitted"] == (
            c.get("batcher_requests_completed", 0)
            + c.get("batcher_requests_failed", 0)
            + c["batcher_requests_migrated"])
    finally:
        b.stop()


def test_resume_record_spec_state_roundtrip():
    """The spec-controller's request-owned policy state survives an
    export/load cycle (gamma, mode, acceptance window)."""
    from distributed_llm_inferencing_tpu.ops.speculative import (
        AdaptiveSpecController)
    a = AdaptiveSpecController(8)
    a.gamma = 2
    a.mode = "plain"
    a._accept.extend([(1, 4), (0, 4)])
    b = AdaptiveSpecController(8)
    b.load_state(a.export_state())
    assert b.gamma == 2 and b.mode == "plain"
    assert list(b._accept) == [(1, 4), (0, 4)]
    # malformed state is ignored field-by-field, never raises
    c = AdaptiveSpecController(8)
    c.load_state({"gamma": "x", "mode": "bogus", "accept": [[1]]})
    assert c.gamma == 8 and c.mode == "spec"


# ---- worker-level: /migrate_out, /role, cross-node resume ---------------

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


def _infer(port, max_new=24, seed=11, do_sample=False, **extra):
    body = {"model_name": "tiny-llama", "prompt": LONG_PROMPT,
            "max_new_tokens": max_new, "seed": seed,
            "sampling": {"do_sample": do_sample, "temperature": 0.8,
                         "top_k": 20}}
    body.update(extra)
    return rq.post(f"http://127.0.0.1:{port}/inference", json=body,
                   timeout=600)


@pytest.fixture(scope="module")
def worker_pair():
    a = _mk_worker()
    b = _mk_worker()
    yield a, b
    for agent, _ in (a, b):
        stop_worker(agent)


def test_role_flip_endpoint(worker_pair):
    (agent, port), _ = worker_pair
    assert rq.get(f"http://127.0.0.1:{port}/health").json()[
        "role"] == "mixed"
    r = rq.post(f"http://127.0.0.1:{port}/role",
                json={"role": "decode"}, timeout=10)
    assert r.status_code == 200
    assert r.json() == {"status": "success", "role": "decode",
                        "previous": "mixed"}
    h = rq.get(f"http://127.0.0.1:{port}/health").json()
    assert h["role"] == "decode"
    snap = agent.metrics.snapshot()
    assert snap["gauges"]["worker_role"] == 2.0
    assert snap["counters"]["role_flips"] == 1
    assert rq.post(f"http://127.0.0.1:{port}/role",
                   json={"role": "gpu"}, timeout=10).status_code == 400
    rq.post(f"http://127.0.0.1:{port}/role", json={"role": "mixed"},
            timeout=10)


def test_migrate_out_validation(worker_pair):
    (_, port), _ = worker_pair
    url = f"http://127.0.0.1:{port}/migrate_out"
    assert rq.post(url, json={}, timeout=10).status_code == 400
    assert rq.post(url, json={"request_tag": "ghost"},
                   timeout=10).status_code == 404


@pytest.mark.parametrize("do_sample", [False, True],
                         ids=["greedy", "sampled"])
def test_worker_migrate_resume_bitwise(worker_pair, do_sample):
    """Cross-node migration over the real wire: /migrate_out snapshot
    on A, 303 handoff with the resume record, resume on B pulling the
    mid-generation KV over /kv_fetch — final output bitwise identical
    to an unmigrated run."""
    (a, pa), (b, pb) = worker_pair
    seed = 21 if do_sample else 22
    ref = _infer(pb, seed=seed, do_sample=do_sample).json()["tokens"]

    tag = f"mig-{seed}"
    out = {}

    def run():
        out["r"] = _infer(pa, seed=seed, do_sample=do_sample,
                          request_tag=tag, timeout=120)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    deadline = time.time() + 60
    breq = None
    while time.time() < deadline:
        breq = a._tagged.get(tag)
        if breq is not None and len(breq.tokens) >= 5:
            break
        time.sleep(0.002)
    assert breq is not None and len(breq.tokens) >= 5
    r = rq.post(f"http://127.0.0.1:{pa}/migrate_out",
                json={"request_tag": tag, "model_name": "tiny-llama"},
                timeout=30)
    assert r.status_code == 200, r.text
    t.join(timeout=60)
    resp = out["r"]
    assert resp.status_code == 303, resp.text
    rec = resp.json()["resume"]
    assert 5 <= len(rec["tokens"]) < 24

    before = b.metrics.snapshot()["counters"].get("kv_transfer_blocks", 0)
    got = _infer(pb, seed=seed, do_sample=do_sample, resume=rec,
                 kv_source={"url": f"http://127.0.0.1:{pa}",
                            "model": "tiny-llama"}).json()
    assert got["tokens"] == ref
    after = b.metrics.snapshot()["counters"].get("kv_transfer_blocks", 0)
    assert after > before      # the resume actually fetched KV from A
    assert a.metrics.snapshot()["counters"]["requests_migrated_out"] >= 1


# ---- master-level: drain migration + chaos ------------------------------

def _cluster(roles, load_kw=None, **master_kw):
    workers = [_mk_worker(role=r, **(load_kw or {})) for r in roles]
    master_kw.setdefault("health_interval", 0.5)
    master_kw.setdefault("disagg", False)
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


def _submit(base, max_new=30, prompt=LONG_PROMPT):
    return rq.post(f"{base}/api/inference/submit", json={
        "model_name": "tiny-llama", "prompt": prompt,
        "max_new_tokens": max_new,
        "sampling": {"do_sample": False, "allow_random_init": True}},
        timeout=30).json()["request_id"]


def _wait_req(base, rid, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline:
        st = rq.get(f"{base}/api/inference/status/{rid}",
                    timeout=30).json()["request"]
        if st["status"] in ("completed", "failed"):
            return st
        time.sleep(0.05)
    raise TimeoutError(f"request {rid} never finished")


def test_master_drain_migrates_inflight_live():
    """Draining a node live-migrates its in-flight request within one
    rebalancer sweep: 303 handoff -> requeue_migrated -> resume on the
    peer, identical result, zero attempts burned."""
    # Single-slot workers + hog requests: a warm tiny-llama decodes 100
    # tokens in ~0.3s, far faster than any realistic drain -> health
    # sweep -> rebalancer chain — so the measured request must WAIT
    # behind hogs on its node (one slot each), which holds it in the
    # batcher (worker-side queued or early-stream, _tagged either way)
    # long enough for the drain chain to land deterministically.
    m, base, workers = _cluster(
        ["mixed", "mixed"], load_kw={"slots": 1},
        rebalance=True, rebalance_interval_s=0.05,
        rebalance_sustain_s=0.5, health_interval=0.1)
    prompt, budget = "please continue the story", 100
    try:
        time.sleep(0.5)          # one health sweep: runtime roles fresh
        ref = _wait_req(base, _submit(base, max_new=budget,
                                      prompt=prompt))
        assert ref["status"] == "completed", ref

        hogs = [_submit(base, max_new=budget,
                        prompt=f"hog {i} holds the single slot")
                for i in range(4)]
        rid = _submit(base, max_new=budget, prompt=prompt)
        # drain the node the moment the request is dispatched AND
        # registered with the worker's batcher (queued behind a hog or
        # already streaming — migrate_out handles both)
        tag = m._tag(rid)
        node = breq = None
        deadline = time.time() + 30
        while time.time() < deadline:
            node = m._processing.get(rid)
            breq = next((w._tagged.get(tag) for w, _ in workers
                         if w._tagged.get(tag) is not None), None)
            if node is not None and breq is not None:
                break
            time.sleep(0.002)
        assert node is not None and breq is not None
        drainer = threading.Thread(
            target=lambda: rq.post(
                f"http://127.0.0.1:{node['port']}/drain",
                json={"timeout": 30}, timeout=60),
            daemon=True, name="test-drain")
        drainer.start()
        st = _wait_req(base, rid)
        assert st["status"] == "completed", st
        assert st["result"] == ref["result"]
        assert st["attempts"] == 0     # a handoff is not a failure
        for h in hogs:                 # nothing lost in the shuffle
            assert _wait_req(base, h)["status"] == "completed"
        mc = m.metrics.snapshot()["counters"]
        assert mc["requests_migrated"] >= 1
        assert mc["rebalancer_migrations"] >= 1
        drainer.join(timeout=60)
        assert not drainer.is_alive()
    finally:
        m.stop()
        for agent, _ in workers:
            stop_worker(agent)


def test_chaos_kill_worker_mid_stream_recovers_via_kv_fetch():
    """FailSafe: kill the decode node mid-request. The failover retry
    re-dispatches with the PERSISTED kv_source hint, so the surviving
    decode node recovers by fetching the prompt's KV from the prefill
    peer — identical output, zero failures, and the recovery shows
    cached/transferred prefill instead of a full re-prefill."""
    m, base, workers = _cluster(
        ["prefill", "decode", "decode"], disagg=True,
        disagg_min_prompt=64, infer_timeout=20)
    (pre, _), (d1, p1), (d2, p2) = workers
    try:
        time.sleep(0.8)
        ref = _wait_req(base, _submit(base))
        assert ref["status"] == "completed", ref

        rid = _submit(base)
        victim = None
        deadline = time.time() + 30
        while time.time() < deadline and victim is None:
            node = m._processing.get(rid)
            if node is not None and node["port"] in (p1, p2):
                victim = node
            time.sleep(0.002)
        assert victim is not None, "request never landed on a decode node"
        killed = d1 if victim["port"] == p1 else d2
        survivor = d2 if killed is d1 else d1
        # hard kill: stop serving AND sever the keep-alive sockets the
        # master would otherwise keep writing into
        killed.service.shutdown()
        st = _wait_req(base, rid, timeout=120)
        assert st["status"] == "completed", st
        assert st["result"] == ref["result"]
        assert st["attempts"] >= 1       # a real failover, not a no-op
        # recovery was a fetch/restore, not a cold re-prefill: the
        # surviving decode node pulled KV or the cost ledger shows
        # cached prefill tokens on the recovered attempt
        sc = survivor.metrics.snapshot()["counters"]
        cost = st.get("cost")
        if isinstance(cost, str):
            cost = json.loads(cost)
        assert (sc.get("kv_transfer_blocks", 0) > 0
                or (cost or {}).get("prefill_cached_tokens", 0) > 0)
    finally:
        m.stop()
        for agent, _ in workers:
            try:
                stop_worker(agent)
            except Exception:
                pass


# ---- rebalancer decision units ------------------------------------------

class _Resp:
    def __init__(self, status_code=200, body=None):
        self.status_code = status_code
        self._body = body or {"status": "success"}
        self.text = json.dumps(self._body)

    def json(self):
        return self._body


def _decision_master(roles, queues, *, sustain=60.0, ratio=3.0):
    """Master with synthetic nodes + seeded TSDB queue-depth series —
    no live workers, no background threads; sweeps run by hand."""
    m = Master(":memory:", dispatcher_threads=0, rebalance=False,
               rebalance_sustain_s=sustain, rebalance_ratio=ratio)
    now = time.time()
    for i, (role, q) in enumerate(zip(roles, queues)):
        nid = m.store.add_node(f"n{i}", "127.0.0.1", 9000 + i,
                               is_active=True)
        m.store.update_node(nid, info={"role": role, "loaded_models": []})
        m._node_runtime[nid] = {"queue": q, "free_blocks": 10,
                                "arena_occ": 0.1, "role": role,
                                "at": now, "models": {}}
        for k in range(4):
            # sustained: 4 points inside the window, spread wider than
            # the TSDB's fine-bucket width so they stay distinct samples
            m.tsdb.record(f"n{i}", "batcher_queue_depth", q,
                          t=now - sustain + 1 + k * (m.tsdb.step_s + 1))
    m._flips = []
    m._worker_post = lambda node, path, body, timeout, stream=False: (
        m._flips.append((node["id"], path, dict(body))) or _Resp())
    m._refresh_node = lambda node: None
    return m


def test_rebalancer_flips_idle_prefill_to_decode():
    """The BENCH_r07 uniform-mix fix: decode pool starving, prefill
    idle -> flip the prefill node into the decode pool (the strict
    prefill pool MAY empty)."""
    m = _decision_master(["prefill", "decode"], [0, 6])
    try:
        m._maybe_flip_roles()
        assert m._flips == [(1, "/role", {"role": "decode"})]
        assert m.metrics.snapshot()["counters"][
            "rebalancer_role_flips"] == 1
    finally:
        m.stop()


def test_rebalancer_flips_spare_decode_to_prefill_never_last():
    # prefill drowning, two decode-capable nodes: flip the idler one
    m = _decision_master(["prefill", "decode", "mixed"], [8, 1, 0])
    try:
        m._maybe_flip_roles()
        assert m._flips == [(3, "/role", {"role": "prefill"})]
    finally:
        m.stop()
    # ...but NEVER the last decode-capable node, however loaded the
    # prefill pool is (every full request needs one)
    m = _decision_master(["prefill", "decode"], [8, 0])
    try:
        m._maybe_flip_roles()
        assert m._flips == []
    finally:
        m.stop()


def test_rebalancer_recreates_prefill_pool_on_disagg_demand():
    """Flip-back path: after the rebalancer emptied the strict prefill
    pool, disagg-eligible demand arriving with nowhere to prefill (the
    scheduler_disagg_no_prefill_pool counter) re-creates the pool from
    a decode-capable spare — emptying the pool must never disable
    disaggregation for the master's lifetime."""
    m = _decision_master(["decode", "decode", "mixed"], [1, 3, 2])
    try:
        m._maybe_flip_roles()
        assert m._flips == []          # no demand signal yet
        m.metrics.inc("scheduler_disagg_no_prefill_pool", 3)
        m._maybe_flip_roles()
        assert m._flips == [(1, "/role", {"role": "prefill"})]
        # the signal was consumed: a quiet next sweep flips nothing
        m._node_runtime[1]["role"] = "decode"   # pretend flip not seen
        m._flips.clear()
        m._maybe_flip_roles()
        assert m._flips == []
    finally:
        m.stop()
    # never down to the last decode-capable node, demand or not
    m = _decision_master(["decode"], [5])
    try:
        m.metrics.inc("scheduler_disagg_no_prefill_pool", 5)
        m._maybe_flip_roles()
        assert m._flips == []
    finally:
        m.stop()


def test_rebalancer_migrate_retries_after_transient_404():
    """A 404 from /migrate_out is transient (the tag registers with
    the batcher only after the submit-time prefetch): the request must
    NOT be poisoned out of future sweeps."""
    m = _decision_master(["mixed", "mixed"], [1, 1])
    try:
        rid = m.store.submit_request("mod", "hello")
        req = m.store.claim_next_pending()
        node = m.store.get_node(1)
        m.store.update_node(1, draining=1)
        m._processing[req["id"]] = node
        answers = [404, 200]
        m._worker_post = lambda *a, **k: (
            m._flips.append(a[1]) or _Resp(answers[len(m._flips) - 1]))
        m._migrate_inflight_off_hot()
        assert m._flips == ["/migrate_out"] and rid not in m._migrated_reqs
        m._migrate_inflight_off_hot()      # retried, 200 settles it
        assert m._flips == ["/migrate_out"] * 2
        assert rid in m._migrated_reqs
        m._migrate_inflight_off_hot()
        assert len(m._flips) == 2          # settled: no third POST
    finally:
        m.stop()


def test_rebalancer_flip_cooldown_and_sustain_requirement():
    m = _decision_master(["prefill", "decode"], [0, 6])
    try:
        m._maybe_flip_roles()
        assert len(m._flips) == 1
        # the flipped node's runtime role changed; make the divergence
        # persist artificially and sweep again: cooldown blocks a
        # re-flip of the same node, and no OTHER candidate exists
        m._node_runtime[1]["role"] = "prefill"   # pretend still split
        m._maybe_flip_roles()
        assert len(m._flips) == 1
    finally:
        m.stop()
    # no sustained data (a single TSDB point) -> no decision
    m = _decision_master(["prefill", "decode"], [0, 6])
    try:
        m.tsdb = type(m.tsdb)(window_s=60, step_s=1)   # wipe history
        m._maybe_flip_roles()
        assert m._flips == []
    finally:
        m.stop()


def test_rebalancer_migrates_off_draining_node():
    m = _decision_master(["mixed", "mixed"], [1, 1])
    try:
        rid = m.store.submit_request("mod", "hello")
        req = m.store.claim_next_pending()
        node = m.store.get_node(1)
        m.store.update_node(1, draining=1)
        m._processing[req["id"]] = node
        m._migrate_inflight_off_hot()
        assert (1, "/migrate_out",
                {"request_tag": m._tag(rid), "model_name": "mod"}) \
            in m._flips
        assert m.metrics.snapshot()["counters"][
            "rebalancer_migrations"] == 1
        # once per request: a second sweep does not re-POST
        m._flips.clear()
        m._migrate_inflight_off_hot()
        assert m._flips == []
    finally:
        m.stop()


def test_requeue_migrated_persists_resume_and_guards_terminal():
    from distributed_llm_inferencing_tpu.runtime.state import Store
    s = Store(":memory:")
    rid = s.submit_request("m", "p")
    s.claim_next_pending()
    s.requeue_migrated(rid, resume={"tokens": [1, 2, 3], "seed": 9},
                       kv_source={"url": "http://w0", "model": "m"},
                       excluded_node_id=4)
    r = s.get_request(rid)
    assert r["status"] == "pending" and r["attempts"] == 0
    assert r["resume"] == {"tokens": [1, 2, 3], "seed": 9}
    assert r["kv_source"] == {"url": "http://w0", "model": "m"}
    assert r["excluded_nodes"] == [4] and r["node_id"] is None
    # the re-claim carries the parsed resume/kv_source along
    row = s.claim_next_pending()
    assert row["resume"]["seed"] == 9 and row["kv_source"]["model"] == "m"
    # a terminal row never resurrects
    s.mark_completed(rid, "out", 1, 0.1, 1.0)
    s.requeue_migrated(rid, resume={"tokens": [9]})
    assert s.get_request(rid)["status"] == "completed"


def test_infer_body_carries_resume_and_persisted_kv_source():
    m = Master(":memory:", dispatcher_threads=0, rebalance=False)
    try:
        req = {"id": 1, "model_name": "m", "prompt": "p", "sampling": {},
               "max_new_tokens": 8, "max_length": None,
               "resume": {"tokens": [1], "seed": 2},
               "kv_source": {"url": "http://w0", "model": "m"}}
        body = m._infer_body(req)
        assert body["resume"] == {"tokens": [1], "seed": 2}
        assert body["kv_source"] == {"url": "http://w0", "model": "m"}
        # in-memory hint (same-dispatch disagg) still wins over the row
        req["_kv_source"] = {"url": "http://w1", "model": "m"}
        assert m._infer_body(req)["kv_source"]["url"] == "http://w1"
    finally:
        m.stop()
