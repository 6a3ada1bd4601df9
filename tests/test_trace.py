"""Tracer unit tests (span nesting, ring-buffer eviction, Chrome export)
plus cross-process propagation: one trace id must link master submit →
worker infer spans over real localhost HTTP.
"""

import json
import time

import pytest
import requests

from distributed_llm_inferencing_tpu.utils import trace
from distributed_llm_inferencing_tpu.utils.trace import SpanCtx, Tracer
from conftest import stop_worker


# ---- span model -------------------------------------------------------

def test_span_nesting_and_ids():
    tr = Tracer(service="t")
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            with tr.span("leaf"):
                pass
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"outer", "inner", "leaf"}
    assert spans["outer"].parent_id is None
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["leaf"].parent_id == spans["inner"].span_id
    # one trace id across the whole tree; unique span ids
    assert len({s.trace_id for s in spans.values()}) == 1
    assert len({s.span_id for s in spans.values()}) == 3
    # children finish before parents, every span has a real duration
    assert spans["leaf"].end <= spans["inner"].end <= spans["outer"].end
    assert all(s.end >= s.start for s in spans.values())
    assert outer.ctx().trace_id == inner.ctx().trace_id


def test_span_contextvar_restored_and_error_attr():
    tr = Tracer()
    assert trace.current() is None
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            assert trace.current() is not None
            raise RuntimeError("x")
    assert trace.current() is None
    (sp,) = tr.spans()
    assert "RuntimeError" in sp.attrs["error"]
    assert sp.end >= sp.start   # recorded despite the exception


def test_explicit_parent_crosses_threads():
    """parent= adopts a remote/cross-thread ctx; parent=None roots fresh."""
    tr = Tracer()
    ctx = SpanCtx(trace_id="feedbeef00000000", span_id="ab" * 8)
    with tr.span("child", parent=ctx):
        pass
    with tr.span("fresh", parent=None):
        pass
    child, fresh = tr.spans()
    assert child.trace_id == "feedbeef00000000"
    assert child.parent_id == "ab" * 8
    assert fresh.trace_id != "feedbeef00000000" and fresh.parent_id is None


def test_record_retroactive():
    tr = Tracer()
    t0 = time.time() - 1.0
    g = tr.record("root", t0, t0 + 0.5, attrs={"k": 1})
    tr.record("sub", t0, t0 + 0.2, parent=g)
    root, sub = tr.spans()
    assert sub.trace_id == root.trace_id == g.trace_id
    assert sub.parent_id == root.span_id
    assert abs(root.duration_ms - 500) < 1


def test_ring_buffer_eviction():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.record(f"s{i}", 0.0, 1.0)
    names = [s.name for s in tr.spans()]
    assert len(names) == 8
    assert names == [f"s{i}" for i in range(12, 20)]  # oldest evicted


# ---- header propagation ----------------------------------------------

def test_inject_extract_roundtrip():
    ctx = SpanCtx(trace_id="11" * 8, span_id="22" * 8)
    h = trace.inject({}, ctx)
    assert h[trace.TRACE_HEADER] == "11" * 8
    assert h[trace.PARENT_HEADER] == "22" * 8
    back = trace.extract(h)
    assert back == ctx
    assert trace.extract({}) is None
    assert trace.inject({}) == {}   # nothing current -> no-op


# ---- Chrome trace-event export ---------------------------------------

def test_chrome_export_schema():
    tr = Tracer(service="unit")
    with tr.span("a", attrs={"n": 3}):
        with tr.span("b"):
            pass
    doc = tr.chrome_trace()
    # valid JSON end to end (what /api/trace serves)
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    # process_name carries host:pid (export pid is synthetic — real pids
    # collide across containers that all run as PID 1)
    assert meta and meta[0]["args"]["name"].startswith("unit")
    assert len(spans) == 2
    for e in spans:
        for key in ("name", "cat", "ph", "ts", "dur", "pid", "tid", "args"):
            assert key in e, f"missing {key}"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0
        assert e["args"]["trace_id"] and e["args"]["span_id"]
    b = next(e for e in spans if e["name"] == "b")
    a = next(e for e in spans if e["name"] == "a")
    assert b["args"]["parent_id"] == a["args"]["span_id"]
    assert a["args"]["n"] == 3


def test_chrome_export_merge_dedupes():
    tr = Tracer()
    with tr.span("x"):
        pass
    evs = tr.chrome_events()
    doc = tr.chrome_trace(extra_events=evs)   # merge our own export back
    span_evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(span_evs) == 1


# ---- cross-process propagation over HTTP -----------------------------

@pytest.fixture()
def cluster():
    from distributed_llm_inferencing_tpu.runtime.master import Master
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
    agent = WorkerAgent()
    wsrv = agent.serve(host="127.0.0.1", port=0, background=True)
    m = Master(":memory:", dispatcher_threads=2, health_interval=0.5)
    m.start_background()
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    yield (agent, wsrv.server_address[1], m, msrv.server_address[1])
    m.stop()
    stop_worker(agent)


def _url(port, path):
    return f"http://127.0.0.1:{port}{path}"


def test_one_trace_links_master_submit_to_worker_infer(cluster):
    """Acceptance: a single end-to-end request yields one connected trace
    — shared trace id, >= 6 spans spanning both the master's and the
    worker's process roles — exportable as Chrome trace JSON."""
    agent, wport, m, mport = cluster
    r = requests.post(_url(mport, "/api/nodes/add"), json={
        "name": "tw", "host": "127.0.0.1", "port": wport})
    assert r.status_code == 200, r.text

    tid = "a1b2c3d4e5f60718"
    sub = requests.post(
        _url(mport, "/api/inference/submit"),
        headers={trace.TRACE_HEADER: tid, trace.PARENT_HEADER: "00" * 8},
        json={"model_name": "tiny-gpt2", "prompt": "hi",
              "max_new_tokens": 4,
              "sampling": {"do_sample": False, "allow_random_init": True}})
    assert sub.status_code == 200, sub.text
    # the response names the trace it belongs to
    assert sub.headers.get(trace.TRACE_HEADER) == tid
    req_id = sub.json()["request_id"]

    deadline = time.time() + 60
    while time.time() < deadline:
        st = requests.get(
            _url(mport, f"/api/inference/status/{req_id}")).json()
        if st["request"]["status"] in ("completed", "failed"):
            break
        time.sleep(0.2)
    assert st["request"]["status"] == "completed", st

    doc = requests.get(_url(mport, "/api/trace")).json()
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"
           and e.get("args", {}).get("trace_id") == tid]
    names = [e["name"] for e in evs]
    assert len(evs) >= 6, names
    # master-side stages
    assert "http POST /api/inference/submit" in names
    assert "master.execute" in names and "master.queued" in names
    assert "master.dispatch" in names
    # worker-side stages, linked by the SAME trace id via the headers the
    # master injected on its /inference call
    assert "http POST /inference" in names
    assert "worker.inference" in names
    assert "engine.generate" in names
    assert "engine.prefill" in names and "engine.decode" in names
    # parent links form one connected tree (every non-root parent exists)
    ids = {e["args"]["span_id"] for e in evs}
    roots = [e for e in evs if "parent_id" not in e["args"]
             or e["args"]["parent_id"] not in ids]
    # the submit span's parent is the client's fake span id -> one root
    assert len(roots) <= 2, [(e["name"], e["args"].get("parent_id"))
                             for e in roots]

    # the worker's own /api/trace also serves valid Chrome JSON with the
    # linked spans
    wdoc = requests.get(_url(wport, "/api/trace")).json()
    wnames = [e["name"] for e in wdoc["traceEvents"]
              if e.get("ph") == "X"
              and e.get("args", {}).get("trace_id") == tid]
    assert "worker.inference" in wnames


def test_error_response_carries_trace_headers(cluster):
    _, wport, _, mport = cluster
    tid = "0102030405060708"
    r = requests.post(_url(wport, "/inference"),
                      headers={trace.TRACE_HEADER: tid},
                      json={"model_name": "not-loaded", "prompt": "x"})
    assert r.status_code == 400
    assert r.headers.get(trace.TRACE_HEADER) == tid
    assert r.headers.get(trace.SPAN_HEADER)
    # 404s too (deliberate unknown path)
    # dlilint: disable=rpc-unknown-path
    r = requests.get(_url(mport, "/no/such/path"),
                     headers={trace.TRACE_HEADER: tid})
    assert r.status_code == 404
    assert r.headers.get(trace.TRACE_HEADER) == tid


def test_405_wrong_method_gets_allow_header(cluster):
    _, wport, _, mport = cluster
    # /health is GET-only on the worker (deliberate wrong method)
    # dlilint: disable=rpc-method-mismatch
    r = requests.post(_url(wport, "/health"), json={})
    assert r.status_code == 405
    assert "GET" in r.headers.get("Allow", "")
    assert r.json()["status"] == "error"
    # /api/inference/submit is POST-only on the master
    # dlilint: disable=rpc-method-mismatch
    r = requests.get(_url(mport, "/api/inference/submit"))
    assert r.status_code == 405
    assert "POST" in r.headers.get("Allow", "")
    # unregistered path still 404s
    # dlilint: disable=rpc-unknown-path
    assert requests.get(_url(wport, "/nope")).status_code == 404
