"""The program account (utils/profiler.py): what each program's first
use cost, by JAX's own events, the batcher's build beside it, and what a
first use leaves behind: a span, counters, and while serving an event
(docs/observability.md, "The program account")."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime import events
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher
from distributed_llm_inferencing_tpu.utils import profiler as profiler_mod
from distributed_llm_inferencing_tpu.utils import trace
from distributed_llm_inferencing_tpu.utils.profiler import PhaseProfiler

GREEDY = SamplingParams.greedy()
ROOT = Path(__file__).resolve().parents[1]
MS = ("trace_ms", "lower_ms", "load_ms")


def batcher(**kw):
    """A batcher of its own programs (not conftest.shared_batcher: these
    cases read whether a call compiled)."""
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    kw = {"num_blocks": 64, "block_size": 8, "slots": 4, "max_seq": 128,
          **kw}
    return ContinuousBatcher(cfg, None, seed=0, **kw)


def serve(b, lengths, new_tokens=9, chunk_cap=4, seed=7):
    rng = np.random.default_rng(seed)
    reqs = [b.submit(rng.integers(3, b.cfg.vocab_size, n).tolist(),
                     max_new_tokens=new_tokens, sampling=GREEDY,
                     eos_token_id=None) for n in lengths]
    for r in reqs:
        r.chunk_cap = chunk_cap
    for _ in range(400):
        b.step()
        if all(r.done.is_set() for r in reqs):
            return reqs
    raise AssertionError("requests did not finish")


def spans_since(t0, name="batcher.program_first_use"):
    return [s for s in trace.get_tracer().spans()
            if s.name == name and s.start >= t0]


def counters(b):
    return {k: v for k, v in b.metrics.snapshot()["counters"].items()
            if k in b.PROGRAM_COUNTERS}


@pytest.fixture
def journal():
    j = events.EventJournal()
    events.set_journal(j)
    yield j
    events.clear_journal(j)


# ---- a first use, and a second ----------------------------------------

def test_first_wave_and_first_chunk_each_leave_one_first_use():
    t0 = time.time()
    b = batcher()
    assert counters(b) == dict.fromkeys(b.PROGRAM_COUNTERS, 0)
    serve(b, [9])              # one wave of one row, chunks of 4 passes
    first = spans_since(t0)
    by_kind = {s.attrs["kind"]: s for s in first}
    assert len(first) == 2 and set(by_kind) == {"admit", "chunk"}
    assert by_kind["admit"].attrs["key"] == [16, 1, 1]
    assert by_kind["chunk"].attrs["key"] == 4
    parents = {s.span_id: s.name for s in trace.get_tracer().spans()}
    assert parents[by_kind["admit"].parent_id] == "batcher.admit_wave"
    assert parents[by_kind["chunk"].parent_id] == "batcher.decode_chunk"
    for s in first:
        a = s.attrs
        assert set(a) == set(events.PROGRAM_FIRST_USE_FIELDS)
        assert all(a[f] > 0 for f in MS), a
        assert a["run_ms"] >= 0 and a["serving"] is False
        assert a["fun_name"] and a["pallas_call_sites"] == 0
        # the four parts are the call's wall
        assert sum(a[f] for f in MS) + a["run_ms"] == pytest.approx(
            s.duration_ms, abs=0.01)
    c = counters(b)
    assert c["batcher_programs_first_use"] == 2
    for f in MS:
        assert c[f"batcher_program_{f}"] == pytest.approx(
            sum(s.attrs[f] for s in first))
    assert c["batcher_program_first_run_ms"] == pytest.approx(
        sum(s.attrs["run_ms"] for s in first))
    # the same shapes again: no program is used for the first time
    t1 = time.time()
    serve(b, [9], seed=8)
    assert spans_since(t1) == [] and counters(b) == c
    rows = b.profiler.programs()["rows"]
    assert [(r["kind"], r["key"]) for r in rows] == [
        ("admit", [16, 1, 1]), ("chunk", 4)]
    # ... and the chunk that compiled is no sample of a pass's mean: two
    # requests of two chunks each, the first of the four left out
    assert b._pass_mean[("decode", 4)][1] == 3


def test_the_build_is_accounted_by_part():
    t0 = time.time()
    b = batcher()
    acct = b.profiler.programs()
    build = acct["build"]
    weights, pool = build["weights"], build["pool"]
    assert weights["bytes"] == sum(
        a.nbytes for a in jax.tree.leaves(b.params))
    assert pool["bytes"] == sum(a.nbytes for a in jax.tree.leaves(b.paged))
    assert weights["wall_ms"] + pool["wall_ms"] <= build["wall_ms"]
    for part in (weights, pool):
        assert part["programs"] >= 0 and all(part[f] >= 0 for f in MS)
        assert sum(part[f] for f in MS) <= part["wall_ms"] + 0.01
    assert acct["process"]["built_s"] >= acct["process"]["imported_s"] > 0
    [whole] = spans_since(t0, "batcher.build")
    parts = {s.name: s for s in trace.get_tracer().spans()
             if s.parent_id == whole.span_id}
    assert set(parts) == {"batcher.build.weights", "batcher.build.pool"}
    assert parts["batcher.build.weights"].attrs["bytes"] == weights["bytes"]
    assert whole.attrs["wall_ms"] == build["wall_ms"]


def test_programs_compiled_ahead_count_their_first_run():
    t0 = time.time()
    b = batcher()
    n = b.warm_decode_programs()
    ahead = spans_since(t0)
    assert len(ahead) == n == len(b.decode_chunks)
    assert all(s.parent_id is None for s in ahead)
    rows = {r["key"]: r for r in b.profiler.programs()["rows"]}
    assert all(r["aot"] and r["kind"] == "chunk" for r in rows.values())
    was = rows[4]["run_ms"]
    c0 = counters(b)
    t1 = time.time()
    serve(b, [9])
    # the admit program compiled; the chunk of 4 only ran, for the first
    # time: no span of its own, its wall on its row and on the counter
    assert [s.attrs["kind"] for s in spans_since(t1)] == ["admit"]
    now = {r["key"]: r for r in b.profiler.programs()["rows"]
           if r["kind"] == "chunk"}
    assert now[4]["run_ms"] > was and now[8]["run_ms"] == rows[8]["run_ms"]
    c1 = counters(b)
    assert c1["batcher_programs_first_use"] == \
        c0["batcher_programs_first_use"] + 1
    [admit] = spans_since(t1)
    assert c1["batcher_program_first_run_ms"] == pytest.approx(
        c0["batcher_program_first_run_ms"] + now[4]["run_ms"] - was
        + admit.attrs["run_ms"], abs=0.01)
    assert b._pass_mean[("decode", 4)][1] == 1    # of its two chunks


# ---- the listener's bookkeeping ---------------------------------------

def test_a_nested_jit_is_counted_once():
    prof = PhaseProfiler()

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 3

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 2)

    with prof.program("chunk", 1) as call:
        outer(jnp.ones((5,))).block_until_ready()
    traces = call.spans[0]
    assert len(traces) >= 2         # the inner trace was heard as well
    heard = sum(end - start for start, end in traces) * 1e3
    [row] = prof.programs()["rows"]
    assert 0 < row["trace_ms"] < heard
    assert row["trace_ms"] == pytest.approx(
        profiler_mod._union_s(traces) * 1e3, abs=0.01)
    assert sum(row[f] for f in MS) + row["run_ms"] == pytest.approx(
        row["wall_ms"], abs=0.01)
    # one kind nested in another counts for the outer one alone
    ms = profiler_mod._compiled_ms(
        ([(0.0, 1.0), (2.0, 2.5)], [(2.0, 4.0)], [(5.0, 7.0)]))
    assert ms == {"trace_ms": 1000.0, "lower_ms": 2000.0, "load_ms": 2000.0}


def test_an_event_on_an_unlabelled_thread_lands_in_eager():
    prof = PhaseProfiler()

    def eager_only_here(x):
        return jnp.cos(x) - 7

    def heard(acct):
        # (a process that has heard MAX_EAGER_NAMES names already keeps
        # the new one under "(others)": a whole run of the suite)
        return sum(ms for when in ("setup", "serving")
                   for name, ms in acct[when]["by_name"].items()
                   if "eager_only_here" in name or name == "(others)")

    def compiled(acct):
        return sum(acct[when]["programs"] for when in ("setup", "serving"))
    before = prof.programs()
    t = threading.Thread(target=lambda: jax.jit(eager_only_here)(
        jnp.ones((3,))).block_until_ready())
    t.start()
    t.join()
    after = prof.programs()
    assert heard(after["eager"]) > heard(before["eager"])
    assert compiled(after["eager"]) > compiled(before["eager"])
    assert after["rows"] == []
    # a labelled call of a program already compiled records nothing
    with prof.program("admit", (8, 0, 1)):
        jnp.ones((3,)).block_until_ready()
    assert prof.programs()["rows"] == [] and not prof.first_use


def test_the_account_is_json_safe_and_bounded(monkeypatch):
    monkeypatch.setattr(profiler_mod, "MAX_PROGRAM_ROWS", 2)
    prof = PhaseProfiler()
    for i in range(4):
        with prof.program("admit", (8 * (i + 1), 0, 1)):
            jax.jit(lambda x, i=i: x * i + i)(
                jnp.ones((2,))).block_until_ready()
    summary = json.loads(json.dumps(prof.summary()))
    acct = summary["programs"]
    assert len(acct["rows"]) == 2 and acct["rows_dropped"] == 2
    assert acct["rows"][0]["key"] == [8, 0, 1]
    assert acct["totals"]["setup"]["programs"] == 2
    assert set(acct) == {"process", "build", "eager", "rows",
                         "rows_dropped", "totals"}
    # a reset of the sampling ring leaves the account
    prof.configure(enabled=True, reset=True)
    assert len(prof.programs()["rows"]) == 2
    # the eager names are bounded too
    acct = profiler_mod.programs()
    monkeypatch.setattr(profiler_mod, "MAX_EAGER_NAMES", 0)
    key = "/jax/core/compile/backend_compile_duration"
    acct._on_span(key, 1.0, 1.5, fun_name="one_more_name")
    names = acct.eager()["serving" if acct.serving else "setup"]["by_name"]
    assert "one_more_name" not in names and names["(others)"] >= 500.0


# ---- while serving -----------------------------------------------------

def test_a_first_use_while_serving_is_journaled(journal, caplog):
    b = batcher()
    serve(b, [9])                       # by hand: set-up
    assert [r["serving"] for r in b.profiler.programs()["rows"]] == \
        [False, False]
    assert [e for e in journal.tail()
            if e["type"] == "program-first-use"] == []
    c0 = counters(b)
    t0 = time.time()
    b.start()
    try:
        rng = np.random.default_rng(3)
        with caplog.at_level("WARNING", logger="dli.batcher"):
            # 40 tokens: the tail bucket of 64, which no warm-up reached
            req = b.submit(rng.integers(3, b.cfg.vocab_size, 40).tolist(),
                           max_new_tokens=5, sampling=GREEDY,
                           eos_token_id=None)
            req.chunk_cap = 4
            assert req.done.wait(100) and not req.error
    finally:
        b.stop()
    [span] = spans_since(t0)
    assert span.attrs["kind"] == "admit" and span.attrs["serving"] is True
    assert span.attrs["key"] == [64, 1, 1]
    [ev] = [e for e in journal.tail() if e["type"] == "program-first-use"]
    declared = events._BY_NAME["program-first-use"]
    assert declared.fields == events.PROGRAM_FIRST_USE_FIELDS
    assert declared.severity == ev["severity"] == "warning"
    assert set(ev["data"]) == set(declared.fields)
    assert ev["data"] == json.loads(json.dumps(span.attrs))
    assert "program first used while serving" in caplog.text
    c1 = counters(b)
    assert c1["batcher_programs_first_use"] == \
        c0["batcher_programs_first_use"] + 1
    assert c1["batcher_program_load_ms"] > c0["batcher_program_load_ms"]
    totals = b.profiler.programs()["totals"]
    assert totals["serving"]["programs"] == 1
    assert totals["setup"]["programs"] == 2
    assert not b.profiler.serving       # stopped: set-up's again


class _Ann:
    seen = []

    def __init__(self, name, **stats):
        _Ann.seen.append((name, stats))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_the_annotation_of_a_run_that_compiles_says_so(monkeypatch):
    monkeypatch.setattr(profiler_mod, "_ANNOTATIONS", (_Ann, _Ann))
    _Ann.seen = []
    b = batcher()
    b.profiler.configure(enabled=True)
    serve(b, [9])
    runs = [(name, stats) for name, stats in _Ann.seen
            if name in ("dli.admit_run", "dli.dispatch")]
    assert [(n, s.get("first_use")) for n, s in runs] == [
        ("dli.admit_run", 1), ("dli.dispatch", 1), ("dli.dispatch", None)]
    assert runs[0][1]["wave"] == 1 and runs[1][1]["chunk"] == 1


# ---- the compile cache's answer -----------------------------------------

CACHED = """
import json, sys
sys.path.insert(0, {tests!r})
import conftest                     # the suite's XLA flags
import jax
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import test_program_account as t
out = []
for _ in range(2):
    b = t.batcher()
    t.serve(b, [9])
    out.append(b.profiler.programs())
print(json.dumps(out))
"""


def test_a_second_batcher_reads_its_programs_from_the_cache(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CACHED).format(
            tests=str(ROOT / "tests"), cache=str(tmp_path / "cache"))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=110)
    assert p.returncode == 0, p.stderr[-2000:]
    cold, warm = json.loads(p.stdout.strip().splitlines()[-1])
    if not sum(r["cache_misses"] for r in cold["rows"]):
        pytest.skip("XLA:CPU wrote no entry to the compile cache here")
    assert [r["cache"] for r in cold["rows"]] == ["miss", "miss"]
    assert cold["totals"]["setup"]["cache_hits"] == 0
    assert [(r["kind"], r["cache"]) for r in warm["rows"]] == [
        ("admit", "hit"), ("chunk", "hit")]
    assert warm["totals"]["setup"]["cache_misses"] == 0
    assert all(r["cache_read_ms"] > 0 for r in warm["rows"])
    # traced and lowered anew all the same: what every start pays
    assert all(r["trace_ms"] > 0 and r["lower_ms"] > 0
               for r in warm["rows"])


# ---- the operator's views ------------------------------------------------

def test_load_model_answers_with_the_loads_own_account():
    import requests as rq
    from conftest import stop_worker
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
    agent = WorkerAgent()
    srv = agent.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        r = rq.post(f"{base}/load_model", timeout=100, json={
            "model_name": "tiny-llama", "allow_random_init": True,
            "dtype": "float32", "serving": "batched", "slots": 4,
            "kv_blocks": 64, "kv_block_size": 8, "max_seq": 128})
        assert r.status_code == 200, r.text
        body = r.json()
        acct = body["programs"]
        assert acct["rows"] == []           # no program used yet
        build = acct["build"]
        assert build["weights"]["bytes"] > 0 and build["pool"]["bytes"] > 0
        assert build["wall_ms"] / 1e3 <= body["load_time_s"]
        # the same account, later, under GET /api/profile
        prof = rq.get(f"{base}/api/profile", timeout=30).json()
        [summary] = [p["summary"] for p in prof["profilers"].values()]
        assert summary["programs"]["build"] == build
    finally:
        stop_worker(agent)



def test_profile_summary_prints_the_program_account(tmp_path, capsys):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "profile_summary", ROOT / "scripts" / "profile_summary.py")
    ps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ps)
    b = batcher()
    serve(b, [9])
    api = tmp_path / "profile.json"
    api.write_text(json.dumps({"status": "success", "profilers": {
        "tiny-llama": {"summary": b.profiler.summary()}}}))
    acct = ps.read_programs(str(api))
    assert acct == json.loads(json.dumps(b.profiler.programs()))
    text = ps.render_programs(acct)
    rows = {ln.split()[0] + " " + ln.split()[1]: ln
            for ln in text.splitlines() if ln.startswith("    ")}
    assert "admit 16x1x1" in rows and "chunk 4" in rows
    assert "weights " + f"{acct['build']['weights']['wall_ms']:.1f}" in rows
    assert f"{acct['rows'][0]['load_ms']:9.1f}" in rows["admit 16x1x1"]
    assert ps.main(["--account", str(api)]) == 0
    assert "program account:" in capsys.readouterr().out
    # a file without one (a benchmark's result line) prints the clocks
    line = tmp_path / "line.json"
    line.write_text(json.dumps({"counters": {"batcher_clock_emit_ms": 2}}))
    assert ps.read_programs(str(line)) is None
    assert ps.main(["--account", str(line)]) == 0
    assert "program account:" not in capsys.readouterr().out
