"""One scheduler timeline: timestamped step phases, their mirror as
profiler annotations, the programs' named scopes, the admit wave's span
attributes and the stall counters (docs/observability.md, "Decode
profiler")."""

import gc
import glob
import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime import events
from distributed_llm_inferencing_tpu.runtime.batcher import ContinuousBatcher
from distributed_llm_inferencing_tpu.utils import clock, trace
from distributed_llm_inferencing_tpu.utils.profiler import (
    PhaseProfiler, step_phases)

GREEDY = SamplingParams.greedy()
SCOPES = ("kv_gather", "attention", "kv_write", "mlp", "moe_route",
          "moe_experts", "lm_head", "sample")


def batcher(model="tiny-llama", **kw):
    cfg = get_config(model).replace(dtype="float32", attn_backend="xla")
    kw = {"num_blocks": 64, "block_size": 8, "slots": 4, "max_seq": 128,
          **kw}
    return ContinuousBatcher(cfg, None, seed=0, **kw)


def serve(b, rng, lengths, new_tokens=6, max_steps=400, chunk_cap=0):
    reqs = [b.submit(rng.integers(3, b.cfg.vocab_size, n).tolist(),
                     max_new_tokens=new_tokens, sampling=GREEDY)
            for n in lengths]
    for r in reqs:
        r.chunk_cap = chunk_cap         # 0: as large as the budget allows
    for _ in range(max_steps):
        b.step()
        if all(r.done.is_set() for r in reqs):
            return reqs
    raise AssertionError("requests did not finish")


# ---- the step's timeline ---------------------------------------------

@pytest.mark.parametrize("sample_every", [1, 3])
def test_sampled_step_is_an_ordered_timeline(sample_every):
    rng = np.random.default_rng(7)
    b = batcher()
    b.profiler = PhaseProfiler(enabled=True, sample_every=sample_every)
    serve(b, rng, [9, 20, 5])
    samples = b.profiler.samples()
    assert samples and any(
        name == "admit_run" for s in samples for name, *_ in s["spans"])
    old_sums = {}
    for s in samples:
        spans = s["spans"]
        starts = [start for _, start, _, _ in spans]
        assert starts == sorted(starts)
        assert all(end >= start for _, start, end, _ in spans)
        assert s["t"] <= spans[0][1] and \
            spans[-1][2] <= s["t"] + s["total"] + 1e-4
        open_ = []          # the brackets still open, outermost first
        for name, start, end, depth in spans:
            del open_[depth:]
            assert len(open_) == depth
            if depth:       # a nested bracket lies inside its parent
                _, p_start, p_end = open_[-1]
                assert p_start <= start and end <= p_end, (name, open_)
                assert name.startswith("admit_") and open_[0][0] == "admit"
            open_.append((name, start, end))
        # what the dict of sums held for this step: every top-level
        # bracket's wall, and the rest under "other"
        top = {}
        for name, start, end, depth in spans:
            if depth == 0:
                top[name] = top.get(name, 0.0) + end - start
        top["other"] = s["total"] - sum(top.values())
        assert step_phases(s) == pytest.approx(top)
        for k, v in top.items():
            old_sums[k] = old_sums.get(k, 0.0) + v
    summ = b.profiler.summary()
    assert set(summ["phases"]) == set(old_sums)
    assert set(summ["phases"]) >= {"admit", "host_prep", "dispatch",
                                   "device_wait", "emit", "bookkeeping"}
    for k, v in old_sums.items():
        assert summ["phases"][k]["s"] == pytest.approx(v, abs=2e-6)
    assert set(summ["nested"]) == {"admit_prep", "admit_run", "admit_post"}
    assert not set(summ["nested"]) & set(summ["phases"])
    assert sum(v["s"] for v in summ["nested"].values()) <= \
        summ["phases"]["admit"]["s"] + 1e-5
    ev = b.profiler.chrome_events(pid=1)
    assert len(ev) == sum(len(s["spans"]) for s in samples)
    assert [e["ts"] for e in ev[:len(samples[0]["spans"])]] == \
        [start * 1e6 for _, start, _, _ in samples[0]["spans"]]


# ---- the clocks that are always on ------------------------------------

@pytest.mark.parametrize("enabled", [False, True])
def test_clocks_run_whether_or_not_the_profiler_does(enabled):
    rng = np.random.default_rng(7)
    b = batcher()
    b.profiler = PhaseProfiler(enabled=enabled)
    assert b.profiler.clocks() == {"steps": 0, "wall_s": 0.0,
                                   "between_s": 0.0, "phases": {},
                                   "nested": {}}
    serve(b, rng, [9, 20, 5], chunk_cap=2)
    b.step()                            # an idle poll adds nothing
    c = b.profiler.clocks()
    assert c["steps"] >= 2 and c["wall_s"] > 0
    assert set(c["phases"]) >= {"admit", "host_prep", "dispatch",
                                "device_wait", "emit", "bookkeeping",
                                "other"}
    assert set(c["nested"]) == {"admit_prep", "admit_run", "admit_post"}
    # the top-level clocks, `other` among them, are the busy steps' wall
    assert sum(c["phases"].values()) == pytest.approx(c["wall_s"], abs=1e-4)
    assert sum(c["nested"].values()) <= c["phases"]["admit"] + 1e-5
    # from a step that left slots running to the next: no step's wall
    assert 0 < c["between_s"] < c["wall_s"]
    flat = {**c["phases"], **c["nested"], "between": c["between_s"]}
    assert b.profiler.read() == pytest.approx(flat, abs=1e-6)
    # ... and the result line's: the same seconds as counters
    counters = b.metrics.snapshot()["counters"]
    for name, sec in flat.items():
        assert counters[f"batcher_clock_{name}_ms"] == \
            pytest.approx(sec * 1e3, abs=1e-2)
    summ = b.profiler.summary()
    assert summ["clocks"] == c
    if not enabled:                     # no sample, no annotation
        assert summ["steps_sampled"] == 0 and summ["phases"] == {}
        return
    # every step was sampled: the ring's sums are the clocks
    assert summ["steps_sampled"] == c["steps"]
    assert summ["wall_s"] == pytest.approx(c["wall_s"], abs=1e-5)
    for kind in ("phases", "nested"):
        assert set(summ[kind]) == set(c[kind])
        for name, row in summ[kind].items():
            assert row["s"] == pytest.approx(c[kind][name], abs=1e-4)


PARTS = ("decode_chunk_ms", "decode_admit_run_ms", "decode_admit_host_ms",
         "decode_emit_ms", "decode_host_ms")


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-mixtral"])
@pytest.mark.parametrize("neighbour", [False, True])
def test_a_request_accounts_for_its_decode_time(model, neighbour):
    rng = np.random.default_rng(7)
    tr = trace.get_tracer()
    b = batcher(model)
    serve(b, rng, [9])     # compile outside the account
    t_mark = time.time()
    first = b.submit(rng.integers(3, b.cfg.vocab_size, 9).tolist(),
                     max_new_tokens=30, sampling=GREEDY, eos_token_id=None)
    first.chunk_cap = 4
    b.step()
    assert len(first.tokens) >= 1 and not first.done.is_set()
    if neighbour:                       # admitted while `first` decodes
        serve(b, rng, [12], new_tokens=2)
    while not first.done.is_set():
        b.step()
    cost = first.cost
    assert first.error is None and cost["decode_tokens"] == 30
    assert all(cost[p] >= 0 for p in PARTS)
    # the five parts are the decode phase, up to the microseconds
    # between a timestamp and the clocks' reading beside it
    assert sum(cost[p] for p in PARTS) == \
        pytest.approx(cost["decode_ms"], abs=1.0)
    assert cost["decode_chunk_ms"] > 0 and cost["decode_emit_ms"] > 0
    assert cost["decode_stall_ms"] == 0
    if neighbour:
        assert cost["decode_admit_run_ms"] > 0
        assert cost["decode_admit_host_ms"] > 0
    else:                               # nobody else was admitted
        assert cost["decode_admit_run_ms"] == 0
        assert cost["decode_admit_host_ms"] < 1.0   # empty waves' polls
    span = [s for s in tr.spans() if s.name == "batcher.decode"
            and s.start >= t_mark and s.attrs["tokens"] == 30][-1]
    assert {k: span.attrs[k] for k in PARTS + ("decode_stall_ms",)} == \
        {k: cost[k] for k in PARTS + ("decode_stall_ms",)}
    assert span.attrs["decode_ms"] == cost["decode_ms"]


# ---- the same phases in a profiler trace ------------------------------

def host_events(trace_dir):
    path = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return out


@pytest.mark.parametrize("enabled", [True, False])
def test_profiler_trace_holds_the_host_phases(tmp_path, enabled):
    rng = np.random.default_rng(7)
    b = batcher()
    b.profiler = PhaseProfiler(enabled=enabled)
    serve(b, rng, [9, 12])     # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve(b, rng, [9, 12])
    finally:
        jax.profiler.stop_trace()
    evs = host_events(tmp_path)
    dli = [e for e in evs if e[0].startswith("dli.")]
    if not enabled:
        assert dli == []
        return
    names = {e[0] for e in dli}
    assert names >= {"dli.step", "dli.admit", "dli.admit_prep",
                     "dli.admit_run", "dli.admit_post", "dli.host_prep",
                     "dli.dispatch", "dli.device_wait", "dli.emit",
                     "dli.bookkeeping"}
    steps = [e for e in dli if e[0] == "dli.step"]
    assert all("step_num" in e[3] for e in steps)
    run = next(e for e in dli if e[0] == "dli.admit_run")
    assert run[3]["rows"] == 2 and run[3]["tail_bucket"] == 16
    assert run[3]["tokens"] == 9 + 12 and "prefix_bucket" in run[3]
    dispatch = sorted((e for e in dli if e[0] == "dli.dispatch"),
                      key=lambda e: e[1])
    waits = sorted((e for e in dli if e[0] == "dli.device_wait"),
                   key=lambda e: e[1])
    assert dispatch and len(dispatch) == len(waits)
    assert all(e[3]["k"] >= 1 and e[3]["slots"] == 2 for e in dispatch)
    # an annotation and the batcher's own span of the same program call
    # carry one number
    spans = trace.get_tracer().spans()
    chunks = {s.attrs["chunk"] for s in spans
              if s.name == "batcher.decode_chunk"}
    waves = {s.attrs["wave"]: s for s in spans
             if s.name == "batcher.admit_wave"}
    assert {e[3]["chunk"] for e in dispatch} <= chunks
    assert len({e[3]["chunk"] for e in dispatch}) == len(dispatch)
    assert waves[run[3]["wave"]].attrs["tokens"] == run[3]["tokens"]
    pairs = [(d[1], w[2]) for d, w in zip(dispatch, waits)]
    ops = [e for e in evs if e[3].get("hlo_module") == "jit_chunk"]
    assert ops
    for _, start, end, _ in ops:    # the device's work, on the host's clock
        assert any(a <= start and end <= z for a, z in pairs)


# ---- names in the programs --------------------------------------------

def lowered(model, program):
    b = batcher(model)
    r, mb = b.slots, b.max_blocks
    paged = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), b.paged)

    def ints(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32)
    with b.mesh:
        if program == "admit":
            t, pb, w = 64, 1, 2     # 128 tokens: mixtral's capacity path
            return b._admit_jit(t, pb, w).lower(
                b.params, ints(w * (t + t // b.block_size + pb + 6)),
                jax.ShapeDtypeStruct((2, w), jnp.float32), paged)
        return b._decode_jit(4, r, mb).lower(
            b.params, ints(r), ints(r * (mb + 7)),
            jax.ShapeDtypeStruct((2, r), jnp.float32), paged)


@pytest.mark.parametrize("model,program", [
    ("tiny-llama", "admit"), ("tiny-llama", "chunk"),
    ("tiny-mixtral", "admit"), ("tiny-mixtral", "chunk")])
def test_programs_carry_the_scope_vocabulary(model, program):
    text = lowered(model, program).as_text(debug_info=True)
    seen = {part for name in re.findall(r'loc\("([^"]+)"', text)
            for part in name.split("/")} & set(SCOPES)
    moe = {"moe_route", "moe_experts"}
    want = set(SCOPES) - ({"mlp"} if model == "tiny-mixtral" else moe)
    assert seen == want


# ---- what an admission cost -------------------------------------------

def test_admit_wave_attributes_add_up():
    rng = np.random.default_rng(7)
    tr = trace.get_tracer()
    b = batcher()
    warm = serve(b, rng, [30])     # one slot decodes meanwhile ...
    assert warm[0].error is None
    t_mark = time.time()
    before = b.metrics.snapshot()["counters"]["prefill_uncached_tokens"]
    long_ = b.submit(rng.integers(3, b.cfg.vocab_size, 20).tolist(),
                     max_new_tokens=40, sampling=GREEDY)
    b.step()                            # ... when the next wave arrives
    reqs = serve(b, rng, [9, 17, 3]) + [long_]
    while not long_.done.is_set():
        b.step()
    after = b.metrics.snapshot()["counters"]["prefill_uncached_tokens"]
    waves = [s for s in tr.spans() if s.name == "batcher.admit_wave"
             and s.start >= t_mark]
    assert len(waves) >= 2
    for w in waves:
        a = w.attrs
        assert 0 < a["tokens"] <= a["padded_tokens"]
        assert a["padded_tokens"] == a["rows"] * a["tail_bucket"]
        assert a["members"] <= a["rows"]
    assert sum(w.attrs["tokens"] for w in waves) == after - before
    assert waves[0].attrs["active"] == 0
    assert any(w.attrs["active"] > 0 for w in waves[1:])
    # each request's queue wait, joined to the wave that ended it
    by_id = {w.span_id: w for w in waves}
    queued = [s for s in tr.spans() if s.name == "batcher.queued"
              and s.start >= t_mark]
    assert len(queued) == len(reqs)
    for q in queued:
        wave = by_id[q.attrs["wave"]]
        assert q.end == wave.start and q.start <= q.end


# ---- stalls -----------------------------------------------------------

@pytest.fixture
def journal():
    j = events.EventJournal()
    events.set_journal(j)
    yield j
    events.clear_journal(j)


def stall_ms(b):
    c = b.metrics.snapshot()["counters"]
    return c["batcher_stall_program_ms"], c["batcher_stall_host_ms"]


@pytest.mark.parametrize("where", ["none", "program", "host"])
def test_stall_counters(journal, where):
    rng = np.random.default_rng(7)
    b = batcher()
    assert stall_ms(b) == (0, 0)
    calls = {"decode": 0}

    def hook(kind, payload, run):
        if kind == "decode":
            calls["decode"] += 1
            if where == "program" and calls["decode"] == 12:
                time.sleep(0.3)         # the device stands still
        return run()
    b.program_hook = hook

    def slow_reader(_tok):
        if where == "host" and calls["decode"] == 12 and "slept" not in calls:
            calls["slept"] = True
            time.sleep(0.3)             # the host stands still, once
    reqs = [b.submit(rng.integers(3, b.cfg.vocab_size, 9).tolist(),
                     max_new_tokens=100, sampling=GREEDY, eos_token_id=None,
                     stream_cb=slow_reader)]
    # chunks of one size only, so the running mean is of like with like
    reqs[0].chunk_cap = 4
    while not reqs[0].done.is_set():
        b.step()
    assert calls["decode"] >= 20
    program, host = stall_ms(b)
    stalls = [e for e in journal.tail() if e["type"] == "scheduler-stall"]
    if where == "none":
        assert (program, host) == (0, 0) and stalls == []
        return
    got, other = (program, host) if where == "program" else (host, program)
    assert 100 <= got <= 400 and other == 0
    assert [e["data"]["where"] for e in stalls] == [where]
    assert stalls[0]["data"]["ms"] == pytest.approx(got, abs=0.1)
    assert stalls[0]["severity"] == "warning"
    # every thread slept: the device (the hook stands for it) ran long,
    # or the scheduler thread waited in its stream callback
    data = stalls[0]["data"]
    assert (data["cause"], data["in"]) == {
        "program": ("device_or_runtime_wait", "device_wait"),
        "host": ("thread_blocked", "emit")}[where]
    assert data["k"] == (4 if where == "program" else 0)
    assert reqs[0].cost["decode_stall_ms"] == pytest.approx(got, abs=0.1)


class _LateClock(clock.SystemClock):
    """The seam's sleep runs `extra` seconds long: the heartbeat, the
    one thread that sleeps through the seam here, wakes that late."""
    extra = 0.0

    def sleep(self, seconds):
        time.sleep(seconds + self.extra)


def _burn(stop):
    """Work that needs no interpreter (BLAS releases it), as a runtime's
    own threads do."""
    a = np.ones((256, 256), np.float32)
    while not stop.is_set():
        a @ a


@pytest.mark.parametrize("cause", ["host_runtime_busy", "gc", "descheduled",
                                   "interpreter_held"])
def test_a_stalled_call_says_what_went_on(journal, cause):
    rng = np.random.default_rng(7)
    tr = trace.get_tracer()
    b = batcher()
    late = _LateClock()
    prev = clock.get_clock()
    clock.set_clock(late)
    # live objects the collector has to walk: a full collection of them
    # takes a fifth of a second
    ballast = [[] for _ in range(3_000_000)] if cause == "gc" else None
    calls = {"decode": 0}

    def hook(kind, payload, run):
        if kind == "decode":
            calls["decode"] += 1
            if calls["decode"] == 12:
                stop = threading.Event()
                burners = [threading.Thread(target=_burn, args=(stop,))
                           for _ in range(2)]
                if cause in ("host_runtime_busy", "interpreter_held"):
                    for t in burners:   # some threads work meanwhile
                        t.start()
                if cause == "gc":
                    gc.collect()
                else:
                    if cause in ("descheduled", "interpreter_held"):
                        late.extra = 0.5    # no heartbeat meanwhile
                    time.sleep(0.5)
                    late.extra = 0.0
                stop.set()
                for t in burners:
                    if t.ident is not None:
                        t.join(10)
        return run()
    b.program_hook = hook
    t_mark = time.time()
    try:
        req = b.submit(rng.integers(3, b.cfg.vocab_size, 9).tolist(),
                       max_new_tokens=100, sampling=GREEDY, eos_token_id=None)
        req.chunk_cap = 4
        while not req.done.is_set():
            b.step()
    finally:
        clock.set_clock(prev)
        del ballast
    stalls = [e for e in journal.tail() if e["type"] == "scheduler-stall"]
    # the one that was made (a crowded host may add small ones of its own)
    data = max(stalls, key=lambda e: e["data"]["ms"])["data"]
    assert data["ms"] >= 150
    assert set(data) == set(events._BY_NAME["scheduler-stall"].fields)
    assert data["where"] == "program" and data["cause"] == cause, data
    assert (data["k"], data["slots"]) == (4, 1)
    assert data["pool_positions"] > 0 and data["memory"] == {}  # the CPU
    half = data["ms"] / 2
    assert (data["gc_ms"] >= half) == (cause == "gc")
    if cause != "gc":       # the collector holds the interpreter itself
        assert (data["heartbeat_late_ms"] >= half) == \
            (cause in ("descheduled", "interpreter_held"))
        assert (data["process_cpu_ms"] >= half) == \
            (cause in ("host_runtime_busy", "interpreter_held"))
    assert data["invol_switches"] >= 0 and data["major_faults"] >= 0
    # the hook's time passes before the launch: the call's own brackets
    # did not grow, so it reads as the wait
    assert data["in"] == "device_wait"
    counters = b.metrics.snapshot()["counters"]
    assert counters[f"batcher_stall_cause_{cause}_ms"] >= data["ms"] - 0.1
    assert sum(counters[f"batcher_stall_cause_{c}_ms"]
               for c in b.STALL_CAUSES) == pytest.approx(
        counters["batcher_stall_program_ms"]
        + counters["batcher_stall_host_ms"], abs=0.1 * len(stalls))
    # the same record as a span over the stalled call, beside its chunk
    span = next(s for s in tr.spans() if s.name == "batcher.stall"
                and s.start >= t_mark and s.attrs == data)
    chunk = next(s for s in tr.spans() if s.name == "batcher.decode_chunk"
                 and s.start == span.start)
    assert chunk.end == span.end and chunk.attrs["k"] == 4
    assert req.cost["decode_stall_ms"] == pytest.approx(
        sum(e["data"]["ms"] for e in stalls), abs=0.1 * len(stalls))


# ---- the operator's reduction (scripts/profile_summary.py) ------------

def test_profile_summary_splits_idle_time_over_host_phases():
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "profile_summary", Path(__file__).resolve().parents[1]
        / "scripts" / "profile_summary.py")
    ps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ps)
    assert ps.scope_of("jit(chunk)/while/body/attention/dot_general") \
        == "attention"
    assert ps.scope_of("jit(admit)/moe_experts/moe_route/top_k") \
        == "moe_route"                          # the innermost counts
    assert ps.scope_of("jit(chunk)/while/body/squeeze") == ps.NO_SCOPE
    # a while of 10 ms around two ops of 3 ms keeps 4 ms for itself
    ops = [("w", 0.0, 0.010), ("attention", 0.001, 0.004),
           ("mlp", 0.005, 0.008)]
    assert {k[0]: pytest.approx(v) for k, v in ps.self_times(ops)} == \
        {"w": 0.004, "attention": 0.003, "mlp": 0.003}
    # the same by scope and by operation (the instruction's name rides
    # fourth), largest first
    named = [(ps.NO_SCOPE, 0.0, 0.010, "while.1"),
             ("sample", 0.001, 0.004, "sort.154"),
             ("sample", 0.005, 0.008, "fusion.1878")]
    scopes, largest = ps.by_scope(
        {"modules": [("jit_chunk", 0.0, 0.010)], "ops": named})
    assert scopes == {"jit_chunk": pytest.approx(
        {ps.NO_SCOPE: 0.004, "sample": 0.006})}
    assert [(o[0], o[1], o[3]) for o in largest["jit_chunk"]] == [
        ("while.1", ps.NO_SCOPE, 1), ("sort.154", "sample", 1),
        ("fusion.1878", "sample", 1)]
    # the device's clock runs 2 ms behind the host's: two chunk runs of
    # 100 ms with 6 ms between them, as the host saw them
    dev = {"modules": [("jit_chunk", 0.000, 0.100),
                       ("jit_chunk", 0.106, 0.206)],
           "ops": [("attention", 0.000, 0.100), ("attention", 0.106, 0.206)]}
    host = [("dli.step", 0.000, 0.1045), ("dli.dispatch", 0.0005, 0.002),
            ("dli.device_wait", 0.002, 0.103), ("dli.emit", 0.103, 0.104),
            ("dli.step", 0.1045, 0.212), ("dli.host_prep", 0.105, 0.106),
            ("dli.dispatch", 0.106, 0.109),
            ("dli.device_wait", 0.109, 0.209)]
    offset, low, high = ps.clock_offset(dev, host)
    assert low <= 0.002 <= high and offset == pytest.approx((low + high) / 2)
    idle = ps.idle_by_phase(dev, host, 0.002)   # gap: 102..108 ms
    assert idle == pytest.approx({
        "device_wait": 0.001, "emit": 0.001, "other": 0.001,
        "host_prep": 0.001, "dispatch": 0.002})


def test_profile_summary_prints_the_account(tmp_path):
    rng = np.random.default_rng(7)
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "profile_summary", Path(__file__).resolve().parents[1]
        / "scripts" / "profile_summary.py")
    ps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ps)
    b = batcher()
    serve(b, rng, [9, 12], chunk_cap=2)
    clocks = b.profiler.clocks()
    want = {**clocks["phases"], **clocks["nested"],
            "between": clocks["between_s"]}
    # a worker's GET /api/profile answer, and a benchmark's result line
    api = tmp_path / "profile.json"
    api.write_text(json.dumps({"status": "success", "profilers": {
        "tiny-llama": {"summary": b.profiler.summary()}}}))
    assert ps.read_account(str(api)) == want
    line = tmp_path / "line.json"
    line.write_text("noise\n" + json.dumps({"counters": {
        k: v for k, v in b.metrics.snapshot()["counters"].items()
        if k.startswith("batcher_")}}))
    assert ps.read_account(str(line)) == pytest.approx(want, abs=1e-5)
    text = ps.render_account(want, {"emit": 0.001, "admit_prep": 0.002})
    rows = {ln.split()[0]: ln for ln in text.splitlines()[1:]}
    assert set(rows) == set(want)
    assert f"{clocks['wall_s']:.4f} s" in text.splitlines()[0]
    assert rows["emit"].endswith("idle     1.000 ms")
    assert rows["admit_prep"].startswith("      admit_prep")  # nested
    assert ps.main(["--account", str(line)]) == 0
