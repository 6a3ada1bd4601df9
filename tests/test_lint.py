"""dlilint suite: each checker catches its seeded-violation fixture AND
runs clean on the real tree.

The fixtures are tiny synthetic repos built in tmp_path and handed to
the checkers through a hand-assembled ``Ctx`` — the same entry points
``python -m tools.dlilint`` drives, minus the repo-root discovery. The
clean-tree assertions are the actual CI gate duplicated in-process, so
a regression that sneaks past scripts/check.sh still fails the tier-1
suite.
"""

import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.dlilint import CHECKERS, run_all
from tools.dlilint.core import Ctx, SourceFile, load_lifecycle, repo_root
from tools.dlilint import check_events, check_jit, check_knobs, \
    check_lifecycle, check_metrics, check_rpc, check_threads, check_time


def _sf(tmp_path, rel, source):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return SourceFile.load(str(p), str(tmp_path))


def _ctx(tmp_path, **kw):
    kw.setdefault("package_files", [])
    kw.setdefault("runtime_files", [])
    kw.setdefault("gate_files", [])
    kw.setdefault("doc_paths", [])
    return Ctx(root=str(tmp_path), **kw)


def _rules(violations):
    return sorted(v.rule for v in violations)


# ---- knobs checker -----------------------------------------------------

def test_knobs_unregistered_read_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import os
        X = os.environ.get("DLI_FAKE_KNOB", "1")
        Y = os.getenv("DLI_OTHER_KNOB")
        Z = os.environ["DLI_SUBSCRIPT_KNOB"]
        """)
    out = check_knobs.check(_ctx(tmp_path, package_files=[sf],
                                 knob_registry={}))
    assert _rules(out) == ["knob-unregistered"] * 3
    names = {v.msg.split()[2] for v in out}
    assert names == {"DLI_FAKE_KNOB", "DLI_OTHER_KNOB",
                     "DLI_SUBSCRIPT_KNOB"}


def test_knobs_name_through_module_constant_resolved(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import os
        KNOB = "DLI_INDIRECT_KNOB"
        V = os.environ.get(KNOB, "0")
        """)
    out = check_knobs.check(_ctx(tmp_path, package_files=[sf],
                                 knob_registry={}))
    assert len(out) == 1 and "DLI_INDIRECT_KNOB" in out[0].msg


def test_knobs_dead_registry_row_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", "x = 1\n")
    out = check_knobs.check(_ctx(tmp_path, package_files=[sf],
                                 knob_registry={"DLI_GHOST": object()}))
    assert _rules(out) == ["knob-dead"]


def test_knobs_doc_dead_token_caught(tmp_path):
    doc = tmp_path / "docs" / "serving.md"
    doc.parent.mkdir()
    doc.write_text("Set `DLI_NO_SUCH_KNOB=1` to win.\n")
    out = check_knobs.check(_ctx(tmp_path, doc_paths=[str(doc)],
                                 knob_registry={}))
    assert _rules(out) == ["knob-doc-dead"]
    assert "DLI_NO_SUCH_KNOB" in out[0].msg


def test_knobs_pragma_suppresses(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import os
        # dlilint: disable=knob-unregistered
        X = os.environ.get("DLI_WAIVED_KNOB")
        """)
    out = check_knobs.check(_ctx(tmp_path, package_files=[sf],
                                 knob_registry={}))
    assert out == []


def test_knobs_shell_read_counts_as_code_read(tmp_path):
    sh = tmp_path / "scripts" / "check.sh"
    sh.parent.mkdir()
    sh.write_text('if [[ "${DLI_SHELL_ONLY:-}" == "1" ]]; then :; fi\n'
                  'DLI_ARMED_FOR_CHILD=1 python x.py\n')
    # the expansion is a read; the assignment form is not
    reads = {n for _, _, n in check_knobs.collect_shell_reads([str(sh)])}
    assert reads == {"DLI_SHELL_ONLY"}
    out = check_knobs.check(_ctx(
        tmp_path, shell_paths=[str(sh)],
        knob_registry={"DLI_SHELL_ONLY": object()}))
    assert out == []   # registered shell-only knob is not knob-dead


def test_knobs_internal_underscore_names_exempt(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import os
        X = os.environ.get("_DLI_PRIVATE_HANDSHAKE")
        """)
    out = check_knobs.check(_ctx(tmp_path, package_files=[sf],
                                 knob_registry={}))
    assert out == []


# ---- metrics checker ---------------------------------------------------

_REGISTERING_MOD = """\
    class M:
        def __init__(self, metrics):
            self.metrics = metrics
            self.metrics.inc("good_counter", 0)
            self.metrics.gauge("good_gauge", 0.0)
            self.metrics.inc("unseeded_counter")   # registered, not at 0

        def step(self):
            self.metrics.observe("good_latency", 0.1)
            for key, mname in (("a", "looped_counter"),):
                self.metrics.inc(mname, 0)
    """


def test_metrics_dashboard_unregistered_series_caught(tmp_path):
    pkg = _sf(tmp_path, "pkg/mod.py", _REGISTERING_MOD)
    dash = _sf(tmp_path, "pkg/dashboard_html.py", """\
        PAGE = '''
        const TS_METRICS = [
          ['good_counter', 'fine'],
          ['ghost_series', 'boom'],
        ];
        '''
        """)
    out = check_metrics.check(_ctx(tmp_path, package_files=[pkg],
                                   dashboard_file=dash))
    assert [v.rule for v in out] == ["metric-unregistered"]
    assert "ghost_series" in out[0].msg


def test_metrics_not_preregistered_caught(tmp_path):
    pkg = _sf(tmp_path, "pkg/mod.py", _REGISTERING_MOD)
    dash = _sf(tmp_path, "pkg/dashboard_html.py", """\
        PAGE = '''
        const TS_METRICS = [
          ['unseeded_counter', 'exists but invisible until first inc'],
          ['looped_counter', 'pre-registered through the loop idiom'],
        ];
        '''
        """)
    out = check_metrics.check(_ctx(tmp_path, package_files=[pkg],
                                   dashboard_file=dash))
    assert _rules(out) == ["metric-not-preregistered"]
    assert "unseeded_counter" in out[0].msg


def test_metrics_doc_counter_without_total_caught(tmp_path):
    pkg = _sf(tmp_path, "pkg/mod.py", _REGISTERING_MOD)
    doc = tmp_path / "docs" / "observability.md"
    doc.parent.mkdir()
    doc.write_text("Watch `dli_good_counter` (sic) and "
                   "`dli_good_counter_total` and `dli_good_gauge` and "
                   "`dli_good_latency_seconds` and `dli_nonexistent_total`.\n")
    out = check_metrics.check(_ctx(tmp_path, package_files=[pkg],
                                   doc_paths=[str(doc)]))
    assert _rules(out) == ["metric-counter-no-total", "metric-unregistered"]


def test_metrics_gate_series_and_fstring_patterns(tmp_path):
    pkg = _sf(tmp_path, "pkg/mod.py", """\
        class M:
            def pick(self, reason, metrics):
                metrics.inc(f"scheduler_pick_{reason}")
        """)
    gate = _sf(tmp_path, "bench.py", """\
        def report(mc):
            ok = mc.get("scheduler_pick_queue_depth", 0)
            bad = mc.get("totally_unknown_series", 0)
        """)
    out = check_metrics.check(_ctx(tmp_path, package_files=[pkg],
                                   gate_files=[gate]))
    assert [v.rule for v in out] == ["metric-unregistered"]
    assert "totally_unknown_series" in out[0].msg


# ---- jit purity checker ------------------------------------------------

def test_jit_impure_time_and_env_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import os
        import time
        import jax

        def step(x):
            t0 = time.perf_counter()
            flag = os.environ.get("DLI_SPEC_ADAPTIVE")
            return x * t0

        fn = jax.jit(step)
        """)
    out = check_jit.check(_ctx(tmp_path, package_files=[sf]))
    assert _rules(out) == ["jit-impure", "jit-impure"]


def test_jit_impure_through_callee_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import jax
        import numpy as np

        def noise(shape):
            return np.random.randn(*shape)

        def step(x):
            return x + noise(x.shape)

        fn = jax.jit(step)
        """)
    out = check_jit.check(_ctx(tmp_path, package_files=[sf]))
    assert _rules(out) == ["jit-impure"]
    assert "np.random" in out[0].msg


def test_jit_logging_and_lock_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import jax
        import logging

        log = logging.getLogger("x")

        class Engine:
            def _block(self, x):
                log.info("tracing now")
                with self._lock:
                    y = x + 1
                return y

            def compile(self):
                return jax.jit(self._block)
        """)
    out = check_jit.check(_ctx(tmp_path, package_files=[sf]))
    assert _rules(out) == ["jit-impure", "jit-impure"]


def test_jit_in_loop_caught_and_cached_ok(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import jax

        def bad(fs, xs):
            out = []
            for x in xs:
                fn = jax.jit(lambda v: v + 1)
                out.append(fn(x))
            return out

        def good(cache, key, f):
            if key not in cache:
                cache[key] = jax.jit(f)
            return cache[key]
        """)
    out = check_jit.check(_ctx(tmp_path, package_files=[sf]))
    assert _rules(out) == ["jit-in-loop"]


def test_jit_pure_function_clean(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import jax
        import jax.numpy as jnp

        def step(x, w):
            return jnp.dot(x, w)

        fn = jax.jit(step, donate_argnums=(0,))
        """)
    out = check_jit.check(_ctx(tmp_path, package_files=[sf]))
    assert out == []


# ---- thread hygiene checker --------------------------------------------

def test_threads_silent_except_caught_and_pragma(tmp_path):
    sf = _sf(tmp_path, "pkg/runtime/mod.py", """\
        def flusher():
            try:
                flush()
            except Exception:
                pass

        def teardown():
            try:
                close()
            # dlilint: disable=silent-except
            except Exception:
                pass
        """)
    out = check_threads.check(_ctx(tmp_path, package_files=[sf],
                                   runtime_files=[sf]))
    assert _rules(out) == ["silent-except"]
    assert out[0].line == 4


def test_threads_logged_except_clean(tmp_path):
    sf = _sf(tmp_path, "pkg/runtime/mod.py", """\
        import logging
        log = logging.getLogger("x")

        def flusher():
            try:
                flush()
            except Exception as e:
                log.warning("flush failed: %r", e)
        """)
    out = check_threads.check(_ctx(tmp_path, package_files=[sf],
                                   runtime_files=[sf]))
    assert out == []


_CYCLING_CLASS = """\
    import threading

    class Biter:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def one_way(self):
            with self._a:
                with self._b:
                    return 1

        def other_way(self):
            with self._b:
                with self._a:
                    return 2
    """


def test_threads_lock_cycle_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", _CYCLING_CLASS)
    out = check_threads.check(_ctx(tmp_path, package_files=[sf]))
    assert _rules(out) == ["lock-order-cycle"]
    assert "Biter._a" in out[0].msg and "Biter._b" in out[0].msg


def test_threads_cycle_through_method_call_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import threading

        class Sneaky:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def helper(self):
                with self._a:
                    return 1

            def outer(self):
                with self._b:
                    return self.helper()

            def direct(self):
                with self._a:
                    with self._b:
                        return 2
        """)
    out = check_threads.check(_ctx(tmp_path, package_files=[sf]))
    assert _rules(out) == ["lock-order-cycle"]


def test_threads_consistent_order_clean(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        import threading

        class Fine:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def m1(self):
                with self._a:
                    with self._b:
                        return 1

            def m2(self):
                with self._a:
                    with self._b:
                        return 2
        """)
    out = check_threads.check(_ctx(tmp_path, package_files=[sf]))
    assert out == []


def test_threads_locks_factory_recognized(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", _CYCLING_CLASS.replace(
        "threading.Lock()", 'locks.lock("x")').replace(
        "import threading", "from pkg.utils import locks"))
    out = check_threads.check(_ctx(tmp_path, package_files=[sf]))
    assert _rules(out) == ["lock-order-cycle"]


# ---- rpc contract checker ----------------------------------------------

_RPC_WORKER_MOD = """\
    class W:
        def __init__(self, s):
            s.add("GET", "/health", self.health)
            s.add("POST", "/work", self.work)
            s.add("POST", "/work/<job_id>/retry", self.retry)
            s.add("POST", "/never_called", self.nope)

        def health(self, body):
            return {}

        def work(self, body):
            used = body.get("used")
            phantom = body.get("phantom_key_nobody_sends")
            return {"used": used, "phantom": phantom}

        def retry(self, body, job_id):
            return {}

        def nope(self, body):
            return {}
    """

_RPC_MASTER_MOD = """\
    class M:
        def _worker_get(self, node, path, timeout):
            pass

        def _worker_post(self, node, path, body, timeout):
            pass

        def go(self, node, jid):
            self._worker_get(node, "/health", 5)
            self._worker_post(node, "/work",
                              {"used": 1, "ghost": 2}, 5)
            self._worker_post(node, f"/work/{jid}/retry", {}, 5)
            self._worker_post(node, "/missing", {}, 5)
            self._worker_get(node, "/work", 5)
    """


def _rpc_ctx(tmp_path, worker_src=_RPC_WORKER_MOD,
             master_src=_RPC_MASTER_MOD, **kw):
    worker = _sf(tmp_path, "pkg/runtime/workerish.py", worker_src)
    master = _sf(tmp_path, "pkg/runtime/masterish.py", master_src)
    return _ctx(tmp_path, package_files=[worker, master], **kw), \
        worker, master


def test_rpc_unknown_path_and_method_mismatch_caught(tmp_path):
    ctx, _w, master = _rpc_ctx(tmp_path)
    out = check_rpc.check(ctx)
    rules = _rules(out)
    assert "rpc-unknown-path" in rules       # POST /missing
    assert "rpc-method-mismatch" in rules    # GET /work (POST-only)
    unknown = [v for v in out if v.rule == "rpc-unknown-path"]
    assert unknown[0].path == master.rel
    assert "/missing" in unknown[0].msg


def test_rpc_param_segments_match(tmp_path):
    """f-string path holes match <param> route segments — no false
    unknown-path on /work/<job_id>/retry."""
    ctx, *_ = _rpc_ctx(tmp_path)
    out = check_rpc.check(ctx)
    assert not any("retry" in v.msg for v in out
                   if v.rule == "rpc-unknown-path")


def test_rpc_dead_route_caught_and_doc_reference_clears(tmp_path):
    ctx, *_ = _rpc_ctx(tmp_path)
    out = check_rpc.check(ctx)
    dead = [v for v in out if v.rule == "rpc-dead-route"]
    assert len(dead) == 1 and "/never_called" in dead[0].msg
    # a doc mention is a reference: operator-facing routes live in docs
    doc = tmp_path / "docs" / "ops.md"
    doc.parent.mkdir(exist_ok=True)
    doc.write_text("Operators may `POST /never_called` to win.\n")
    ctx2, *_ = _rpc_ctx(tmp_path, doc_paths=[str(doc)])
    out2 = check_rpc.check(ctx2)
    assert not [v for v in out2 if v.rule == "rpc-dead-route"]


def test_rpc_quiet_set_typo_caught(tmp_path):
    quiet = _sf(tmp_path, "pkg/runtime/httpdish.py", """\
        QUIET_TRACE_PATHS = frozenset({"/health", "/helth_typo"})
        """)
    ctx, *_ = _rpc_ctx(tmp_path)
    ctx.package_files.append(quiet)
    out = check_rpc.check(ctx)
    quiets = [v for v in out if v.rule == "rpc-quiet-unknown"]
    assert len(quiets) == 1 and "/helth_typo" in quiets[0].msg


def test_rpc_fault_point_without_intercept_caught(tmp_path):
    tests = _sf(tmp_path, "tests/test_x.py", """\
        GOOD = {"point": "/work", "mode": "error"}
        ALSO_GOOD = {"point": "rpc:/work", "mode": "timeout"}
        GLOB = {"point": "/wor*", "mode": "reset"}
        BAD = {"point": "/work_typo", "mode": "error"}
        """)
    ctx, *_ = _rpc_ctx(tmp_path, test_files=[tests])
    out = check_rpc.check(ctx)
    faults = [v for v in out if v.rule == "rpc-fault-unknown"]
    assert len(faults) == 1 and "/work_typo" in faults[0].msg


def test_rpc_body_unread_and_unsent_caught(tmp_path):
    ctx, worker, master = _rpc_ctx(tmp_path)
    out = check_rpc.check(ctx)
    unread = [v for v in out if v.rule == "rpc-body-unread"]
    assert len(unread) == 1
    assert "'ghost'" in unread[0].msg and unread[0].path == master.rel
    unsent = [v for v in out if v.rule == "rpc-body-unsent"]
    assert len(unsent) == 1
    assert "phantom_key_nobody_sends" in unsent[0].msg
    assert unsent[0].path == worker.rel


def test_rpc_body_reads_follow_helpers(tmp_path):
    """Keys read by a helper the handler hands the body to count as
    read — no false unread on builder/validator splits."""
    worker_src = """\
        class W:
            def __init__(self, s):
                s.add("POST", "/work", self.work)

            def work(self, body):
                return self._inner(dict(body))

            def _inner(self, body):
                return body.get("used")
        """
    ctx, *_ = _rpc_ctx(tmp_path, worker_src=worker_src)
    out = check_rpc.check(ctx)
    # 'used' is read through dict(body) -> self._inner; 'ghost' (which
    # nothing reads) still fires
    unread = [v for v in out if v.rule == "rpc-body-unread"]
    assert not any("'used'" in v.msg for v in unread)
    assert any("'ghost'" in v.msg for v in unread)


def test_rpc_pragma_suppresses(tmp_path):
    master_src = _RPC_MASTER_MOD.replace(
        'self._worker_post(node, "/missing", {}, 5)',
        'self._worker_post(node, "/missing", {}, 5)  '
        '# dlilint: disable=rpc-unknown-path')
    ctx, *_ = _rpc_ctx(tmp_path, master_src=master_src)
    out = check_rpc.check(ctx)
    assert not any("/missing" in v.msg for v in out
                   if v.rule == "rpc-unknown-path")


# ---- lifecycle checker -------------------------------------------------

_LIFECYCLE = load_lifecycle(repo_root())


def _t(name, source, target, fn, guard, durability, counts_attempt):
    return _LIFECYCLE.Transition(name, source, target, fn, guard,
                                 durability, counts_attempt, "")


_LIFE_STATE_MOD = """\
    class Store:
        def mark_completed(self, rid):
            self._submit_write(
                "UPDATE requests SET status='completed' WHERE id=? "
                "AND status NOT IN ('completed','failed')", (rid,),
                barrier=True)

        def mark_failed(self, rid):
            self._exec(
                "UPDATE requests SET status='failed' WHERE id=?",
                (rid,))

        def vanish(self, rid):
            self._exec(
                "UPDATE requests SET status='vanished' WHERE id=?",
                (rid,))

        def requeue(self, rid):
            self._submit_write(
                "UPDATE requests SET status='pending' WHERE id=?",
                (rid,), barrier=True)
    """

_LIFE_TABLE = (
    _t("complete", ("processing",), "completed", "mark_completed",
       "not-terminal", "barrier", False),
    # declared barrier + where-guard, but the site uses _exec with no
    # WHERE status constraint -> lifecycle-barrier AND lifecycle-guard
    _t("fail", ("processing",), "failed", "mark_failed", "where",
       "barrier", False),
    # declared attempt accounting the SQL lacks -> lifecycle-attempts
    _t("requeue", ("processing",), "pending", "requeue", "none",
       "barrier", True),
    # declared transition with no site -> lifecycle-unused
    _t("ghost", ("pending",), "failed", "cancel_pending", "where",
       "sync-txn", False),
)


def test_lifecycle_fixture_catches_each_rule(tmp_path):
    sf = _sf(tmp_path, "pkg/runtime/state.py", _LIFE_STATE_MOD)
    out = check_lifecycle.check_sites(sf, _LIFE_TABLE)
    rules = _rules(out)
    assert "lifecycle-undeclared" in rules    # status='vanished'
    assert "lifecycle-barrier" in rules       # fail via _exec
    assert "lifecycle-guard" in rules         # fail without WHERE guard
    assert "lifecycle-attempts" in rules      # requeue w/o attempts+1
    assert "lifecycle-unused" in rules        # ghost
    # the correct site is NOT flagged
    assert not any("mark_completed" in v.msg or v.line == 2
                   for v in out if v.rule != "lifecycle-unused")


def test_lifecycle_clean_fixture_passes(tmp_path):
    sf = _sf(tmp_path, "pkg/runtime/state.py", """\
        class Store:
            def mark_completed(self, rid):
                self._submit_write(
                    "UPDATE requests SET status='completed' "
                    "WHERE id=? AND status NOT IN "
                    "('completed','failed')", (rid,), barrier=True)
        """)
    table = (_t("complete", ("processing",), "completed",
                "mark_completed", "not-terminal", "barrier", False),)
    assert check_lifecycle.check_sites(sf, table) == []


def test_lifecycle_locked_select_guard(tmp_path):
    src = """\
        class Store:
            def claim(self):
                with self._lock:
                    rows = self._all(
                        "SELECT * FROM requests WHERE "
                        "status='pending' LIMIT 1")
                    with self._db:
                        self._db.executemany(
                            "UPDATE requests SET status='processing' "
                            "WHERE id=?", [(1,)])
        """
    sf = _sf(tmp_path, "pkg/runtime/state.py", src)
    table = (_t("claim", ("pending",), "processing", "claim",
                "locked-select", "sync-txn", False),)
    assert check_lifecycle.check_sites(sf, table) == []
    # drop the lock: the locked-select guard must fail
    sf2 = _sf(tmp_path, "pkg/runtime/state2.py", src.replace(
        "with self._lock:", "if True:"))
    out = check_lifecycle.check_sites(sf2, table)
    # losing the lock breaks BOTH the locked-select guard and the
    # sync-txn durability claim
    assert _rules(out) == ["lifecycle-barrier", "lifecycle-guard"]


def test_lifecycle_diagram_byte_checked(tmp_path):
    doc = tmp_path / "robustness.md"
    doc.write_text("# Robustness\n\nno diagram yet\n")
    out = check_lifecycle.check_diagram(str(doc), _LIFECYCLE)
    assert _rules(out) == ["lifecycle-diagram-stale"]
    assert check_lifecycle.write_lifecycle_diagram(str(doc), _LIFECYCLE)
    assert check_lifecycle.check_diagram(str(doc), _LIFECYCLE) == []
    # drift by one byte -> stale again
    doc.write_text(doc.read_text().replace("pending", "pending ", 1))
    out = check_lifecycle.check_diagram(str(doc), _LIFECYCLE)
    assert _rules(out) == ["lifecycle-diagram-stale"]
    # idempotent regenerate restores byte equality
    assert check_lifecycle.write_lifecycle_diagram(str(doc), _LIFECYCLE)
    assert not check_lifecycle.write_lifecycle_diagram(str(doc),
                                                       _LIFECYCLE)


def test_lifecycle_declared_machine_is_sane():
    """The committed table covers the four states, reaches both
    terminals, and every terminal transition declares a durability
    mechanism."""
    ts = _LIFECYCLE.TRANSITIONS
    assert {t.target for t in ts} == set(_LIFECYCLE.STATES)
    for t in ts:
        if t.target in _LIFECYCLE.TERMINAL:
            assert t.durability in ("barrier", "sync-txn")
    assert any(t.counts_attempt for t in ts)


# ---- events checker ----------------------------------------------------

class _EvDecl:
    def __init__(self, doc="documented"):
        self.doc = doc
        self.fields = ()


def test_events_undeclared_emit_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        from distributed_llm_inferencing_tpu.runtime import events
        events.emit("ghost-event", node_id=1)
        events.emit("real-event")
        """)
    out = check_events.check(_ctx(
        tmp_path, package_files=[sf],
        event_registry={"real-event": _EvDecl()}))
    assert _rules(out) == ["event-undeclared"]
    assert "ghost-event" in out[0].msg


def test_events_self_attribute_emit_resolved(tmp_path):
    """The master's ``self.events.emit(...)`` form counts as an emit
    site too (the dotted callee ends in events.emit)."""
    sf = _sf(tmp_path, "pkg/mod.py", """\
        class M:
            def go(self):
                self.events.emit("real-event", node_id=1)
        """)
    out = check_events.check(_ctx(
        tmp_path, package_files=[sf],
        event_registry={"real-event": _EvDecl()}))
    assert out == []


def test_events_unemitted_declared_type_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", "x = 1\n")
    out = check_events.check(_ctx(
        tmp_path, package_files=[sf],
        event_registry={"never-fired": _EvDecl()}))
    assert _rules(out) == ["event-unemitted"]
    assert "never-fired" in out[0].msg


def test_events_undoc_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        from distributed_llm_inferencing_tpu.runtime import events
        events.emit("bare-event")
        """)
    out = check_events.check(_ctx(
        tmp_path, package_files=[sf],
        event_registry={"bare-event": _EvDecl(doc="  ")}))
    assert _rules(out) == ["event-undoc"]


def test_events_pragma_suppresses(tmp_path):
    sf = _sf(tmp_path, "pkg/mod.py", """\
        from distributed_llm_inferencing_tpu.runtime import events
        # dlilint: disable=event-undeclared
        events.emit("waived-event")
        """)
    out = check_events.check(_ctx(tmp_path, package_files=[sf],
                                  event_registry={}))
    assert out == []


def test_events_table_stale_caught(tmp_path):
    """A drifted (or missing) generated block in observability.md fails;
    write_event_table repairs it to a fixed point."""
    from tools.dlilint.core import load_events
    events_mod = load_events(repo_root())
    doc = tmp_path / "docs" / "observability.md"
    doc.parent.mkdir()
    doc.write_text("# Observability\n")
    sf = _sf(tmp_path, "pkg/mod.py", "\n".join(
        f'events.emit("{name}")' for name in events_mod.registry()) + "\n")
    ctx = _ctx(tmp_path, package_files=[sf],
               event_registry=events_mod.registry(),
               events_mod=events_mod,
               observability_md=str(doc))
    out = check_events.check(ctx)
    assert _rules(out) == ["event-table-stale"]
    assert check_events.write_event_table(str(doc), events_mod)
    assert check_events.check(ctx) == []
    # idempotent: a second write is a no-op
    assert not check_events.write_event_table(str(doc), events_mod)
    # hand edits to the block fail again
    doc.write_text(doc.read_text().replace("| `breaker-open` |",
                                           "| `breaker-open!!` |"))
    out = check_events.check(ctx)
    assert _rules(out) == ["event-table-stale"]


def test_events_real_registry_fully_emitted():
    """Acceptance: three-way parity on the committed tree — every
    declared type has a live emit site and the docs appendix is the
    registry's exact rendering (the byte check runs via
    test_real_tree_clean; this pins the emit-site leg explicitly)."""
    ctx = Ctx.for_repo()
    emitted = {name for _, _, name in
               check_events.collect_emit_sites(
                   ctx.package_files + ctx.gate_files)}
    declared = set(ctx.event_registry)
    assert declared <= emitted, (
        f"declared-but-never-emitted: {sorted(declared - emitted)}")
    assert emitted <= declared, (
        f"emitted-but-undeclared: {sorted(emitted - declared)}")


# ---- the real tree is the fixture for "runs clean" ---------------------

# ---- time checker ------------------------------------------------------

def test_time_direct_call_and_bare_ref_caught(tmp_path):
    """Calls AND bare references: a ``default_factory=time.time``
    stamps rows just as directly as a call does."""
    sf = _sf(tmp_path, "pkg/runtime/mod.py", """\
        import time
        t0 = time.time()
        m = time.monotonic
        def nap():
            time.sleep(1.0)
        """)
    out = check_time.check(_ctx(tmp_path, runtime_files=[sf]))
    assert _rules(out) == ["time-direct"] * 3


def test_time_from_import_caught(tmp_path):
    sf = _sf(tmp_path, "pkg/runtime/mod.py", """\
        from time import sleep, perf_counter
        """)
    out = check_time.check(_ctx(tmp_path, runtime_files=[sf]))
    # sleep is seamed; perf_counter measures the host and stays legal
    assert _rules(out) == ["time-direct"]


def test_time_host_measurement_exempt(tmp_path):
    """perf_counter/time_ns measure the host, not the cluster
    timeline — the virtual clock must never warp them."""
    sf = _sf(tmp_path, "pkg/runtime/mod.py", """\
        import time
        a = time.perf_counter()
        b = time.time_ns()
        c = time.strftime("%F")
        """)
    assert check_time.check(_ctx(tmp_path, runtime_files=[sf])) == []


def test_time_outside_runtime_not_scanned(tmp_path):
    """The seam covers runtime/ only: bench harness, tools and tests
    legitimately measure wall time."""
    sf = _sf(tmp_path, "pkg/other/mod.py", """\
        import time
        t0 = time.time()
        """)
    assert check_time.check(_ctx(tmp_path, runtime_files=[],
                                 package_files=[sf])) == []


def test_time_pragma_suppression(tmp_path):
    sf = _sf(tmp_path, "pkg/runtime/mod.py", """\
        import time
        t0 = time.time()   # dlilint: disable=time-direct

        t1 = time.time()
        """)
    out = check_time.check(_ctx(tmp_path, runtime_files=[sf]))
    assert len(out) == 1 and out[0].line == 4


@pytest.fixture(scope="module")
def repo_results():
    return run_all()


@pytest.mark.parametrize("checker", sorted(CHECKERS))
def test_real_tree_clean(repo_results, checker):
    assert repo_results[checker] == [], (
        f"dlilint {checker} found violations on the committed tree — "
        f"run `python -m tools.dlilint` (docs/static_analysis.md)")


def test_knob_registry_three_way_parity():
    """Acceptance: code knobs == registry == docs, exactly. "Code"
    includes shell scripts: a check.sh-only knob (DLI_VERIFY_BUDGET) is a
    knob like any other."""
    from distributed_llm_inferencing_tpu.utils import knobs
    ctx = Ctx.for_repo()
    reads = {name for _, _, name in
             check_knobs.collect_env_reads(
                 ctx.package_files + ctx.gate_files)}
    reads |= {name for _, _, name in
              check_knobs.collect_shell_reads(ctx.shell_paths)}
    assert reads == set(knobs.registry()), (
        "registry drifted from code reads")
    with open(ctx.serving_md, encoding="utf-8") as f:
        serving = f.read()
    missing = [n for n in knobs.registry() if n not in serving]
    assert not missing, f"knobs missing from docs/serving.md: {missing}"


def test_cli_exits_zero_on_clean_tree():
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-m", "tools.dlilint"],
                       cwd=root, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "— clean" in r.stdout
