"""Cluster flight recorder: a typed, durable journal of control-plane
decisions.

PRs 8 and 12 made the cluster *decide* things — transfer-vs-recompute
plans, breaker trips, role flips, live migrations — but each decision
survived only as a transient ``log.warning`` line or a bare counter.
After an incident there was no way to prove what the recovery actually
did (FailSafe, arxiv 2511.14116, is only trustworthy with a
post-incident record), and the ROADMAP item-2 planner needs decision
history that outlives the master process.

This module is the declared half plus the journal:

- :data:`EVENT_TYPES` — every event type the cluster may emit, declared
  as data (name, severity, doc, fields) in the ``runtime/lifecycle.py``
  style. ``tools/dlilint/check_events.py`` enforces three-way parity:
  every ``events.emit("<type>", ...)`` site names a declared type, every
  declared type has an emit site, and the generated appendix in
  ``docs/observability.md`` matches this registry byte-for-byte
  (regenerate with ``python -m tools.dlilint --write-event-table``).
- :class:`EventJournal` — a bounded in-memory ring of recent events plus
  durable persistence through the ``Store`` group-commit path (the new
  ``events`` table, retention-capped), served at ``GET /api/events`` and
  merged into ``GET /api/requests/<id>/journey``.
- module-level :func:`emit` — the fire-and-forget helper decision sites
  call. It routes to the installed journal (the master installs its own
  at construction) and NEVER raises: a journaling hiccup must not turn
  a servable request into a failure.

Like ``lifecycle.py``, the registry part is pure data + string
rendering, importable by the dlilint checker without pulling in sqlite
or jax (the journal half leans only on ``utils.locks`` + stdlib).
"""

from __future__ import annotations

import collections
import json
import logging
import os
from typing import Dict, NamedTuple, Optional, Tuple

from distributed_llm_inferencing_tpu.utils import clock, locks

log = logging.getLogger("dli_tpu.events")

SEVERITIES = ("info", "warning", "error")

# Markers delimiting the generated appendix in docs/observability.md.
DOC_BEGIN = ("<!-- BEGIN GENERATED EVENT TABLE "
             "(python -m tools.dlilint --write-event-table) -->")
DOC_END = "<!-- END GENERATED EVENT TABLE -->"
DOC_PATH = os.path.join("docs", "observability.md")


class EventType(NamedTuple):
    name: str                 # stable kebab-case id, the wire `type`
    severity: str             # default severity: info | warning | error
    doc: str                  # one-line meaning, rendered into the docs
    fields: Tuple[str, ...]   # declared `data` keys (documented; a site
    #                           may emit a subset when inputs are absent)


# what a program's first use leaves behind (utils/profiler.py, the
# program account): the event's fields and the span's attributes
PROGRAM_FIRST_USE_FIELDS = (
    "kind", "key", "fun_name", "trace_ms", "lower_ms", "load_ms", "cache",
    "cache_read_ms", "run_ms", "pallas_call_sites", "serving")

EVENT_TYPES = (
    # ---- fleet membership / health -----------------------------------
    EventType(
        "node-added", "info",
        "A worker registered (or re-registered) with the master.",
        ("name", "host", "port", "readded")),
    EventType(
        "node-removed", "info",
        "A worker was removed from the registry (operator action).",
        ("name",)),
    EventType(
        "node-drain", "info",
        "A worker's self-declared draining flag changed — planned "
        "shutdown starting or finishing.",
        ("draining",)),
    EventType(
        "breaker-open", "warning",
        "A node's circuit breaker tripped OPEN (strike threshold "
        "reached, or a half-open probe failed): the node is "
        "unschedulable until a health probe half-opens it.",
        ("strikes", "prev_state")),
    EventType(
        "breaker-half-open", "info",
        "An open node answered a health probe: schedulable again as a "
        "single-probe candidate until a real request closes the "
        "breaker.", ()),
    EventType(
        "breaker-closed", "info",
        "A half-open probe request succeeded (or strikes cleared): the "
        "node is fully schedulable again.", ()),
    EventType(
        "node-refresh-failed", "warning",
        "A post-load node snapshot refresh failed — dispatch proceeded "
        "on the stale snapshot (was a log.warning-only path before the "
        "flight recorder).", ("error",)),
    # ---- scheduling / dispatch ---------------------------------------
    EventType(
        "request-submitted", "info",
        "A request entered the queue. The event's own ts is the "
        "arrival timestamp and the data carries the workload shape "
        "(prompt length, token budget), so the journal doubles as a "
        "replayable arrival trace: tools/dlisim reconstructs a real "
        "run's workload from exactly these rows (a debug bundle is "
        "sim-replayable because collect_debug_bundle.sh exports them).",
        ("model", "prompt_chars", "max_new_tokens", "max_length",
         "slo_class", "tenant", "adapter")),
    EventType(
        "admission-rejected", "warning",
        "The overload front door refused a submit — degradation-ladder "
        "class shed, pending-queue cap, or the tenant's token bucket — "
        "with an honest 429 + Retry-After. One event per refusal: a "
        "shed is never a silent drop (docs/robustness.md \"Overload "
        "control\").",
        ("tenant", "slo_class", "reason", "retry_after_s", "level")),
    EventType(
        "overload-level", "warning",
        "The overload ladder moved one rung (up under pressure, down "
        "on recovery), with the gauge values that justified the "
        "transition — the postmortem reconstructs the whole brownout "
        "walk from these rows alone.",
        ("level", "prev_level", "direction", "burn_rate",
         "queue_depth")),
    EventType(
        "request-park", "warning",
        "No schedulable node for a claimed request: parked behind a "
        "backoff delay, or terminally failed when the attempt budget "
        "was already burned.",
        ("attempts", "terminal", "delay_s")),
    EventType(
        "request-requeued", "warning",
        "A dispatch attempt failed and the request re-entered the "
        "queue: the failed node is excluded (or the retry stays pinned "
        "on a sticky timeout) and the next attempt parks behind "
        "backoff.",
        ("error", "attempts", "sticky", "excluded", "delay_s")),
    EventType(
        "disagg-plan", "info",
        "A transfer-vs-recompute verdict for a disaggregation-eligible "
        "request, carrying the actual inputs that decided it "
        "(estimated prompt tokens, warmest advertised prefix, learned "
        "prefill EWMA, pool sizes).",
        ("verdict", "est_tokens", "warm_tokens",
         "prefill_ewma_ms_per_tok", "prefill_pool", "decode_pool",
         "prefill_node", "decode_node")),
    EventType(
        "disagg-prefill-failed", "warning",
        "Phase 1 of a disaggregated dispatch failed on the prefill "
        "node: the request degraded to plain recompute dispatch on the "
        "decode node (was a log.warning-only path).",
        ("error", "status")),
    # ---- live migration / elasticity ---------------------------------
    EventType(
        "migrate-out", "info",
        "A worker answered an in-flight dispatch with a 303 handoff: "
        "the resume record (stream cursor) was persisted and the "
        "request re-queued with a kv_source hint back at the source "
        "arena.", ("resume_tokens",)),
    EventType(
        "migrate-resume", "info",
        "A dispatch attempt carried a migrated request's resume record "
        "to the chosen node (one event per attempt — a failed-over "
        "resume emits again on the next node; the terminal lifecycle "
        "entry names where the stream actually finished).",
        ("resume_tokens", "attempt")),
    EventType(
        "migrate-anomaly", "warning",
        "A /migrate_out RPC did not hand off cleanly: transport "
        "failure (retried next sweep) or a 409 completion race "
        "(settled, nothing to migrate) — was a log-only path.",
        ("status", "error")),
    EventType(
        "role-flip", "info",
        "The elastic rebalancer flipped a worker between the "
        "prefill/decode pools (or re-created an emptied prefill pool "
        "on disagg demand).",
        ("role", "prev_role", "reason")),
    EventType(
        "plan-chosen", "info",
        "The auto-parallelism planner (parallel/planner.py) chose a "
        "deployment plan: mesh shape + prefill/decode role split, "
        "ranked over the enumerated candidates by the profile-fed "
        "cost model. The data carries the full decision inputs — "
        "fitted node classes, workload shape, learned rates — so the "
        "choice is reconstructable from the journal alone.",
        ("model", "plan_id", "mesh", "role_split", "prefill_nodes",
         "candidates", "scored", "score", "classes",
         "est_prompt_tokens", "est_decode_tokens",
         "prefill_ewma_ms_per_tok", "decode_tokens_per_weight_pass",
         "slo_e2e_ms", "reason")),
    EventType(
        "rebalance-divergence", "info",
        "A rebalancer sweep found sustained pool-utilization "
        "divergence past the configured ratio, with the pool means "
        "that justified the (attempted) flip.",
        ("prefill_mean", "decode_mean", "ratio", "action")),
    # ---- SLO / telemetry / store -------------------------------------
    EventType(
        "slo-burn", "warning",
        "The fast-window error-budget burn rate crossed the alerting "
        "threshold (1.0 = consuming exactly the budget) — in either "
        "direction.", ("burn_rate", "direction")),
    EventType(
        "store-flush-failed", "error",
        "A group-commit store flush failed (disk full / I/O error): "
        "the batch was re-buffered in order and the flusher retries; "
        "barrier waiters stay blocked until a flush succeeds.",
        ("error", "ops")),
    EventType(
        "fault-armed", "warning",
        "A fault-injection schedule was armed on a service (env or "
        "runtime admin API) — chaos experiments are part of the "
        "post-incident record too.",
        ("service", "count", "points")),
    # ---- replicated control plane (runtime/replication.py) -----------
    EventType(
        "lease-acquired", "warning",
        "A standby's lease deadline expired and it took the leader "
        "lease at term+1: this master now schedules/dispatches (the "
        "takeover-recovery event that follows carries the requeue "
        "count).", ("term", "holder", "prev_applied")),
    EventType(
        "lease-lost", "warning",
        "A leading master observed a higher (or winning) term — via a "
        "peer frame, a peer ack, or a worker's stale-term fence — and "
        "stepped down: it stops scheduling immediately and its store "
        "is resynced from the new leader's snapshot.",
        ("term", "reason", "holder")),
    EventType(
        "takeover-recovery", "warning",
        "The crash-recovery requeue run at lease takeover: every "
        "request the dead leader held in 'processing' re-entered the "
        "queue (attempt counted; poison requests at the budget fail "
        "instead).", ("term", "recovered")),
    EventType(
        "replication-lag", "warning",
        "Standby acks fell behind the op-log head past the warn "
        "threshold — or a durability-barrier wait timed out and the "
        "write degraded to leader-only durability. The info-severity "
        "twin marks recovery (acks caught back up).",
        ("ops_behind", "lag_ms", "acked_seq", "log_seq",
         "barrier_timeout")),
    EventType(
        "stale-term-rejected", "warning",
        "A worker fenced this master's dispatch with 409 + "
        "X-DLI-Stale-Term: a newer term holds the lease. Emitted by "
        "the deposed master (to its in-memory ring) as it steps down "
        "— the paused-then-revived-leader trail a postmortem needs.",
        ("term", "observed_term")),
    # ---- continuous batcher (runtime/batcher.py) -----------------------
    EventType(
        "scheduler-stall", "warning",
        "The batcher's step loop stood still: a decode chunk's wall per "
        "pass was over twice the running mean of earlier chunks "
        "(`where` program), or a busy step spent over 100 ms outside "
        "its program calls (`where` host). `ms` is what was lost (over "
        "the mean, over the 100 ms), also added to "
        "`dli_batcher_stall_{program,host}_ms_total` and, by `cause`, "
        "to one of the `batcher_stall_cause_*` counters. `in` is the bracket "
        "that grew (`dispatch` or `device_wait` of the stalled chunk, "
        "`spec_verify`; the step's largest host bracket), "
        "`pool_positions` the last chunk's read extent; over the "
        "stalled call (or step): the scheduler thread's and the "
        "process's CPU time, the process's involuntary context switches "
        "and major faults, the cycle collector's time and the worst "
        "lateness of the process's 50 ms heartbeat; `memory` is the "
        "fullest device's `memory_stats()` at that moment; `cause` is "
        "what `ContinuousBatcher._stall_cause` makes of them (`gc`, "
        "`interpreter_held`, `descheduled`, `host_runtime_busy`, "
        "`device_or_runtime_wait`, `thread_blocked`). The same record "
        "is a `batcher.stall` span over the call.",
        ("where", "ms", "in", "k", "slots", "pool_positions",
         "thread_cpu_ms", "process_cpu_ms", "invol_switches",
         "major_faults", "gc_ms", "heartbeat_late_ms", "memory",
         "cause")),
    EventType(
        "program-first-use", "warning",
        "A request waited for a compilation: the batcher's scheduler "
        "thread called a program it had not used yet (a new tail, "
        "prefix or wave bucket, a chunk size no warm-up reached), "
        "and JAX traced, lowered and compiled it, or read it from the "
        "compile cache, inside the step. `kind` (`admit`, `chunk`, "
        "`spec`) and `key` (tail x prefix blocks x rows; passes) name "
        "the program, `fun_name` is JAX's; `trace_ms`, `lower_ms` and "
        "`load_ms` are what `jax.monitoring`'s events covered (`load_ms` "
        "the backend's: the cache's read and deserialize when `cache` is "
        "`hit`, of which `cache_read_ms` the read, XLA's compile when "
        "`miss`, `off` with no cache asked), `run_ms` the rest of the "
        "call (arguments, first run, the sync), `pallas_call_sites` the "
        "kernels traced, `serving` always true here. The same record is "
        "a `batcher.program_first_use` span under the step's "
        "`batcher.admit_wave` / `batcher.decode_chunk` (left for set-up's "
        "first uses too), a row of `GET /api/profile`'s `programs`, and "
        "is added to `dli_batcher_program_*`.",
        PROGRAM_FIRST_USE_FIELDS),
    # ---- multi-LoRA adapter serving (models/lora.py) ------------------
    EventType(
        "adapter-loaded", "info",
        "A LoRA adapter became host-resident on a worker — an explicit "
        "operator /load_adapter, or the master's lazy dispatch-time "
        "load for a request naming an adapter the chosen node lacked.",
        ("adapter", "model", "rank", "nbytes", "lazy")),
    EventType(
        "adapter-evicted", "info",
        "The bounded host adapter store evicted an idle adapter (LRU "
        "by bytes) to make room for a newly loaded one — the evicted "
        "name reloads lazily on its next request.",
        ("adapter", "model", "evicted_for")),
    EventType(
        "adapter-load-failed", "error",
        "An adapter load was refused (bad source, shape mismatch "
        "against the base model, store full of pinned adapters): the "
        "request path fails rather than silently serving base "
        "weights.", ("adapter", "model", "error")),
)

_BY_NAME: Dict[str, EventType] = {t.name: t for t in EVENT_TYPES}


def _check_registry() -> None:
    """The registry must be self-consistent before anything trusts it."""
    assert len(_BY_NAME) == len(EVENT_TYPES), "duplicate event type names"
    for t in EVENT_TYPES:
        assert t.name == t.name.lower() and " " not in t.name, t.name
        assert t.severity in SEVERITIES, t.name
        assert t.doc.strip(), f"{t.name}: undocumented event type"
        assert isinstance(t.fields, tuple), t.name
        assert len(t.fields) == len(set(t.fields)), t.name


_check_registry()


def registry() -> Dict[str, EventType]:
    """Name -> EventType for the whole declared set."""
    return dict(_BY_NAME)


def names() -> frozenset:
    return frozenset(_BY_NAME)


def get(name: str) -> EventType:
    return _BY_NAME[name]


class EventJournal:
    """Bounded ring of recent events + durable persistence through the
    master's :class:`~runtime.state.Store` group-commit path.

    Every emit lands in the in-memory ring immediately and (when a
    store is attached) queues one INSERT into the ``events`` table
    through the same write-behind buffer the request-status writes use
    — journaling rides the group commit, it never adds its own
    transaction to the hot path. Retention: the table is pruned back to
    ``retain`` rows every ``_PRUNE_EVERY`` persisted events, so a
    long-lived master's journal is a sliding window, not an unbounded
    log."""

    _PRUNE_EVERY = 512

    def __init__(self, store=None, ring: Optional[int] = None,
                 retain: Optional[int] = None):
        if ring is None:
            ring = int(os.environ.get("DLI_EVENTS_RING", 2048))
        if retain is None:
            retain = int(os.environ.get("DLI_EVENTS_RETAIN", 20000))
        self._store = store
        # Replicated control plane (runtime/replication.py): a STANDBY
        # master journals to its in-memory ring only — the durable
        # journal rows arrive from the leader through op-log
        # replication, and a replica writing its own would fork the
        # replicated autoincrement stream. Flipped at promote/demote.
        self.durable = True
        self._retain = max(1, int(retain))
        self._lock = locks.lock("events.ring")
        self._ring: collections.deque = collections.deque(
            maxlen=max(1, int(ring)))
        self._emitted = 0
        self._since_prune = 0

    def emit(self, etype: str, *, node_id=None, request_id=None,
             trace_id: Optional[str] = None, severity: Optional[str] = None,
             t: Optional[float] = None, **data) -> dict:
        """Record one event. ``etype`` MUST be declared in
        :data:`EVENT_TYPES` (an undeclared type raises — the static
        checker keeps call sites honest, this keeps dynamic ones);
        ``severity`` overrides the declared default (a site may escalate,
        e.g. a routine verdict observed during an incident)."""
        decl = _BY_NAME.get(etype)
        if decl is None:
            raise ValueError(f"undeclared event type {etype!r} "
                             "(declare it in runtime/events.py)")
        sev = severity or decl.severity
        if sev not in SEVERITIES:
            raise ValueError(f"unknown severity {sev!r}")
        ev = {
            "ts": clock.now() if t is None else float(t),
            "type": etype,
            "severity": sev,
            "node_id": int(node_id) if node_id is not None else None,
            "request_id": (int(request_id) if request_id is not None
                           else None),
            "trace_id": trace_id,
            "data": {k: v for k, v in data.items() if v is not None},
        }
        with self._lock:
            self._ring.append(ev)
            self._emitted += 1
            self._since_prune += 1
            prune = self._since_prune >= self._PRUNE_EVERY
            if prune:
                self._since_prune = 0
        if self._store is not None and self.durable:
            # one buffered INSERT through the group-commit write-behind
            # path (barrier=False: durability within a flush cycle, no
            # hot-path commit wait); the periodic prune rides the same
            # buffer, so the retention cap costs no extra transaction
            self._store.append_event(
                ev["ts"], etype, sev, ev["node_id"], ev["request_id"],
                trace_id, json.dumps(ev["data"]))
            if prune:
                self._store.prune_events(self._retain)
        return ev

    def tail(self, n: int = 100) -> list:
        """Most recent events from the in-memory ring (newest last)."""
        with self._lock:
            evs = list(self._ring)
        return evs[-max(0, int(n)):]

    def counts(self) -> dict:
        with self._lock:
            return {"emitted": self._emitted, "ring": len(self._ring),
                    "ring_cap": self._ring.maxlen,
                    "retain": self._retain}


# ---- module-level emit: the decision sites' entry point ---------------
#
# The master installs its journal here at construction; decision sites
# anywhere in the process (master loops, state.py's flusher, the fault
# injector) call ``events.emit(...)`` without plumbing a journal handle
# through every layer. Installed journal wins; with none installed
# (worker-only processes, unit tests) the helper is a no-op.

_GLOBAL: Optional[EventJournal] = None


def set_journal(journal: Optional[EventJournal]) -> None:
    global _GLOBAL
    _GLOBAL = journal


def clear_journal(journal: EventJournal) -> None:
    """Uninstall ``journal`` if it is the installed one (a stopped
    master must not unhook a newer master's journal — benches run
    several in one process)."""
    global _GLOBAL
    if _GLOBAL is journal:
        _GLOBAL = None


def get_journal() -> Optional[EventJournal]:
    return _GLOBAL


def emit(etype: str, **kw) -> Optional[dict]:
    """Fire-and-forget emit to the installed journal. Never raises:
    the flight recorder observes the control plane, it must not be able
    to fail it."""
    j = _GLOBAL
    if j is None:
        return None
    try:
        return j.emit(etype, **kw)
    except Exception as e:
        log.warning("event emit %r failed: %r", etype, e)
        return None


# ---- generated docs appendix ------------------------------------------

def markdown_table() -> str:
    """One row per declared event type, as embedded in
    docs/observability.md."""
    rows = ["| Event type | Severity | Data fields | Meaning |",
            "| --- | --- | --- | --- |"]
    for t in EVENT_TYPES:
        fields = ", ".join(f"`{f}`" for f in t.fields) or "—"
        rows.append(f"| `{t.name}` | {t.severity} | {fields} | {t.doc} |")
    return "\n".join(rows)


def generated_block() -> str:
    """Marker-delimited block for docs/observability.md; the dlilint
    events checker fails when the committed block != this string."""
    return (f"{DOC_BEGIN}\n\n"
            "This table is generated from `runtime/events.py` — edit "
            "the declared registry,\nthen run `python -m tools.dlilint "
            "--write-event-table`. Hand edits here are\noverwritten "
            "and fail the `events` checker.\n\n"
            f"{markdown_table()}\n\n{DOC_END}")
