"""Central registry of every ``DLI_*`` environment knob.

Eight PRs accreted ~60 env knobs across 15 modules, each read at its
point of use with an inline default — and the docs knob tables drifted
(14 knobs existed only in code when this registry landed). This module
is the single source of truth the ``dlilint`` knobs checker
(tools/dlilint/check_knobs.py) enforces three-way parity against:

    every DLI_* env read in code  ==  this registry  ==  docs/serving.md

The registry is *declarative*: modules keep reading their knobs where
they always did (an env read at point-of-use stays greppable and
avoids import cycles into this module from, say, ``native/__init__``).
What the registry adds:

- ``KNOBS`` — name, default (as the *documented* string), parser kind,
  one-line doc, and the module that owns the read.
- ``markdown_table()`` / ``generated_block()`` — the generated knob
  table embedded in docs/serving.md between the BEGIN/END markers
  below. Regenerate with ``python -m tools.dlilint --write-knob-table``;
  the checker fails if the committed block drifts from the registry.
- ``value(name)`` — parse the live env value with the registered
  parser/default, for new call sites that don't want to re-implement
  the int/float/bool parse (existing reads are not rewritten).

Adding a knob: add the env read where it belongs, add a ``Knob`` row
here, run ``python -m tools.dlilint --write-knob-table``. Forgetting
any leg fails CI.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, NamedTuple, Optional

# Markers delimiting the generated table in docs/serving.md.
DOC_BEGIN = "<!-- BEGIN GENERATED KNOB TABLE (python -m tools.dlilint --write-knob-table) -->"
DOC_END = "<!-- END GENERATED KNOB TABLE -->"
DOC_PATH = os.path.join("docs", "serving.md")


class Knob(NamedTuple):
    name: str          # full env var name, DLI_ prefix included
    default: str       # documented default, as a human-readable string
    kind: str          # int | float | bool | str | enum | json | path
    doc: str           # one-line effect, rendered into the table
    owner: str         # module that reads it (repo-relative, for docs)


def _b(raw: Optional[str], default: bool) -> bool:
    if raw is None or raw == "":
        return default
    return raw.lower() not in ("0", "false", "")


_PARSERS: Dict[str, Callable[[Optional[str], str], object]] = {
    "int": lambda raw, d: int(raw if raw not in (None, "") else d),
    "float": lambda raw, d: float(raw if raw not in (None, "") else d),
    "bool": lambda raw, d: _b(raw, d not in ("0", "false", "unset", "")),
    "str": lambda raw, d: raw if raw is not None else (
        None if d == "unset" else d),
    "enum": lambda raw, d: raw if raw not in (None, "") else d,
    "json": lambda raw, d: raw if raw is not None else None,
    "path": lambda raw, d: raw if raw not in (None, "") else (
        None if d == "unset" else d),
}

_P = "distributed_llm_inferencing_tpu"

KNOBS = (
    # ---- platform / model loading ------------------------------------
    Knob("DLI_PLATFORM", "unset", "enum",
         "Force the JAX platform (`cpu`/`tpu`); unset takes JAX's "
         "default, and worker/generate/bench refuse a default of cpu.",
         f"{_P}/__init__.py"),
    Knob("DLI_ATTENTION", "auto", "enum",
         "Dense-cache flash kernels of the single-stream engine "
         "(`pallas`/`xla`/`auto`): test/debug override. The batcher "
         "does not read it: its pool read is `transformer._pool_kernel`'s "
         "choice.", f"{_P}/ops/attention.py"),
    Knob("DLI_INT4_PALLAS", "auto", "enum",
         "Int4 fused-unpack Pallas matmul: `1` force, `0` disable, "
         "`auto` = on where supported.", f"{_P}/ops/pallas/quant_matmul.py"),
    Knob("DLI_MLA_LATENT", "1", "bool",
         "MLA latent-KV decode layout on eligible meshes; `0` pins the "
         "materialized layout.", f"{_P}/runtime/engine.py"),
    Knob("DLI_ALLOW_DOWNLOAD", "unset", "bool",
         "`1` lets workers fetch hub checkpoints for non-local model "
         "names.", f"{_P}/models/convert.py"),
    Knob("DLI_MODEL_CACHE", "~/.cache/dli_models", "path",
         "Where opted-in hub downloads land (share via mounted volume "
         "across workers).", f"{_P}/models/convert.py"),
    Knob("DLI_BUNDLE_TIMEOUT", "30", "float",
         "Seconds per fetch for `scripts/collect_debug_bundle.sh` "
         "(each endpoint is best-effort).",
         "scripts/collect_debug_bundle.sh"),
    # ---- decode hot path ---------------------------------------------
    Knob("DLI_SPEC_ADAPTIVE", "1", "bool",
         "Adaptive speculation (acceptance/tok-s-tracked gamma shrink + "
         "plain fallback); `0` pins always-draft.",
         f"{_P}/runtime/engine.py"),
    # ---- control plane (master) --------------------------------------
    Knob("DLI_DISPATCH_WORKERS", "8", "int",
         "Dispatcher threads pumping the claim -> group -> RPC "
         "pipeline.", f"{_P}/runtime/master.py"),
    Knob("DLI_DISPATCH_BATCH", "8", "int",
         "Max requests one claim transaction takes (max sub-requests "
         "per batch RPC).", f"{_P}/runtime/master.py"),
    Knob("DLI_RPC_POOL", "1", "bool",
         "`0` disables per-node keep-alive session pooling entirely "
         "(A/B lever).", f"{_P}/runtime/master.py"),
    Knob("DLI_RPC_POOL_SIZE", "8", "int",
         "Keep-alive connections each per-node `requests.Session` "
         "pools.", f"{_P}/runtime/master.py"),
    Knob("DLI_RPC_CONNECT_TIMEOUT", "5.0", "float",
         "Connect half of the `(connect, read)` RPC timeout tuple.",
         f"{_P}/runtime/master.py"),
    Knob("DLI_BATCH_RPC_MAX", "256", "int",
         "Per-RPC sub-request cap, read by BOTH master (chunks groups) "
         "and worker (400s bigger batches).", f"{_P}/runtime/master.py"),
    Knob("DLI_RETRY_BACKOFF_BASE", "0.5", "float",
         "Base of the exponential retry backoff (seconds), with full "
         "jitter.", f"{_P}/runtime/master.py"),
    Knob("DLI_RETRY_BACKOFF_MAX", "30.0", "float",
         "Ceiling of the exponential retry backoff (seconds).",
         f"{_P}/runtime/master.py"),
    Knob("DLI_STORE_FLUSH_MS", "0", "float",
         "Optional accumulation window per group-commit store flush.",
         f"{_P}/runtime/state.py"),
    Knob("DLI_IDEM_CACHE", "256", "int",
         "Completed-result LRU entries the worker keeps for idempotent "
         "replay of master timeout retries.", f"{_P}/runtime/worker.py"),
    # ---- scheduling ---------------------------------------------------
    Knob("DLI_SCHED_EWMA_ALPHA", "0.2", "float",
         "Smoothing for the per-node completion-latency EWMA "
         "tie-breaker.", f"{_P}/runtime/master.py"),
    Knob("DLI_SCHED_STALE_S", "30.0", "float",
         "Age beyond which worker-reported queue/KV/digest snapshots "
         "stop informing picks.", f"{_P}/runtime/master.py"),
    Knob("DLI_SCHED_PREFIX_WEIGHT", "1.0", "float",
         "Scales the advertised cached-token estimate for affinity "
         "routing; `0` disables affinity.", f"{_P}/runtime/master.py"),
    Knob("DLI_SCHED_PREFIX_SLACK", "2", "int",
         "Load headroom (queue entries) within which prefix affinity "
         "may override the load-based pick.", f"{_P}/runtime/master.py"),
    Knob("DLI_SCHED_ARENA_FULL", "0.9", "float",
         "Arena-occupancy fraction above which prefill picks avoid a "
         "node while an alternative exists.", f"{_P}/runtime/master.py"),
    Knob("DLI_SCHED_SAMPLE", "128", "int",
         "Fleet size above which a pick scores a power-of-d-choices "
         "random sample of this many candidates instead of every node "
         "(per-pick cost stays O(sample) at 1000 nodes; `0` always "
         "scans the full fleet).", f"{_P}/runtime/master.py"),
    Knob("DLI_SCHED_AGING_S", "30", "float",
         "Deadline-style aging for the priority claim: one SLO-class "
         "tier of effective priority per this many seconds of pending "
         "wait, so `batch` cannot starve (`<=0` = pure class "
         "priority).", f"{_P}/runtime/state.py"),
    # ---- overload front door (docs/robustness.md "Overload control") -
    Knob("DLI_ADMIT_RATE", "0", "float",
         "Per-tenant token-bucket refill (admitted submits/s per "
         "`X-DLI-Tenant`); excess gets 429 + Retry-After. `0` disables "
         "bucket admission.", f"{_P}/runtime/master.py"),
    Knob("DLI_ADMIT_BURST", "0", "float",
         "Token-bucket depth (burst headroom) per tenant; `0` = "
         "max(1, rate).", f"{_P}/runtime/master.py"),
    Knob("DLI_ADMIT_MAX_PENDING", "0", "int",
         "Total pending-queue depth cap at admission; past it submits "
         "get 429 with a Retry-After computed from the measured drain "
         "rate. `0` = unbounded.", f"{_P}/runtime/master.py"),
    Knob("DLI_OVERLOAD", "1", "bool",
         "`0` kills the master's overload ladder loop (shedding/"
         "brownout; admission knobs still apply).",
         f"{_P}/runtime/master.py"),
    Knob("DLI_OVERLOAD_INTERVAL_S", "2.0", "float",
         "Seconds between overload-ladder sweeps.",
         f"{_P}/runtime/master.py"),
    Knob("DLI_OVERLOAD_BURN", "1.0", "float",
         "Fast-window burn rate the ladder escalates at (with queue "
         "pressure); `<=0` drops the burn condition (queue-only "
         "ladder).", f"{_P}/runtime/master.py"),
    Knob("DLI_OVERLOAD_QUEUE", "64", "float",
         "Sustained master queue depth the ladder escalates at; "
         "de-escalation needs both signals under half their "
         "thresholds.", f"{_P}/runtime/master.py"),
    Knob("DLI_OVERLOAD_HOLD_S", "10.0", "float",
         "Minimum dwell between ladder transitions (hysteresis) and "
         "the sustained-queue averaging window.",
         f"{_P}/runtime/master.py"),
    Knob("DLI_OVERLOAD_CHUNK_CAP", "8", "int",
         "decode_chunk_cap injected into latency-tier dispatches at "
         "ladder rung 3+ (brownout); `0` skips the cap rung's chunk "
         "action.", f"{_P}/runtime/master.py"),
    Knob("DLI_HTTPD_MAX_INFLIGHT", "0", "int",
         "Bounded in-flight request cap per HTTP service; past it "
         "ingress answers 503 + Retry-After before any handler runs. "
         "`0` = uncapped.", f"{_P}/runtime/httpd.py"),
    # ---- disaggregation / KV transfer --------------------------------
    Knob("DLI_WORKER_ROLE", "mixed", "enum",
         "This worker's pool: `prefill`, `decode`, or `mixed`.",
         f"{_P}/runtime/worker.py"),
    Knob("DLI_DISAGG", "1", "bool",
         "`0` kills the disaggregation policy master-side (roles still "
         "report; routing honors pools).", f"{_P}/runtime/master.py"),
    Knob("DLI_DISAGG_MIN_PROMPT_CHARS", "256", "int",
         "Prompts shorter than this never disaggregate.",
         f"{_P}/runtime/master.py"),
    Knob("DLI_DISAGG_RECOMPUTE_FLOOR_MS", "0", "float",
         "Recompute wins when the learned prefill EWMA prices it below "
         "this floor; `0` = always transfer when pools exist.",
         f"{_P}/runtime/master.py"),
    Knob("DLI_KV_FETCH_MAX_MB", "256", "float",
         "Byte cap on one `/kv_fetch` response (server truncates, "
         "client caps reads).", f"{_P}/runtime/worker.py"),
    Knob("DLI_KV_FETCH_CONCURRENCY", "4", "int",
         "Concurrent peer KV fetches per worker; the excess queues on "
         "a semaphore (`dli_kv_fetch_queued_total`) instead of "
         "thundering-herding one source worker.",
         f"{_P}/runtime/kvwire.py"),
    Knob("DLI_KV_HOST_DTYPE", "native", "enum",
         "Host-arena KV storage: `native` keeps full-precision pages "
         "(bitwise restore), `int8` stores per-(layer, head) symmetric "
         "int8 blocks (~3.9x more prefix tokens per MB, same bytes on "
         "the wire).", f"{_P}/runtime/batcher.py"),
    Knob("DLI_KV_WIRE_OVERLAP", "1", "bool",
         "Receive-overlapped KV restore: device scatter of arrived "
         "blocks overlaps the socket read of the rest; `0` = fetch "
         "fully, then restore.", f"{_P}/runtime/batcher.py"),
    Knob("DLI_KV_WIRE_QUEUE", "4", "int",
         "Decoded-frame queue depth between the KV fetch receiver "
         "thread and the restore consumer (bounds memory while "
         "overlapping).", f"{_P}/runtime/kvwire.py"),
    Knob("DLI_REBALANCE", "1", "bool",
         "`0` kills the master's elastic rebalancer loop (role flips + "
         "live in-flight migration).", f"{_P}/runtime/master.py"),
    Knob("DLI_REBALANCE_INTERVAL_S", "5.0", "float",
         "Seconds between rebalancer sweeps.",
         f"{_P}/runtime/master.py"),
    Knob("DLI_REBALANCE_SUSTAIN_S", "30.0", "float",
         "TSDB window pool-utilization divergence must persist over "
         "before a role flip — and the per-node flip cooldown.",
         f"{_P}/runtime/master.py"),
    Knob("DLI_REBALANCE_RATIO", "3.0", "float",
         "Sustained pool queue-depth divergence factor that triggers a "
         "role flip / hot-node shed.", f"{_P}/runtime/master.py"),
    # ---- prefix-cache tier -------------------------------------------
    Knob("DLI_KV_HOST_MB", "256", "float",
         "Host-RAM KV arena budget per loaded model (MB); `0` disables "
         "the tier.", f"{_P}/runtime/batcher.py"),
    # ---- multi-LoRA adapter serving ----------------------------------
    Knob("DLI_LORA_HOST_MB", "64", "float",
         "Host-RAM budget for the paged LoRA adapter store (MB); LRU "
         "eviction above it, pinned (in-flight) adapters never evict.",
         f"{_P}/models/lora.py"),
    Knob("DLI_LORA_SLOTS", "4", "int",
         "Device adapter slots per batcher wave (slot 0 is always the "
         "base model); distinct adapters beyond this queue at admit.",
         f"{_P}/models/lora.py"),
    Knob("DLI_LORA_MAX_RANK", "16", "int",
         "Largest adapter rank a worker accepts; the batched gathered "
         "pack zero-pads every adapter to one static rank.",
         f"{_P}/models/lora.py"),
    Knob("DLI_PREFIX_DIGEST_CHUNK", "256", "int",
         "Bytes of prompt text per digest-chain link (master and "
         "workers must agree).", f"{_P}/runtime/kvtier.py"),
    Knob("DLI_PREFIX_DIGEST_TOP_K", "32", "int",
         "Distinct prefix chains a worker advertises (recency-bounded).",
         f"{_P}/runtime/kvtier.py"),
    # ---- observability -----------------------------------------------
    Knob("DLI_LOG_LEVEL", "INFO", "enum",
         "Root log level for the `dli.*` loggers.",
         f"{_P}/utils/logging.py"),
    Knob("DLI_LOG_FILE", "unset", "path",
         "Mirror logs to this file in addition to stderr.",
         f"{_P}/utils/logging.py"),
    Knob("DLI_TRACE_SERVICE", "dli", "str",
         "Service name stamped on this process's trace spans.",
         f"{_P}/utils/trace.py"),
    Knob("DLI_PROFILE", "0", "bool",
         "Arm the sampling decode profiler at batcher construction.",
         f"{_P}/utils/profiler.py"),
    Knob("DLI_PROFILE_SAMPLE", "1", "int",
         "Record every Nth batcher step while profiling.",
         f"{_P}/utils/profiler.py"),
    Knob("DLI_PROFILE_CAPACITY", "2048", "int",
         "Bound on the profiler's step-sample ring.",
         f"{_P}/utils/profiler.py"),
    Knob("DLI_TSDB_STEP_S", "5.0", "float",
         "Fine-ring bucket width of the master TSDB (and its scrape "
         "cadence).", f"{_P}/runtime/tsdb.py"),
    Knob("DLI_TSDB_WINDOW_S", "3600.0", "float",
         "Total history window the TSDB retains per series.",
         f"{_P}/runtime/tsdb.py"),
    Knob("DLI_TSDB_MAX_SERIES", "512", "int",
         "Per-node series cap — a buggy worker must not grow master "
         "memory without bound.", f"{_P}/runtime/tsdb.py"),
    Knob("DLI_TSDB_SNAPSHOT_S", "30.0", "float",
         "Seconds between TSDB ring snapshots into the master store "
         "(restored at startup, so series history spans restarts); "
         "`0` disables durability.", f"{_P}/runtime/master.py"),
    Knob("DLI_EVENTS_RING", "2048", "int",
         "Bounded in-memory ring of recent flight-recorder events per "
         "journal.", f"{_P}/runtime/events.py"),
    Knob("DLI_EVENTS_RETAIN", "20000", "int",
         "Rows the durable `events` table retains (oldest pruned on "
         "the journal's cadence).", f"{_P}/runtime/events.py"),
    Knob("DLI_SLO_TTFT_MS", "2000.0", "float",
         "SLO target for TTFT (queue + prefill) per request.",
         f"{_P}/runtime/tsdb.py"),
    Knob("DLI_SLO_ITL_P95_MS", "250.0", "float",
         "SLO target for a request's own p95 inter-token gap.",
         f"{_P}/runtime/tsdb.py"),
    Knob("DLI_SLO_TARGET", "0.99", "float",
         "Attainment objective the error-budget burn rate is computed "
         "against.", f"{_P}/runtime/tsdb.py"),
    # ---- robustness / chaos ------------------------------------------
    Knob("DLI_FAULTS", "unset", "json",
         "JSON fault schedule armed at service construction "
         "(see docs/robustness.md).", f"{_P}/utils/faults.py"),
    Knob("DLI_FAULTS_ENABLE", "unset", "bool",
         "Registers the runtime fault-admin API (`/api/faults`) even "
         "with no schedule armed — a kill switch, keep off in prod.",
         f"{_P}/runtime/httpd.py"),
    Knob("DLI_FAULTS_SEED", "0", "int",
         "Seed for replayable fault schedules.", f"{_P}/utils/faults.py"),
    Knob("DLI_LOCK_CHECK", "0", "bool",
         "Arm the runtime lock-order watchdog: runtime locks become "
         "instrumented wrappers recording per-thread acquisition order "
         "with cycle detection (see docs/static_analysis.md).",
         f"{_P}/utils/locks.py"),
    Knob("DLI_LOCK_HELD_WARN_MS", "5000", "float",
         "Held-too-long threshold for the lock watchdog's reports.",
         f"{_P}/utils/locks.py"),
    Knob("DLI_VERIFY_BUDGET", "20", "float",
         "Wall-clock seconds the `dliverify` interleaving explorer may "
         "spend per run (`scripts/check.sh` step; exploration past the "
         "budget is reported, never silently truncated).",
         "scripts/check.sh"),
    Knob("DLI_VERIFY_MUTATIONS", "unset", "str",
         "TEST-ONLY comma list re-arming historical bugs "
         "(`half_open_probe`, `requeue_exclusion`, `stale_term_check`) "
         "so the dliverify mutation gate can prove the explorer "
         "catches them. Never set in production.",
         f"{_P}/utils/faults.py"),
    # ---- replicated control plane ------------------------------------
    Knob("DLI_HA_PEERS", "unset", "str",
         "Comma list of the OTHER masters' base URLs: arms the "
         "leader-leased replicated control plane (op-log replication "
         "+ automatic failover). Unset = solo master, HA off.",
         f"{_P}/runtime/replication.py"),
    Knob("DLI_HA_ADVERTISE", "unset", "str",
         "Base URL peers/clients reach THIS master at (heartbeat "
         "holder URL + standby 307 redirects). Required for a "
         "multi-host HA pair bound to 0.0.0.0 — a wildcard bind "
         "address is never advertised.",
         f"{_P}/runtime/replication.py"),
    Knob("DLI_HA_LEASE_MS", "3000", "float",
         "Leader lease duration: heartbeats every lease/3; a standby "
         "whose lease deadline expires takes over at term+1.",
         f"{_P}/runtime/replication.py"),
    Knob("DLI_HA_REPL_BARRIER", "0", "bool",
         "Durability barrier: client-visible terminal statuses and "
         "submit acks wait for a standby ack (bounded at 2 lease "
         "intervals, then degrades to leader-only durability with a "
         "journaled `replication-lag` event).",
         f"{_P}/runtime/replication.py"),
    Knob("DLI_HA_REPL_LAG_WARN_MS", "1000", "float",
         "Standby-ack lag behind the op-log head that journals a "
         "`replication-lag` warning (hysteresis: one event per edge).",
         f"{_P}/runtime/replication.py"),
    # ---- auth ---------------------------------------------------------
    Knob("DLI_AUTH_ENABLED", "unset", "bool",
         "`1` enables bearer-token auth on worker endpoints.",
         f"{_P}/runtime/worker.py"),
    Knob("DLI_AUTH_KEY", "unset", "str",
         "Fleet bearer token (workers verify, master presents).",
         f"{_P}/runtime/worker.py"),
    Knob("DLI_MASTER_AUTH_KEY", "unset", "str",
         "Bearer token protecting the master's own API surface.",
         f"{_P}/runtime/master.py"),
    # ---- bench harness ------------------------------------------------
    Knob("DLI_BENCH_BUDGET_S", "2400", "float",
         "Wall-clock budget for one bench invocation.", "bench.py"),
    Knob("DLI_BENCH_PLAN_MIN_X", "1.15", "float",
         "Planner A/B gate: minimum planner-chosen vs naive-uniform "
         "goodput ratio on the heterogeneous fleet.", "bench.py"),
    # ---- cluster simulator (tools/dlisim, docs/simulator.md) ---------
    Knob("DLI_SIM_NODES", "1000", "int",
         "Fleet size for the sim_scale bench gate's headline leg.",
         "bench.py"),
    Knob("DLI_SIM_REQUESTS", "100000", "int",
         "Request count for the sim_scale bench gate's headline leg.",
         "bench.py"),
    Knob("DLI_SIM_SEED", "42", "int",
         "Deterministic seed for every sim_scale/sim_calibrate leg.",
         "bench.py"),
    Knob("DLI_SIM_TOL_GOODPUT", "0.5", "float",
         "Calibration gate: max relative sim-vs-real goodput error.",
         "bench.py"),
    Knob("DLI_SIM_TOL_TTFT", "0.75", "float",
         "Calibration gate: max relative sim-vs-real TTFT p50 error.",
         "bench.py"),
    Knob("DLI_SIM_TOL_QUEUE", "1.0", "float",
         "Calibration gate: max relative sim-vs-real mean queue-depth "
         "error (absolute slack of 3 requests applies near zero).",
         "bench.py"),
    # ---- auto-parallelism planner (parallel/planner.py) --------------
    Knob("DLI_PLANNER_ENABLE", "1", "bool",
         "Master switch for the heterogeneity-aware auto-parallelism "
         "planner: `0` keeps `/api/plans/auto` refusing and the "
         "rebalancer on its divergence heuristic.",
         f"{_P}/parallel/planner.py"),
    Knob("DLI_PLANNER_BUDGET", "128", "int",
         "Search budget: max (mesh x role-split) candidates one "
         "planner search scores.", f"{_P}/parallel/planner.py"),
    Knob("DLI_PLANNER_TOLERANCE", "0.25", "float",
         "Sim-agreement tolerance: the dlisim planner sweep asserts "
         "the planner's top choice reaches >= (1 - tolerance) of the "
         "sim-measured best goodput.", f"{_P}/parallel/planner.py"),
    Knob("DLI_PLANNER_COOLDOWN_S", "300", "float",
         "Re-plan cooldown: `/api/plans/auto` returns the persisted "
         "decision unchanged when it is younger than this (pass "
         "`force` to override).", f"{_P}/runtime/master.py"),
)

_BY_NAME: Dict[str, Knob] = {k.name: k for k in KNOBS}


def registry() -> Dict[str, Knob]:
    """Name -> Knob for the whole fleet."""
    return dict(_BY_NAME)


def names() -> frozenset:
    return frozenset(_BY_NAME)


def get(name: str) -> Knob:
    return _BY_NAME[name]


def value(name: str):
    """Read + parse the live env value of a registered knob. For *new*
    call sites; existing reads keep their point-of-use parse (the
    registry documents, it does not intermediate)."""
    k = _BY_NAME[name]
    raw = os.environ.get(name)
    try:
        return _PARSERS[k.kind](raw, k.default)
    except (TypeError, ValueError):
        return _PARSERS[k.kind](None, k.default)


def markdown_table() -> str:
    """The full generated knob table (one row per registered knob,
    sorted), as embedded in docs/serving.md."""
    rows = ["| Knob | Default | Type | Effect |",
            "| --- | --- | --- | --- |"]
    for k in sorted(KNOBS):
        rows.append(f"| `{k.name}` | `{k.default}` | {k.kind} | {k.doc} "
                    f"*(read in `{k.owner}`)* |")
    return "\n".join(rows)


def generated_block() -> str:
    """Marker-delimited block for docs/serving.md; the dlilint knobs
    checker fails when the committed block != this string."""
    return (f"{DOC_BEGIN}\n\n"
            "This table is generated from `utils/knobs.py` — edit the "
            "registry, then run\n`python -m tools.dlilint "
            "--write-knob-table`. Hand edits here are overwritten\n"
            "and fail the `knobs` checker.\n\n"
            f"{markdown_table()}\n\n{DOC_END}")
