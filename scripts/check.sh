#!/usr/bin/env bash
# Tier-1 gate: byte-compile everything, then run the ROADMAP.md tier-1
# verify command. Later PRs run this in CI (.github/workflows/tier1.yml)
# so "no worse than seed" is checked automatically.
set -uo pipefail

cd "$(dirname "$0")/.."

echo "== compileall =="
python -m compileall -q distributed_llm_inferencing_tpu tests bench.py \
    benchmarks tools || exit 1

echo "== dlilint (repo-native invariant checkers) =="
# AST-checked invariants (docs/static_analysis.md): metrics registered +
# pre-registered at 0, DLI_* knobs in code == utils/knobs.py == docs,
# no host work inside jitted code, no silent except-pass in runtime
# threads, no static lock-order cycles — plus the protocol half
# (dliproto): every master->worker RPC path/method/body-key against the
# route tables, every fault point against a live intercept site, and
# every request-status write against the declared lifecycle machine
# (runtime/lifecycle.py, with the byte-checked diagram in
# docs/robustness.md). Prints per-checker counts; any violation fails
# the build here.
python -m tools.dlilint || exit 1

echo "== dliverify (exhaustive-interleaving model checker) =="
# Deterministic-scheduler exploration of the REAL breaker/idempotency/
# drain/claim code over every thread interleaving of its bounded
# scenarios (docs/static_analysis.md "dliverify"): half-open admits one
# probe, a tag executes once, claims are disjoint, terminal states
# never flip, drain strands nothing, exclusions are honored. The
# mutation gate then re-arms two historical bugs and REQUIRES a
# counterexample trace for each — proving the explorer still catches
# regressions. Seconds-scale; budget per scenario via DLI_VERIFY_BUDGET.
# The outer timeout scales with the budget (10 scenarios + import slack)
# so a raised budget can't be SIGTERMed into a diagnostic-free exit 124
# before the explorer's own INCOMPLETE reporting fires.
VB="${DLI_VERIFY_BUDGET:-20}"
VT=$(python -c "print(int(float('$VB') * 12 + 180))")
timeout -k 10 "$VT" env JAX_PLATFORMS=cpu \
    python -m tools.dliverify --budget "$VB" || exit 1
timeout -k 10 "$VT" env JAX_PLATFORMS=cpu \
    python -m tools.dliverify --mutate half_open_probe --budget "$VB" \
    || exit 1
timeout -k 10 "$VT" env JAX_PLATFORMS=cpu \
    python -m tools.dliverify --mutate requeue_exclusion --budget "$VB" \
    || exit 1
timeout -k 10 "$VT" env JAX_PLATFORMS=cpu \
    python -m tools.dliverify --mutate stale_term_check --budget "$VB" \
    || exit 1

echo "== perf hot-path suite (adaptive speculation) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_adaptive_spec.py \
    -q -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== wave speculation + pallas kernel parity + decode-speed smoke =="
# Wave-level batched speculation (per-slot draft widths, per-request
# controllers — docs/serving.md "Wave-level speculation") and the
# interpret-mode differential suite pinning every pallas kernel — incl.
# the paged pool kernel the decode chunks take by themselves
# (transformer._pool_kernel) — against its XLA oracle; the smoke gates the
# per-slot tokens-per-weight-pass amortization and the single-stream
# spec-vs-plain regression (BENCH_r05's inversion must stay gone)
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_spec_wave.py tests/test_pallas_parity.py \
    -q -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --scenario decode_speed --smoke || exit 1

echo "== control-plane suite + saturation smoke (batched dispatch) =="
# Multiplexed batched dispatch, pooled RPC, queue-aware scheduling
# (docs/serving.md "Control plane"); the smoke drives a live
# master + in-proc worker and gates on zero failures + connection reuse
timeout -k 10 600 env JAX_PLATFORMS=cpu DLI_FAULTS_ENABLE=1 \
    python -m pytest tests/test_dispatch_batch.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --scenario control_plane --smoke || exit 1

echo "== prefix-cache tier suite + shared-prefix smoke (kv offload + affinity) =="
# Host-RAM KV offload arena + prefix-digest advertisement + affinity
# routing (docs/serving.md "Prefix-cache tier"); the smoke drives a live
# master + 2 in-proc workers over a shared-system-prompt workload and
# gates on zero failures + affinity picks + cached-prefill fraction
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_kvtier.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --scenario prefix_cache --smoke || exit 1

echo "== multi-LoRA adapter serving suite + routed smoke =="
# Paged host adapter store, per-slot batched gathered application
# (mixed-adapter waves bitwise vs dedicated batchers), adapter-affinity
# routing with the convoy guard, loud load-failure semantics
# (docs/serving.md "Multi-LoRA adapter serving"); the smoke drives a
# live master + 2 in-proc workers over interleaved base/adapter traffic
# and gates zero failures, lazy dispatch-time loads, affinity picks,
# and the adapter-loaded trail in /api/events (JSON at
# /tmp/dli_bench_multi_lora.json for the CI artifact)
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_lora.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --scenario multi_lora --smoke || exit 1

echo "== disaggregated prefill/decode + KV transfer suite + smoke =="
# Role-split pools, /kv_fetch wire, bitwise transferred-decode, chaos on
# the transfer (docs/architecture.md "Disaggregation"); the smoke drives
# a live master + prefill/decode worker pair and gates on zero failures
# plus at least one real cross-node KV transfer
timeout -k 10 600 env JAX_PLATFORMS=cpu DLI_FAULTS_ENABLE=1 \
    python -m pytest tests/test_disagg.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
# int8 KV tier differential suite: per-(layer, head) quantize/dequant
# bounds, wire-frame corruption rejection, arena byte honesty, and the
# greedy-match gate for decode continued from quantized transferred KV
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_kvblock_quant.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --scenario disagg --smoke || exit 1

echo "== live migration + elastic rebalancing suite + smoke =="
# Mid-generation KV snapshot + bitwise resume, /migrate_out + 303
# handoff, role flips, rebalancer policy (docs/robustness.md "Live
# in-flight migration"); the smoke drives a live master + role-split
# fleet and gates one proactive role flip on a uniform mix plus
# kill-mid-wave recovery with zero lost/duplicated tokens (the bench
# JSON lands at /tmp/dli_bench_rebalance.json for the CI artifact)
timeout -k 10 600 env JAX_PLATFORMS=cpu DLI_FAULTS_ENABLE=1 \
    python -m pytest tests/test_migration.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --scenario rebalance --smoke || exit 1

echo "== replicated control plane suite + kill-the-leader chaos smoke =="
# Leader-leased master pair over op-log replication (docs/robustness.md
# "Replicated control plane"): the suite covers the op-log capture/
# apply path, lease validation, redirects, and the barrier degradation;
# the smoke runs a LIVE 2-master/2-worker fleet, SIGKILLs the leader
# subprocess mid-wave, and gates standby takeover within 2 lease
# intervals, zero lost/duplicated requests (idempotency-tag
# accounting), survivor dashboard reads clean throughout, and the
# takeover reconstructable from the replicated event journal (JSON at
# /tmp/dli_bench_ha.json for the CI artifact; leader subprocess log at
# /tmp/dli_ha_leader.log)
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_ha.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 900 env JAX_PLATFORMS=cpu \
    python bench.py --scenario ha --smoke || exit 1

echo "== telemetry plane + flight recorder (TSDB + cost ledger + SLO + events) =="
# Time-series retention, per-request cost ledger, SLO accounting, decode
# profiler (docs/observability.md "Telemetry plane"), and the flight
# recorder (durable event journal + request journeys + TSDB
# snapshot/restore, docs/observability.md "Flight recorder"); the smoke
# drives a live master + in-proc worker, waits two scrape intervals,
# asserts /api/timeseries serves multi-sample series + the cost ledger
# round-trips + events flow into /api/events + the journey endpoint
# returns a connected timeline, and leaves a debug bundle at
# /tmp/dli_debug_bundle.tar.gz (uploaded as a CI artifact on tier-1
# failure, together with the /tmp/dli_events.json journal export)
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_tsdb.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 900 env JAX_PLATFORMS=cpu DLI_FAULTS_ENABLE=1 \
    python -m pytest tests/test_events.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python scripts/telemetry_smoke.py || exit 1

echo "== cluster observatory (virtual-clock sim: scale + calibration gates) =="
# Trace-calibrated discrete-event simulator (docs/simulator.md): the
# suites pin the clock seam (utils/clock.py) and the sim harness
# (tools/dlisim drives the REAL _pick_node/breaker/Store on a
# VirtualClock); the scale gate pushes 100k requests through a
# 1000-node fleet in <120s wall with a deterministic decision journal
# and sub-linear per-pick cost; the calibration gate replays a live
# smoke run's own arrival trace through the fitted worker model and
# fails on sim-vs-real divergence beyond the documented tolerances
# (artifacts: /tmp/dli_bench_sim.json, /tmp/dli_sim_calibration.json)
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_clock.py tests/test_dlisim.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --scenario sim_scale --smoke || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --scenario sim_calibrate --smoke || exit 1

echo "== overload front door (admission + priority + shedding ladder) =="
# SLO-class admission control, per-tenant token buckets, priority claims
# with anti-starvation aging, and the burn-rate degradation ladder
# (docs/robustness.md "Overload control"); the smoke drives an open-loop
# diurnal storm to ~4x measured capacity against a live master + warm
# in-proc worker and gates honest 429s (Retry-After on every refusal),
# zero admitted failures, a full ladder walk up AND back reconstructable
# from /api/events, then replays the same policy deterministically in
# the virtual-clock sim and asserts the anti-starvation wave bound
# (JSON at /tmp/dli_bench_overload.json for the CI artifact)
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_admission.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 900 env JAX_PLATFORMS=cpu \
    python bench.py --scenario overload --smoke || exit 1

echo "== auto-parallelism planner (cost model + sim sweep + live smoke) =="
# Heterogeneity-aware plan search (docs/architecture.md "Auto-
# parallelism planner"): analytic cost model over fleet-fitted node
# classes, (mesh x role split) enumeration under memory feasibility,
# decision records persisted in the replicated meta table. The sim
# sweep replays a 120-node two-class fleet through tools/dlisim and
# fails if the planner's top choice falls outside DLI_PLANNER_TOLERANCE
# of the sim-measured best split; the smoke drives a live 3-worker
# fleet with one fault-throttled node and gates the full
# decision->persistence->rebalancer-steering path (JSON artifacts:
# /tmp/dli_planner_sweep.json, /tmp/dli_bench_plan.json)
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_planner.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m tools.dlisim --planner-sweep --nodes 120 --requests 2000 \
    --duration 200 --seed 42 --out /tmp/dli_planner_sweep.json || exit 1
timeout -k 10 900 env JAX_PLATFORMS=cpu \
    python bench.py --scenario plan --smoke || exit 1

echo "== chaos suite (fault injection + self-healing dispatch + lock watchdog) =="
# Deterministic fault schedules: a failure here reproduces locally with
#   DLI_FAULTS_SEED=0 JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q
# (see docs/robustness.md for the fault-point spec / runbook)
# DLI_LOCK_CHECK=1 arms the runtime lock-order watchdog (utils/locks.py)
# for the whole chaos run: every runtime lock becomes an instrumented
# wrapper recording per-thread acquisition order, and the conftest
# session gate fails the suite on ANY lock-order cycle — dynamic
# inversions fail the build here, not production.
timeout -k 10 600 env JAX_PLATFORMS=cpu DLI_FAULTS_ENABLE=1 \
    DLI_FAULTS_SEED=0 DLI_LOCK_CHECK=1 \
    python -m pytest tests/test_chaos.py tests/test_node_lifecycle.py \
    tests/test_locks.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== tier-1 tests (ROADMAP.md verify command) =="
# (the chaos/lifecycle and perf hot-path suites already ran above —
#  skipped here so check.sh doesn't pay for them twice; the bare ROADMAP
#  command still collects them)
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly \
    --ignore=tests/test_chaos.py --ignore=tests/test_node_lifecycle.py \
    --ignore=tests/test_locks.py \
    --ignore=tests/test_adaptive_spec.py \
    --ignore=tests/test_spec_wave.py \
    --ignore=tests/test_pallas_parity.py \
    --ignore=tests/test_dispatch_batch.py \
    --ignore=tests/test_kvtier.py \
    --ignore=tests/test_lora.py \
    --ignore=tests/test_disagg.py \
    --ignore=tests/test_kvblock_quant.py \
    --ignore=tests/test_migration.py \
    --ignore=tests/test_tsdb.py \
    --ignore=tests/test_events.py \
    --ignore=tests/test_ha.py \
    --ignore=tests/test_clock.py \
    --ignore=tests/test_dlisim.py \
    --ignore=tests/test_admission.py \
    --ignore=tests/test_planner.py \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
exit $rc
