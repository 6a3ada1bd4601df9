"""Continuous batching over the paged KV cache.

The reference served one prompt per blocking HTTP request, fully serialized
per worker (1 gunicorn sync worker, reference: worker/Dockerfile:47,
worker/app.py:252-330). The engine (runtime/engine.py) batches only within
one ``generate`` call. This scheduler is the serving-native upgrade: a
fixed pool of decode *slots* advances every active request together,
admitting queued requests into freed slots mid-flight — in-flight
batching, so short and long generations share the chip without
head-of-line blocking.

Two dispatch-amortization levers keep the host off the critical path (a
host round trip per dispatch is costly next to a decode step):

- **Chunked decode**: each scheduler step launches ONE program that runs
  up to K decode iterations on device (models/transformer.py
  paged_decode_chunk) with per-slot budget/eos lifecycle as data. The
  host syncs once per K tokens, and admission/growth/preemption decisions
  happen at chunk boundaries (growth blocks for the whole chunk are
  pre-allocated before dispatch).
- **Wave admission**: queued requests are admitted in waves — one batched
  tail-prefill program per (tail, prefix) bucket with first-token
  sampling fused in, so a burst of N requests costs 1-2 dispatches of
  TTFT, not 2N.

Memory is paged (ops/paged_kvcache.py): which HBM blocks each sequence
owns is decided host-side by the native C++ allocator
(native/src/block_pool.cc), whose radix tree lets requests with a shared
prompt prefix reuse already-prefilled blocks — admission then prefills
only the tail. Under memory pressure the youngest slot is preempted back
to the queue (its prefix stays warm in the radix cache, so the re-run is
mostly a cache hit).

Per-request sampling params ride the jitted programs as data
(ops/sampling.py sample_batch), so one compiled program serves any mix of
greedy/temperature/top-k/top-p requests.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inferencing_tpu.models import lora as lora_mod
from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.config import ModelConfig
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.native import BlockPool
from distributed_llm_inferencing_tpu.ops import kvblock_quant as kvq
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    flat_pool, flat_rows, head_rows, init_paged_cache, window_columns)
from distributed_llm_inferencing_tpu.ops.sampling import (
    PREFIX_K, SamplingParams, sample_batch)
from distributed_llm_inferencing_tpu.parallel import sharding as shd
from distributed_llm_inferencing_tpu.parallel.mesh import (
    MeshSpec, create_mesh, validate_spec)
from distributed_llm_inferencing_tpu.runtime import events
from distributed_llm_inferencing_tpu.runtime import kvtier as kvtier_mod
from distributed_llm_inferencing_tpu.runtime import kvwire as kvwire_mod
from distributed_llm_inferencing_tpu.runtime import tsdb as tsdb_mod
from distributed_llm_inferencing_tpu.utils import clock, locks, trace
from distributed_llm_inferencing_tpu.utils.metrics import Metrics
from distributed_llm_inferencing_tpu.utils.profiler import (
    PhaseProfiler, call_deltas, call_readings, mark_imported)

log = logging.getLogger("dli.batcher")

TAIL_BUCKETS_X_BS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)  # × block_size
PREFIX_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512)  # blocks
# Most score elements a head that one admission program may hold: rows x
# tail x (prefix + tail), as the program is bucketed. paged_attend_prefix
# holds every head's scores in float32 (at 32 heads the budget is 2.2
# GB; 16 rows of a 512 tail over a 512-block prefix would be 9 GB), so
# rows past it wait for the next step (_collect_wave). The budget is the
# widest wave 64 slots form over no cached prefix, 64 rows of a 512 tail
# and the one dummy prefix block; over 512 blocks of 16 it leaves 2 rows.
WAVE_SCORE_BUDGET = 64 * 512 * (512 + 16)
WAVE_SCORE_HEADS = 32   # ... the head count it was sized at
# ... and, for a model with a per-slot cache (state layers, ring
# layers), the bytes of per-token transients one admission program may
# hold at its widest point (_wave_token_budget: the MLP's gate, up and
# product rows, or the chunked scan's projections and float32 rows):
# Falcon-H1-34B's 129 KB a token make it 4,096 tokens a wave, 0.5 GB,
# where the score budget alone would let 32,768 through (4.2 GB beside
# 12.9 GB of arguments); MiMo-V2.5's dense layer (16,384 wide: 96 KB a
# token) 6,826, so 2 rows of 2048 / 4 of 1024 / 8 of 512 / 16 of 256.
WAVE_TRANSIENT_BYTES = 640 * 1024 * 1024


def _asked_host_mb(kv_host_mb: Optional[float]) -> float:
    """The host arena a caller asked for, by keyword or DLI_KV_HOST_MB
    (0: none asked): what a model whose slots hold a cache of their own
    refuses above 0 (the arena moves blocks alone)."""
    if kv_host_mb is not None:
        return kv_host_mb
    try:
        return float(os.environ.get("DLI_KV_HOST_MB", 0))
    except ValueError:
        return 0


def _wave_score_budget(cfg: ModelConfig) -> float:
    """Most score elements a head one admission program may hold: the
    bound is in bytes (heads x 4 B x elements), so a model of more than
    WAVE_SCORE_HEADS query heads takes fewer elements a head
    (MiMo-V2.5's 64: half). The models it was sized with keep
    WAVE_SCORE_BUDGET."""
    return WAVE_SCORE_BUDGET * min(1.0, WAVE_SCORE_HEADS / cfg.num_heads)


def _wave_token_budget(cfg: ModelConfig) -> float:
    """Most tokens (rows x tail, as bucketed) one admission program may
    carry, from the widest per-token transient of the MLP and of the
    chunked scan. A model without a per-slot cache has no such bound:
    its waves are cut by the score budget alone, as they were."""
    if not cfg.slot_cache:
        return float("inf")
    item = jnp.dtype(cfg.dtype).itemsize
    widest = 3 * cfg.intermediate_size * item
    if cfg.ssm is not None:
        c = cfg.ssm
        widest = max(widest, (c.proj_dim + 2 * c.conv_dim) * item
                     + 4 * (c.conv_dim + 3 * c.d_ssm
                            + 2 * c.chunk_size * c.n_heads))
    return WAVE_TRANSIENT_BYTES // widest


@dataclasses.dataclass
class BatchRequest:
    """One queued/active generation. The handle the caller waits on."""
    prompt: List[int]
    max_new_tokens: int
    sampling: SamplingParams
    eos_token_id: Optional[int] = None
    stream_cb: Optional[Callable[[int], None]] = None
    seed: int = 0    # output is a pure fn of (params, prompt, seed)
    # results
    tokens: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    # timing
    submitted_at: float = dataclasses.field(default_factory=clock.now)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # cost ledger: when the FIRST admission wave carrying this request
    # started dispatching — queue_ms = admitted_at - submitted_at, and
    # queue + prefill + decode sum exactly to the e2e span
    admitted_at: Optional[float] = None
    # span id of that wave's batcher.admit_wave (joins batcher.queued)
    _wave_span: Optional[str] = None
    # the finished record (phase ms + resource counts), built once in
    # _observe_finished; the worker attaches it to the response payload
    cost: Optional[dict] = None
    # submitter's trace context (utils/trace.py SpanCtx): the scheduler
    # runs in its own thread, so the link to the originating HTTP request
    # rides the request object instead of a contextvar
    trace_ctx: Optional[object] = None
    _last_emit_at: Optional[float] = None
    # the scheduler's phase clocks and its stalls' sum when the first
    # token was emitted: read again at finish, the differences split
    # decode_ms by where it went (_decode_account)
    _clocks0: Optional[tuple] = None
    # internal scheduling state
    _blocks: List[int] = dataclasses.field(default_factory=list)
    _preemptions: int = 0
    _cancelled: bool = False
    # chunked-prefill progress: high-water of cached+chunk across partial
    # passes, and how many passes failed to advance it (radix eviction
    # between chunks can undo progress — bounded, or two pool-sized
    # prompts could re-prefill each other's evictions forever)
    _chunk_high: int = 0
    _chunk_stalls: int = 0
    # prompt extent already counted into the prefill cached/uncached
    # metrics: a resumed chunk pass (or a preemption re-admission)
    # re-matches this request's OWN earlier blocks, which must not be
    # reported as cross-request cache wins
    _prefill_counted: int = 0
    # set when a no-free-slot pop found the request non-partial (its long
    # prompt is mostly radix-cached): skip re-popping it — and the
    # match_prefix + alloc churn that costs — until a slot frees
    _noslot_bounce: bool = False
    # state layers (cfg.ssm): the slot a chunked prompt took at its
    # first chunk and keeps between chunks, its state in that slot's row
    # (None: holds none); its blocks so far are _blocks, its positions
    # so far _prefill_counted
    _held_slot: Optional[int] = None
    # Disaggregated prefill/decode (runtime/kvwire.py): where to pull
    # missing prefix KV from ({"url": peer base URL, "model": name} — the
    # master's kv_source dispatch hint), and whether to export this
    # request's prompt KV into the host arena at finish so a decode peer
    # can fetch it. One peer RPC per request, success or not.
    kv_source: Optional[dict] = None
    kv_export: bool = False
    _peer_fetch_done: bool = False
    _kv_transfer_bytes: int = 0
    # Multi-LoRA serving (models/lora.py): the adapter this request's
    # tokens run through (None = base weights) and the device-pack slot
    # its wave rows gather (0 = base; assigned at admission prep and
    # stable while the adapter's refcount pins the slot). The refcount
    # is taken at submit and released exactly once at the terminal
    # accounting point (_observe_finished).
    adapter: Optional[str] = None
    _lora_slot: int = 0
    _lora_released: bool = False
    # Per-request decode-chunk ceiling (master brownout rung 3 sends
    # body["decode_chunk_cap"] on latency-class dispatches — see
    # runtime/master.py _infer_body and docs/robustness.md "Overload
    # control"). 0 = uncapped. While a capped request is active it
    # clamps the WHOLE wave's chunk choice in _step_inner: shorter
    # slices reach scheduling boundaries sooner, which is the point.
    chunk_cap: int = 0
    # Live in-flight migration (docs/robustness.md "Live migration"):
    # _migrate_requested asks the scheduler to snapshot+evict this
    # request at the next chunk boundary (migrate_out blocks on done);
    # resume_record is the JSON-safe handoff — emitted tokens, seed,
    # sampler position, spec-controller state — a destination batcher
    # resumes from bitwise-exactly; _migrated marks the terminal
    # "handed off" outcome (distinct from failed in every account).
    _migrate_requested: bool = False
    _migrated: bool = False
    resume_record: Optional[dict] = None
    # cost-ledger accumulators (freed with the request)
    _gaps: List[float] = dataclasses.field(default_factory=list)
    _cost_cached: int = 0       # prompt tokens served from cache tiers
    _cost_uncached: int = 0     # prompt tokens actually prefilled
    _weight_passes: int = 0     # decode iterations this request rode
    _kv_peak: int = 0           # peak device KV blocks owned at once
    _arena_restored_bytes: int = 0
    _arena_offloaded_bytes: int = 0
    _spec_acc: int = 0          # draft tokens accepted beyond 1/iteration
    _spec_rej: int = 0          # draft tokens rejected by verification
    _spec_drafted: int = 0      # draft tokens proposed for this request
    # wave-level speculation: this request's OWN drafting controller
    # (ops/speculative.py AdaptiveSpecController) — created lazily at its
    # first speculative chunk, surviving preemption/re-admission so a
    # request's acceptance history follows it across slots
    _spec_ctl: Optional[object] = None

    def wait(self, timeout: Optional[float] = None) -> List[int]:
        if not self.done.wait(timeout):
            raise TimeoutError("generation still running")
        if self.error:
            raise RuntimeError(self.error)
        return self.tokens

    def cancel(self):
        """Ask the scheduler to drop this request (frees its slot/blocks at
        the next chunk boundary; already-generated tokens are kept)."""
        self._cancelled = True

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return (self.first_token_at - self.submitted_at) * 1e3

    @property
    def latency_ms(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return (self.finished_at - self.submitted_at) * 1e3


class ContinuousBatcher:
    """Slot-based continuous batching scheduler.

    One jitted program per step; the model may be mesh-sharded. Tensor /
    expert parallelism (tp/ep) ride GSPMD — params and the paged cache
    carry NamedShardings and XLA partitions the step's matmuls/attention
    over ICI. Pipeline parallelism (pp > 1) swaps the decode-chunk and
    admission programs for GPipe-scheduled shard_map versions
    (parallel/paged_pipeline.py) with slots as the microbatch dimension
    and the paged pool's layer axis sharded per stage — the serving path
    for models too big for one slice's tp×ep. Batch-dim parallelism (dp)
    and sequence sharding (sp) are rejected: the slot scheduler owns the
    batch dimension, and decode chunks never span one sequence.

    Drive it either with an owned background thread (``start()``/``stop()``)
    or synchronously via ``step()`` (tests, custom loops).
    """

    # Decode-chunk sizes (tokens per dispatched program), tried in order.
    # Each step picks the largest chunk some active slot can fill; per-slot
    # budget/eos masks handle slots that finish mid-chunk. Mirrors the
    # engine's DECODE_CHUNKS trade (one shared schedule — a tuning there
    # is a tuning here): bigger chunks amortize dispatch RTT, at the cost
    # of chunk-granularity admission/cancellation latency.
    from distributed_llm_inferencing_tpu.runtime.engine import (
        InferenceEngine as _Eng)
    DECODE_CHUNKS = _Eng.DECODE_CHUNKS
    del _Eng
    # A dispatch round trip can cost several decode steps of compute,
    # so rounding the chunk UP past the largest
    # remaining budget (budget masks make overshoot steps dead compute)
    # is a win as long as the overshoot stays small.
    CHUNK_OVERSHOOT_MAX = 8
    # Stall accounting (batcher_stall_{program,host}_ms; fixed, no knob).
    # A chunk stalled when its wall per pass is over twice the running
    # mean of earlier chunks of its size: live slots, context and
    # arrival rate move a pass by a few percent (PERF.md section 5), so
    # a doubling is the device or its runtime standing still, not load.
    # What it took over that mean is what was lost, and is counted.
    STALL_PROGRAM_FACTOR = 2.0
    # ... judged once this many chunks of that size were seen (a
    # program's first use, which compiles or reads the compile cache, is
    # not one: the call's label says so, _note_program)
    STALL_MIN_CHUNKS = 4
    # A busy step's host part (wall outside program calls) is a few
    # milliseconds for 16 slots; 100 ms is over ten times that, and
    # two thirds of a decode pass on the v5e: a stream reader sees it.
    STALL_HOST_BOUND_S = 0.100
    # Either loss counts from 50 ms on: under it, it is the scheduling
    # noise of a shared host and moves no end-to-end metric.
    STALL_FLOOR_S = 0.050
    # Why it stood still (``cause`` on the event and the span,
    # batcher_stall_cause_<cause>_ms): one fixed rule over what the
    # process did during the stalled call (or step, for a host stall),
    # each test against HALF of what was lost, so that whatever is named
    # accounts for most of the loss, in this order because each later
    # test is only meaningful once the earlier ones failed:
    #   gc                     the cycle collector ran that long (it
    #                          holds the interpreter, so it would also
    #                          read as a late heartbeat);
    #   interpreter_held       the heartbeat woke that late while the
    #                          process burned CPU: some thread ran and
    #                          kept the interpreter from the others;
    #   descheduled            the heartbeat woke that late and nothing
    #                          burned CPU: the whole process did not run
    #                          (a frozen or starved container);
    #   host_runtime_busy      the heartbeat was on time and the process
    #                          burned CPU: threads that need no
    #                          interpreter worked (the runtime's own);
    #   device_or_runtime_wait every thread idle, heartbeat on time: the
    #                          device ran long or the runtime waited on
    #                          it (``memory`` and, where a trace runs,
    #                          its XLA Modules line decide which);
    #   thread_blocked         the same in a host stall, where no program
    #                          was awaited: the scheduler thread waited
    #                          in a host bracket (a stream callback, a
    #                          lock).
    STALL_CAUSES = ("gc", "interpreter_held", "descheduled",
                    "host_runtime_busy", "device_or_runtime_wait",
                    "thread_blocked")
    # A program's first use (utils/profiler.py, the program account):
    # how many, what their trace, lowering, load (the compile cache's
    # read and deserialize on a hit, XLA's compile on a miss) and first
    # run took, and what the cache answered
    PROGRAM_COUNTERS = ("batcher_programs_first_use",
                        "batcher_program_trace_ms",
                        "batcher_program_lower_ms",
                        "batcher_program_load_ms",
                        "batcher_program_first_run_ms",
                        "batcher_program_cache_hits",
                        "batcher_program_cache_misses")

    @staticmethod
    def _stall_cause(where: str, lost_ms: float, did: dict) -> str:
        half = lost_ms / 2
        if did["gc_ms"] >= half:
            return "gc"
        burned = did["process_cpu_ms"] >= half
        if did["heartbeat_late_ms"] >= half:
            return "interpreter_held" if burned else "descheduled"
        if burned:
            return "host_runtime_busy"
        return ("device_or_runtime_wait" if where == "program"
                else "thread_blocked")

    def __init__(self, cfg: ModelConfig, params=None, *,
                 num_blocks: int = 512, block_size: int = 16,
                 slots: int = 8, max_seq: Optional[int] = None,
                 seed: int = 0, force_python_pool: bool = False,
                 mesh_spec: Optional[MeshSpec] = None,
                 prefill_chunk: Optional[int] = 32,
                 decode_chunk_cap: Optional[int] = None,
                 speculative: Optional[str] = None, spec_gamma: int = 4,
                 spec_adaptive: Optional[bool] = None,
                 kv_host_mb: Optional[float] = None,
                 kv_digest_chunk: Optional[int] = None,
                 kv_fetcher=None,
                 metrics: Optional[Metrics] = None):
        # this step loop's phase clocks, always on, its opt-in sampling
        # profiler (utils/profiler.py; DLI_PROFILE=1 or worker POST
        # /api/profile) and its program account, which the build below
        # is the first entry of
        self.profiler = PhaseProfiler.from_env()
        # shared with the worker's registry when serving (so /metrics
        # carries the scheduler's gauges/histograms); owned otherwise
        self.metrics = metrics or Metrics()
        self.mesh_spec = mesh_spec or MeshSpec()
        for ax in ("dp", "sp"):
            if getattr(self.mesh_spec, ax) > 1:
                raise ValueError(
                    f"batched serving shards tensors (tp/ep) and pipeline "
                    f"stages (pp); {ax}={getattr(self.mesh_spec, ax)} "
                    "unsupported (the slot scheduler owns the batch dim)")
        if self.mesh_spec.pp > 1:
            # pipeline-parallel serving (parallel/paged_pipeline.py):
            # slots microbatch over pp inside one GPipe-scheduled program
            # (speculative chunks included — the draft/acceptance state
            # rides the ppermute ring, paged_speculative_chunk_pp)
            slots = -(-slots // self.mesh_spec.pp) * self.mesh_spec.pp
        if cfg.mla:
            # an MLA model's pool is latent (one shared row a token a
            # layer, ops/paged_kvcache.py) and decode is the absorbed
            # form; what has no such path is refused here by name, not
            # served from a materialized pool 21 times the size
            refused = [why for why, hit in (
                ("kv_quant (the latent row is the compressed form)",
                 cfg.kv_quant is not None),
                ("pp > 1 (parallel/paged_pipeline.py carries K and V "
                 "planes)", self.mesh_spec.pp > 1),
                ("speculative decoding (paged_speculative_chunk carries "
                 "K and V side buffers)", bool(speculative)),
                ("sliding windows or score softcapping (the absorbed "
                 "form threads neither)",
                 cfg.sliding_window is not None
                 or cfg.attn_windows is not None
                 or cfg.attn_softcap is not None))
                if hit]
            if refused:
                raise ValueError(
                    f"{cfg.name}: MLA serves from the latent paged pool, "
                    "which cannot take " + "; ".join(refused))
        if cfg.loop_steps > 1:
            # a looped model's stack runs loop_steps times a pass over
            # one set of weights, a K and V plane a (step, layer) pair
            # (transformer.loop_layer_stack); what does not carry the
            # loop is refused here by name, not run for one step
            refused = [why for why, hit in (
                ("speculative decoding (paged_speculative_chunk verifies "
                 "through one pass of the stack)", bool(speculative)),
                ("pp > 1 (parallel/paged_pipeline.py's stages own one "
                 "pass's layer slices of the pool)", self.mesh_spec.pp > 1))
                if hit]
            if refused:
                raise ValueError(
                    f"{cfg.name}: a looped stack (loop_steps="
                    f"{cfg.loop_steps}) cannot take " + "; ".join(refused))
        if cfg.ssm is not None:
            # state layers (a Mamba-2 mixer a block, ops/ssm.py) keep a
            # recurrent state and a conv window a serving slot beside the
            # block pool (ops/paged_kvcache.py); what does not carry
            # them is refused here by name, not served without a state
            host_mb = _asked_host_mb(kv_host_mb)
            refused = [why for why, hit in (
                ("speculative decoding (a rejected draft's state cannot "
                 "be rolled back)", bool(speculative)),
                ("pp > 1 or any mesh of more than one device "
                 "(parallel/paged_pipeline.py and sharding.py have no "
                 "rule for the state plane)",
                 self.mesh_spec.num_devices > 1),
                ("kv_quant (the state is float32 and is not quantized)",
                 cfg.kv_quant is not None),
                ("kv_host_mb > 0 / DLI_KV_HOST_MB (the host arena, "
                 "kvwire fetches and migrate_out move K and V blocks, "
                 "no state)", host_mb > 0))
                if hit]
            if refused:
                raise ValueError(
                    f"{cfg.name}: state-space layers cannot take "
                    + "; ".join(refused))
            kv_host_mb = 0   # unset: no arena for this model
        if cfg.swa is not None:
            # layer kinds (MiMo-V2): the windowed layers' K and V lie in
            # a ring a serving slot beside the pool, which holds the full
            # layers alone (ops/paged_kvcache.py); what carries no ring
            # is refused here by name, not served from half a cache
            host_mb = _asked_host_mb(kv_host_mb)
            refused = [why for why, hit in (
                ("speculative decoding (paged_speculative_chunk carries "
                 "one kind of side buffer and no ring)", bool(speculative)),
                ("pp > 1 or any mesh of more than one device "
                 "(parallel/paged_pipeline.py and sharding.py have no "
                 "rule for the ring or for a share of the experts)",
                 self.mesh_spec.num_devices > 1),
                ("kv_quant (pool and ring are not quantized)",
                 cfg.kv_quant is not None),
                ("kv_host_mb > 0 / DLI_KV_HOST_MB (the host arena, "
                 "kvwire fetches and migrate_out move the pool's blocks, "
                 "no ring)", host_mb > 0))
                if hit]
            if refused:
                raise ValueError(
                    f"{cfg.name}: windowed layers in a per-slot ring "
                    "cannot take " + "; ".join(refused))
            kv_host_mb = 0   # unset: no arena for this model
        self.cfg = cfg = cfg.replace(
            # the paged programs read no attention backend (the dense
            # cache's flash kernels, ops/attention.py): whatever was
            # asked for, stats() reports the XLA form they run beside
            # the pool kernel
            attn_backend="xla",
            # int4 pallas routing hint (models/config.py): this GSPMD
            # program din-shards o/down over tp, and the kernel's
            # partition rule would all-gather those shards every step
            tp_row_sharded=self.mesh_spec.tp > 1,
            mla_latent_cache=cfg.mla,
            # the experts' decode-sized grouped matmuls
            # (models/transformer.py _expert_stream): a Pallas call,
            # which GSPMD does not partition
            expert_matmul=_expert_backend(self.mesh_spec.num_devices),
            # ... and the decode chunk's read of the pool
            # (transformer._pool_kernel), by the same rule
            pool_kernel=_expert_backend(self.mesh_spec.num_devices))
        validate_spec(self.mesh_spec, cfg)
        self.mesh = create_mesh(self.mesh_spec)
        self.block_size = block_size
        self.slots = slots
        self.max_seq = min(max_seq or cfg.max_position_embeddings,
                           cfg.max_position_embeddings)
        self.max_blocks = -(-self.max_seq // block_size)
        # Chunked prefill (vLLM-style): prompts whose un-cached tail
        # exceeds this many blocks admit one chunk per step — KV lands in
        # the radix cache, the request requeues, and the next wave's
        # prefix match resumes exactly where the chunk ended. Bounds how
        # long one huge prompt can stall co-running decode. None/0
        # disables; snapped to a tail bucket so chunk programs hit the
        # same compile cache as ordinary admissions.
        if prefill_chunk:
            self.prefill_chunk = next(
                (m for m in TAIL_BUCKETS_X_BS if m >= prefill_chunk),
                TAIL_BUCKETS_X_BS[-1])
        else:
            self.prefill_chunk = None
        self._chunked_admissions = 0
        # Decode-chunk cap (latency-tier knob): bigger chunks amortize
        # dispatch RTT, but a K-token chunk also delivers its tokens as
        # one K-sized burst — a latency-tier model (or an ITL-measuring
        # bench) caps the chunk so inter-token gaps track real steps.
        self._decode_chunk_cap = (int(decode_chunk_cap)
                                  if decode_chunk_cap else None)
        # Speculative decoding (models/transformer.py
        # paged_speculative_chunk): on-device prompt-lookup drafts, up to
        # spec_gamma+1 tokens per slot per iteration. Greedy requests get
        # the speedup with bit-identical output; sampling requests run
        # one exact token per iteration (no speedup, no distribution
        # drift).
        if speculative not in (None, "ngram"):
            raise ValueError(f"unknown speculative mode {speculative!r}")
        self.speculative = speculative
        self.spec_gamma = int(spec_gamma)
        self._spec_accepted = 0
        # Adaptive drafting (ops/speculative.py AdaptiveSpecController):
        # gamma shrinks / drafting auto-falls-back to plain chunks when
        # measured acceptance or tok/s says drafting loses, with periodic
        # re-probes — "speculative=ngram" must never be slower than off.
        # Default on; DLI_SPEC_ADAPTIVE=0 pins the always-draft behavior
        # (A/B and the fixed-gamma parity tests).
        if spec_adaptive is None:
            spec_adaptive = os.environ.get(
                "DLI_SPEC_ADAPTIVE", "1") not in ("0", "false")
        self._spec_adaptive = bool(spec_adaptive)
        # Wave-level speculation: ONE shared verify pass serves the whole
        # active wave with PER-SLOT draft widths as data — each request
        # carries its own AdaptiveSpecController (BatchRequest._spec_ctl),
        # so a draft-hostile request converges to width 0 and rides the
        # wave's verify pass as plain decode while its draft-friendly
        # chunk-mates keep their speedup (no wave-wide fallback cliff).
        self._spec_wave_dispatches = 0
        # Cross-request arbitration state: measured spec / plain tok/s
        # and the probe clocks are HOST+WORKLOAD properties,
        # not per-request ones — a fresh request's controller seeds from
        # them (and starts in plain mode when the fleet measurements say
        # drafting loses), so short generations inherit the fleet's
        # verdict instead of each re-paying the discovery cost.
        # Acceptance windows, gamma and MODE transitions stay
        # per-request: one draft-hostile request still can't drag its
        # chunk-mates off the speculative path.
        self._wave_shared = {"spec_tps": None, "plain_tps": None,
                             "since_plain_probe": 0, "since_probe": 0}
        # register the headline gauge + wave counters at 0 up front so a
        # scrape (and the TSDB catalog behind it) can't confuse "no
        # decode yet" with "metric not exported" — PR 5's radix-counter
        # rule applied to the amortization plane
        self.metrics.gauge("decode_tokens_per_weight_pass", 0.0)
        # the dashboard's TSDB panel charts these from the first scrape;
        # without pre-registration the series is invisible until the
        # first submit/step (dlilint metric-not-preregistered)
        self.metrics.gauge("batcher_queue_depth", 0.0)
        self.metrics.gauge("batcher_free_kv_blocks", 0.0)
        # live-migration handoffs (distinct from failed in every
        # account); registered at 0 so a scrape can't confuse "no
        # migrations yet" with "metric not exported"
        self.metrics.inc("batcher_requests_migrated", 0)
        # stall counters (milliseconds the scheduler stood still, by
        # side); alert on dli_batcher_stall_*_ms_total
        self.metrics.inc("batcher_stall_program_ms", 0)
        self.metrics.inc("batcher_stall_host_ms", 0)
        for cause in self.STALL_CAUSES:   # ... and by cause (_stall_cause)
            self.metrics.inc(f"batcher_stall_cause_{cause}_ms", 0)
        # programs first used (compiled, or read from the compile cache)
        # and what that took (_report_first_use)
        for name in self.PROGRAM_COUNTERS:
            self.metrics.inc(name, 0)
        self._stall_lost_s = 0.0  # both sides' sum: a request reads it twice
        self._wave_count = 0      # admit programs run (`wave` on their spans)
        self._pool_positions = 0  # the last decode chunk's (_run_decode)
        self._window_positions = 0   # ... and what a windowed layer read
        self._pool_kernel = None  # whether they read it by the kernel
        self._wave_cut = None   # (tail, prefix) group the bound last cut
        self._wave_token_budget = _wave_token_budget(cfg)
        # state layers: slots that chunked prompts hold between chunks
        self._holds: set = set()
        # admission waves cut short by WAVE_SCORE_BUDGET
        self.metrics.inc("batcher_admit_waves_bounded", 0)
        # traversals of the layer stack by decode passes: loop_steps a
        # weight pass (batcher_weight_passes stays one a decode pass)
        self.metrics.inc("batcher_stack_passes", 0)
        self._pass_mean = {}      # (kind, k) -> [mean wall per pass, n]
        self._step_program_s = 0.0   # this step's wall inside programs
        if speculative:
            for name in ("spec_wave_dispatches", "spec_wave_drafted_tokens",
                         "spec_wave_accepted_tokens",
                         "spec_wave_plain_rides"):
                self.metrics.inc(name, 0)
        # device-drafting token history, maintained incrementally (a
        # per-step rebuild would be O(slots * max_seq) host work on the
        # hot path): row i holds slot i's prompt + emitted tokens
        self._hist = (np.zeros((slots, self.max_seq + 1), np.int32)
                      if speculative else None)
        # lockstep-mirror watermark: how many leading entries of each hist
        # row the followers hold (spec dispatches broadcast only the
        # per-slot delta past it — the appends themselves are derived from
        # the replayed program's outputs on both sides)
        self._hist_synced = (np.zeros((slots,), np.int64)
                             if speculative else None)
        with self.profiler.program("build", "weights") as built:
            if params is None:
                params = init_params(cfg, jax.random.PRNGKey(seed))
            else:
                from distributed_llm_inferencing_tpu.ops.quant import (
                    maybe_quantize, maybe_quantize_embed)
                params = maybe_quantize_embed(maybe_quantize(params, cfg),
                                              cfg)
            with self.mesh:
                self.params = shd.shard_params(params, self.mesh, cfg,
                                               self.mesh_spec)
                del params     # or the stacked leaves below live on in it
                if cfg.is_moe and self.mesh_spec.pp == 1:
                    # (a model with layer kinds: each kind's MoE stack)
                    for name in ("layers", "layers_full"):
                        if name in self.params:
                            self.params[name] = _unstack_layers(
                                self.params.pop(name))
            # (the host's wall: what the device still owes of the
            # weights is not waited for, and lands in the first program's
            # ``run_ms``)
            built.attrs["bytes"] = _tree_bytes(self.params)

        # +1: block 0 is the reserved dummy every inactive table entry
        # points at, so it never carries real KV
        self.pool = BlockPool(num_blocks + 1, block_size,
                              force_python=force_python_pool)
        [self._dummy] = self.pool.alloc(1)
        # overwrite the 0 pre-registration with the truth now the pool
        # exists — a scrape between construction and the first step must
        # not read "0 free blocks" as exhaustion
        self.metrics.gauge("batcher_free_kv_blocks", self.pool.free_count())
        with self.profiler.program("build", "pool") as built:
            self.paged = jax.device_put(
                init_paged_cache(cfg, num_blocks + 1, block_size,
                                 slots=slots,
                                 devices=self.mesh_spec.num_devices),
                shd.named(self.mesh,
                          shd.paged_cache_specs(cfg, self.mesh_spec)))
            built.attrs["bytes"] = _tree_bytes(self.paged)
        # a one-device pool of few K/V heads stores a position's heads in
        # one row (ops/paged_kvcache.heads_in_rows); the host arena, the
        # wire and migration keep a block by heads, [L, bs, Hkv, w],
        # whatever the device's form (_host_pages, _run_restore): an int8
        # arena's scales stay a head's, and peers of either form
        # exchange blocks. (A model with layer kinds has no arena.)
        self._flat_heads = ((cfg.num_kv_heads, cfg.head_dim)
                            if cfg.swa is None and flat_pool(cfg, self.paged)
                            else None)
        # state layers: what a slot holds beside its blocks (0 for a
        # model without them), true prompt positions through the chunked
        # scan, and live slots x decode passes through the one-step update
        self._state_bytes_per_slot = self.paged.state_bytes_per_slot
        self.metrics.gauge("batcher_ssm_state_bytes_per_slot",
                           float(self._state_bytes_per_slot))
        self.metrics.inc("batcher_ssm_scan_positions", 0)
        self.metrics.inc("batcher_ssm_step_slot_passes", 0)
        # ring layers: what a slot's ring takes (0 for a model without
        # one), and the ring positions a windowed layer read, a slot,
        # summed over decode passes (beside batcher_decode_pool_positions)
        self._ring_bytes_per_slot = self.paged.ring_bytes_per_slot
        self.metrics.gauge("batcher_kv_ring_bytes_per_slot",
                           float(self._ring_bytes_per_slot))
        self.metrics.inc("batcher_decode_ring_positions", 0)
        # what one cached token takes of the pool, from the pool's own
        # shape (a latent pool: L x lane_width(rd + r) x 2 bytes)
        self.metrics.gauge("batcher_kv_bytes_per_token",
                           float(self.paged.bytes_per_token))
        if cfg.is_moe:
            # (experts_held: layer passes x the experts this program
            # holds; rows_away stays 0 where it holds them all)
            for name in transformer.MOE_STATS + ("experts_held",):
                self.metrics.inc(f"batcher_moe_{name}", 0)
        self.block_tables = np.full((slots, self.max_blocks), self._dummy,
                                    np.int32)
        # Host-RAM KV offload tier (runtime/kvtier.py): radix-evicted
        # blocks copy their device KV pages into a bounded, content-keyed
        # host arena; admission restores matching blocks with one scatter
        # instead of re-prefilling. DLI_KV_HOST_MB (or the kv_host_mb
        # kwarg) sizes the arena; 0 disables the tier — advertisement
        # included (docs/serving.md "Prefix-cache tier").
        if kv_host_mb is None:
            try:
                kv_host_mb = float(os.environ.get(
                    "DLI_KV_HOST_MB", kvtier_mod.DEFAULT_HOST_MB))
            except ValueError:
                kv_host_mb = kvtier_mod.DEFAULT_HOST_MB
        # Arena storage dtype (ops/kvblock_quant.py): "native" keeps the
        # exact device bytes (bitwise restore), "int8" packs ~3.9x more
        # prefix tokens per MB and ships ~3.9x fewer wire bytes, at a
        # bounded dequant error per restored block.
        kv_dtype = os.environ.get("DLI_KV_HOST_DTYPE", "native")
        if kv_dtype not in kvtier_mod.HOST_DTYPES:
            kv_dtype = "native"
        self.kvtier = (kvtier_mod.KVTier(
            block_size, kv_host_mb,
            digest_chunk=kv_digest_chunk or kvtier_mod.DIGEST_CHUNK,
            dtype=kv_dtype)
            if kv_host_mb and kv_host_mb > 0 else None)
        if self.kvtier is not None:
            self.pool.set_evict_hook(self._offload_evicted)
        # Cross-node KV transfer (runtime/kvwire.py): the worker injects
        # its shared KVFetchClient (pooled peer sessions, fault point,
        # conn accounting in the worker registry); a standalone batcher
        # builds its own lazily at the first kv_source admission.
        self.kv_fetcher = kv_fetcher
        # Receive-overlapped restore (DLI_KV_WIRE_OVERLAP, default on):
        # peer fetches stream through kvwire.FetchStream so the device
        # scatter of block N overlaps the receive of block N+1; 0 falls
        # back to the serial fetch-then-scatter path.
        self._wire_overlap = os.environ.get(
            "DLI_KV_WIRE_OVERLAP", "1") not in ("0", "false", "no", "")
        # Single-flight prefetch registry: concurrent fetches to the
        # same (peer, model) — shared-prefix fan-in, a dying node's mass
        # drain — coalesce onto one leader transfer with the digest
        # union deduped; waiters block on the leader's round and find
        # the blocks arena-resident.
        self._kvf_lock = locks.lock("batcher.kvfetch")
        self._kvf_inflight: Dict[tuple, dict] = {}
        if self.kvtier is not None:
            # pre-register the transfer plane at 0 (PR 5 rule): the TSDB
            # catalog and a first scrape must see the counters exist
            for name in ("kv_transfer_blocks", "kv_transfer_bytes",
                         "kv_transfer_ms", "kv_transfer_failures",
                         "kvtier_exported_blocks",
                         "kv_prefetch_coalesced"):
                self.metrics.inc(name, 0)
            self.metrics.gauge("kv_restore_overlap_ratio", 0.0)
        self._restore_fns = {}        # restore-scatter jits per row bucket
        self._last_pool_stats = {}    # radix counter -> metrics delta base
        # cost-ledger attribution: the request whose admission prep is
        # currently allocating (scheduler-thread-local by construction) —
        # arena offloads triggered by ITS alloc bill to it
        self._admitting: Optional[BatchRequest] = None
        # declarative SLO targets (runtime/tsdb.py): used worker-side
        # only to flag SLO-violating requests for trace tail-retention
        self._slo_targets = tsdb_mod.slo_targets()
        # Multi-LoRA serving (models/lora.py): a bounded host adapter
        # tier (LRU by bytes, DLI_LORA_HOST_MB) feeding DLI_LORA_SLOTS
        # device pack slots (+ reserved slot 0 = base). Loading or
        # evicting an adapter rebuilds the stacked device pack DATA —
        # shapes are static in (slots, max_rank), so adapter mixes
        # never recompile. Refcounts pin a slotted adapter while any
        # submitted request still references it.
        self._lora_lock = locks.lock("batcher.lora")
        self._lora_store = lora_mod.LoRAHostStore()
        self._lora_max_rank = lora_mod.max_rank_from_env()
        self._lora_slot_names: List[Optional[str]] = \
            [None] * (lora_mod.slots_from_env() + 1)
        self._lora_refs: Dict[str, int] = {}
        self._lora_last_use: Dict[str, int] = {}
        self._lora_seq = 0
        self._params_lora = None   # params tree + layers["lora"] pack
        # pre-register the adapter plane at 0 (PR 5 rule): the TSDB
        # catalog and a first scrape must see the series exist before
        # the first load/submit
        self.metrics.gauge("lora_host_bytes", 0.0)
        self.metrics.gauge("lora_host_adapters", 0.0)
        for name in ("lora_loads", "lora_evictions", "lora_load_failures",
                     "lora_requests"):
            self.metrics.inc(name, 0)
        self.context_lens = np.zeros((slots,), np.int32)
        self.active: List[Optional[BatchRequest]] = [None] * slots
        self._admit_order: collections.deque = collections.deque()  # slot ids

        self.queue: collections.deque = collections.deque()
        self._lock = locks.lock("batcher.state")
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._step_count = 0
        self._tokens_out = 0

        self._prefill_fns = {}   # (tail, prefix, wave) -> compiled admit
        self._decode_fns = {}    # chunk k -> compiled decode chunk

        # Multi-host seam (runtime/multihost.py): when set, every device
        # program this scheduler launches is routed through
        # ``program_hook(kind, payload, run)`` — the lockstep leader
        # broadcasts (kind, payload) to follower hosts, which ``replay()``
        # the identical program, then calls ``run()`` in sequence order.
        # The *scheduling decisions* stay leader-local; only their compiled
        # consequences are replicated, so followers need no pool/queue.
        # Chunked decode + wave admission make this one broadcast per K
        # tokens / per admission wave, not per token (round-2's per-token
        # mirror was the multi-host throughput ceiling).
        self.program_hook = None
        self._record_build()

    @property
    def decode_chunks(self):
        """DECODE_CHUNKS filtered by the instance's decode_chunk_cap —
        a live view (tests override DECODE_CHUNKS per instance)."""
        if self._decode_chunk_cap is None:
            return self.DECODE_CHUNKS
        return tuple(c for c in self.DECODE_CHUNKS
                     if c <= self._decode_chunk_cap) \
            or (min(self.DECODE_CHUNKS),)

    # ---- public API ---------------------------------------------------

    def _make_request(self, prompt: Sequence[int], max_new_tokens: int = 100,
                      sampling: Optional[SamplingParams] = None,
                      eos_token_id: Optional[int] = None,
                      stream_cb: Optional[Callable[[int], None]] = None,
                      seed: Optional[int] = None,
                      kv_source: Optional[dict] = None,
                      kv_export: bool = False,
                      kv_transfer_bytes: int = 0,
                      resume: Optional[dict] = None,
                      trace_ctx=None,
                      chunk_cap: Optional[int] = None,
                      adapter: Optional[str] = None) -> BatchRequest:
        """Validate and build one BatchRequest WITHOUT enqueueing it —
        submit()/submit_many() construct first so a bad spec can never
        leave siblings half-enqueued."""
        if not prompt:
            raise ValueError("empty prompt")
        if isinstance(resume, dict) and resume.get("adapter"):
            # a migrated-in request keeps its source adapter: serving
            # the continuation on base weights would silently change
            # the model mid-stream
            adapter = str(resume["adapter"])
        if isinstance(resume, dict) and resume.get("seed") is not None:
            # a live-migration resume MUST keep the source's seed: the
            # position-keyed PRNG ((seed, steps) per emitted position)
            # is what makes the continued sampled stream draw the same
            # tokens the unmigrated run would have
            seed = int(resume["seed"])
        if seed is None:
            seed = time.time_ns() % (1 << 31)
        req = BatchRequest(prompt=list(map(int, prompt)),
                           max_new_tokens=int(max_new_tokens),
                           sampling=sampling or SamplingParams(),
                           eos_token_id=eos_token_id, stream_cb=stream_cb,
                           seed=int(seed),
                           kv_source=(kv_source if isinstance(kv_source,
                                                              dict)
                                      else None),
                           kv_export=bool(kv_export),
                           adapter=(str(adapter) if adapter else None),
                           chunk_cap=max(0, int(chunk_cap or 0)),
                           # explicit ctx for callers submitting from a
                           # helper thread (SSE streams), ambient otherwise
                           trace_ctx=trace_ctx or trace.current())
        # cost-ledger seed for a submit-time prefetch (the worker pulls
        # the peer KV on its handler thread, then attributes here)
        req._kv_transfer_bytes = int(kv_transfer_bytes or 0)
        if isinstance(resume, dict) and resume.get("tokens"):
            # live-migration resume: pre-seed the emitted tokens. They
            # are never re-emitted (no _emit pass, so the stream
            # callback fires only for NEW tokens — zero duplicates) and
            # admission prefills prompt+tokens exactly like a
            # preemption re-admission, so the continuation is bitwise
            # the unmigrated run's tail.
            req.tokens = [int(t) for t in resume["tokens"]]
            if len(req.tokens) >= req.max_new_tokens:
                raise ValueError(
                    f"resume record carries {len(req.tokens)} emitted "
                    f"tokens >= max_new_tokens {req.max_new_tokens} — "
                    "the source should have completed, not migrated")
            spec_state = resume.get("spec")
            if (spec_state and self.speculative
                    and self._spec_adaptive and self.spec_gamma >= 1):
                from distributed_llm_inferencing_tpu.ops.speculative \
                    import AdaptiveSpecController
                # request-owned policy state (gamma/mode/acceptance)
                # migrates; throughput EMAs re-seed from THIS host's
                # shared arbitration state — they measure the host
                ctl = self._seed_wave_ctl(
                    AdaptiveSpecController(self.spec_gamma))
                ctl.load_state(spec_state)
                req._spec_ctl = ctl
        if len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({len(req.prompt)}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_seq {self.max_seq}")
        if req.adapter:
            # LAST validation: pinning is the only step with a side
            # effect, so an earlier raise can never leak a refcount
            self._pin_lora(req.adapter)   # ValueError when not loaded
            self.metrics.inc("lora_requests")
        return req

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 100,
               sampling: Optional[SamplingParams] = None,
               eos_token_id: Optional[int] = None,
               stream_cb: Optional[Callable[[int], None]] = None,
               seed: Optional[int] = None,
               kv_source: Optional[dict] = None,
               kv_export: bool = False,
               kv_transfer_bytes: int = 0,
               resume: Optional[dict] = None,
               trace_ctx=None,
               chunk_cap: Optional[int] = None,
               adapter: Optional[str] = None) -> BatchRequest:
        req = self._make_request(prompt, max_new_tokens, sampling,
                                 eos_token_id, stream_cb, seed,
                                 kv_source, kv_export, kv_transfer_bytes,
                                 resume, trace_ctx, chunk_cap=chunk_cap,
                                 adapter=adapter)
        with self._lock:
            self.queue.append(req)
            depth = len(self.queue)
        self.metrics.inc("batcher_requests_submitted")
        self.metrics.gauge("batcher_queue_depth", depth)
        self._work.set()
        return req

    def submit_many(self, specs: Sequence[dict]) -> List[BatchRequest]:
        """Multi-submit entry for batched RPC dispatch (the worker's
        ``/inference_batch`` handler): validate and build every request
        FIRST (all-or-nothing — a ValueError enqueues nothing), then
        append them under ONE lock acquisition with one scheduler wake,
        preserving the caller's order end-to-end. One master dispatch
        batch therefore admits FIFO, exactly as submitted."""
        reqs: List[BatchRequest] = []
        try:
            for spec in specs:
                reqs.append(self._make_request(**spec))
        except Exception:
            # all-or-nothing: drop the adapter refcounts the already-
            # built siblings pinned, or a failing batch would pin its
            # adapters forever
            for r in reqs:
                self._release_lora(r)
            raise
        if not reqs:
            return []
        with self._lock:
            self.queue.extend(reqs)
            depth = len(self.queue)
        self.metrics.inc("batcher_requests_submitted", len(reqs))
        self.metrics.gauge("batcher_queue_depth", depth)
        self._work.set()
        return reqs

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="batcher")
            self.profiler.set_serving(True)
            self._thread.start()

    def stop(self):
        """Stop the loop and fail every in-flight/queued request, so no
        client blocks until its timeout on an unloading worker."""
        self._stop.set()
        self._work.set()
        if self._thread:
            self._thread.join(timeout=30)
            self._thread = None
        self.profiler.set_serving(False)
        for slot in range(self.slots):
            req = self.active[slot]
            if req is not None:
                req.error = req.error or "scheduler stopped"
                self._finish_slot(slot)
        with self._lock:
            drained = list(self.queue)
            self.queue.clear()
        for req in drained:
            self._fail_req(req, "scheduler stopped")

    def inflight(self) -> int:
        """Requests the scheduler still owes an answer (active slots +
        queue) — what a graceful drain waits on (runtime/worker.py
        _wait_idle polls this alongside its own handler count)."""
        with self._lock:
            queued = len(self.queue)
        return sum(a is not None for a in self.active) + queued

    def stats(self) -> dict:
        return {
            "slots": self.slots,
            "mesh": self.mesh_spec.axis_sizes(),
            # the backend pinned at construction ("xla"), and the
            # kernels that would run in pallas interpret mode — only
            # ever by request (tests); chip_smoke.py refuses a load that
            # lists any
            "attn_backend": self.cfg.attn_backend,
            "interpreted_kernels": (
                ["int4_matmul"]
                if os.environ.get("DLI_INT4_PALLAS") == "interpret" else []),
            "active": sum(a is not None for a in self.active),
            "queued": len(self.queue),
            "steps": self._step_count,
            "tokens_out": self._tokens_out,
            "block_size": self.block_size,
            "blocks_free": self.pool.free_count(),
            "chunk_sizes": sorted({key[0] for key in self._decode_fns
                                   if not isinstance(key[0], str)}),
            "chunked_admissions": self._chunked_admissions,
            "prefill_chunk": self.prefill_chunk,
            "speculative": self.speculative,
            "spec_accepted_tokens": self._spec_accepted,
            "spec_wave": self._spec_wave_stats(),
            "pool": self.pool.stats(),
            # host KV tier + routing advertisement (runtime/kvtier.py):
            # the digests ride the worker's /health body into the
            # master's per-node runtime snapshot; state.py strips them
            # from the PERSISTED node row (ephemeral routing state)
            "kvtier": (self.kvtier.stats()
                       if self.kvtier is not None else None),
            "prefix_digests": (self.kvtier.index.advertise()
                               if self.kvtier is not None else None),
            # resident-adapter advertisement: rides the worker's /health
            # body into the master's runtime snapshot the same way the
            # prefix digests do, feeding adapter-affinity routing
            "adapters": self.lora_stats(),
        }

    def _spec_wave_stats(self) -> Optional[dict]:
        """Aggregate view of wave-level speculation: per-request
        controllers live on the requests (BatchRequest._spec_ctl), so
        the batcher-level summary counts ACTIVE requests' modes/widths —
        the live width mix a scraper sees, not lifetime history."""
        if not self.speculative:
            return None
        ctls = [a._spec_ctl for a in self.active
                if a is not None and a._spec_ctl is not None]
        return {
            "dispatches": self._spec_wave_dispatches,
            "active_controllers": len(ctls),
            "drafting": sum(c.mode == "spec" for c in ctls),
            "plain": sum(c.mode == "plain" for c in ctls),
            "fallbacks": sum(c.fallbacks for c in ctls),
            "gamma_mean": (round(float(np.mean([c.gamma for c in ctls])),
                                 2) if ctls else None),
        }

    # ---- multi-LoRA adapters (models/lora.py) -------------------------

    def load_adapter(self, name: str, source: str) -> dict:
        """Make an adapter host-resident (worker ``POST /load_adapter``
        and the master's lazy dispatch-time load land here). Device slot
        assignment is deferred to the first admission that needs it.
        Idempotent for an already-resident name. Returns
        ``{name, rank, nbytes, evicted}`` — the caller emits the
        adapter-loaded / adapter-evicted events. ValueError on any
        problem (bad source, shape mismatch, store full of pinned
        adapters) — the request path NEVER falls back to base weights."""
        if self.mesh_spec.pp > 1:
            raise ValueError(
                "LoRA serving does not support pp > 1 (the pipelined "
                "chunk programs re-stage layers without the delta pack)")
        lora_mod.validate_base_model(self.cfg)
        with self._lora_lock:
            ad = self._lora_store.get(name)
            evicted: List[str] = []
            if ad is None:
                try:
                    ad = lora_mod.resolve(self.cfg, name, source,
                                          max_rank=self._lora_max_rank)
                    pinned = {n for n, c in self._lora_refs.items() if c}
                    evicted = self._lora_store.put(ad, pinned=pinned)
                except ValueError:
                    self.metrics.inc("lora_load_failures")
                    raise
                self.metrics.inc("lora_loads")
                self.metrics.inc("lora_evictions", len(evicted))
                # a host-evicted adapter cannot back a device slot: clear
                # its slot (refcount 0 by the pinned set) and rebuild
                dirty = False
                for i in range(1, len(self._lora_slot_names)):
                    if self._lora_slot_names[i] in evicted:
                        self._lora_slot_names[i] = None
                        dirty = True
                if dirty:
                    self._rebuild_lora_pack()
            self._gauge_lora()
            return {"name": ad.name, "rank": ad.rank, "nbytes": ad.nbytes,
                    "evicted": evicted}

    def unload_adapter(self, name: str) -> bool:
        """Drop an adapter from the host store and its device slot.
        Refuses (ValueError) while live requests reference it."""
        with self._lora_lock:
            if self._lora_refs.get(name, 0):
                raise ValueError(
                    f"adapter {name!r} has live requests; drain first")
            dirty = False
            for i in range(1, len(self._lora_slot_names)):
                if self._lora_slot_names[i] == name:
                    self._lora_slot_names[i] = None
                    dirty = True
            dropped = self._lora_store.drop(name)
            if dirty:
                self._rebuild_lora_pack()
            self._gauge_lora()
            return dropped

    def lora_stats(self) -> dict:
        with self._lora_lock:
            return {
                "resident": sorted(self._lora_store.names()),
                "slotted": [n for n in self._lora_slot_names[1:] if n],
                "slots": len(self._lora_slot_names) - 1,
                "host": self._lora_store.stats(),
                "active_refs": {n: c for n, c in self._lora_refs.items()
                                if c},
            }

    def _gauge_lora(self):
        st = self._lora_store.stats()
        self.metrics.gauge("lora_host_bytes", st["bytes"])
        self.metrics.gauge("lora_host_adapters", st["adapters"])

    def _pin_lora(self, name: str):
        """Submit-time refcount: pins the adapter against host eviction
        (and its slot, once assigned, against slot reuse) from the
        moment the request exists. ValueError when not host-resident —
        an unknown adapter is the caller's structured 400."""
        if self.program_hook is not None:
            raise ValueError(
                "LoRA adapters cannot ride multi-host lockstep serving "
                "(followers hold no adapter store to replay against)")
        with self._lora_lock:
            if self._lora_store.get(name) is None:
                raise ValueError(
                    f"unknown adapter {name!r} (POST /load_adapter first)")
            self._lora_refs[name] = self._lora_refs.get(name, 0) + 1

    def _release_lora(self, req: BatchRequest):
        """Exactly-once refcount release at the terminal accounting
        point (_observe_finished serves every outcome: finished, failed,
        migrated). The slot itself stays resident for affinity reuse —
        only slot pressure from a new adapter reclaims it."""
        if not req.adapter or req._lora_released:
            return
        req._lora_released = True
        with self._lora_lock:
            n = self._lora_refs.get(req.adapter, 0)
            if n > 1:
                self._lora_refs[req.adapter] = n - 1
            else:
                self._lora_refs.pop(req.adapter, None)

    def _assign_lora_slot(self, name: str) -> int:
        """Bind an adapter to a device pack slot at admission prep.
        Reuses the existing slot (refcounts keep it stable while any
        request references it), else takes a free slot, else evicts the
        least-recently-used refcount-0 slot. All pinned -> ValueError
        (the admission path fails the request with a clear error)."""
        with self._lora_lock:
            ad = self._lora_store.get(name)
            if ad is None:
                raise ValueError(
                    f"adapter {name!r} evicted from the host store "
                    "before admission (DLI_LORA_HOST_MB)")
            names = self._lora_slot_names
            if name in names:
                s = names.index(name)
            else:
                free = [i for i in range(1, len(names))
                        if names[i] is None]
                if free:
                    s = free[0]
                else:
                    idle = [i for i in range(1, len(names))
                            if not self._lora_refs.get(names[i], 0)]
                    if not idle:
                        raise ValueError(
                            f"adapter {name!r}: all {len(names) - 1} "
                            "device adapter slots are pinned by live "
                            "requests (DLI_LORA_SLOTS)")
                    s = min(idle, key=lambda i: self._lora_last_use.get(
                        names[i], 0))
                    self.metrics.inc("lora_evictions")
                names[s] = name
                self._rebuild_lora_pack()
            self._lora_seq += 1
            self._lora_last_use[name] = self._lora_seq
            return s

    def _rebuild_lora_pack(self):
        """Re-stack the device pack from the current slot assignment and
        swap the lora params tree. Shapes depend only on (slots,
        max_rank) — every rebuild hits the same compiled programs.
        Caller holds _lora_lock."""
        slot_ads = [None] + [
            (self._lora_store.peek(n) if n else None)
            for n in self._lora_slot_names[1:]]
        pack = lora_mod.build_pack(self.cfg, slot_ads, self._lora_max_rank)
        with self.mesh:
            pack_dev = jax.tree_util.tree_map(jnp.asarray, pack)
        p = dict(self.params)
        p["layers"] = dict(self.params["layers"], lora=pack_dev)
        self._params_lora = p

    # ---- compiled steps ----------------------------------------------

    # Args cross host->device as TWO packed arrays (int32 + f32) per
    # dispatch, unpacked on device: every eager transfer is a host round
    # trip of its own, and 13 tiny arrays per chunk cost more than the
    # chunk itself.

    def _admit_jit(self, t: int, pb: int, b: int, use_lora: bool = False):
        """Wave-admission program: batched tail prefill + fused first-token
        sampling — one dispatch per (tail-bucket, prefix-bucket) group.
        ``use_lora`` variants append per-row adapter slot ids to the ints
        pack and gather the rank-r delta per row (ops/lora.py); base
        waves keep the base program — a zero-cost skip, not a masked
        delta."""
        key = (t, pb, b, use_lora)
        fn = self._prefill_fns.get(key)
        if fn is None:
            cfg = self.cfg
            nb = t // self.block_size
            pp, mesh, dummy = self.mesh_spec.pp, self.mesh, self._dummy

            def admit(p, ints, floats, paged):
                toks = ints[:b * t].reshape(b, t)
                tb = ints[b * t:b * (t + nb)].reshape(b, nb)
                pfb = ints[b * (t + nb):b * (t + nb + pb)].reshape(b, pb)
                rest = ints[b * (t + nb + pb):]
                kw = {}
                if cfg.slot_cache:
                    # state layers, ring layers: each row's serving slot
                    # rides last
                    rest, kw["slots"] = rest[:-b], rest[-b:]
                if use_lora:
                    tl, pfl, seeds, steps, tks, ds, aids = \
                        rest.reshape(7, b)
                else:
                    tl, pfl, seeds, steps, tks, ds = rest.reshape(6, b)
                    aids = None
                temps, tps = floats
                if pp > 1:
                    from distributed_llm_inferencing_tpu.parallel import (
                        paged_pipeline)
                    last, paged = paged_pipeline.paged_prefill_tail_pp(
                        p, cfg, toks, tl, tb, pfb, pfl, paged, dummy,
                        mesh=mesh)
                else:
                    last, paged = transformer.paged_prefill_tail(
                        p, cfg, toks, tl, tb, pfb, pfl, paged,
                        lora_ids=aids, logits_as_computed=True, **kw)
                with jax.named_scope("sample"):
                    first = sample_batch(last, seeds, steps, temps, tks,
                                         tps, ds.astype(bool))
                return first, paged

            fn = jax.jit(admit, donate_argnums=(3,))
            self._prefill_fns[key] = fn
        return fn

    def _decode_jit(self, k: int, r: int, mb: int, use_lora: bool = False):
        """K-token decode chunk (transformer.paged_decode_chunk), one host
        sync per K tokens for all slots. ``use_lora`` variants append
        per-slot adapter ids to the ints pack."""
        fn = self._decode_fns.get((k, r, mb, use_lora))
        if fn is None:
            cfg, dummy = self.cfg, self._dummy
            pp, mesh = self.mesh_spec.pp, self.mesh

            def chunk(p, tokens, ints, floats, paged):
                bt = ints[:r * mb].reshape(r, mb)
                if use_lora:
                    (cl, seeds, steps0, tks, budget, eos_ids, ds,
                     aids) = ints[r * mb:].reshape(8, r)
                else:
                    (cl, seeds, steps0, tks, budget, eos_ids,
                     ds) = ints[r * mb:].reshape(7, r)
                    aids = None
                temps, tps = floats
                if pp > 1:
                    from distributed_llm_inferencing_tpu.parallel import (
                        paged_pipeline)
                    toks, emits, paged = paged_pipeline.paged_decode_chunk_pp(
                        p, cfg, k, tokens, paged, bt, cl, seeds, steps0,
                        temps, tks, tps, ds.astype(bool), budget, eos_ids,
                        dummy, mesh=mesh)
                    # the pipelined chunk counts no expert loads and
                    # attends every slot's whole block table
                    return toks, emits, jnp.zeros(
                        (len(transformer.MOE_STATS),), jnp.int32), \
                        jnp.int32(mb * paged.block_size), paged
                toks, emits, moe, pool_pos, win_pos, paged = \
                    transformer.paged_decode_chunk(
                        p, cfg, k, tokens, paged, bt, cl, seeds, steps0,
                        temps, tks, tps, ds.astype(bool), budget, eos_ids,
                        dummy, lora_ids=aids)
                if cfg.attn_windows is not None or cfg.swa is not None:
                    # [pool, window]; a model of one kind keeps the
                    # program it had
                    pool_pos = jnp.stack([pool_pos, win_pos])
                return toks, emits, moe, pool_pos, paged

            fn = jax.jit(chunk, donate_argnums=(4,))
            self._decode_fns[(k, r, mb, use_lora)] = fn
        return fn

    def _spec_jit(self, k: int, g: int, r: int, mb: int, hh: int,
                  use_lora: bool = False):
        """K speculative verify iterations
        (transformer.paged_speculative_chunk): up to (g+1)K tokens per
        slot per host sync. ``g`` is the compiled STATIC maximum draft
        width; the per-slot effective widths ride the ints pack as data
        (wave-level speculation), so one compiled program serves every
        width mix the per-request controllers produce. ``use_lora``
        variants append per-slot adapter ids after the widths."""
        key = ("spec", k, g, r, mb, hh, use_lora)
        fn = self._decode_fns.get(key)
        if fn is None:
            cfg, dummy = self.cfg, self._dummy
            pp, mesh = self.mesh_spec.pp, self.mesh

            def chunk(p, ints, floats, paged):
                bt = ints[:r * mb].reshape(r, mb)
                hist = ints[r * mb:r * (mb + hh)].reshape(r, hh)
                rest = ints[r * (mb + hh):]
                if use_lora:
                    (tokens, cl, seeds, steps0, tks, budget, eos_ids,
                     ds, gammas, aids) = rest.reshape(10, r)
                else:
                    (tokens, cl, seeds, steps0, tks, budget, eos_ids,
                     ds, gammas) = rest.reshape(9, r)
                    aids = None
                temps, tps = floats
                if pp > 1:
                    from distributed_llm_inferencing_tpu.parallel import (
                        paged_pipeline)
                    return paged_pipeline.paged_speculative_chunk_pp(
                        p, cfg, k, g, tokens, hist, paged, bt, cl, seeds,
                        steps0, temps, tks, tps, ds.astype(bool), budget,
                        eos_ids, dummy, gammas=gammas, mesh=mesh)
                return transformer.paged_speculative_chunk(
                    p, cfg, k, g, tokens, hist, paged, bt, cl, seeds,
                    steps0, temps, tks, tps, ds.astype(bool), budget,
                    eos_ids, dummy, gammas=gammas, lora_ids=aids)

            fn = jax.jit(chunk, donate_argnums=(3,))
            self._decode_fns[key] = fn
        return fn

    def warm_decode_programs(self) -> int:
        """AOT-compile (jit.lower().compile()) every decode-chunk program
        this scheduler can dispatch — the plain chunk per DECODE_CHUNKS
        size and, with speculation, each distinct ceil(k/(gamma+1))
        verify variant — and install the compiled executables in the
        program cache.

        A speculative trajectory's chunk-size sequence is
        acceptance-dependent, so workload warmup cannot cover the
        program space: a late-appearing tail variant then pays its XLA
        compile inside a measured window (or a live request's ITL).
        Bench legs call this after their admission warmup; serving can
        call it at model-load time. Returns the number of programs
        compiled. No-op for programs already warm (AOT executables feed
        the persistent compilation cache, so repeat processes pay
        deserialization, not compilation)."""
        r, mb = self.slots, self.max_blocks
        paged_sds = jax.tree_util.tree_map(
            lambda a: (None if a is None else
                       jax.ShapeDtypeStruct(a.shape, a.dtype)),
            self.paged)
        floats = jax.ShapeDtypeStruct((2, r), jnp.float32)
        toks = jax.ShapeDtypeStruct((r,), jnp.int32)
        n = 0
        with self.mesh:
            for k in self.decode_chunks:
                fn = self._decode_jit(k, r, mb)
                if hasattr(fn, "lower"):   # not yet AOT-compiled
                    ints = jax.ShapeDtypeStruct((r * (mb + 7),), jnp.int32)
                    with self.profiler.program("chunk", k, aot=True):
                        self._decode_fns[(k, r, mb, False)] = fn.lower(
                            self.params, toks, ints, floats,
                            paged_sds).compile()
                    n += 1
                if not (self.speculative and self.spec_gamma >= 1):
                    continue
                g, hh = self.spec_gamma, self._hist.shape[1]
                k_it = -(-k // (g + 1))
                sfn = self._spec_jit(k_it, g, r, mb, hh)
                if hasattr(sfn, "lower"):
                    ints = jax.ShapeDtypeStruct(
                        (r * (mb + hh + 9),), jnp.int32)
                    with self.profiler.program("spec", (k_it, g), aot=True):
                        self._decode_fns[("spec", k_it, g, r, mb, hh,
                                          False)] = \
                            sfn.lower(self.params, ints, floats,
                                      paged_sds).compile()
                    n += 1
        self._report_first_use()
        return n

    # ---- program launch (shared by the scheduler and lockstep replay) --

    def _run_admit(self, a: dict) -> np.ndarray:
        """Launch one admission wave's program from a JSON-safe arg dict.
        Pure device-program execution: no scheduler state is read, so a
        follower replaying the leader's args evolves its cache shard
        bit-identically. Returns first tokens [B]."""
        toks = np.asarray(a["toks"], np.int32)
        tb = np.asarray(a["tail_alloc"], np.int32)
        pfb = np.asarray(a["pfb"], np.int32)
        b = toks.shape[0]
        use_lora = "aids" in a
        ints = np.concatenate([
            toks.reshape(-1), tb.reshape(-1), pfb.reshape(-1),
            np.asarray(a["tail_len"], np.int32),
            np.asarray(a["cached"], np.int32),
            np.asarray(a["seeds"], np.int32),
            np.asarray(a["steps"], np.int32),
            np.asarray(a["tks"], np.int32),
            np.asarray(a["ds"], np.int32)] + (
            [np.asarray(a["aids"], np.int32)] if use_lora else []) + (
            [np.asarray(a["slots"], np.int32)] if "slots" in a else []))
        floats = np.stack([np.asarray(a["temps"], np.float32),
                           np.asarray(a["tps"], np.float32)])
        key = (toks.shape[1], pfb.shape[1], b) + (("lora",) * use_lora)
        fn = self._admit_jit(toks.shape[1], pfb.shape[1], b, use_lora)
        with self.mesh, self.profiler.program("admit", key):
            first, self.paged = fn(self._wave_params(use_lora),
                                   jnp.asarray(ints),
                                   jnp.asarray(floats), self.paged)
            return np.asarray(first)   # ONE host sync per admission wave

    def _run_decode(self, a: dict):
        """Launch one decode chunk's program from a JSON-safe arg dict:
        pack, dispatch, ONE host sync. Returns host arrays
        (toks [K, R], emits [K, R]). The pool positions each slot was
        attended over on each of the chunk's passes (the rung the program
        chose, transformer._pool_rung) come back with them, into
        ``_pool_positions`` for the chunk's span and, a pass, into
        ``batcher_decode_pool_positions``: its ratio to
        ``batcher_weight_passes`` is the mean extent. What a windowed
        layer read instead (paged_kvcache.window_read; the same number
        for a model without one) goes to ``_window_positions`` and
        ``batcher_decode_window_positions`` alike."""
        bt = np.asarray(a["bt"], np.int32)
        r, mb = bt.shape
        use_lora = "aids" in a
        ints = np.concatenate([bt.reshape(-1)] + [
            np.asarray(a[key], np.int32) for key in
            ("cl", "seeds", "steps", "tks", "budget", "eos", "ds")] + (
            [np.asarray(a["aids"], np.int32)] if use_lora else []))
        floats = np.stack([np.asarray(a["temps"], np.float32),
                           np.asarray(a["tps"], np.float32)])
        k = int(a["k"])
        key = (k, "lora") if use_lora else k
        # chunk names this host call's device run and its
        # batcher.decode_chunk span exactly; k and slots describe it,
        # first_use says the call is about to compile or is the first
        # run of a program compiled ahead (the account says what it took)
        stats = {}
        if self.profiler.enabled:
            stats = {"k": k, "slots": int(np.count_nonzero(a["budget"])),
                     "chunk": self._step_count + 1}
            if ((k, r, mb, use_lora) not in self._decode_fns
                    or self.profiler.awaits_run("chunk", key)):
                stats["first_use"] = 1
        fn = self._decode_jit(k, r, mb, use_lora)
        with self.mesh, self.profiler.program("chunk", key):
            with self.profiler.phase("dispatch", **stats):
                toks, emits, moe, pool_positions, self.paged = fn(
                    self._wave_params(use_lora),
                    jnp.asarray(np.asarray(a["tokens"], np.int32)),
                    jnp.asarray(ints), jnp.asarray(floats), self.paged)
            with self.profiler.phase("device_wait"):
                # the counters come back with the tokens: one sync
                toks, emits, moe, pool_positions = jax.device_get(
                    (toks, emits, moe if self.cfg.is_moe else (),
                     pool_positions))
            for name, n in zip(transformer.MOE_STATS, moe):
                self.metrics.inc(f"batcher_moe_{name}", int(n))
            if self.cfg.is_moe:
                self.metrics.inc(
                    "batcher_moe_experts_held", int(moe[0]) * (
                        self.cfg.experts_held or (0, self.cfg.num_experts))[1])
            self._pool_positions, self._window_positions = (
                int(n) for n in np.broadcast_to(pool_positions, (2,)))
            self.metrics.inc("batcher_decode_pool_positions",
                             self._pool_positions * int(a["k"]))
            self.metrics.inc("batcher_decode_window_positions",
                             self._window_positions * int(a["k"]))
            self.metrics.inc("batcher_decode_ring_positions",
                             self._window_positions * int(a["k"])
                             if self.cfg.swa is not None else 0)
            self.metrics.inc("batcher_pool_kernel_passes",
                             int(a["k"]) * self.pool_kernel)
            return toks, emits

    @property
    def pool_kernel(self) -> bool:
        """Whether the plain decode chunks read the pool by the Pallas
        kernel (ops/pallas/paged_attention.py): the program's own choice
        (transformer._pool_kernel, a trace-time constant of the config
        as pinned, the parameters' form and the pool's shape), asked
        once. The pipelined chunk and the speculative one keep the XLA
        form. ``batcher_pool_kernel_passes`` over
        ``batcher_weight_passes`` is 1.0 where it holds, and the
        ``batcher.decode_chunk`` span carries it as ``pool_kernel``."""
        if self._pool_kernel is None:
            self._pool_kernel = self.mesh_spec.pp == 1 and bool(
                transformer._pool_kernel(self.cfg, self.paged))
        return self._pool_kernel

    def _hist_deltas(self) -> list:
        """JSON-safe per-slot history deltas for the lockstep broadcast:
        ``[slot, offset, tokens]`` for every active row the followers are
        behind on. Non-empty only right after a slot (re)admission — every
        other append is derived from replayed program outputs on both
        sides — so the broadcast is O(new prompt), not O(slots * max_seq)
        per chunk. Advances the watermark."""
        out = []
        for r in range(self.slots):
            if self.active[r] is None:
                continue
            k = min(int(self.context_lens[r]) + 1, self.max_seq + 1)
            s = int(self._hist_synced[r])
            if k > s:
                out.append([r, s, self._hist[r, s:k].tolist()])
                self._hist_synced[r] = k
        return out

    def _apply_spec_hist(self, toks, keeps, cl):
        """Mirror a speculative chunk's kept tokens into the drafting
        history. Pure function of the program's (inputs, outputs), so the
        leader and every replaying follower evolve identical rows without
        the history ever riding the broadcast."""
        for r in range(keeps.shape[1]):
            pos = int(cl[r]) + 1
            kept = 0
            for t in range(keeps.shape[0]):
                for tok in toks[t, r, : int(keeps[t, r])]:
                    if pos <= self.max_seq:
                        self._hist[r, pos] = int(tok)
                    pos += 1
                    kept += 1
            if self._hist_synced is not None and kept:
                self._hist_synced[r] = min(self._hist_synced[r] + kept,
                                           self.max_seq + 1)

    def _apply_plain_hist(self, toks, emits, cl):
        """Mirror a PLAIN decode chunk's emitted tokens into the drafting
        history: the adaptive speculation controller interleaves plain
        chunks (fallback / probes) into a speculative batcher, and stale
        history rows would draft garbage (rejected — correct but wasted).
        The plain case IS the spec case at draft width 1 — ``emits`` is a
        monotone 0/1 keeps column — so the lockstep-critical watermark
        arithmetic lives once, in _apply_spec_hist. No-op when drafting
        is off."""
        if self._hist is None:
            return
        self._apply_spec_hist(np.asarray(toks)[:, :, None],
                              np.asarray(emits).astype(np.int32), cl)

    def _run_spec_decode(self, a: dict):
        """Launch one speculative chunk's program. Returns (toks
        [K, R, g+1], keeps [K, R], eos_seen [K, R]) as host arrays —
        ``eos_seen`` is cumulative per row, distinguishing an eos death
        from merely running out of chunk iterations."""
        bt = np.asarray(a["bt"], np.int32)
        if "hist" in a:
            hist = np.asarray(a["hist"], np.int32)
        else:   # lockstep replay: apply the leader's deltas to our copy
            for r, off, row in a.get("hist_delta") or []:
                self._hist[r, off:off + len(row)] = row
            hist = self._hist
        r, mb = bt.shape
        gammas = np.asarray(a["gammas"], np.int32)
        use_lora = "aids" in a
        ints = np.concatenate([bt.reshape(-1), hist.reshape(-1)] + [
            np.asarray(a[key], np.int32) for key in
            ("tokens", "cl", "seeds", "steps", "tks", "budget", "eos", "ds")
        ] + [gammas] + (
            [np.asarray(a["aids"], np.int32)] if use_lora else []))
        floats = np.stack([np.asarray(a["temps"], np.float32),
                           np.asarray(a["tps"], np.float32)])
        fn = self._spec_jit(int(a["k"]), int(a["gamma"]), r, mb,
                            hist.shape[1], use_lora)
        key = (int(a["k"]), int(a["gamma"])) + (("lora",) * use_lora)
        # draft+verify run fused in one device program; the profiler
        # attributes the whole dispatch+sync to the verify phase (the
        # host-side drafting state prep is tagged spec_draft by the step)
        with self.mesh, self.profiler.program("spec", key):
            with self.profiler.phase("spec_verify"):
                toks, keeps, eos_seen, self.paged = fn(
                    self._wave_params(use_lora), jnp.asarray(ints),
                    jnp.asarray(floats), self.paged)
                return jax.device_get((toks, keeps, eos_seen))

    def _note_program(self, before: tuple, kind: str = "", k: int = 0,
                      slots: int = 0) -> Tuple[float, float]:
        """Close one program call that began at ``before``
        (``call_readings()``, taken at its start): its wall, read once
        here, is program time of this step, and a decode chunk (``k``
        passes) is judged against the running mean of earlier chunks of
        its kind and size, unless it was the program's first use (the
        call's label saw it compile, or run for the first time: seconds
        that are no pass's, which the program account holds). Admit
        waves only add their wall: their sizes differ too widely for a
        mean to say anything. Returns the call's (start, end) in epoch
        seconds, for its histogram and span."""
        t0, t1 = before[0], time.perf_counter()
        wall_s = t1 - t0
        span = self.profiler.epoch(t0), self.profiler.epoch(t1)
        self._step_program_s += wall_s
        if not k or self.profiler.first_use:
            return span
        st = self._pass_mean.setdefault((kind, k), [0.0, 0])
        mean, n = st
        per_pass = wall_s / k
        lost = wall_s - mean * k
        if (n >= self.STALL_MIN_CHUNKS and lost >= self.STALL_FLOOR_S
                and per_pass > self.STALL_PROGRAM_FACTOR * mean):
            # the bracket that grew: the launch, if it took at least
            # half of what was lost, else the wait for the outputs (a
            # speculative chunk has one bracket for both)
            launch_s = self.profiler.step_clocks().get("dispatch", 0.0)
            grew = ("spec_verify" if kind != "decode" else
                    "dispatch" if launch_s >= lost / 2 else "device_wait")
            self._stall("program", lost, k, slots, before, span, grew)
            # a stall is not the norm: it enters the mean at the limit
            per_pass = self.STALL_PROGRAM_FACTOR * mean
        st[:] = mean + (per_pass - mean) / min(n + 1, 64), n + 1
        return span

    def _report_first_use(self, parent=None):
        """Journal the programs the labelled calls since the last report
        used for the first time (utils/profiler.py: the program account
        holds their rows already): the counters, one
        ``batcher.program_first_use`` span over each call that compiled,
        under the step's own span where it has one (``parent``), and,
        while the scheduler thread serves, a ``program-first-use`` event
        and a warning: a request waited for a compilation."""
        for row, run_ms, compiled in self.profiler.take_unreported():
            m = self.metrics
            m.inc("batcher_program_first_run_ms", run_ms)
            if not compiled:    # the first run of a program compiled ahead
                continue
            m.inc("batcher_programs_first_use")
            for name in ("trace", "lower", "load"):
                m.inc(f"batcher_program_{name}_ms", row[f"{name}_ms"])
            m.inc("batcher_program_cache_hits", row["cache_hits"])
            m.inc("batcher_program_cache_misses", row["cache_misses"])
            attrs = {f: row[f] for f in events.PROGRAM_FIRST_USE_FIELDS}
            trace.get_tracer().record(
                "batcher.program_first_use", row["start"], row["end"],
                parent=parent, attrs=attrs)
            if row["serving"]:
                log.warning("program first used while serving: %s",
                            json.dumps(attrs))
                events.emit("program-first-use", **attrs)

    def _record_build(self):
        """Close the constructor's account and leave its spans:
        ``batcher.build`` over the whole of it, ``batcher.build.weights``
        and ``batcher.build.pool`` under it."""
        build = self.profiler.built()
        tracer = trace.get_tracer()
        whole = tracer.record(
            "batcher.build", build["start"], build["end"],
            attrs={"model": self.cfg.name, "wall_ms": build["wall_ms"],
                   **{f"eager_{k}": v for k, v in build["eager"].items()}})
        for name in ("weights", "pool"):
            part = build[name]
            tracer.record(f"batcher.build.{name}", part["start"],
                          part["end"], parent=whole,
                          attrs={k: v for k, v in part.items()
                                 if k not in ("start", "end")})

    def _stall(self, where: str, lost_s: float, k: int, slots: int,
               before: tuple, span: Tuple[float, float], grew: str):
        """Count one stall (``where``: program | host), say what the
        process and the device's memory were doing over it (``before``:
        the readings at the stalled call's or step's start), name its
        cause (``_stall_cause``) and journal it, as an event and as a
        ``batcher.stall`` span over the call."""
        ms = round(lost_s * 1e3, 1)
        rec = {"where": where, "ms": ms, "in": grew, "k": k, "slots": slots,
               "pool_positions": self._pool_positions,
               **call_deltas(before), "memory": self._device_memory()}
        rec["cause"] = self._stall_cause(where, ms, rec)
        self._stall_lost_s += lost_s
        self.metrics.inc(f"batcher_stall_{where}_ms", lost_s * 1e3)
        self.metrics.inc(f"batcher_stall_cause_{rec['cause']}_ms",
                         lost_s * 1e3)
        log.warning("scheduler stall (%s): %.0f ms lost, k=%d, slots=%d: %s",
                    where, lost_s * 1e3, k, slots, json.dumps(rec))
        events.emit("scheduler-stall", **rec)
        trace.get_tracer().record("batcher.stall", *span, attrs=rec)

    def _device_memory(self) -> dict:
        """``memory_stats()`` of this process's fullest device, as the
        backend gives them (none on the CPU). Read when a stall fired,
        never on the step's path."""
        best: dict = {}
        for d in self.mesh.devices.flat:
            if d.process_index != jax.process_index():
                continue
            try:
                st = d.memory_stats() or {}
            except Exception as e:   # a backend without the call
                log.debug("memory_stats: %r", e)
                continue
            if st.get("bytes_in_use", 0) >= best.get("bytes_in_use", 0):
                best = {key: int(v) for key, v in st.items()
                        if isinstance(v, (int, float))}
        return best

    def _wave_params(self, use_lora: bool):
        """The parameter tree a wave's program runs against: the base
        tree, or — when any slot in the wave carries an adapter id — the
        LoRA-augmented tree whose ``layers`` dict gains the stacked
        device pack. Same structure and shapes every rebuild, so the
        use_lora=True program never recompiles across adapter mixes."""
        if not use_lora:
            return self.params
        if self._params_lora is None:
            raise RuntimeError(
                "wave carries adapter ids but no LoRA pack is built")
        return self._params_lora

    def replay(self, kind: str, args: dict):
        """Re-execute a program the lockstep leader broadcast. SPMD
        correctness requires every host to launch identical programs in
        identical order — the caller (LockstepFollower) provides the
        ordering; identical args provide the identity."""
        if kind == "admit":
            self._run_admit(args)
        elif kind == "decode":
            if self._hist is not None:
                # admission-time rows ride the broadcast (see
                # _dispatch_plain_chunk); appends derive from outputs
                for r, off, row in args.get("hist_delta") or []:
                    self._hist[r, off:off + len(row)] = row
            toks, emits = self._run_decode(args)
            # adaptive speculation interleaves plain chunks: followers
            # mirror the leader's history appends from program outputs
            self._apply_plain_hist(toks, emits,
                                   np.asarray(args["cl"], np.int32))
        elif kind == "spec_decode":
            toks, keeps, _ = self._run_spec_decode(args)
            if "hist" not in args:
                # mirror the leader's host-side history appends from the
                # program's own outputs (see _apply_spec_hist)
                self._apply_spec_hist(toks, keeps,
                                      np.asarray(args["cl"], np.int32))
        else:
            raise ValueError(f"unknown batcher program kind {kind!r}")
        self._report_first_use()

    # ---- scheduling ---------------------------------------------------

    def _bucket_tail(self, n: int) -> int:
        for m in TAIL_BUCKETS_X_BS:
            if n <= m * self.block_size:
                return min(m * self.block_size,
                           self.max_blocks * self.block_size)
        raise ValueError(f"tail of {n} tokens exceeds buckets")

    def _bucket_prefix(self, nb: int) -> int:
        for m in PREFIX_BUCKETS:
            if nb <= m:
                return min(m, self.max_blocks) if m else 0
        raise ValueError(f"prefix of {nb} blocks exceeds buckets")

    @staticmethod
    def _bucket_wave(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def _shared_wave_blocks(self, wave: List[dict], prompt: List[int]) -> int:
        """Longest common full-block prefix (in blocks) between `prompt`
        and any prompt already in the admission wave."""
        bs = self.block_size
        best = 0
        for m in wave:
            n = 0
            for a, b in zip(m["prompt"], prompt):
                if a != b:
                    break
                n += 1
            best = max(best, n // bs)
        return best

    # ---- host KV tier (offload on evict, restore on admission) --------

    def _offload_evicted(self, evictions):
        """Eviction hook (native BlockPool.set_evict_hook): copy each
        evicted radix block's still-resident device KV pages into the
        host arena, keyed by the block's token-chain digest. Runs
        synchronously inside ``pool.alloc`` — after the block id returns
        to the free list but before any program that could overwrite it
        is dispatched, which is exactly the window where the device bytes
        are still the evicted prefix's KV. One batched device->host
        gather covers every block the alloc evicted."""
        if self.kvtier is None or self.program_hook is not None:
            return
        ev = [(b, toks) for b, toks in evictions if toks]
        if not ev:
            return
        # a restored block's arena entry stays resident (HostKVArena.get
        # keeps it), so its re-eviction needs no copy at all — filter
        # before the gather, which is a blocking device sync
        digs = [self.kvtier.block_digests(toks)[-1] for _, toks in ev]
        keep = [j for j, d in enumerate(digs)
                if not self.kvtier.arena.peek(d)]
        if not keep:
            return
        w0 = clock.now()
        pages = self._host_pages([ev[j][0] for j in keep])
        stored = 0
        nbytes = 0
        for col, j in enumerate(keep):
            cols = [p[:, col] for p in pages]
            if self.kvtier.arena.put(digs[j], cols):
                stored += 1
                nbytes += sum(c.nbytes for c in cols)
        self.metrics.inc("kvtier_offloaded_blocks", stored)
        if self._admitting is not None and nbytes:
            # cost ledger: the alloc that evicted these blocks belongs to
            # the request currently admitting/growing — its ledger shows
            # the device->host traffic it displaced
            self._admitting._arena_offloaded_bytes += nbytes
        trace.get_tracer().record(
            "batcher.kv_offload", w0, clock.now(),
            attrs={"blocks": len(ev), "stored": stored})

    def _host_pages(self, blocks):
        """Blocks ``blocks`` of every paged-cache leaf, device to host
        (a blocking sync), by heads: [L, n, bs, Hkv, w] a leaf, the
        arena's and the wire's form (a flat pool's rows viewed so on
        the host, where it is no copy; _run_restore is the way back)."""
        idx = np.asarray(blocks, np.int32)
        leaves = [lf for lf in self.paged if lf is not None]
        with self.mesh:
            pages = jax.device_get([lf[:, idx] for lf in leaves])
        if self._flat_heads:
            pages = [head_rows(pg, *self._flat_heads) for pg in pages]
        return pages

    def _restore_jit(self, b: int, nleaves: int):
        """Scatter ``b`` restored blocks back into every paged-cache
        leaf at once (the block axis is axis 1) — the admission-side twin
        of ops/paged_kvcache.write_block_run, but for whole blocks whose
        contents come from the host arena rather than fresh prefill."""
        fn = self._restore_fns.get(b)
        if fn is None:
            def restore(ids, vals, *leaves):
                return tuple(lf.at[:, ids].set(v.astype(lf.dtype))
                             for lf, v in zip(leaves, vals))
            fn = jax.jit(restore,
                         donate_argnums=tuple(range(2, 2 + nleaves)))
            self._restore_fns[b] = fn
        return fn

    def _run_restore(self, blocks, pages):
        """Write arena pages for ``blocks`` back to device. Row count is
        bucketed to a power of two (padding rows target the reserved
        dummy block, whose content is never read) so restores of any
        length share a handful of compiled scatters."""
        nb = len(blocks)
        b = 1
        while b < nb:
            b *= 2
        ids = np.full((b,), self._dummy, np.int32)
        ids[:nb] = blocks
        live = [lf for lf in self.paged if lf is not None]
        vals = []
        for j, lf in enumerate(live):
            # one C-level stack per leaf, not a python copy per page —
            # this runs on the scheduler thread between decode chunks
            stacked = np.stack([pg[j] for pg in pages], axis=1)
            if self._flat_heads:   # as the device stores them
                stacked = flat_rows(stacked)
            if b == nb and stacked.dtype == lf.dtype:
                vals.append(stacked)
                continue
            v = np.zeros((lf.shape[0], b) + tuple(lf.shape[2:]),
                         dtype=lf.dtype)
            v[:, :nb] = stacked
            vals.append(v)
        fn = self._restore_jit(b, len(live))
        with self.mesh:
            new_leaves = fn(jnp.asarray(ids),
                            tuple(jnp.asarray(v) for v in vals), *live)
        it = iter(new_leaves)
        self.paged = type(self.paged)(
            *[next(it) if lf is not None else None for lf in self.paged])

    def _restore_from_arena(self, prompt, n, prefix_blocks, cached):
        """Second-tier prefix lookup on a (partial) radix miss: restore
        the longest consecutive run of arena-held blocks that extends the
        radix match, register them in the radix tree, and return the
        extended (prefix_blocks, cached). Opportunistic — any failure
        (no free device blocks, arena LRU race) simply falls back to
        prefilling that span. In native arena mode the restored bytes
        are the exact evicted bytes, so downstream outputs are bitwise
        identical to a cold prefill; in int8 mode they are the
        bounded-error dequant (ops/kvblock_quant.py)."""
        bs = self.block_size
        start = cached // bs
        limit = (n - 1) // bs   # >=1 token must remain for the tail
        if start >= limit:
            return prefix_blocks, cached
        digs = self.kvtier.block_digests(prompt[:limit * bs])
        run = []
        for i in range(start, limit):
            if self.kvtier.arena.peek(digs[i]):
                run.append(digs[i])
            else:
                break
        if not run:
            return prefix_blocks, cached
        blocks = self.pool.alloc(len(run))
        if blocks is None:
            return prefix_blocks, cached
        pages = []
        for d in run:
            pg = self.kvtier.arena.get(d)
            if pg is None:   # LRU-dropped by our own alloc's offloads
                break
            pages.append(pg)
        if len(pages) < len(blocks):
            self.pool.release(blocks[len(pages):])
            blocks = blocks[:len(pages)]
        if not blocks:
            return prefix_blocks, cached
        w0 = clock.now()
        self._run_restore(blocks, pages)
        end = start + len(blocks)
        self.pool.insert_prefix(prompt[:end * bs], blocks, skip=start)
        self.metrics.inc("kvtier_restored_blocks", len(blocks))
        self.metrics.inc("kvtier_restored_tokens", len(blocks) * bs)
        if self._admitting is not None:
            self._admitting._arena_restored_bytes += sum(
                p.nbytes for pg in pages for p in pg)
        trace.get_tracer().record(
            "batcher.kv_restore", w0, clock.now(),
            attrs={"blocks": len(blocks), "tokens": len(blocks) * bs})
        return prefix_blocks + blocks, end * bs

    def _get_kv_fetcher(self):
        """The shared peer-fetch client (worker-injected), or a lazily
        built one for standalone batchers. None only if the import
        itself fails (no requests on the box)."""
        if self.kv_fetcher is None:
            try:
                from distributed_llm_inferencing_tpu.runtime.kvwire import (
                    KVFetchClient)
                self.kv_fetcher = KVFetchClient(metrics=self.metrics)
            except Exception:
                return None
        return self.kv_fetcher

    def _fetch_into_arena(self, url, model, prompt, limit,
                          start: int = 0, progress=None) -> int:
        """Pull the arena-missing chain digests of ``prompt``'s blocks
        ``[start, limit)`` from the peer at ``url`` into the LOCAL host
        arena. A native peer's bytes are its exact evicted/exported
        device bytes (restore stays bitwise identical to a cold
        prefill); an int8 peer ships quantized records that restore to
        a bounded-error dequant. Strictly opportunistic: ANY failure —
        transport, corrupt frame, peer missing the blocks, shape drift
        — degrades to recompute, never to a request failure. Returns
        the wire bytes stored (0 on failure).

        Single-flight: concurrent calls against the same (peer, model)
        — shared-prefix fan-in, the drain of a dying node's whole
        resident set — coalesce. The first caller leads and fetches the
        deduped union of every caller's still-missing digests (one
        socket, batched rounds while new waiters keep arriving);
        waiters block on the leader and find their blocks
        arena-resident, so each digest crosses the wire exactly once.
        ``progress(stream)``, if given, runs on the LEADER's thread
        after each block lands (the receive-overlap consumer hook)."""
        bs = self.block_size
        digs = self.kvtier.block_digests(prompt[:limit * bs])
        want = [d for d in digs[start:limit]
                if not self.kvtier.arena.peek(d)]
        if not want:
            return 0
        key = (str(url), str(model))
        with self._kvf_lock:
            fl = self._kvf_inflight.get(key)
            leader = fl is None
            if leader:
                # dict-as-ordered-set: consecutive digest order survives
                # the dedup, so the leader's batch streams in scatter
                # order
                fl = {"pending": dict.fromkeys(want, True),
                      "event": threading.Event()}
                self._kvf_inflight[key] = fl
            else:
                for d in want:
                    fl["pending"].setdefault(d, True)
        if not leader:
            self.metrics.inc("kv_prefetch_coalesced")
            # leader guarantees the event fires (finally below); the
            # timeout is a backstop so a stuck transfer can only stall
            # this caller as long as its own fetch could have
            fl["event"].wait(timeout=90.0)
            return 0
        total = 0
        try:
            while True:
                with self._kvf_lock:
                    batch = [d for d in fl["pending"]
                             if not self.kvtier.arena.peek(d)]
                    fl["pending"].clear()
                if not batch:
                    break
                total += self._wire_fetch(url, model, batch,
                                          progress=progress)
                # digests still missing after the round (peer didn't
                # have them / validation refused them) were cleared
                # above: only NEW waiters' digests survive into the
                # next round, so the loop terminates when arrivals do
        finally:
            with self._kvf_lock:
                self._kvf_inflight.pop(key, None)
            fl["event"].set()
        return total

    def _admit_fetched(self, digest, obj, expect) -> bool:
        """Shape/dtype-check one fetched block against the live paged
        leaves BEFORE the arena sees it: a buggy/mismatched peer
        (different model or cache config) must degrade to recompute,
        not crash the scheduler thread inside the restore scatter.
        Quantized records check their LOGICAL specs — what they will
        dequantize to at restore time."""
        if kvq.is_quantized_block(obj):
            specs = kvq.logical_specs(obj)
        else:
            specs = [(tuple(p.shape), p.dtype) for p in obj]
        if (len(specs) != len(expect)
                or any(shp != eshp or dt != edt
                       for (shp, dt), (eshp, edt) in zip(specs, expect))):
            self.metrics.inc("kv_transfer_failures")
            return False
        return self.kvtier.arena.put(digest, obj, count_offload=False)

    def _wire_fetch(self, url, model, want, progress=None) -> int:
        """One wire transfer of ``want`` digests (single-flight leader
        body). Streams frames through kvwire.FetchStream when
        DLI_KV_WIRE_OVERLAP is on — each block is validated and
        arena-admitted as its frame decodes, with ``progress`` driving
        the caller's overlap consumer — else one blocking fetch.
        Mid-stream faults keep the blocks that already landed (valid
        arena entries); the rest recomputes."""
        fetcher = self._get_kv_fetcher()
        if fetcher is None:
            return 0
        live = [lf for lf in self.paged if lf is not None]
        # (a flat pool's blocks travel by heads: _host_pages)
        expect = [((lf.shape[0], lf.shape[2])
                   + tuple(self._flat_heads or lf.shape[3:]), lf.dtype)
                  for lf in live]
        w0 = clock.now()
        blocks = bytes_in = 0
        err = None
        try:
            # injected fetchers may implement only the blocking API;
            # overlap is an optimization, not a contract
            if self._wire_overlap and hasattr(fetcher, "fetch_stream"):
                stream = fetcher.fetch_stream(url, model, want)
                for d, obj in stream:
                    if self._admit_fetched(d, obj, expect):
                        blocks += 1
                        bytes_in += kvwire_mod.stored_nbytes(obj)
                        if progress is not None:
                            progress(stream)
            else:
                got = fetcher.fetch(url, model, want)
                for d in want:
                    obj = got.get(d)
                    if obj is None:
                        continue   # peer didn't have it: plain recompute
                    if self._admit_fetched(d, obj, expect):
                        blocks += 1
                        bytes_in += kvwire_mod.stored_nbytes(obj)
        except Exception as e:
            self.metrics.inc("kv_transfer_failures")
            err = str(e)[:200]
        elapsed = clock.now() - w0
        self.metrics.inc("kv_transfer_blocks", blocks)
        self.metrics.inc("kv_transfer_bytes", bytes_in)
        self.metrics.inc("kv_transfer_ms", elapsed * 1e3)
        attrs = {"peer": url, "blocks": blocks, "bytes": bytes_in}
        if err:
            attrs["error"] = err
        trace.get_tracer().record(
            "batcher.kv_fetch", w0, clock.now(), attrs=attrs)
        return bytes_in

    def prefetch_kv(self, prompt: Sequence[int], kv_source) -> int:
        """Caller-thread transfer for a disaggregated request: pull the
        prompt's prefix blocks from the ``kv_source`` peer into the host
        arena BEFORE submission. The worker calls this on its HTTP
        handler thread, so the wire transfer overlaps the decode loop —
        admission then finds the blocks arena-resident and pays only the
        device scatter, instead of stalling every co-resident decode
        stream behind a blocking fetch. Returns bytes transferred (0 on
        any failure: the request simply recomputes)."""
        if (self.kvtier is None or self.program_hook is not None
                or not isinstance(kv_source, dict)):
            return 0
        url = kv_source.get("url")
        if not url:
            return 0
        prompt = list(map(int, prompt))
        limit = (len(prompt) - 1) // self.block_size
        if limit <= 0:
            return 0
        try:
            return self._fetch_into_arena(
                url, str(kv_source.get("model") or ""), prompt, limit)
        except Exception:
            self.metrics.inc("kv_transfer_failures")
            return 0

    def _restore_from_peer(self, req, prompt, n, prefix_blocks, cached):
        """Scheduler-thread fallback of :meth:`prefetch_kv` for direct
        batcher users (the worker prefetches at submit time instead and
        clears ``kv_source``): pull the request's missing block digests
        from its designated peer into the local arena. With
        DLI_KV_WIRE_OVERLAP (the default) the transfer is
        receive-overlapped: as frames land in the arena, every ~8
        blocks the consecutive run scatters to device through the
        ordinary ``_restore_from_arena`` machinery WHILE the receiver
        thread keeps pulling later frames off the socket — scatter of
        block N overlaps receive of block N+1 instead of paying
        fetch-then-scatter serially. The achieved overlap (scatter
        seconds inside the transfer wall, as a fraction) lands in the
        ``kv_restore_overlap_ratio`` gauge. Returns the (possibly
        extended) ``(prefix_blocks, cached)``."""
        src = req.kv_source
        if (src is None or req._peer_fetch_done or self.kvtier is None
                or self.program_hook is not None):
            return prefix_blocks, cached
        url = src.get("url") if isinstance(src, dict) else None
        if not url:
            req._peer_fetch_done = True
            return prefix_blocks, cached
        bs = self.block_size
        start = cached // bs
        limit = (n - 1) // bs
        if start >= limit:
            return prefix_blocks, cached
        digs = self.kvtier.block_digests(prompt[:limit * bs])
        if all(self.kvtier.arena.peek(d) for d in digs[start:limit]):
            return prefix_blocks, cached   # nothing missing: no RPC, no flag
        req._peer_fetch_done = True
        state = {"pb": prefix_blocks, "cached": cached,
                 "arrived": 0, "overlap_s": 0.0}

        def scatter_ready(stream):
            # the overlap consumer: runs on THIS (scheduler) thread
            # between the leader's frame decodes; ~8-block chunks
            # amortize the per-scatter digest walk and jit dispatch
            state["arrived"] += 1
            if state["arrived"] < 8 and not stream.receiving_done:
                return
            state["arrived"] = 0
            t0 = clock.now()
            receiving = not stream.receiving_done
            state["pb"], state["cached"] = self._restore_from_arena(
                prompt, n, state["pb"], state["cached"])
            if receiving:
                state["overlap_s"] += clock.now() - t0

        w0 = clock.now()
        got = self._fetch_into_arena(
            url, str(src.get("model") or ""), prompt, limit, start=start,
            progress=scatter_ready if self._wire_overlap else None)
        req._kv_transfer_bytes += got
        wall = clock.now() - w0
        if got and self._wire_overlap and wall > 0:
            self.metrics.gauge("kv_restore_overlap_ratio",
                               min(1.0, state["overlap_s"] / wall))
        return state["pb"], state["cached"]

    def _export_request_kv(self, req, seq=None, n_ctx=None):
        """KV export into the host arena under token-chain digests —
        the blocks a peer's ``/kv_fetch`` will ask for. Two callers:

        - finish-time export for a disaggregated prefill pass
          (``kv_export`` dispatch flag): ``seq`` defaults to the PROMPT,
          whose KV the prefill pass just wrote in full;
        - a mid-generation migration snapshot (``_service_migrations``):
          ``seq`` is prompt+emitted tokens and ``n_ctx`` the slot's
          context length — only positions whose KV is actually on
          device export (the last emitted token's KV lands with the
          NEXT chunk's input, so it is prefilled on the destination).

        Runs while the request still owns its blocks (before release),
        so the device bytes are exactly the computed prefix. Skips
        blocks the eviction path already offloaded."""
        if (self.kvtier is None or self.program_hook is not None
                or req.error or not req._blocks):
            return
        bs = self.block_size
        seq = list(req.prompt) if seq is None else list(seq)
        n = len(seq) if n_ctx is None else min(int(n_ctx), len(seq))
        n_full = min(n // bs, len(req._blocks))
        if n_full <= 0:
            return
        digs = self.kvtier.block_digests(seq[:n_full * bs])
        keep = [i for i in range(n_full)
                if not self.kvtier.arena.peek(digs[i])]
        if not keep:
            return
        w0 = clock.now()
        pages = self._host_pages([req._blocks[i] for i in keep])
        stored = 0
        for col, i in enumerate(keep):
            cols = [p[:, col] for p in pages]
            if self.kvtier.arena.put(digs[i], cols, count_offload=False):
                stored += 1
        self.metrics.inc("kvtier_exported_blocks", stored)
        trace.get_tracer().record(
            "batcher.kv_export", w0, clock.now(),
            attrs={"blocks": n_full, "stored": stored})

    # ---- live in-flight migration ------------------------------------

    def migrate_out(self, req: BatchRequest,
                    timeout: float = 10.0) -> Optional[dict]:
        """Snapshot + evict one in-flight request (worker ``POST
        /migrate_out``): ask the scheduler to export the request's KV
        through its last context position into the host arena and hand
        back a resume record at the next chunk boundary. Blocks until
        the request is terminal either way; returns the resume record,
        or None when the request completed/failed first (the
        migrate-vs-complete race — the caller answers 409 and the
        normal result stands), cannot migrate (multi-host lockstep), or
        the scheduler never serviced the flag within ``timeout``."""
        if self.program_hook is not None:
            return None          # lockstep: host-side evict can't ride
        if self.cfg.slot_cache:
            raise ValueError(
                f"{self.cfg.name}: migrate_out exports K and V blocks; a "
                "state-space layer's state and a windowed layer's ring "
                "are not among them")
        req._migrate_requested = True
        self._work.set()
        if not req.done.wait(timeout):
            req._migrate_requested = False
            return None
        return req.resume_record if req._migrated else None

    def _service_migrations(self):
        """Run at every step boundary: snapshot+evict requests flagged
        by :meth:`migrate_out`. Active slots export their computed KV
        (the destination's ``/kv_fetch`` + arena restore turns the
        resume into a scatter + one-token tail prefill instead of a
        re-prefill); queued requests hand off their resume record alone
        — their KV, if any, is radix-resident and exports on eviction
        like always."""
        pending = any(a is not None and a._migrate_requested
                      for a in self.active)
        with self._lock:
            queued = [r for r in self.queue if r._migrate_requested]
            for r in queued:
                self.queue.remove(r)
        for req in queued:
            self._finish_migrated(req)
        if not pending:
            return
        for slot in range(self.slots):
            req = self.active[slot]
            if req is None or not req._migrate_requested:
                continue
            try:
                self._export_request_kv(
                    req, seq=req.prompt + req.tokens,
                    n_ctx=int(self.context_lens[slot]))
            except Exception as e:
                log.warning("migration KV export failed for slot %d "
                            "(%r); destination will recompute", slot, e)
            # free like a preemption: the radix keeps refcount-0
            # leaves warm, the arena holds the export for /kv_fetch
            self.pool.release(req._blocks)
            req._blocks = []
            self.active[slot] = None
            self.block_tables[slot, :] = self._dummy
            self.context_lens[slot] = 0
            if slot in self._admit_order:
                self._admit_order.remove(slot)
            self._finish_migrated(req)

    def _finish_migrated(self, req: BatchRequest):
        """Terminal "handed off" outcome. The resume record is
        everything a destination batcher needs to continue bitwise-
        exactly: emitted tokens (the stream cursor — the destination
        re-emits nothing), the seed whose position-keyed PRNG makes the
        continued sampled stream draw the same tokens, the sampler
        budget/eos, and the spec-controller policy state."""
        req.resume_record = {
            "prompt_tokens": list(req.prompt),
            "tokens": list(req.tokens),
            "seed": int(req.seed),
            "steps": len(req.tokens),
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": req.eos_token_id,
            "spec": (req._spec_ctl.export_state()
                     if req._spec_ctl is not None else None),
            "adapter": req.adapter,
        }
        req._migrated = True
        req.error = "migrated"
        req.finished_at = clock.now()
        self._observe_finished(req)
        req.done.set()

    def _gauge_stall_streak(self, req):
        """chunk_prefill_stall_streak = the WORST current streak across
        chunked-prefill requests, not the last writer's — one progressing
        prompt must not zero the gauge while another sits one stall from
        a 'pool exhausted' failure (``req`` is mid-admission, so it is
        not in the queue)."""
        with self._lock:
            worst = max((r._chunk_stalls for r in self.queue), default=0)
        self.metrics.gauge("chunk_prefill_stall_streak",
                           max(worst, req._chunk_stalls))

    def _sync_cache_metrics(self):
        """Mirror the native pool's lifetime radix counters — and the
        host arena's occupancy — into the metrics registry, so the
        cluster-metrics pipeline (master /api/cluster_metrics) sees them:
        until now prefix_hits/misses lived only in ``stats()["pool"]``,
        invisible to /metrics scrapes."""
        st = self.pool.stats()
        last = self._last_pool_stats
        for key, mname in (("prefix_hits", "radix_prefix_hits"),
                           ("prefix_misses", "radix_prefix_misses"),
                           ("evictions", "radix_evictions")):
            d = st[key] - last.get(key, 0)
            # inc even when 0: the counter must EXIST in /metrics from
            # the first step (a scraper can't tell "no hits yet" from
            # "metric not exported" otherwise)
            self.metrics.inc(mname, max(0, d))
            last[key] = st[key]
        if self.kvtier is not None:
            a = self.kvtier.arena.stats()
            # stored (possibly quantized) bytes — the honest budget
            # fraction
            self.metrics.gauge("kvtier_host_bytes", a["bytes"])
            self.metrics.gauge(
                "kvtier_occupancy",
                a["bytes"] / max(1, a["capacity_bytes"]))

    def _prep_admit(self, req: BatchRequest) -> Optional[dict]:
        """Host-side admission prep: radix prefix match + block allocation.
        None if blocks are unavailable (caller decides preempt/requeue).

        For a preempted request the already-generated tokens are part of
        the prefill (generation resumes where it left off — streamed
        tokens are never re-emitted).
        """
        if req.adapter:
            # bind the adapter to a device slot now (not at submit):
            # slots are a wave-level resource, and admission is where
            # the request joins a wave. All-slots-pinned raises — the
            # caller fails the request rather than silently serving
            # base weights.
            req._lora_slot = self._assign_lora_slot(req.adapter)
        bs = self.block_size
        prompt = req.prompt + req.tokens
        n = len(prompt)
        # Leave >=1 token for the tail: prefill must produce the last
        # token's logits (a fully-cached prompt would have nothing to run).
        if not self.cfg.slot_cache:
            prefix_blocks, cached = self.pool.match_prefix(prompt[:n - 1])
        elif req._held_slot is not None:
            # a later chunk of a chunked prompt: its prefix is its own
            # earlier chunks' blocks, its state its held slot's row
            prefix_blocks, cached = list(req._blocks), req._prefill_counted
        else:
            # state layers, ring layers: K and V blocks without the
            # state (the ring) at their end are of no use, and the radix
            # cache holds neither, so no prefix is matched (and none
            # inserted, _post_admit)
            prefix_blocks, cached = [], 0
        if self.kvtier is not None and self.program_hook is None:
            # tier 2b: a disaggregated request pulls its missing prefix
            # blocks from the prefill peer, receive-overlapped — the
            # consecutive runs scatter while later frames are still on
            # the wire (runtime/kvwire.py; any failure degrades to
            # recompute) ...
            prefix_blocks, cached = self._restore_from_peer(
                req, prompt, n, prefix_blocks, cached)
            # ... then tier 2: extend the radix match from the host
            # arena — the streamed tail plus anything already resident —
            # before falling back to recompute (multi-host lockstep opts
            # out: a host-initiated scatter cannot ride the program
            # broadcast)
            prefix_blocks, cached = self._restore_from_arena(
                prompt, n, prefix_blocks, cached)
        tail_alloc = []
        partial = False
        try:
            tail_len = n - cached
            cap = (self.prefill_chunk * bs) if self.prefill_chunk else None
            if cap is not None and tail_len > cap:
                # chunked prefill: run only the next `cap` tokens (block
                # aligned — `cached` is whole blocks and cap is too), so
                # >= 1 token always remains for the sampling admission
                partial = True
                tail_len = cap
                n = cached + cap
            t = self._bucket_tail(tail_len)      # may raise ValueError
            tail_alloc = self.pool.alloc(t // bs)
            if tail_alloc is None:
                self._release_prefix(req, prefix_blocks)
                return None
            pb = max(self._bucket_prefix(len(prefix_blocks)), 1)
        except ValueError:
            # refuse-the-request path: drop the references this prep took,
            # or repeated oversized requests pin radix blocks forever
            self._release_prefix(req, prefix_blocks)
            self.pool.release(tail_alloc or [])
            raise
        return {"t": t, "pb": pb, "n": n, "cached": cached,
                "tail_len": tail_len, "prompt": prompt, "partial": partial,
                "prefix_blocks": prefix_blocks, "tail_alloc": tail_alloc}

    def _release_prefix(self, req: BatchRequest, prefix_blocks):
        """Give back the references a prep took on its prefix blocks. A
        chunked prompt that holds a slot (state layers) took none: its
        prefix is its own blocks, which go with the request."""
        if req._held_slot is None:
            self.pool.release(prefix_blocks)

    def _drop_hold(self, req: BatchRequest):
        """A chunked prompt that holds a slot between chunks (state
        layers) gives up the slot and the blocks of its chunks so far:
        it failed, was cancelled, or starts again from its first token."""
        if req._held_slot is not None:
            self._holds.discard(req._held_slot)
            req._held_slot = None
            self.pool.release(req._blocks)
            req._blocks = []
            req._prefill_counted = 0

    def _admit_wave(self):
        """Admit queued requests into free slots as bucketed waves: one
        batched program per (tail, prefix) bucket group.

        Chunked-prefill (partial) members need no slot — their chunk only
        writes KV into the radix cache — so a long prompt keeps making
        admission progress even when every decode slot is busy. One
        partial per wave: it requeues to the front, and pulling the queue
        past a front request that is mid-prefill would break FIFO order.
        """
        with self.profiler.phase("admit_prep"):
            wave = self._collect_wave()
        if not wave:
            return
        groups: dict = {}
        for m in wave:
            groups.setdefault((m["t"], m["pb"]), []).append(m)
        for (t, pb), members in groups.items():
            self._admit_group(t, pb, members)

    def _collect_wave(self) -> List[dict]:
        """The host half of _admit_wave: pop queued requests while they
        fit, each with its radix match and block allocation done."""
        wave: List[dict] = []
        taken: set = set()
        self._wave_cut = None   # the (tail, prefix) group the bound cut
        while True:
            free = [i for i, a in enumerate(self.active)
                    if a is None and i not in taken
                    and i not in self._holds]
            if not free:
                # no decode slot — only worth popping if the head could
                # chunk-admit (needs no slot); cheap length pre-filter,
                # the authoritative partial decision is _prep_admit's
                cap = (self.prefill_chunk or 0) * self.block_size
                with self._lock:
                    head = self.queue[0] if self.queue else None
                if (head is None or cap == 0 or head._noslot_bounce
                        or len(head.prompt) + len(head.tokens) - 1 <= cap):
                    break
                if self.cfg.slot_cache and head._held_slot is None:
                    break   # a per-slot cache: a first chunk takes a slot
            with self._lock:
                req = self.queue.popleft() if self.queue else None
            if req is None:
                break
            req._noslot_bounce = False   # re-marked below if it bounces again
            if req._cancelled:
                self._fail_req(req, "cancelled")
                continue
            try:
                # cost-ledger attribution window: arena offloads fired
                # by this prep's allocs bill to this request
                self._admitting = req
                prep = self._prep_admit(req)
            except ValueError as e:
                self._fail_req(req, str(e))
                continue
            finally:
                self._admitting = None
            if (prep is not None and wave and not self.cfg.slot_cache
                    and (self._shared_wave_blocks(wave, prep["prompt"])
                         * self.block_size > prep["cached"])):
                # an earlier wave member is about to insert a longer shared
                # prefix into the radix cache than this request would hit
                # now — defer one chunk so the re-match reuses those blocks
                # (saves both the blocks and the prefill compute)
                self.pool.release(prep["prefix_blocks"])
                self.pool.release(prep["tail_alloc"])
                self._requeue_front(req)
                break
            if prep is not None and self._past_score_budget(wave, prep):
                # its (tail, prefix) group's program would hold more
                # scores than WAVE_SCORE_BUDGET: run what the wave has,
                # this request FIRST next step
                self._wave_cut = (prep["t"], prep["pb"])
                self.metrics.inc("batcher_admit_waves_bounded")
                self._release_prefix(req, prep["prefix_blocks"])
                self.pool.release(prep["tail_alloc"])
                self._requeue_front(req)
                break
            if prep is None:
                if wave:
                    # part of the wave is already allocated — admit it now,
                    # retry this request FIRST next step
                    self._requeue_front(req)
                    break
                # Free memory by preempting the youngest slot, then retry
                # this request FIRST next step (it goes in front of the
                # preempted one, or ping-pong would starve it).
                preempted = self._preempt_youngest()
                if not preempted and not self._admit_order:
                    # no active slots to free: this prompt can never fit
                    self._fail_req(req, "KV block pool exhausted")
                else:
                    self._requeue_front(req)
                break
            prep["req"] = req
            if req._held_slot is not None:
                # state layers: the slot its first chunk took
                prep["slot"] = req._held_slot
                wave.append(prep)
                if prep["partial"]:
                    break
                continue
            if prep["partial"] and not self.cfg.slot_cache:
                prep["slot"] = None
                wave.append(prep)
                break
            if not free:
                # a full admission does need a slot; put the request back
                # and run whatever the wave already holds. Mark it so the
                # no-slot pre-filter above stops re-popping (and
                # re-prepping) it every step until a slot frees.
                req._noslot_bounce = True
                self.pool.release(prep["prefix_blocks"])
                self.pool.release(prep["tail_alloc"])
                self._requeue_front(req)
                break
            prep["slot"] = free[0]
            taken.add(free[0])
            wave.append(prep)
            if prep["partial"]:   # state layers: a first chunk, slot taken
                break
        return wave

    def _past_score_budget(self, wave: List[dict], prep: dict) -> bool:
        """Whether one more row takes the admission program of ``prep``'s
        (tail, prefix) group past WAVE_SCORE_BUDGET. A row alone always
        runs, whatever its size."""
        t, pb = prep["t"], prep["pb"]
        rows = 1 + sum(m["t"] == t and m["pb"] == pb for m in wave)
        if rows == 1:
            return False
        b = self._wave_rows(rows)
        return (b * t * (pb * self.block_size + t)
                > _wave_score_budget(self.cfg)
                or b * t > self._wave_token_budget)

    def _wave_rows(self, members: int) -> int:
        """Rows of the admission program that carries ``members``."""
        b = self._bucket_wave(members)
        if self.mesh_spec.pp > 1:   # wave rows microbatch over pp stages
            b = -(-b // self.mesh_spec.pp) * self.mesh_spec.pp
        return b

    def _admit_group(self, t: int, pb: int, members: List[dict]):
        """One batched admission program for wave members sharing a
        (tail-bucket, prefix-bucket); rows padded to a power-of-two wave
        size (padding rows write only the reserved dummy block)."""
        with self.profiler.phase("admit_prep"):
            b, admit_args = self._pack_admit(t, pb, members)
        # what the wave costs and whom: real uncached tail tokens, the
        # tokens the program's shape pays for, and the slots that stand
        # still while it runs
        tokens = sum(m["tail_len"] for m in members)
        # ... what it did not have to prefill: cached positions its rows
        # attend that an earlier admission left (as prefill_cached_tokens
        # counts them: a chunked prompt's own earlier chunks are not)
        hits = sum(max(0, m["cached"] - m["req"]._prefill_counted)
                   for m in members)
        active = sum(a is not None for a in self.active)
        self._wave_count += 1
        before = call_readings()
        w0 = self.profiler.epoch(before[0])
        for m in members:
            # cost ledger: queue phase ends when the FIRST wave carrying
            # the request starts dispatching (chunked-prefill passes and
            # preemption re-admissions keep the original stamp)
            if m["req"].admitted_at is None:
                m["req"].admitted_at = w0
        # wave names this call's device run and its span exactly;
        # first_use says the program is about to be compiled
        stats = {}
        if self.profiler.enabled:
            stats = dict(rows=b, tail_bucket=t, prefix_bucket=pb,
                         tokens=tokens, wave=self._wave_count)
            if (t, pb, b, "aids" in admit_args) not in self._prefill_fns:
                stats["first_use"] = 1
        with self.profiler.phase("admit_run", **stats):
            if self.program_hook is not None:
                first = self.program_hook(
                    "admit", admit_args, lambda: self._run_admit(admit_args))
            else:
                first = self._run_admit(admit_args)
        w0, w1 = self._note_program(before)
        self.metrics.observe("batcher_admit_wave", w1 - w0)
        wave = trace.get_tracer().record(
            "batcher.admit_wave", w0, w1,
            attrs={"wave": self._wave_count,
                   "members": len(members), "rows": b,
                   "tail_bucket": t, "prefix_bucket": pb,
                   "tokens": tokens, "padded_tokens": b * t,
                   "active": active, "prefix_positions": hits,
                   "loop_steps": self.cfg.loop_steps,
                   "ssm_state_bytes_per_slot":
                       self._state_bytes_per_slot,
                   "kv_ring_bytes_per_slot": self._ring_bytes_per_slot,
                   **self._gathered_prefix(b, pb),
                   "bounded": int(self._wave_cut == (t, pb))})
        self._report_first_use(wave)
        with self.profiler.phase("admit_post"):
            for j, m in enumerate(members):
                if m["req"]._wave_span is None:
                    m["req"]._wave_span = wave.span_id
                self._post_admit(m, int(first[j]))

    def _gathered_prefix(self, b: int, pb: int) -> dict:
        """Prefix positions one layer of an admission program gathers
        over its ``b`` rows: the whole prefix bucket in a full layer, the
        columns that hold the window in a windowed one
        (paged_kvcache.window_read)."""
        bs = self.block_size
        out = {"gathered_full": b * pb * bs}
        wins = {w for w in self.cfg.attn_windows or () if w is not None}
        if wins:
            out["gathered_win"] = b * bs * max(
                window_columns(w, bs, pb) or pb for w in wins)
        return out

    def _pack_admit(self, t: int, pb: int, members: List[dict]):
        """(rows, JSON-safe args) of one admission program."""
        bs = self.block_size
        b = self._wave_rows(len(members))
        toks = np.zeros((b, t), np.int32)
        tail_len = np.ones((b,), np.int32)
        tail_blocks = np.full((b, t // bs), self._dummy, np.int32)
        pfb = np.full((b, pb), self._dummy, np.int32)
        cached = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.int32)
        steps = np.zeros((b,), np.int32)
        temps = np.full((b,), 1.0, np.float32)
        tks = np.zeros((b,), np.int32)
        tps = np.ones((b,), np.float32)
        ds = np.zeros((b,), bool)
        aids = np.zeros((b,), np.int32)
        for j, m in enumerate(members):
            req = m["req"]
            toks[j, :m["tail_len"]] = \
                m["prompt"][m["cached"]:m["cached"] + m["tail_len"]]
            tail_len[j] = m["tail_len"]
            tail_blocks[j, :] = m["tail_alloc"]
            pfb[j, :len(m["prefix_blocks"])] = m["prefix_blocks"]
            cached[j] = m["cached"]
            sp = req.sampling
            seeds[j] = req.seed
            steps[j] = len(req.tokens)
            temps[j] = sp.temperature
            tks[j] = sp.top_k
            tps[j] = sp.top_p
            ds[j] = sp.do_sample
            aids[j] = req._lora_slot

        admit_args = {
            "toks": toks.tolist(), "tail_len": tail_len.tolist(),
            "tail_alloc": tail_blocks.tolist(), "pfb": pfb.tolist(),
            "cached": cached.tolist(), "seeds": seeds.tolist(),
            "steps": steps.tolist(), "temps": temps.tolist(),
            "tks": tks.tolist(), "tps": tps.tolist(), "ds": ds.tolist(),
        }
        if aids.any():
            # the key's PRESENCE selects the lora program variant — a
            # base-only wave compiles/runs the unaugmented program, and
            # lockstep followers replaying the args pick the same one
            admit_args["aids"] = aids.tolist()
        if self.cfg.slot_cache:
            # each row's state (ring) row; padding rows write the dummy
            # row behind the slots' (ops/paged_kvcache.py)
            admit_args["slots"] = (
                [m["slot"] for m in members]
                + [self.slots] * (b - len(members)))
        return b, admit_args

    def _post_admit(self, m: dict, first: int):
        """Register one admitted wave member: release padding blocks, enter
        the prompt's full blocks into the radix cache, bind the slot, and
        emit the fused-sampled first token.

        Chunked-prefill members (m["partial"]) stop after the radix
        registration: their KV now lives in the prefix cache, so the
        request requeues (front) and the next wave's match_prefix resumes
        one chunk further — no slot is bound and the chunk program's
        sampled token is discarded (it isn't the prompt's last position).
        """
        req, slot = m["req"], m["slot"]
        bs = self.block_size
        n, cached, tail_len = m["n"], m["cached"], m["tail_len"]
        tail_alloc, prefix_blocks = m["tail_alloc"], m["prefix_blocks"]
        # prefill amortization counters (bench --scenario prefix_cache
        # A/Bs the cluster-wide cached fraction): tokens served from the
        # cache tiers vs tokens actually run through prefill — counted at
        # real admission, not at prep (a rolled-back wave-overflow prep
        # would double count), and only BEYOND the request's own prior
        # extent (a resumed chunk pass re-matching its own pass-N-1
        # blocks is not a cache win)
        self.metrics.inc("prefill_cached_tokens",
                         max(0, cached - req._prefill_counted))
        self.metrics.inc("prefill_uncached_tokens", tail_len)
        # cost ledger mirrors the cluster counters' exact expressions, so
        # a request's record reconciles with the kvtier metrics deltas
        req._cost_cached += max(0, cached - req._prefill_counted)
        req._cost_uncached += tail_len
        req._prefill_counted = max(req._prefill_counted, n)
        tail_real = tail_alloc[: -(-tail_len // bs)]
        self.pool.release(tail_alloc[len(tail_real):])  # padding blocks

        # register the prompt's full blocks in the radix cache
        n_full = n // bs
        skip = cached // bs
        if n_full > skip and not self.cfg.slot_cache:
            self.pool.insert_prefix(m["prompt"][:n_full * bs],
                                    tail_real[:n_full - skip], skip)
        self.metrics.inc("batcher_ssm_scan_positions",
                         tail_len if self.cfg.ssm is not None else 0)

        if m.get("partial") and self.cfg.slot_cache:
            # state layers, ring layers: the chunk's K and V are of use
            # only with the state (the ring) at their end, which this
            # slot's row now holds; the
            # request keeps the slot and its blocks, and its next chunk
            # goes on from both (_prep_admit)
            req._blocks = prefix_blocks + tail_real
            req._kv_peak = max(req._kv_peak, len(req._blocks))
            req._held_slot = slot
            self._holds.add(slot)
            self._chunked_admissions += 1
            if not req._cancelled:
                self._requeue_front(req)
            else:
                self._fail_req(req, "cancelled")
            return
        if m.get("partial"):
            # drop our references — the radix keeps the chunk's blocks
            # alive (refcount-0 leaves evict only under pool pressure,
            # in which case the re-match simply re-prefills that chunk)
            self.pool.release(prefix_blocks)
            self.pool.release(tail_real)
            self._chunked_admissions += 1
            if n > req._chunk_high:
                req._chunk_high = n
                req._chunk_stalls = 0
                self._gauge_stall_streak(req)
            else:
                # eviction between passes undid progress; bounded, or two
                # pool-sized prompts could re-prefill each other forever.
                # Surfaced as a counter + streak gauge so operators see
                # cache-pressure thrash BEFORE it becomes a stall/failure
                # (docs/serving.md "Prefix-cache tier").
                req._chunk_stalls += 1
                self.metrics.inc("chunk_prefill_stalls")
                self._gauge_stall_streak(req)
                if req._chunk_stalls > 4:
                    self._fail_req(req, "KV block pool exhausted "
                                        "(chunked prefill made no progress)")
                    return
            if not req._cancelled:
                self._requeue_front(req)
            else:
                self._fail_req(req, "cancelled")
            return

        if req._held_slot is not None:   # the last chunk: the hold ends
            self._holds.discard(slot)
            req._held_slot = None
        req._blocks = prefix_blocks + tail_real
        req._kv_peak = max(req._kv_peak, len(req._blocks))
        self.block_tables[slot, :] = self._dummy
        owned = prefix_blocks + tail_real
        self.block_tables[slot, :len(owned)] = owned
        self.context_lens[slot] = n
        self.active[slot] = req
        self._admit_order.append(slot)
        if self._hist is not None:
            known = m["prompt"][: self.max_seq + 1]
            self._hist[slot, : len(known)] = known
            self._hist_synced[slot] = 0   # row rewritten: full re-sync
        if req.first_token_at is None:
            req.first_token_at = clock.now()
            req._clocks0 = (self.profiler.read(req.first_token_at),
                            self._stall_lost_s)
        self._emit(req, first)
        if self._hist is not None and req.tokens:
            # the fused-sampled first token extends the history
            self._hist[slot, min(n, self.max_seq)] = req.tokens[-1]
        if req.done.is_set() or len(req.tokens) >= req.max_new_tokens:
            self._finish_slot(slot)

    def _requeue_front(self, req: BatchRequest):
        """Put a request back at the queue head (chunked-prefill resume,
        preemption, wave overflow) — one counted path for every retry."""
        self.metrics.inc("batcher_requeues")
        with self._lock:
            self.queue.appendleft(req)

    def _fail_req(self, req: BatchRequest, error: Optional[str] = None):
        """Terminal failure for a request that never reaches _finish_req
        (cancelled in queue, admission refusal, pool exhaustion, scheduler
        stop/error) — same metrics/trace accounting as a normal finish, so
        submitted always reconciles with completed+failed."""
        self._drop_hold(req)
        req.error = req.error or error or "failed"
        req.finished_at = req.finished_at or clock.now()
        self._observe_finished(req)
        req.done.set()

    def _emit(self, req: BatchRequest, token: int):
        """Append a sampled token; mark done on eos (eos not kept)."""
        if req.eos_token_id is not None and token == req.eos_token_id:
            self._finish_req(req)
            return
        now = clock.now()
        if req._last_emit_at is not None:
            # per-GAP inter-token latency: near-zero inside a chunk's
            # burst, chunk-sized at boundaries, and stall-sized across a
            # preemption/re-prefill — a per-request mean would average
            # that 2s pause invisible
            gap = now - req._last_emit_at
            self.metrics.observe("batcher_inter_token", gap)
            # per-request gap list for the cost record's ITL p95 (the
            # SLO evaluator's per-request signal); bounded by the
            # request's own max_new_tokens, freed with the request
            req._gaps.append(gap)
        req._last_emit_at = now
        req.tokens.append(token)
        self._tokens_out += 1
        if req.stream_cb:
            try:
                req.stream_cb(token)
            except Exception as e:
                # delivery is best-effort (the client likely vanished),
                # but a broken callback must not fail silently forever
                if not getattr(req, "_stream_cb_warned", False):
                    req._stream_cb_warned = True
                    log.warning("stream callback failed for request "
                                "%s (%r); further tokens buffered only",
                                getattr(req, "request_tag", "?"), e)

    def _finish_req(self, req: BatchRequest):
        if req.kv_export:
            # disaggregated prefill pass: park the prompt's KV in the
            # host arena (while the blocks are still owned) so the
            # decode peer's /kv_fetch finds it
            try:
                self._export_request_kv(req)
            except Exception as e:
                # export is best-effort; the peer recomputes — but the
                # disagg plan paid for this prefill expecting a transfer
                log.warning("kv export failed for request %s (%r); "
                            "decode peer will recompute",
                            getattr(req, "request_tag", "?"), e)
        self.pool.release(req._blocks)
        req._blocks = []
        req.finished_at = clock.now()
        self._observe_finished(req)   # before done.set(): a waiter may
        req.done.set()                # scrape /metrics|/api/trace at once

    def _cost_record(self, req: BatchRequest, end: float) -> dict:
        """Assemble the request's cost-ledger record. The three phases
        partition [submitted_at, end) exactly — queue ends when the
        first admission wave starts dispatching, prefill ends at the
        first token, decode ends at finish — so queue + prefill + decode
        sum to the e2e span by construction (preemption re-prefills land
        in the decode phase, where the stall actually happened)."""
        admitted = req.admitted_at if req.admitted_at is not None else end
        first = req.first_token_at if req.first_token_at is not None \
            else admitted
        gaps = sorted(req._gaps)
        cost = {
            "queue_ms": round(max(0.0, admitted - req.submitted_at) * 1e3,
                              3),
            "prefill_ms": round(max(0.0, first - admitted) * 1e3, 3),
            "decode_ms": round(max(0.0, end - first) * 1e3, 3),
            **self._decode_account(req, end),
            "prefill_cached_tokens": req._cost_cached,
            "prefill_uncached_tokens": req._cost_uncached,
            "decode_tokens": len(req.tokens),
            "weight_passes": req._weight_passes,
            "kv_blocks_peak": req._kv_peak,
            "arena_restored_bytes": req._arena_restored_bytes,
            "arena_offloaded_bytes": req._arena_offloaded_bytes,
            "kv_transfer_bytes": req._kv_transfer_bytes,
            "spec_accepted_tokens": req._spec_acc,
            "spec_rejected_tokens": req._spec_rej,
            "spec_drafted_tokens": req._spec_drafted,
            "preemptions": req._preemptions,
        }
        if gaps:
            cost["itl_p95_ms"] = round(
                gaps[min(len(gaps) - 1, int(len(gaps) * 0.95))] * 1e3, 3)
            cost["itl_max_ms"] = round(gaps[-1] * 1e3, 3)
        return cost

    def _decode_account(self, req: BatchRequest, end: float) -> dict:
        """Where the request's decode phase went, from the scheduler's
        phase clocks as they stood at its first token and at ``end``:
        five parts that sum to ``decode_ms``, and what the stall
        accounting judged lost meanwhile, which lies inside them. Empty
        for a request that never emitted a token."""
        if req._clocks0 is None:
            return {}
        then, lost0 = req._clocks0
        now = self.profiler.read(end)

        def ms(*names):
            return sum(now.get(n, 0.0) - then.get(n, 0.0)
                       for n in names) * 1e3
        admit_run = ms("admit_run")
        return {key: round(v, 3) for key, v in (
            # decode program calls, this request's and its neighbours'
            ("decode_chunk_ms", ms("dispatch", "device_wait",
                                   "spec_verify")),
            # others' admit programs, and the host work around them
            # (radix match, packing, slot binding)
            ("decode_admit_run_ms", admit_run),
            ("decode_admit_host_ms", ms("admit") - admit_run),
            ("decode_emit_ms", ms("emit")),
            ("decode_host_ms", ms("host_prep", "spec_draft",
                                  "bookkeeping", "other", "between")),
            ("decode_stall_ms", (self._stall_lost_s - lost0) * 1e3))}

    def _observe_finished(self, req: BatchRequest):
        """Per-request histograms + retroactive trace spans, reconstructed
        from the request's own timestamps (the scheduler thread has no
        ambient trace context — the link rides req.trace_ctx), plus the
        cost-ledger record the worker returns with the result."""
        self._release_lora(req)   # every terminal outcome funnels here
        m = self.metrics
        m.inc("batcher_requests_migrated" if req._migrated
              else "batcher_requests_failed" if req.error
              else "batcher_requests_completed")
        end = req.finished_at or clock.now()
        if not req._migrated:
            # a migrated-out request's [submit, handoff) span is not a
            # served request — feeding it into the latency histograms
            # would skew the SLO inputs low and double-count the request
            # across the fleet (the destination's sample is the real one)
            m.observe("batcher_e2e_latency", end - req.submitted_at)
            if req.first_token_at is not None:
                m.observe("batcher_ttft",
                          req.first_token_at - req.submitted_at)
        cost = req.cost = self._cost_record(req, end)
        tr = trace.get_tracer()
        attrs = {"tokens": len(req.tokens), "preemptions": req._preemptions,
                 "queue_ms": cost["queue_ms"],
                 "prefill_ms": cost["prefill_ms"],
                 "decode_ms": cost["decode_ms"]}
        if req.error:
            attrs["error"] = req.error
        g = tr.record("batcher.request", req.submitted_at, end,
                      parent=req.trace_ctx, attrs=attrs)
        if req.admitted_at is not None:
            tr.record("batcher.queued", req.submitted_at, req.admitted_at,
                      parent=g, attrs={"wave": req._wave_span})
        if req.first_token_at is not None:
            tr.record("batcher.ttft", req.submitted_at, req.first_token_at,
                      parent=g)
            tr.record("batcher.decode", req.first_token_at, end, parent=g,
                      attrs={"tokens": len(req.tokens),
                             **{k: v for k, v in cost.items()
                                if k.startswith("decode_") and
                                k.endswith("_ms")}})
        # trace tail-sampling: errored and SLO-violating requests keep
        # their spans in the tracer's retained ring, so the postmortem
        # doesn't race the main ring's oldest-first eviction (a
        # migrated-out request is a handoff, not an error worth a slot)
        if (req.error and not req._migrated) or tsdb_mod.cost_within_slo(
                cost, self._slo_targets) is False:
            tr.retain(g.trace_id)

    def _finish_slot(self, slot: int):
        req = self.active[slot]
        self.active[slot] = None
        self.block_tables[slot, :] = self._dummy
        self.context_lens[slot] = 0
        if slot in self._admit_order:
            self._admit_order.remove(slot)
        if req is not None and not req.done.is_set():
            self._finish_req(req)

    def _preempt_youngest(self) -> bool:
        """Free the most recently admitted slot, requeueing its request."""
        if not self._admit_order:
            return False
        self.metrics.inc("batcher_preemptions")
        slot = self._admit_order.pop()
        req = self.active[slot]
        self.active[slot] = None
        self.block_tables[slot, :] = self._dummy
        self.context_lens[slot] = 0
        if req is not None:
            self.pool.release(req._blocks)
            req._blocks = []
            if self.cfg.slot_cache:
                # nothing of a preempted request's state or ring is
                # kept: it is prefilled again from its first token
                req._prefill_counted = 0
            req._preemptions += 1
            if req._preemptions > 5:
                self._fail_req(req, "preempted repeatedly: KV pool too small")
            else:
                # generated tokens are kept; re-admission prefills
                # prompt+tokens and resumes (see _prep_admit)
                self._requeue_front(req)
        return True

    def _ensure_growth(self, slot: int, k: int = 1) -> bool:
        """Make sure the slot owns every block a k-step chunk can write
        (positions [cl, cl + min(k, remaining) - 1]) — allocated up front
        so the whole chunk runs without host intervention."""
        req = self.active[slot]
        pos0 = int(self.context_lens[slot])
        k_eff = max(1, min(k, req.max_new_tokens - len(req.tokens)))
        bi0 = pos0 // self.block_size
        bi1 = (pos0 + k_eff - 1) // self.block_size
        if bi1 >= self.max_blocks:
            return False
        need = [bi for bi in range(bi0, bi1 + 1)
                if self.block_tables[slot, bi] == self._dummy]
        if not need:
            return True
        self._admitting = req   # bill growth-triggered offloads here too
        try:
            got = self.pool.alloc(len(need))
        finally:
            self._admitting = None
        if got is None:
            return False
        for bi, blk in zip(need, got):
            self.block_tables[slot, bi] = blk
        req._blocks.extend(got)
        req._kv_peak = max(req._kv_peak, len(req._blocks))
        return True

    # ---- the step -----------------------------------------------------

    def step(self) -> int:
        """Admit a wave + one K-token decode chunk. Returns active slots."""
        busy = 0
        work0 = (self._step_count, self._tokens_out)
        self._step_program_s = 0.0
        before = call_readings()   # should the host stand still this step
        prof_rec = self.profiler.step_begin()
        try:
            busy = self._step_inner()
            return busy
        finally:
            # the hot-path gauges the dashboard and /metrics surface: how
            # deep the queue is, how full the slots are, how much KV
            # headroom remains — refreshed every scheduler step
            m = self.metrics
            with self.profiler.phase("bookkeeping"):
                if busy:   # idle polls would drown the step histogram
                    m.observe("batcher_step", self.profiler.elapsed())
                m.gauge("batcher_queue_depth", len(self.queue))
                active_slots = sum(a is not None for a in self.active)
                m.gauge("batcher_active_slots", active_slots)
                if busy:   # idle polls would peg occupancy at 0
                    m.gauge("batcher_batch_occupancy",
                            active_slots / self.slots)
                m.gauge("batcher_free_kv_blocks", self.pool.free_count())
                self._sync_cache_metrics()
            # idle polls are discarded — the profile attributes steps
            # that did work, not the wait-for-work loop. "Did work" is
            # dispatched-or-emitted, NOT end-of-step occupancy: a short
            # request can admit, decode, and finish inside ONE step
            # (busy == 0 on return), and that step is exactly the kind
            # the profile must see
            did_work = bool(busy) or \
                (self._step_count, self._tokens_out) != work0
            wall = self.profiler.step_end(prof_rec, keep=did_work,
                                          active=busy)
            if did_work:
                # the step's clocks as counters, so that a scrape (and a
                # benchmark's result line, which prints the window's
                # batcher_* deltas) holds the busy wall by bracket
                for name, s in self.profiler.last_step.items():
                    m.inc(f"batcher_clock_{name}_ms", s * 1e3)
                # the step's wall outside its program calls: the host
                host_over = (wall - self._step_program_s
                             - self.STALL_HOST_BOUND_S)
                if host_over >= self.STALL_FLOOR_S:
                    self._host_stall(host_over, busy, before, wall)

    # brackets in which the step waits for a program, not the host
    PROGRAM_BRACKETS = ("dispatch", "device_wait", "spec_verify",
                        "admit", "admit_run")

    def _host_stall(self, lost_s: float, busy: int, before: tuple,
                    wall_s: float):
        """A busy step's host part went over its bound: the stall lies
        over the whole step, in the host bracket that took the most of
        it (the nested ``admit_prep`` / ``admit_post`` stand for
        ``admit``, whose rest is ``admit_run``)."""
        host = {name: s for name, s in self.profiler.last_step.items()
                if name not in self.PROGRAM_BRACKETS}
        t0 = self.profiler.epoch(before[0])
        self._stall("host", lost_s, 0, busy, before, (t0, t0 + wall_s),
                    max(host, key=host.get))

    def _step_inner(self) -> int:
        # service migration snapshots first: a flagged slot must not
        # ride another chunk (its exported KV would go stale) and its
        # freed slot/blocks are admission capacity this same step
        self._service_migrations()
        # drop cancelled slots next — frees their blocks for admission
        for slot in range(self.slots):
            req = self.active[slot]
            if req is not None and req._cancelled:
                req.error = req.error or "cancelled"
                self._finish_slot(slot)

        with self.profiler.phase("admit"):
            self._admit_wave()

        active = [i for i, a in enumerate(self.active) if a is not None]
        if not active:
            return 0

        with self.profiler.phase("host_prep"):
            # chunk size: cover the largest remaining budget in one
            # dispatch when the overshoot is small (dead compute beats a
            # round trip); otherwise the largest chunk some slot can fill
            max_rem = max(self.active[i].max_new_tokens
                          - len(self.active[i].tokens) for i in active)
            chunks = self.decode_chunks
            # per-request brownout cap (req.chunk_cap, from the master's
            # rung-3 decode_chunk_cap dispatch field): the tightest cap
            # among active riders clamps the wave — the filtered set is
            # a subset of decode_chunks (or its warmed min fallback), so
            # no unwarmed program shape is ever requested
            caps = [self.active[i].chunk_cap for i in active
                    if self.active[i].chunk_cap > 0]
            if caps:
                chunks = tuple(c for c in chunks if c <= min(caps)) \
                    or (min(chunks),)
            up = min((c for c in chunks if c >= max_rem),
                     default=None)
            if up is not None and up - max_rem <= self.CHUNK_OVERSHOOT_MAX:
                k = up
            else:
                k = next(c for c in chunks if c <= max_rem)

            # growth blocks for every position this chunk can write
            for slot in range(self.slots):
                while (self.active[slot] is not None
                       and not self._ensure_growth(slot, k)):
                    # _preempt_youngest may free `slot` itself — the loop
                    # condition re-checks before retrying
                    if not self._preempt_youngest():
                        self.active[slot].error = \
                            "cannot grow KV allocation"
                        self._finish_slot(slot)
                        break
            active = [i for i, a in enumerate(self.active) if a is not None]
            if not active:
                return 0

            r = self.slots
            tokens = np.zeros((r,), np.int32)
            seeds = np.zeros((r,), np.int32)
            steps = np.zeros((r,), np.int32)
            temps = np.full((r,), 1.0, np.float32)
            tks = np.zeros((r,), np.int32)
            tps = np.ones((r,), np.float32)
            ds = np.zeros((r,), bool)
            budget = np.zeros((r,), np.int32)
            eos = np.full((r,), -1, np.int32)
            aids = np.zeros((r,), np.int32)
            for i in active:
                req = self.active[i]
                tokens[i] = req.tokens[-1]
                seeds[i] = req.seed
                steps[i] = len(req.tokens)
                temps[i] = req.sampling.temperature
                tks[i] = req.sampling.top_k
                tps[i] = req.sampling.top_p
                ds[i] = req.sampling.do_sample
                budget[i] = min(k, req.max_new_tokens - len(req.tokens))
                if req.eos_token_id is not None:
                    eos[i] = req.eos_token_id
                aids[i] = req._lora_slot

            decode_args = {
                "k": int(k),
                "tokens": tokens.tolist(), "bt": self.block_tables.tolist(),
                "cl": self.context_lens.tolist(), "seeds": seeds.tolist(),
                "steps": steps.tolist(), "temps": temps.tolist(),
                "tks": tks.tolist(), "tps": tps.tolist(), "ds": ds.tolist(),
                "budget": budget.tolist(), "eos": eos.tolist(),
            }
            if aids.any():
                # key PRESENCE selects the lora program variant (see
                # _admit_group); a base-only wave pays zero delta cost
                decode_args["aids"] = aids.tolist()
        if self.speculative:
            return self._step_spec_wave(active, decode_args)
        self._dispatch_plain_chunk(active, decode_args)
        return len([a for a in self.active if a is not None])

    def _dispatch_plain_chunk(self, active, decode_args: dict) -> int:
        """One plain K-token decode chunk: dispatch (hook-aware), sync,
        emit, finish dead slots. Shared by the plain step and the
        adaptive-speculation fallback/probe path. Returns tokens
        emitted."""
        k = int(decode_args["k"])
        before = call_readings()
        if self.program_hook is not None:
            if self._hist is not None:
                # adaptive fallback under lockstep: a freshly-admitted
                # row's prompt region must still reach the followers, or
                # _apply_plain_hist would advance the watermark past a
                # hole the next spec probe's delta then skips forever
                decode_args = dict(decode_args,
                                   hist_delta=self._hist_deltas())
            toks, emits = self.program_hook(
                "decode", decode_args, lambda: self._run_decode(decode_args))
        else:
            toks, emits = self._run_decode(decode_args)
        self._step_count += 1
        w0, w1 = self._note_program(before, "decode", k, len(active))
        self.metrics.observe("batcher_decode_chunk", w1 - w0)
        chunk = trace.get_tracer().record(
            "batcher.decode_chunk", w0, w1,
            attrs={"chunk": self._step_count, "k": k, "slots": len(active),
                   "kv_bytes_per_token": self.paged.bytes_per_token,
                   "loop_steps": self.cfg.loop_steps,
                   "ssm_state_bytes_per_slot":
                       self._state_bytes_per_slot,
                   "kv_ring_bytes_per_slot": self._ring_bytes_per_slot,
                   "pool_positions": self._pool_positions,
                   "window_positions": self._window_positions,
                   "pool_kernel": int(self.pool_kernel)})
        self._report_first_use(chunk)
        # drafting history stays current even when the adaptive controller
        # runs plain chunks in a speculative batcher — pure function of
        # program outputs, so lockstep followers mirror it in replay()
        self._apply_plain_hist(toks, emits,
                               np.asarray(decode_args["cl"], np.int32))
        return self._emit_chunk_outputs(active, toks, emits, k, decode_args)

    def _emit_chunk_outputs(self, active, toks, emits, passes: int,
                            decode_args: dict) -> int:
        """Emit/finish/amortization epilogue for a plain chunk's [K, R]
        outputs (the speculative path's are [K, R, G+1] keeps-shaped:
        _emit_spec_outputs). Returns tokens emitted."""
        budget = decode_args["budget"]
        emitted = live_passes = 0
        with self.profiler.phase("emit"):
            for i in active:
                req = self.active[i]
                # emits[:, i] is True exactly for this slot's emitted
                # prefix (monotone: once dead — eos or budget — never
                # true again; the device masks eos out, so _emit's eos
                # branch can't re-trigger)
                cnt = int(emits[:, i].sum())
                for tok in toks[:cnt, i]:
                    self._emit(req, int(tok))
                emitted += cnt
                req._weight_passes += passes
                self.context_lens[i] += cnt
                hit_eos = cnt < int(budget[i])   # stopped pre-budget
                live_passes += cnt + hit_eos   # the eos pass ran alive
                if hit_eos or len(req.tokens) >= req.max_new_tokens:
                    self._finish_slot(i)
        self.metrics.inc("batcher_ssm_step_slot_passes",
                         live_passes if self.cfg.ssm is not None else 0)
        self._count_passes(decode_args, passes, emitted)
        return emitted

    def _count_passes(self, decode_args: dict, passes: int,
                      emitted: int) -> None:
        """Amortization: emitted tokens per weight-streaming pass (one
        pass per decode or verify iteration, however wide a draft is) —
        THE number continuous batching and wave speculation exist to
        raise. Gauge for live /metrics, counters for windowed ratios
        (bench.py takes per-rep deltas). ``batcher_sample_full_passes``
        counts the passes of chunks in which some sampling row has top_k
        off or beyond PREFIX_K, so that sample_batch's full tier ran:
        its ratio to ``batcher_weight_passes`` is that tier's share."""
        self.metrics.gauge("decode_tokens_per_weight_pass",
                           emitted / passes if passes else 0.0)
        self.metrics.inc("batcher_weight_passes", passes)
        self.metrics.inc("batcher_stack_passes",
                         passes * self.cfg.loop_steps)
        self.metrics.inc("batcher_tokens_emitted", emitted)
        if any(d and not 0 < tk <= PREFIX_K
               for tk, d in zip(decode_args["tks"], decode_args["ds"])):
            self.metrics.inc("batcher_sample_full_passes", passes)

    def _emit_spec_outputs(self, active, toks, keeps, eos_seen,
                           k_it: int, gammas) -> dict:
        """Emit/accounting epilogue for [K, R, G+1]-shaped speculative
        outputs. Per slot: emit the kept tokens, advance context/ledger
        counters, finish on the device's cumulative eos flag or an
        exhausted budget (a slot may legitimately emit fewer than its
        budget when every draft missed — 1 token/iteration). Returns
        {slot: (req, cnt, live, drafted)} for the controllers' feedback."""
        out = {}
        with self.profiler.phase("emit"):
            for i in active:
                req = self.active[i]
                g_i = int(gammas[i])
                cnt = int(keeps[:, i].sum())
                for t in range(keeps.shape[0]):
                    for tok in toks[t, i, : int(keeps[t, i])]:
                        self._emit(req, int(tok))
                # speedup accounting: tokens beyond one-per-iteration
                live = int((keeps[:, i] > 0).sum())
                acc_i = cnt - live
                drafted_i = g_i * live
                self._spec_accepted += acc_i
                req._weight_passes += k_it
                req._spec_acc += acc_i
                req._spec_rej += max(0, drafted_i - acc_i)
                req._spec_drafted += drafted_i
                self.context_lens[i] += cnt
                out[i] = (req, cnt, live, drafted_i)
                if bool(eos_seen[-1, i]) \
                        or len(req.tokens) >= req.max_new_tokens:
                    self._finish_slot(i)
        return out

    def _seed_wave_ctl(self, ctl):
        """Seed a fresh per-request controller from the batcher's shared
        arbitration state: the throughput EMAs and probe clocks carry
        over (they measure the host/workload, not the request), and when
        the fleet measurements already say drafting loses — the same
        hysteresis rule the controller applies itself — the request
        starts in plain mode instead of re-discovering the inversion
        over its own (possibly whole) lifetime. Probes keep both arms
        measured at the fleet cadence, so a workload shift flips the
        verdict back within probe_every chunks."""
        sh = self._wave_shared
        ctl.spec_tps = sh["spec_tps"]
        ctl.plain_tps = sh["plain_tps"]
        ctl._since_plain_probe = sh["since_plain_probe"]
        ctl._since_probe = sh["since_probe"]
        if (ctl.spec_tps is not None and ctl.plain_tps is not None
                and ctl.spec_tps < ctl.plain_tps * ctl.hysteresis):
            ctl.mode = "plain"
        return ctl

    def _sync_wave_shared(self, ctl):
        """Write one controller's arbitration state back to the shared
        store (last writer wins: active controllers tick in lockstep, so
        any of them is a good fleet clock)."""
        sh = self._wave_shared
        sh["spec_tps"] = ctl.spec_tps
        sh["plain_tps"] = ctl.plain_tps
        sh["since_plain_probe"] = ctl._since_plain_probe
        sh["since_probe"] = ctl._since_probe

    def _step_spec_wave(self, active, decode_args: dict) -> int:
        """Wave-level batched speculation: ONE fused draft+verify program
        serves the whole active wave, with per-slot draft widths riding
        as data (transformer.paged_speculative_chunk ``gammas``):
        ceil(k / (gamma+1)) verify iterations cover the same token budget
        when drafts miss, and up to (gamma+1)x fewer dispatches when they
        hit. Block growth was already ensured for k tokens — accepted
        cache writes never exceed the budget, and rejected scratch
        entries scatter to the dummy block.

        Each active request consults its OWN AdaptiveSpecController for
        this chunk's width: 0 means the slot rides the shared verify
        pass as plain decode (one exact token per iteration — including
        its plain-arm probes, which measure what riding actually costs
        it), so one draft-hostile request never drags its chunk-mates
        off the speculative path. The compiled program's gamma stays the
        configured static maximum — width mixes change DATA, never the
        compile key. Only when EVERY slot chooses 0 does the step run a
        true plain chunk (cheaper than a degenerate all-width-0 verify).

        Greedy rows are bitwise identical to plain decode at any width
        assignment (argmax acceptance); sampled rows keep the exact
        target distribution per position (ops/speculative.py
        accept_rejection_batch position-keyed PRNG), and the lockstep
        broadcast carries the widths in the args, so followers replay
        the identical program."""
        from distributed_llm_inferencing_tpu.ops.speculative import (
            AdaptiveSpecController)
        m = self.metrics
        g_max = self.spec_gamma
        with self.profiler.phase("spec_draft"):
            gammas = np.zeros((self.slots,), np.int32)
            for i in active:
                req = self.active[i]
                if self._spec_adaptive and g_max >= 1:
                    if req._spec_ctl is None:
                        req._spec_ctl = self._seed_wave_ctl(
                            AdaptiveSpecController(g_max))
                    gammas[i] = req._spec_ctl.choose()
                else:
                    gammas[i] = max(0, g_max)
        drafting = [i for i in active if gammas[i] > 0]
        riding = [i for i in active if gammas[i] == 0]
        m.gauge("spec_mode", 1.0 if drafting else 0.0)

        if not drafting:
            # every controller (or an explicit zero-draft spec_gamma)
            # says plain this chunk: run a true plain program and feed
            # each request's controller its own slice of the measurement
            k = int(decode_args["k"])
            compiled = (k, self.slots, self.max_blocks,
                        "aids" in decode_args) not in self._decode_fns
            reqs = {i: self.active[i] for i in active}
            before = {i: len(r.tokens) for i, r in reqs.items()}
            w0 = clock.now()
            self._dispatch_plain_chunk(active, decode_args)
            dt = clock.now() - w0
            for i, req in reqs.items():
                if req._spec_ctl is not None:
                    req._spec_ctl.record(
                        "plain", emitted=len(req.tokens) - before[i],
                        elapsed_s=dt, compiled=compiled)
                    self._sync_wave_shared(req._spec_ctl)
            return len([a for a in self.active if a is not None])

        g1 = g_max + 1
        k_it = -(-int(decode_args["k"]) // g1)
        args = dict(decode_args, k=k_it, gamma=g_max,
                    gammas=gammas.tolist())
        spec_key = ("spec", k_it, g_max, self.slots, self.max_blocks,
                    self._hist.shape[1], "aids" in decode_args)
        compiled = spec_key not in self._decode_fns
        # the controllers' clock is the runtime's (a test makes it tick
        # by reads); the stall accounting's is the profiler's
        before = call_readings()
        w0 = clock.now()
        if self.program_hook is not None:
            # lockstep: widths are scheduler decisions, so they ride the
            # broadcast args; history still ships as per-slot deltas
            with self.profiler.phase("spec_draft"):
                args["hist_delta"] = self._hist_deltas()
            local = dict(args, hist=self._hist)
            toks, keeps, eos_seen = self.program_hook(
                "spec_decode", args, lambda: self._run_spec_decode(local))
        else:
            args["hist"] = self._hist
            toks, keeps, eos_seen = self._run_spec_decode(args)
        self._step_count += 1
        self._spec_wave_dispatches += 1
        w1 = clock.now()
        m.inc("spec_wave_dispatches")
        m.observe("batcher_decode_chunk", w1 - w0)
        self._note_program(before, f"spec{g_max}", k_it, len(active))
        chunk = trace.get_tracer().record(
            "batcher.spec_wave_chunk", w0, w1,
            attrs={"chunk": self._step_count, "k": k_it,
                   "gamma_max": g_max, "slots": len(active),
                   "drafting": len(drafting), "riding": len(riding)})
        self._report_first_use(chunk)
        self._apply_spec_hist(toks, keeps,
                              np.asarray(decode_args["cl"], np.int32))

        per = self._emit_spec_outputs(active, toks, keeps, eos_seen,
                                      k_it, gammas)
        emitted = sum(cnt for (_, cnt, _, _) in per.values())
        drafted_total = sum(d for (_, _, _, d) in per.values())
        accepted_total = emitted - sum(
            live for (_, _, live, _) in per.values())
        dt = w1 - w0
        for i, (req, cnt, live, drafted_i) in per.items():
            if req._spec_ctl is None:
                continue
            if int(gammas[i]) > 0:
                req._spec_ctl.record("spec", emitted=cnt, elapsed_s=dt,
                                     drafted=drafted_i,
                                     accepted=cnt - live,
                                     compiled=compiled)
            else:
                req._spec_ctl.record("plain", emitted=cnt, elapsed_s=dt,
                                     compiled=compiled)
            self._sync_wave_shared(req._spec_ctl)
        self._count_passes(decode_args, k_it, emitted)
        m.inc("spec_wave_drafted_tokens", drafted_total)
        m.inc("spec_wave_accepted_tokens", accepted_total)
        m.inc("spec_wave_plain_rides", len(riding))
        if drafted_total:
            m.gauge("spec_acceptance_rate", accepted_total / drafted_total)
        return len([a for a in self.active if a is not None])

    # ---- background loop ----------------------------------------------

    def _loop(self):
        while not self._stop.is_set():
            try:
                busy = self.step()
            except Exception as e:
                # e.g. the lockstep hook reporting a degraded slice: fail
                # every waiter fast instead of letting them block to their
                # timeouts against a dead scheduler
                for slot in range(self.slots):
                    if self.active[slot] is not None:
                        self.active[slot].error = f"scheduler error: {e}"
                        self._finish_slot(slot)
                with self._lock:
                    drained = list(self.queue)
                    self.queue.clear()
                for req in drained:
                    self._fail_req(req, f"scheduler error: {e}")
                self._stop.set()
                return
            if not busy and not self.queue:
                self._work.wait(timeout=0.05)
                self._work.clear()


def _tree_bytes(tree) -> int:
    """Bytes of the arrays of ``tree``, as placed."""
    return int(sum(a.nbytes for a in jax.tree.leaves(tree)))


def _unstack_layers(layers: dict) -> list:
    """[L, ...]-stacked MoE layers -> a list of per-layer trees, which the
    layer loops run unrolled (transformer.scan_layer_stack). The grouped
    expert matmul takes whole buffers: under a scan each pass first
    copies every layer's experts out of the stack (1.2 GB a layer for
    kanana-2-30b-a3b, as much again as the pass has to read). Consumes
    ``layers`` leaf by leaf, so that no more than one stacked leaf lives
    twice (the caller must hold no other reference to the stack)."""
    def split(tree):
        for key in list(tree):
            if isinstance(tree[key], dict):
                split(tree[key])
            else:
                stacked = tree.pop(key)
                tree[key] = [stacked[i] for i in range(stacked.shape[0])]
                del stacked
    split(layers)
    n = len(jax.tree.leaves(layers, is_leaf=lambda x: isinstance(x, list))[0])
    return [jax.tree.map(lambda per: per[i], layers,
                         is_leaf=lambda x: isinstance(x, list))
            for i in range(n)]


def _expert_backend(num_devices: int = 1, platform: str = "") -> str:
    """``cfg.expert_matmul`` and ``cfg.pool_kernel`` of a program over
    ``num_devices`` devices of ``platform`` (the process's own backend
    unless a described one is named:
    scripts/compile_serving_programs.py): a Pallas kernel only in a
    one-device TPU program, which GSPMD need not partition."""
    on_tpu = (platform or jax.default_backend()) == "tpu"
    return "pallas" if on_tpu and num_devices == 1 else "xla"


mark_imported()   # the serving code is imported: one clock read
