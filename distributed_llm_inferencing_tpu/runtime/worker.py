"""Worker agent: the per-TPU-host data-plane process.

Capability-equivalent to the reference worker (worker/app.py:49-413) with
the same lifecycle RPC surface — /health, /load_model, /load_shard,
/unload_model, /inference — plus what the reference lacked: streaming
inference (SSE), Prometheus metrics, race-safe model lifecycle (the
reference mutated module globals from Flask handlers and was safe only
because gunicorn ran one sync worker, SURVEY.md §5.2).

The execution engine behind each loaded model is a jitted, mesh-sharded
JAX program (runtime/engine.py) instead of HF ``generate`` on torch
(reference: worker/app.py:297-305).
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Dict, Optional

import jax

from distributed_llm_inferencing_tpu import native
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
from distributed_llm_inferencing_tpu.runtime import events, httpd
from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
from distributed_llm_inferencing_tpu.utils import clock, locks, trace
from distributed_llm_inferencing_tpu.utils.faults import mutation_enabled
from distributed_llm_inferencing_tpu.utils.logging import setup_logging
from distributed_llm_inferencing_tpu.utils.metrics import Metrics
from distributed_llm_inferencing_tpu.utils.tokenizer import load_tokenizer

log = setup_logging("worker")

# Completed-result cache size for idempotent dispatch: the master
# retries with the same request_tag after a timeout, and the cached
# result makes at-least-once delivery execute exactly once.
IDEM_CACHE = int(os.environ.get("DLI_IDEM_CACHE", 256))

# Upper bound on sub-requests per /inference_batch RPC: each sub costs
# a worker thread, so the cap turns an arbitrarily long client list
# from a thread bomb into a 400.
BATCH_RPC_MAX = int(os.environ.get("DLI_BATCH_RPC_MAX", 256))

# Disaggregated serving role (FlowKV, docs/architecture.md): `prefill`
# nodes take long-prompt prefill passes, `decode` nodes take decode
# traffic (pulling prefix KV from prefill peers over /kv_fetch), and
# the default `mixed` keeps the pre-disaggregation behavior — a fleet
# that never sets the knob never changes. Role is MUTABLE worker state
# (POST /role): the master's elastic rebalancer flips workers between
# pools at runtime, re-advertised on /health and charted via the
# numeric dli_worker_role gauge below.
WORKER_ROLES = ("prefill", "decode", "mixed")
ROLE_CODE = {"mixed": 0.0, "prefill": 1.0, "decode": 2.0}

# How long a /migrate_out snapshot may wait on the scheduler before the
# endpoint gives up (the request then just keeps running here).
MIGRATE_TIMEOUT_S = 10.0

# Byte budget for one /kv_fetch response (the size cap on the KV export
# wire): the stream truncates at the cap and reports how many blocks
# were cut, and the fetching peer recomputes the rest.
KV_FETCH_MAX_MB = float(os.environ.get("DLI_KV_FETCH_MAX_MB", 256))

# Lease-fencing headers an HA master stamps on every RPC
# (docs/robustness.md "Replicated control plane"). Workers track the
# newest (term, holder nonce) they have seen and 409 any state-changing
# RPC from an older term — a paused-then-revived old leader can never
# double-dispatch, migrate, drain, or flip roles. Calls WITHOUT the
# headers (solo masters, direct clients, tests) are never fenced.
MASTER_TERM_HEADER = "X-DLI-Master-Term"
MASTER_NONCE_HEADER = "X-DLI-Master-Nonce"
STALE_TERM_HEADER = "X-DLI-Stale-Term"


class LoadedModel:
    def __init__(self, engine, tokenizer, source: str, batcher=None):
        self.engine = engine            # None in batched serving mode
        self.tokenizer = tokenizer
        self.source = source
        self.batcher = batcher          # ContinuousBatcher or None
        self.lock = locks.lock("worker.model")  # engine.generate is not reentrant


class WorkerAgent:
    """Holds loaded models and serves the lifecycle + inference RPC API."""

    def __init__(self, auth_key: Optional[str] = None,
                 role: Optional[str] = None):
        auth_key = auth_key if auth_key is not None else (
            os.environ.get("DLI_AUTH_KEY")
            if os.environ.get("DLI_AUTH_ENABLED", "").lower() in ("1", "true")
            else None)
        role = (role or os.environ.get("DLI_WORKER_ROLE") or "mixed").lower()
        if role not in WORKER_ROLES:
            raise ValueError(f"DLI_WORKER_ROLE must be one of "
                             f"{WORKER_ROLES}, got {role!r}")
        self.role = role
        self.models: Dict[str, LoadedModel] = {}
        self._models_lock = locks.lock("worker.models")
        self._loading: set = set()
        self.metrics = Metrics()
        self.started = clock.now()
        trace.set_service("worker")
        self.service = httpd.JsonHTTPService("worker", auth_key)
        s = self.service
        s.add("GET", "/health", self.health)
        s.add("GET", "/metrics", self.prometheus)
        s.add("GET", "/api/trace", self.api_trace)
        s.add("POST", "/load_model", self.load_model)
        s.add("POST", "/load_shard", self.load_shard)
        s.add("POST", "/unload_model", self.unload_model)
        # multi-LoRA adapter lifecycle (models/lora.py): make an adapter
        # host-resident / drop it; requests then name it per-submit
        s.add("POST", "/load_adapter", self.load_adapter)
        s.add("POST", "/unload_adapter", self.unload_adapter)
        s.add("POST", "/inference", self.inference)
        s.add("POST", "/inference_batch", self.inference_batch)
        # elastic disaggregation (docs/robustness.md "Live migration"):
        # runtime role flips and live in-flight request handoff
        s.add("POST", "/role", self.set_role)
        s.add("POST", "/migrate_out", self.migrate_out)
        # KV export wire (runtime/kvwire.py): stream host-arena blocks
        # to a decode-role peer as length-prefixed binary frames
        s.add("POST", "/kv_fetch", self.kv_fetch)
        s.add("POST", "/inference_stream", self.inference_stream)
        s.add("POST", "/cancel", self.cancel)
        s.add("POST", "/drain", self.drain)
        s.add("POST", "/undrain", self.undrain)
        s.add("POST", "/profile/start", self.profile_start)
        s.add("POST", "/profile/stop", self.profile_stop)
        # decode phase profiler (utils/profiler.py), distinct from the
        # XLA device profiler above: GET reads per-model summaries,
        # POST toggles at runtime
        s.add("GET", "/api/profile", self.api_profile)
        s.add("POST", "/api/profile", self.api_profile_config)
        s.add("GET", "/memory_profile", self.memory_profile)
        s.add("POST", "/ssh_setup", self.ssh_setup)
        self._profile_dir: Optional[str] = None
        self._profile_lock = locks.lock("worker.profile")
        # request_tag -> in-flight batcher request, so a caller (the master
        # on its own timeout, or an operator) can cancel and free the slot
        self._tagged: Dict[str, object] = {}
        self._tagged_lock = locks.lock("worker.tagged")
        # Idempotent dispatch (at-least-once delivery, exactly-once
        # execution): completed results keyed by request_tag in a bounded
        # LRU, plus an in-flight registry so a duplicate dispatch JOINS
        # the running execution instead of re-generating.
        self._idem: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self._idem_lock = locks.lock("worker.idem")
        self._inflight_tags: Dict[str, threading.Event] = {}
        # graceful drain: finish in-flight work, 503 new inference
        self._draining = False
        self._active = 0
        self._active_cv = locks.condition("worker.active")
        # shared peer-fetch client for every batched model on this
        # worker (pooled keep-alive sessions to each prefill peer, the
        # worker's own fault injector for rpc:/kv_fetch chaos, conn
        # accounting in this registry); lazily built — engine-only
        # workers never pay the requests import
        self._peer_client = None
        self._peer_client_lock = locks.lock("worker.peer_client")
        # pre-register the serve-side transfer counters and the
        # headline throughput counter the dashboard's TSDB rate series
        # charts (PR 5 rule — dlilint metric-not-preregistered)
        for name in ("kv_fetch_requests", "kv_fetch_served_blocks",
                     "kv_fetch_served_bytes", "kv_fetch_missing_blocks",
                     # compression accounting: raw = full-precision bytes
                     # the served blocks restore to, sent = the stored
                     # (possibly int8-quantized) bytes that actually
                     # crossed the wire — raw/sent is the wire
                     # compression ratio the planner prices with
                     "kv_wire_raw_bytes", "kv_wire_sent_bytes",
                     "tokens_generated", "role_flips",
                     "requests_migrated_out",
                     "stale_term_rejections"):
            self.metrics.inc(name, 0)
        # worker-side lease validation state: the newest master (term,
        # holder nonce) observed on any fenced RPC; see _term_guard
        self._master_term: tuple = (0, None)
        self._master_term_lock = locks.lock("worker.master_term")
        # numeric role gauge (0 mixed / 1 prefill / 2 decode): the
        # dashboard charts role flips as a TSDB sparkline, so the
        # series must exist from the first scrape. The literal-0 call
        # is the dlilint metric-not-preregistered contract (PR 5 rule
        # — the checker wants the registered-at-0 site); the second
        # call overwrites it with this worker's actual role.
        self.metrics.gauge("worker_role", 0.0)
        self.metrics.gauge("worker_role", ROLE_CODE.get(self.role, 0.0))

    # ---- worker-side lease validation --------------------------------

    def note_master_term(self, nonce: str, term: int) -> bool:
        """One fenced RPC's term check (docs/robustness.md "Replicated
        control plane"): True = current — the caller may proceed and
        the worker's high-water (term, holder) advanced if newer;
        False = stale (an older term, or a competing holder at the
        SAME term — the split-brain guard: whoever presented a term
        first holds it here, anyone else must take a higher one)."""
        with self._master_term_lock:
            cur_term, cur_nonce = self._master_term
            if term > cur_term:
                self._master_term = (int(term), str(nonce))
                return True
            if term == cur_term and (cur_nonce is None
                                     or cur_nonce == nonce):
                if cur_nonce is None:
                    self._master_term = (int(term), str(nonce))
                return True
        if mutation_enabled("stale_term_check"):
            # dliverify mutation gate (docs/static_analysis.md): skip
            # the worker-side fence — the double-dispatch bug the
            # `lease_takeover` scenario must catch. Test-only flag.
            return True
        self.metrics.inc("stale_term_rejections")
        return False

    def master_term(self) -> int:
        """Newest master term this worker has fenced against."""
        with self._master_term_lock:
            return self._master_term[0]

    def _term_guard(self, _request):
        """None when the caller may proceed; else the 409 refusal for a
        stale-term dispatch (the ``X-DLI-Stale-Term`` response header
        tells the old leader which term deposed it, so it steps down
        instead of striking/requeueing state it no longer owns)."""
        if _request is None:
            return None
        raw = _request.headers.get(MASTER_TERM_HEADER)
        if not raw:
            return None       # un-fenced caller (solo master / client)
        try:
            term = int(raw)
        except (TypeError, ValueError):
            return None
        nonce = _request.headers.get(MASTER_NONCE_HEADER) or ""
        if self.note_master_term(nonce, term):
            return None
        cur = self.master_term()
        return 409, {"status": "error", "stale_term": True,
                     "message": f"master term {term} is stale "
                                f"(current lease term: {cur})"}, \
            {STALE_TERM_HEADER: str(cur)}

    # ---- endpoints ---------------------------------------------------

    def health(self, body):
        """Parity with reference /health (worker/app.py:49-92): status +
        resource stats + loaded model inventory; TPU stats replace CUDA."""
        devices = []
        for d in jax.devices():
            entry = {"id": d.id, "platform": d.platform,
                     "kind": getattr(d, "device_kind", "unknown")}
            try:
                ms = d.memory_stats()
                if ms:
                    entry["bytes_in_use"] = ms.get("bytes_in_use")
                    entry["bytes_limit"] = ms.get("bytes_limit")
                    # the planner's memory-feasibility input (node-class
                    # fitting, parallel/planner.py): per-device HBM a
                    # candidate plan's weights + KV must fit under
                    entry["memory_bytes"] = ms.get("bytes_limit")
            except Exception as e:
                # CPU backends raise per scrape — stats stay best-effort
                log.debug("device memory_stats unavailable: %r", e)
            devices.append(entry)
        try:
            import psutil
            cpu = psutil.cpu_percent(interval=None)
            mem = psutil.virtual_memory().percent
        except Exception:
            cpu = mem = None
        with self._models_lock:  # load/unload mutate concurrently
            loaded = []
            for n, m in self.models.items():
                if m.batcher is not None:
                    loaded.append({"name": n, "source": m.source,
                                   "serving": "batched",
                                   "max_seq": m.batcher.max_seq,
                                   "scheduler": m.batcher.stats()})
                else:
                    loaded.append({"name": n, "source": m.source,
                                   "mesh": m.engine.mesh_spec.axis_sizes(),
                                   "max_seq": m.engine.max_seq,
                                   "adapters": m.engine.adapter_stats()})
        # host-arena occupancy fraction (worst across batched models):
        # the master's scheduler keeps prefill traffic off nodes whose
        # arena is about to evict the blocks a decode peer needs
        occ = None
        for lm in loaded:
            kv = (lm.get("scheduler") or {}).get("kvtier")
            if isinstance(kv, dict) and kv.get("occupancy") is not None:
                occ = max(occ or 0.0, float(kv["occupancy"]))
        return {
            "status": "draining" if self._draining else "online",
            "uptime_s": clock.now() - self.started,
            "role": self.role,
            "arena_occupancy": occ,
            "resources": {"cpu": cpu, "memory": mem, "devices": devices,
                          "device": jax.default_backend()},
            "compile_cache": _compile_cache_state(),
            # seconds g++ took to build the block pool in this process
            # (None: an up-to-date library was already on disk)
            "native_build_s": native.build_seconds,
            "loaded_models": loaded,
            "metrics": self.metrics.snapshot(),
        }

    def prometheus(self, body):
        return (self.metrics.prometheus().encode(), "text/plain; version=0.0.4")

    def api_trace(self, body):
        """This process's span ring buffer as Chrome trace-event JSON
        (utils/trace.py) — load in Perfetto, or let the master's
        /api/trace merge it into the cluster-wide timeline. When the
        decode profiler is armed, its sampled per-phase step spans merge
        onto a dedicated track of the same export."""
        tracer = trace.get_tracer()
        extra = []
        with self._models_lock:
            models = list(self.models.values())
        for m in models:
            if m.batcher is not None and m.batcher.profiler.enabled:
                extra.extend(m.batcher.profiler.chrome_events(
                    tracer.export_pid()))
        return tracer.chrome_trace(extra_events=extra)

    def _batcher_profilers(self):
        with self._models_lock:
            return [(n, m.batcher.profiler)
                    for n, m in self.models.items()
                    if m.batcher is not None]

    def api_profile(self, body):
        """Decode-profiler readout: per-phase wall attribution of the
        batcher step loop (``PhaseProfiler.summary()``) per batched
        model. With the profiler off the payload still holds the
        always-on ``clocks`` (the busy steps' wall by bracket since the
        batcher was built); the sampled tables are then empty."""
        out = {}
        for name, p in self._batcher_profilers():
            out[name] = {"summary": p.summary()}
        return {"status": "success", "profilers": out}

    def api_profile_config(self, body):
        """Runtime toggle: ``{"enabled": true, "sample_every": 4}``
        arms every batched model's profiler (``reset`` clears the
        ring). Applies to models loaded NOW; a model loaded later
        starts from the DLI_PROFILE env default."""
        cfgs = {}
        for name, p in self._batcher_profilers():
            cfgs[name] = p.configure(
                enabled=body.get("enabled"),
                sample_every=body.get("sample_every"),
                reset=bool(body.get("reset")))
        if not cfgs:
            return 409, {"status": "error",
                         "message": "no batched models loaded"}
        return {"status": "success", "profilers": cfgs}

    def _do_load(self, body) -> tuple:
        name = body.get("model_name")
        if not name:
            return 400, {"status": "error", "message": "model_name required"}
        with self._models_lock:
            if name in self.models:
                # idempotent, like reference worker/app.py:106-110
                return 200, {"status": "success",
                             "message": f"model {name} already loaded"}
            if name in self._loading:
                # the double-load race the reference left open (SURVEY §5.2)
                return 409, {"status": "error",
                             "message": f"model {name} load in progress"}
            self._loading.add(name)
        try:
            return self._do_load_inner(body, name)
        finally:
            with self._models_lock:
                self._loading.discard(name)

    def _do_load_inner(self, body, name) -> tuple:
        ckpt = body.get("checkpoint_path")
        native = body.get("native_checkpoint")
        mesh = MeshSpec.from_dict(body.get("mesh", {}))
        t0 = clock.now()
        if body.get("serving") == "batched" and any(
                getattr(mesh, ax) > 1 for ax in ("dp", "sp")):
            # validate BEFORE any (possibly huge) checkpoint restore; the
            # batcher shards tensors (tp/ep) and pipeline stages (pp) but
            # owns the batch dimension itself (runtime/batcher.py)
            return 400, {"status": "error",
                         "message": "batched serving supports tp/ep/pp "
                                    "mesh axes; drop dp/sp or use "
                                    "default mode"}
        if native:
            # converted-once artifact (models/checkpoint.py): no torch on
            # the serving path, restore is sharded when a mesh is in play
            from distributed_llm_inferencing_tpu.models import checkpoint
            from distributed_llm_inferencing_tpu.parallel.mesh import create_mesh
            cfg, params = checkpoint.load_checkpoint(
                native,
                mesh=create_mesh(mesh) if mesh.num_devices > 1 else None,
                mesh_spec=mesh if mesh.num_devices > 1 else None,
                dtype=body.get("dtype"))
            cfg = cfg.replace(name=name)
            source = native
        elif ckpt:
            from distributed_llm_inferencing_tpu.models.convert import load_hf_model
            cfg, params = load_hf_model(ckpt)
            cfg = cfg.replace(name=name)
            source = ckpt
        else:
            try:
                cfg = get_config(name)
            except KeyError as e:
                return 400, {"status": "error", "message": str(e)}
            params = None  # random init — explicit opt-in
            if not body.get("allow_random_init"):
                return 400, {
                    "status": "error",
                    "message": "no checkpoint_path given; pass "
                               "allow_random_init=true for a demo model"}
            source = "random-init"
        if body.get("dtype"):
            cfg = cfg.replace(dtype=body["dtype"])
        if body.get("kv_quantize"):
            # int8 KV cache (ops/kvcache.py): halves cache traffic and
            # footprint for long contexts, on top of weight int8
            cfg = cfg.replace(kv_quant=body["kv_quantize"])
        if body.get("quantize"):
            cfg = cfg.replace(quant=body["quantize"])
            if params is not None:
                # donate: the float tree is ours and never reused, so each
                # weight frees as its int8 twin lands (peak ≈ float model +
                # one stacked weight, not 1.5x). Pre-baked int8 checkpoints
                # (`convert --quantize int8`) skip even that.
                from distributed_llm_inferencing_tpu.ops.quant import (
                    maybe_quantize)
                params = maybe_quantize(params, cfg, donate=True)
        if body.get("embed_quantize"):
            # per-row int8 token-embedding table (ops/quant.py): the
            # tied-head read and the table footprint both halve
            cfg = cfg.replace(embed_quant=body["embed_quantize"])
            if params is not None:
                from distributed_llm_inferencing_tpu.ops.quant import (
                    maybe_quantize_embed)
                params = maybe_quantize_embed(params, cfg, donate=True)
        from distributed_llm_inferencing_tpu.utils.tokenizer import has_tokenizer
        tok_dir = body.get("tokenizer_path") or next(
            (d for d in (ckpt, native) if has_tokenizer(d)), None)
        tok = load_tokenizer(tok_dir, cfg.vocab_size)
        if body.get("serving") == "batched":
            # Continuous batching over the paged KV cache
            # (runtime/batcher.py) — requests share decode steps instead of
            # serializing behind the per-model lock.
            from distributed_llm_inferencing_tpu.runtime.batcher import (
                ContinuousBatcher)
            batcher = ContinuousBatcher(
                cfg, params,
                num_blocks=int(body.get("kv_blocks", 512)),
                block_size=int(body.get("kv_block_size", 16)),
                slots=int(body.get("slots", 8)),
                max_seq=body.get("max_seq"),
                # chunked prefill cap (blocks); 0/null disables
                prefill_chunk=(int(body["prefill_chunk"])
                               if body.get("prefill_chunk") is not None
                               else None) if "prefill_chunk" in body else 32,
                # on-device prompt-lookup speculative decoding
                # (transformer.paged_speculative_chunk): greedy requests
                # get up to spec_gamma+1 tokens/iteration bit-identically
                speculative=body.get("speculative"),
                spec_gamma=int(body.get("spec_gamma", 4)),
                # host-RAM KV offload arena budget (runtime/kvtier.py);
                # None defers to DLI_KV_HOST_MB, 0 disables the tier
                kv_host_mb=(float(body["kv_host_mb"])
                            if body.get("kv_host_mb") is not None
                            else None),
                kv_digest_chunk=(int(body["kv_digest_chunk"])
                                 if body.get("kv_digest_chunk") else None),
                # latency-tier knob: cap the decode-chunk size so token
                # gaps track real steps instead of K-sized bursts
                decode_chunk_cap=(int(body["decode_chunk_cap"])
                                  if body.get("decode_chunk_cap")
                                  else None),
                # cross-node KV transfer (runtime/kvwire.py): every
                # batched model shares the worker's peer-fetch client
                kv_fetcher=self.peer_client(),
                mesh_spec=mesh, metrics=self.metrics)
            batcher.start()
            lm = LoadedModel(None, tok, source, batcher=batcher)
            stats = batcher.stats()
        else:
            engine = InferenceEngine(
                cfg, params, mesh_spec=mesh, max_seq=body.get("max_seq"),
                metrics=self.metrics)
            lm = LoadedModel(engine, tok, source)
            stats = engine.stats()
        with self._models_lock:
            self.models[name] = lm
        self.metrics.inc("models_loaded")
        log.info("loaded %s from %s in %.1fs", name, source, clock.now() - t0)
        out = {"status": "success", "message": f"model {name} loaded",
               "load_time_s": clock.now() - t0, "stats": stats}
        if lm.batcher is not None:
            # where the load's seconds went (utils/profiler.py, the
            # program account): the build, and the programs used so far
            out["programs"] = lm.batcher.profiler.programs()
        return 200, out

    def load_model(self, body, _request=None):
        # lease-fenced like every state-changing RPC: a revived stale
        # leader must not (re)load models under the current leader
        stale = self._term_guard(_request)
        if stale:
            return stale
        if self._draining:
            return self._refuse_draining()
        with self.metrics.time("load_model"):
            return self._do_load(body)

    def load_shard(self, body, _request=None):
        """Reference parity (worker/app.py:139-206): registering a 'shard'.

        TPU-native meaning: a placement plan (mesh spec + partition specs,
        parallel/plan.py) rather than a weight-file directory — loading a
        'shard' is loading the model with that plan's mesh. Lease-fenced
        like /load_model.
        """
        stale = self._term_guard(_request)
        if stale:
            return stale
        if self._draining:
            return self._refuse_draining()
        plan = body.get("plan")
        if not plan:
            return 400, {"status": "error",
                         "message": "plan required (parallel/plan.py output)"}
        body = dict(body)
        body.setdefault("model_name", plan.get("model"))
        body.setdefault("mesh", plan.get("mesh", {}))
        body.setdefault("max_seq", plan.get("max_seq"))
        return self._do_load(body)

    def unload_model(self, body, _request=None):
        """Parity with worker/app.py:208-250; device buffers are dropped by
        deleting the engine (XLA frees HBM on GC). Lease-fenced: a
        revived stale leader's best-effort unload (remove_node tail)
        must not evict a model the current leader is serving
        mid-generation."""
        stale = self._term_guard(_request)
        if stale:
            return stale
        name = body.get("model_name")
        with self._models_lock:
            m = self.models.pop(name, None)
        if m is None:
            return 404, {"status": "error",
                         "message": f"model {name} not loaded"}
        if m.batcher is not None:
            m.batcher.stop()
        del m
        import gc
        gc.collect()
        self.metrics.inc("models_unloaded")
        return {"status": "success", "message": f"model {name} unloaded"}

    def load_adapter(self, body, _request=None):
        """Make a LoRA adapter host-resident for a loaded model
        (lease-fenced like /load_model; the master's lazy dispatch-time
        load and operator calls both land here). Idempotent for an
        already-resident name. Any refusal is a structured 400 — a
        request naming an unloadable adapter FAILS, it never silently
        serves base weights."""
        stale = self._term_guard(_request)
        if stale:
            return stale
        if self._draining:
            return self._refuse_draining()
        model = body.get("model_name")
        adapter = body.get("adapter")
        source = body.get("source")
        if not (model and adapter and source):
            return 400, {"status": "error",
                         "message": "model_name, adapter and source "
                                    "required"}
        m = self.models.get(model)
        if m is None:
            return 404, {"status": "error",
                         "message": f"model {model} not loaded"}
        with self.metrics.time("load_adapter"):
            try:
                if m.batcher is not None:
                    info = m.batcher.load_adapter(adapter, source)
                else:
                    ad = m.engine.load_adapter(name=adapter, source=source)
                    info = {"name": ad.name, "rank": ad.rank,
                            "nbytes": ad.nbytes, "evicted": []}
            except ValueError as e:
                events.emit("adapter-load-failed", adapter=adapter,
                            model=model, error=str(e))
                return 400, {"status": "error", "adapter": adapter,
                             "message": str(e)}
        for ev in info.get("evicted", []):
            events.emit("adapter-evicted", adapter=ev, model=model,
                        evicted_for=adapter)
        events.emit("adapter-loaded", adapter=adapter, model=model,
                    rank=info.get("rank"), nbytes=info.get("nbytes"),
                    lazy=bool(body.get("lazy")))
        return {"status": "success", **info}

    def unload_adapter(self, body, _request=None):
        """Drop a host-resident adapter (refused while requests still
        reference it). Lease-fenced like /unload_model."""
        stale = self._term_guard(_request)
        if stale:
            return stale
        model = body.get("model_name")
        adapter = body.get("adapter")
        m = self.models.get(model)
        if m is None:
            return 404, {"status": "error",
                         "message": f"model {model} not loaded"}
        try:
            if m.batcher is not None:
                dropped = m.batcher.unload_adapter(adapter)
            else:
                dropped = m.engine.unload_adapter(adapter)
        except ValueError as e:
            return 409, {"status": "error", "message": str(e)}
        if not dropped:
            return 404, {"status": "error",
                         "message": f"adapter {adapter} not resident"}
        return {"status": "success", "adapter": adapter}

    def _prep_inference(self, body):
        name = body.get("model_name")
        m = self.models.get(name)
        if m is None:
            raise KeyError(f"model {name} not loaded")
        resume = body.get("resume")
        resume = resume if isinstance(resume, dict) else None
        if (resume and resume.get("prompt_tokens")
                and "prompt_tokens" not in body):
            # a migrated-in request resumes from the SOURCE's exact
            # token ids — re-tokenizing the text would be identical on
            # a same-tokenizer fleet, but exactness is the contract
            prompt = [int(t) for t in resume["prompt_tokens"]]
        elif "prompt_tokens" in body:
            prompt = [int(t) for t in body["prompt_tokens"]]
        else:
            prompt = m.tokenizer.encode(body.get("prompt", ""))
        if not prompt:
            raise ValueError("empty prompt")
        sp_body = body.get("sampling", {})
        sp = SamplingParams(
            temperature=float(sp_body.get("temperature", 0.8)),
            top_k=int(sp_body.get("top_k", 50)),
            top_p=float(sp_body.get("top_p", 0.95)),
            do_sample=bool(sp_body.get("do_sample", True)))
        # reference parity: max_length counts prompt+new (views.py:351);
        # max_new_tokens preferred.
        if "max_new_tokens" in body:
            max_new = int(body["max_new_tokens"])
        else:
            max_new = max(1, int(body.get("max_length", 100)) - len(prompt))
        spec = body.get("speculative")
        try:
            gamma = int(body.get("spec_gamma", 4))
        except (TypeError, ValueError):
            raise ValueError("spec_gamma must be an integer")
        if spec is not None:
            if spec != "ngram":
                raise ValueError(f"unknown speculative mode {spec!r} "
                                 "(supported: 'ngram')")
            if not 1 <= gamma <= 16:
                raise ValueError("spec_gamma must be in [1, 16]")
            if m.batcher is not None:
                raise ValueError(
                    "speculative decoding is engine-mode only; this model "
                    "serves via the continuous batcher")
        # single source of generate() kwargs: every serving path (blocking,
        # SSE, lockstep co-execution) passes these verbatim, so they can
        # never silently disagree about a request's decode configuration.
        # A resume record's seed wins: an engine-mode node receiving a
        # migrated request regenerates the FULL stream from position 0,
        # and the position-keyed PRNG makes that reproduction exact only
        # under the source's seed.
        if resume is not None and resume.get("seed") is not None:
            seed = int(resume["seed"])
        else:
            seed = int(body.get("seed", time.time_ns() % (1 << 31)))
        # a migrated request must resume under its source ADAPTER too —
        # same exactness contract as the seed above
        if resume is not None and resume.get("adapter"):
            adapter = str(resume["adapter"])
        else:
            adapter = body.get("adapter") or None
        gen_kw = {
            "seed": seed,
            "speculative": spec,
            "spec_gamma": gamma,
            "adapter": adapter,
        }
        return m, prompt, sp, max_new, gen_kw

    # ---- drain / idempotency plumbing --------------------------------

    def _refuse_draining(self):
        return 503, {"status": "error", "draining": True,
                     "message": "worker is draining; retry another node"}, \
               {"Retry-After": "5"}

    def _try_begin_inference(self) -> bool:
        """Atomically either register an in-flight inference or refuse
        because a drain is in progress. The draining check and the
        active-count increment share one lock: without that, a request
        could pass the check before drain set the flag yet not be
        counted when drain samples the in-flight total — and drain
        would report idle with work about to start."""
        with self._active_cv:
            if self._draining:
                return False
            self._active += 1
        return True

    def _end_inference(self):
        with self._active_cv:
            self._active -= 1
            self._active_cv.notify_all()

    def _busy_count(self) -> int:
        """Requests still owed an answer. A batched HTTP request shows
        up in BOTH the handler count and its batcher's inflight() —
        max() de-duplicates that (it is exact for idle detection: zero
        iff both are zero) while still covering batcher requests whose
        handler already gave up (cancelled/abandoned tags)."""
        with self._active_cv:
            n = self._active
        with self._models_lock:
            models = list(self.models.values())
        batched = sum(m.batcher.inflight() for m in models
                      if m.batcher is not None)
        return max(n, batched)

    def _wait_idle(self, timeout: float) -> bool:
        deadline = clock.now() + timeout
        while clock.now() < deadline:
            if self._busy_count() == 0:
                return True
            clock.sleep(0.05)
        return self._busy_count() == 0

    def drain(self, body, _request=None):
        """Graceful drain — no reference counterpart (its only lifecycle
        was kill -9). Marks the worker draining: new inference gets 503
        with Retry-After (the master fails over without recording a
        strike, runtime/master.py), in-flight batcher/engine requests
        run to completion, and this call returns once idle (or when
        ``timeout`` seconds elapse, reporting what is still in flight).
        Lease-fenced: only the current lease holder may drain this
        worker — a revived old leader's drain is a 409."""
        stale = self._term_guard(_request)
        if stale:
            return stale
        with self._active_cv:   # fences against _try_begin_inference
            self._draining = True
        self.metrics.gauge("draining", 1)
        idle = self._wait_idle(float(body.get("timeout", 30)))
        return {"status": "success", "drained": idle,
                "in_flight": self._busy_count()}

    def undrain(self, body, _request=None):
        """Re-open a drained worker for new inference (lease-fenced
        like /drain)."""
        stale = self._term_guard(_request)
        if stale:
            return stale
        with self._active_cv:
            self._draining = False
        self.metrics.gauge("draining", 0)
        return {"status": "success"}

    def inference(self, body, _request=None):
        # semantic span under the HTTP server span; the batcher/engine
        # below parent their own spans to it (contextvar or req.trace_ctx)
        stale = self._term_guard(_request)
        if stale:
            return stale
        if not self._try_begin_inference():
            return self._refuse_draining()
        try:
            with trace.get_tracer().span(
                    "worker.inference",
                    attrs={"model": str(body.get("model_name")),
                           "tag": str(body.get("request_tag") or "")}):
                return self._inference_idempotent(body)
        finally:
            self._end_inference()

    def inference_batch(self, body, _request=None):
        """Multiplexed dispatch: N sub-requests in ONE RPC, per-request
        results streamed back as chunked JSON lines the moment each
        completes (httpd.jsonl_stream keeps the connection reusable).
        Every sub-request keeps the exact /inference semantics — its own
        idempotency tag (replay/join), its own drain refusal, its own
        structured error — so a master can fail/requeue ONE sub-request
        without touching its batch siblings. Batcher-mode models admit
        owned (fresh-tag) sub-requests through ContinuousBatcher
        .submit_many in wire order, so FIFO survives the multiplexing.
        """
        stale = self._term_guard(_request)
        if stale:
            # whole-batch refusal: every sub came from the same stale
            # master, and the current leader re-dispatches them all
            return stale
        subs = body.get("requests")
        if not isinstance(subs, list) or not subs:
            return 400, {"status": "error",
                         "message": "requests: non-empty list required"}
        if len(subs) > BATCH_RPC_MAX:
            # one thread + one queue slot per sub: an uncapped list is
            # a one-connection thread bomb (masters send DISPATCH_BATCH)
            return 400, {"status": "error",
                         "message": f"requests: at most {BATCH_RPC_MAX} "
                                    f"sub-requests per batch RPC"}
        if self._draining:
            # whole-batch refusal BEFORE any work starts: the master
            # fails the batch over without a breaker strike
            return self._refuse_draining()
        model = body.get("model_name")
        with self._models_lock:
            m = self.models.get(model)
        self.metrics.inc("batch_rpcs")
        self.metrics.inc("batch_sub_requests", len(subs))
        import queue as _queue
        out: "_queue.Queue" = _queue.Queue()
        ctx = trace.current()   # sub-request work runs on helper threads

        def emit(tag, status, payload):
            out.put({"request_tag": tag, "status": status, "body": payload})

        def norm(res):
            if isinstance(res, tuple):
                return res[0], res[1]
            return 200, res

        def run_generic(sub_body, tag):
            """One sub-request through the standard idempotent path —
            joins, engine-mode models, untagged requests."""
            try:
                if not self._try_begin_inference():
                    st, pl = norm(self._refuse_draining())
                else:
                    try:
                        # the master injects each sub-request's own trace
                        # context into its body — parent there so this
                        # span lands in the request's trace, not the
                        # batch RPC's
                        with trace.get_tracer().span(
                                "worker.inference",
                                parent=trace.extract(sub_body) or ctx,
                                attrs={"model": str(model),
                                       "tag": tag or ""}):
                            st, pl = norm(self._inference_idempotent(
                                sub_body))
                    finally:
                        self._end_inference()
            except Exception as e:
                st, pl = 500, {"status": "error", "message": str(e)}
            emit(tag, st, pl)

        owned = []   # (sub_body, tag, my_event-or-None) for batcher path
        for sub in subs:
            sub_body = dict(sub)
            sub_body["model_name"] = model
            tag = (str(sub.get("request_tag"))
                   if sub.get("request_tag") else None)
            if m is not None and m.batcher is not None:
                if tag is None:
                    owned.append((sub_body, None, None))
                    continue
                kind, obj = self._idem_claim(tag)
                if kind == "cached":
                    self.metrics.inc("idempotent_hits")
                    emit(tag, 200, dict(obj, idempotent=True))
                    continue
                if kind == "own":
                    owned.append((sub_body, tag, obj))
                    continue
                # kind == "join": the generic path's join loop handles it
            threading.Thread(target=run_generic, args=(sub_body, tag),
                             daemon=True).start()

        self._start_owned_batch(m, owned, emit, ctx)

        def events():
            # every sub-request emits exactly one line, on every path
            for _ in range(len(subs)):
                yield out.get()

        return httpd.jsonl_stream(_request, events())

    def _start_owned_batch(self, m, owned, emit, ctx):
        """Prep + multi-submit the owned (fresh) batcher sub-requests in
        wire order, then wait each out on its own thread. Prep/validation
        failures resolve per sub-request (400 line + ownership release),
        never the batch."""
        specs, metas = [], []
        for sub_body, tag, my_ev in owned:
            t0 = clock.now()
            try:
                _m, prompt, sp, max_new, _gk = self._prep_inference(sub_body)
                if len(prompt) + max_new > m.batcher.max_seq:
                    raise ValueError(
                        f"prompt ({len(prompt)}) + max_new_tokens "
                        f"({max_new}) exceeds max_seq {m.batcher.max_seq}")
            except Exception as e:
                # EVERY prep failure must resolve this sub in place —
                # an exception escaping the loop would leak the earlier
                # subs' _active counts and never-released idempotency
                # events (specs built but submit_many never reached)
                if my_ev is not None:
                    self._idem_release(tag, my_ev, None)
                st = 400 if isinstance(e, (KeyError, ValueError)) else 500
                emit(tag, st, {"status": "error", "message": str(e)})
                continue
            if not self._try_begin_inference():
                if my_ev is not None:
                    self._idem_release(tag, my_ev, None)
                st, pl = self._refuse_draining()[:2]
                emit(tag, st, pl)
                continue
            resume = sub_body.get("resume")
            specs.append({"prompt": prompt, "max_new_tokens": max_new,
                          "sampling": sp,
                          "eos_token_id": m.tokenizer.eos_token_id,
                          "seed": sub_body.get("seed"),
                          "kv_transfer_bytes": 0,
                          "kv_export": bool(sub_body.get("kv_export")),
                          "resume": (resume if isinstance(resume, dict)
                                     else None),
                          "chunk_cap": sub_body.get("decode_chunk_cap"),
                          "adapter": sub_body.get("adapter"),
                          "trace_ctx": trace.extract(sub_body) or ctx})
            self._note_prefix(m, sub_body, prompt)
            metas.append((sub_body, tag, my_ev, t0))
        # peer KV prefetches run CONCURRENTLY across the batch: serial
        # blocking fetches in the loop above would let one dead peer's
        # connect timeout delay every later sibling's submission by the
        # full timeout each — in parallel the batch pays one timeout

        def _fetch_seq(i):
            return self._resume_seq(specs[i]["prompt"],
                                    specs[i].get("resume"))

        fetch_idx = [i for i, (sub_body, *_r) in enumerate(metas)
                     if sub_body.get("kv_source")]
        if fetch_idx:
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(
                    max_workers=min(8, len(fetch_idx))) as ex:
                for i, pre in zip(fetch_idx, ex.map(
                        lambda i: self._prefetch_kv(
                            m, metas[i][0], _fetch_seq(i)),
                        fetch_idx)):
                    specs[i]["kv_transfer_bytes"] = pre
        try:
            reqs = m.batcher.submit_many(specs) if specs else []
        except Exception as e:
            # all-or-nothing submit refused the whole group: release
            # every admitted sub (count + idempotency event) in place
            for _sub_body, tag, my_ev, _t0 in metas:
                if my_ev is not None:
                    self._idem_release(tag, my_ev, None)
                self._end_inference()
                emit(tag, 500, {"status": "error", "message": str(e)})
            return
        for breq, meta in zip(reqs, metas):
            threading.Thread(target=self._wait_owned,
                             args=(m, breq, emit) + meta,
                             daemon=True).start()

    def _wait_owned(self, m, breq, emit, sub_body, tag, my_ev, t0):
        """Block on one batch-submitted generation; mirror the single
        /inference result shape, metrics, cancel registration, and
        idempotency-cache population."""
        res = None
        st, pl = 500, {"status": "error", "message": "internal error"}
        if tag is not None:
            with self._tagged_lock:
                self._tagged[tag] = breq
        try:
            with self.metrics.time("inference"):
                toks = breq.wait(
                    timeout=float(sub_body.get("timeout", 300)))
            res = {
                "status": "success",
                "result": m.tokenizer.decode(toks),
                "tokens": toks,
                "execution_time": clock.now() - t0,
                "ttft_ms": breq.ttft_ms,
                "cost": breq.cost,
                "scheduler": m.batcher.stats(),
            }
            self.metrics.inc("requests_completed")
            self.metrics.inc("tokens_generated", len(toks))
            st, pl = 200, res
        except TimeoutError as e:
            breq.cancel()   # free the slot; don't generate for nobody
            st, pl = 408, {"status": "error", "message": str(e)}
        except (ValueError, RuntimeError) as e:
            if breq._migrated:
                # live-migration handoff rides this sub-request's own
                # result line: 303 + resume record, same semantics as
                # the single-dispatch path
                st, pl = 303, {"status": "migrated",
                               "resume": breq.resume_record,
                               "request_tag": tag}
            else:
                st, pl = 400, {"status": "error", "message": str(e)}
        except Exception as e:
            st, pl = 500, {"status": "error", "message": str(e)}
        finally:
            if tag is not None:
                with self._tagged_lock:
                    self._tagged.pop(tag, None)
                self._idem_release(tag, my_ev, res)
            self._end_inference()
            emit(tag, st, pl)

    def set_role(self, body, _request=None):
        """Runtime role flip (the master's elastic rebalancer,
        docs/robustness.md "Live migration"): role becomes mutable
        worker state, re-advertised on the next /health and charted
        via the numeric ``dli_worker_role`` gauge. The routing
        consequences are entirely the master's — this worker serves
        whatever is dispatched to it either way. Lease-fenced: only
        the current lease holder may flip roles."""
        stale = self._term_guard(_request)
        if stale:
            return stale
        role = str(body.get("role") or "").lower()
        if role not in WORKER_ROLES:
            return 400, {"status": "error",
                         "message": f"role must be one of {WORKER_ROLES},"
                                    f" got {role!r}"}
        prev, self.role = self.role, role
        self.metrics.gauge("worker_role", ROLE_CODE.get(role, 0.0))
        if prev != role:
            self.metrics.inc("role_flips")
            log.info("worker role flipped %s -> %s", prev, role)
        return {"status": "success", "role": role, "previous": prev}

    def migrate_out(self, body, _request=None):
        """Live in-flight migration handoff (master rebalancer): ask
        the owning batcher to snapshot the tagged request — export its
        computed KV through the last context position into the host
        arena (where a destination's /kv_fetch finds it) and evict the
        slot. The ORIGINAL dispatch then answers with a 303 + resume
        record — the handoff descriptor rides the already-open RPC, so
        the master's dispatch thread stays the request's only lifecycle
        owner; this endpoint only triggers and confirms. 404: no such
        in-flight tag. 409: the request completed first (the
        migrate-vs-complete race — the normal result stands, the
        request_tag idempotency cache replays it, nothing double-emits)
        or the serving mode cannot migrate (engine mode, lockstep).
        Lease-fenced: a stale master must not migrate a request the
        current leader is streaming."""
        stale = self._term_guard(_request)
        if stale:
            return stale
        tag = body.get("request_tag")
        if not tag:
            return 400, {"status": "error",
                         "message": "request_tag required"}
        with self._tagged_lock:
            req = self._tagged.get(str(tag))
        if req is None:
            return 404, {"status": "error",
                         "message": f"no in-flight request tagged {tag!r}"}
        name = body.get("model_name")
        with self._models_lock:
            models = ([self.models[name]] if name in self.models
                      else list(self.models.values()))
        batcher = next((m.batcher for m in models
                        if m.batcher is not None), None)
        if batcher is None:
            return 409, {"status": "error",
                         "message": "engine-mode requests cannot migrate"}
        rec = batcher.migrate_out(req, timeout=MIGRATE_TIMEOUT_S)
        if rec is None:
            return 409, {"status": "error",
                         "message": f"request {tag!r} completed before "
                                    "the snapshot (or cannot migrate)"}
        self.metrics.inc("requests_migrated_out")
        return {"status": "success", "request_tag": str(tag)}

    def peer_client(self):
        """The worker-wide KVFetchClient (runtime/kvwire.py), built on
        first use and injected into every batched model's batcher."""
        with self._peer_client_lock:
            if self._peer_client is None:
                from distributed_llm_inferencing_tpu.runtime.kvwire import (
                    KVFetchClient)
                self._peer_client = KVFetchClient(
                    auth_key=self.service.auth_key,
                    faults=self.service.faults, metrics=self.metrics)
            return self._peer_client

    def kv_fetch(self, body, _request=None):
        """KV export wire (runtime/kvwire.py): given a model and a list
        of block digests, stream the matching host-arena blocks back as
        length-prefixed binary frames over the chunked httpd response.
        Auth-gated like every route (fleet bearer token); size-capped at
        DLI_KV_FETCH_MAX_MB — past the cap the stream truncates and the
        terminal frame says so, and the peer recomputes the rest. Blocks
        the arena no longer holds are simply reported missing: eviction
        raced the fetch, recompute covers it."""
        from distributed_llm_inferencing_tpu.runtime import kvwire
        name = body.get("model_name")
        with self._models_lock:
            m = self.models.get(name)
        if m is None or m.batcher is None or m.batcher.kvtier is None:
            return 404, {"status": "error",
                         "message": f"model {name} not serving a KV "
                                    "arena on this worker"}
        digests = body.get("digests")
        if (not isinstance(digests, list) or not digests
                or not all(isinstance(d, str) for d in digests)):
            return 400, {"status": "error",
                         "message": "digests: non-empty list of strings "
                                    "required"}
        if len(digests) > kvwire.MAX_DIGESTS:
            return 400, {"status": "error",
                         "message": f"at most {kvwire.MAX_DIGESTS} "
                                    "digests per fetch"}
        arena = m.batcher.kvtier.arena
        cap = int(KV_FETCH_MAX_MB * 1024 * 1024)
        self.metrics.inc("kv_fetch_requests")

        def frames():
            sent = served = truncated = 0
            missing = []
            for i, d in enumerate(digests):
                # ship the STORED representation as-is: an int8 arena's
                # block crosses the wire as its quantized record (kvq8
                # frame), never requantized or inflated on send
                obj = arena.peek_stored(d)
                if obj is None:
                    missing.append(d)
                    self.metrics.inc("kv_fetch_missing_blocks")
                    continue
                frame = kvwire.encode_stored(d, obj)
                if sent + len(frame) > cap:
                    truncated = len(digests) - i
                    break
                sent += len(frame)
                served += 1
                self.metrics.inc("kv_fetch_served_blocks")
                self.metrics.inc("kv_fetch_served_bytes", len(frame))
                self.metrics.inc("kv_wire_sent_bytes",
                                 kvwire.stored_nbytes(obj))
                self.metrics.inc("kv_wire_raw_bytes",
                                 kvwire.logical_nbytes(obj))
                yield frame
            # served_bytes: what actually crossed, so a size-capped
            # partial is distinguishable from a disconnect and the
            # peer's recompute fallback is sized to the true shortfall
            yield kvwire.encode_end(served, missing, truncated,
                                    served_bytes=sent)

        return httpd.binary_stream(_request, frames())

    @staticmethod
    def _resume_seq(prompt, resume):
        """The sequence whose prefix KV a dispatch should prefetch:
        prompt plus any migrated-in resume tokens — a resumed request's
        prefix covers its already-emitted tokens too. The single
        definition both dispatch paths use, so they can never prefetch
        different prefixes for the same resume record."""
        if not isinstance(resume, dict):
            return prompt
        return prompt + [int(t) for t in resume.get("tokens") or []]

    def _prefetch_kv(self, m, body, prompt) -> int:
        """Submit-time KV prefetch for a disaggregated dispatch (the
        ``kv_source`` hint): pull the prompt's prefix blocks from the
        prefill peer into the local arena ON THIS HANDLER THREAD — the
        transfer overlaps the batcher's decode loop instead of stalling
        co-resident streams at admission. Returns bytes transferred for
        the cost ledger; the request is then submitted WITHOUT the
        kv_source (no scheduler-thread fetch fallback: a dead peer must
        cost this request a recompute, not stall the decode loop on a
        connect timeout)."""
        src = body.get("kv_source")
        if not src or m.batcher is None:
            return 0
        try:
            return m.batcher.prefetch_kv(prompt, src)
        except Exception:
            return 0

    def _note_prefix(self, m, body, prompt) -> None:
        """Feed a served prompt into the prefix-digest advertisement
        (runtime/kvtier.py PrefixDigestIndex): called at batcher submit
        time — the prompt's KV is entering the radix cache — with the
        prompt TEXT, because the master routes on text-level digests (it
        never tokenizes). Token-id submissions have no text to chain and
        are simply not advertised."""
        b = m.batcher
        if (b is not None and b.kvtier is not None
                and isinstance(body.get("prompt"), str) and body["prompt"]):
            b.kvtier.note_text(body["prompt"], len(prompt))

    def _idem_claim(self, tag: str):
        """One atomic look at the idempotency state for ``tag``:
        ``("cached", result)`` — a completed result to replay;
        ``("join", event)`` — an execution is in flight, wait on it;
        ``("own", event)`` — the caller now OWNS the execution and must
        _idem_release() when done (the registered event is returned)."""
        with self._idem_lock:
            cached = self._idem.get(tag)
            if cached is not None:
                self._idem.move_to_end(tag)
                return "cached", cached
            ev = self._inflight_tags.get(tag)
            if ev is not None:
                return "join", ev
            my_ev = self._inflight_tags[tag] = threading.Event()
            return "own", my_ev

    def _idem_release(self, tag: str, my_ev: threading.Event, res):
        """End an owned execution: cache a success dict for replays
        (bounded LRU), drop the in-flight registration, and wake joiners
        — they re-check the cache under the lock."""
        with self._idem_lock:
            if isinstance(res, dict):   # 200 success: cache for replays
                self._idem[tag] = res
                self._idem.move_to_end(tag)
                while len(self._idem) > IDEM_CACHE:
                    self._idem.popitem(last=False)
            self._inflight_tags.pop(tag, None)
            my_ev.set()

    def _inference_idempotent(self, body):
        """Exactly-once execution around _inference_execute: a duplicate
        dispatch (master timeout retry — at-least-once delivery) either
        replays the cached result or joins the still-running execution
        and waits for ITS result, so the generation never runs twice for
        one request_tag."""
        tag = str(body["request_tag"]) if body.get("request_tag") else None
        if tag is None:
            return self._inference_execute(body)
        deadline = clock.now() + float(body.get("timeout", 300))
        while True:
            kind, obj = self._idem_claim(tag)
            if kind == "cached":
                self.metrics.inc("idempotent_hits")
                return dict(obj, idempotent=True)
            if kind == "own":
                my_ev = obj
                break
            # join the in-flight execution instead of re-generating
            self.metrics.inc("idempotent_joins")
            if not obj.wait(timeout=max(0.0, deadline - clock.now())):
                # in_flight tells the master the generation is STILL
                # running here — retry this node (join again later), do
                # not fail over and re-generate on a peer
                return 408, {"status": "error", "in_flight": True,
                             "message": f"execution for tag {tag!r} still "
                                        "running past the request budget"}
            # loop: either its result is cached now (replay it), or the
            # original attempt failed — then we take ownership and re-run
        res = None
        try:
            res = self._inference_execute(body)
            return res
        finally:
            self._idem_release(tag, my_ev, res if isinstance(res, dict)
                               else None)

    def _inference_execute(self, body):
        t0 = clock.now()
        try:
            m, prompt, sp, max_new, gen_kw = self._prep_inference(body)
        except (KeyError, ValueError) as e:
            return 400, {"status": "error", "message": str(e)}
        if m.batcher is not None:
            # batched serving: enqueue and wait — no per-model lock, the
            # batcher interleaves this request with others in flight
            tag = body.get("request_tag")
            resume = body.get("resume")
            resume = resume if isinstance(resume, dict) else None
            req = None
            try:
                with self.metrics.time("inference"):
                    pre = self._prefetch_kv(
                        m, body, self._resume_seq(prompt, resume))
                    req = m.batcher.submit(
                        prompt, max_new_tokens=max_new, sampling=sp,
                        eos_token_id=m.tokenizer.eos_token_id,
                        seed=body.get("seed"),
                        kv_transfer_bytes=pre,
                        kv_export=bool(body.get("kv_export")),
                        resume=resume,
                        # master brownout rung 3: per-request decode
                        # chunk ceiling on latency-class dispatches
                        chunk_cap=body.get("decode_chunk_cap"),
                        adapter=body.get("adapter"))
                    self._note_prefix(m, body, prompt)
                    if tag:
                        with self._tagged_lock:
                            self._tagged[str(tag)] = req
                    toks = req.wait(timeout=float(body.get("timeout", 300)))
            except TimeoutError as e:
                req.cancel()   # free the slot; don't generate for nobody
                return 408, {"status": "error", "message": str(e)}
            except (ValueError, RuntimeError) as e:
                if req is not None and req._migrated:
                    # live-migration handoff: 303-style — the master
                    # re-dispatches with the resume record + a
                    # kv_source hint back at this worker's arena
                    return 303, {"status": "migrated",
                                 "resume": req.resume_record,
                                 "request_tag": str(tag) if tag else None}
                return 400, {"status": "error", "message": str(e)}
            finally:
                if tag:
                    with self._tagged_lock:
                        self._tagged.pop(str(tag), None)
            self.metrics.inc("requests_completed")
            self.metrics.inc("tokens_generated", len(toks))
            return {
                "status": "success",
                "result": m.tokenizer.decode(toks),
                "tokens": toks,
                "execution_time": clock.now() - t0,
                "ttft_ms": req.ttft_ms,
                "cost": req.cost,
                "scheduler": m.batcher.stats(),
            }
        try:
            with self.metrics.time("inference"), m.lock:
                res = m.engine.generate(
                    [prompt], max_new_tokens=max_new, sampling=sp,
                    eos_token_id=m.tokenizer.eos_token_id, **gen_kw)
        except ValueError as e:   # request-shape errors (e.g. context
            # window exceeded incl. the speculative gamma margin) are the
            # caller's fault, not a server fault
            return 400, {"status": "error", "message": str(e)}
        text = m.tokenizer.decode(res.tokens[0])
        self.metrics.inc("requests_completed")
        self.metrics.inc("tokens_generated", len(res.tokens[0]))
        self.metrics.gauge("last_decode_tokens_per_s", res.decode_tokens_per_s)
        return {
            "status": "success",
            "result": text,
            "tokens": res.tokens[0],
            "execution_time": clock.now() - t0,  # parity: worker/app.py:317
            "prefill_ms": res.prefill_ms,
            "decode_ms": res.decode_ms,
            "tokens_per_s": res.decode_tokens_per_s,
            "cost": res.cost(),
        }

    def engine_stream_events(self, body, schedule):
        """Engine-mode SSE event stream. ``schedule(fn)`` runs the blocking
        generation (a daemon thread here; the lockstep leader schedules it
        at the op's sequence slot instead — runtime/multihost.py). Prep
        happens INSIDE fn so it observes whatever model state the
        scheduled order establishes (e.g. after an earlier unload)."""
        import queue
        q: "queue.Queue" = queue.Queue()
        done = object()
        ctx = trace.current()   # handler thread's span; run() is scheduled
        # onto another thread, so the link is explicit

        def run():
            try:
                with trace.get_tracer().span("worker.inference_stream",
                                             parent=ctx):
                    return self._run_stream(body, q)
            except Exception as e:
                q.put({"event": "error", "message": str(e)})
            finally:
                q.put(done)

        schedule(run)

        def events():
            while True:
                item = q.get()
                if item is done:
                    break
                yield item
            self.metrics.inc("requests_completed")

        return events()

    def _run_stream(self, body, q):
        m, prompt, sp, max_new, gen_kw = self._prep_inference(body)
        if m.batcher is not None:
            raise ValueError(
                "engine_stream_events is for engine-mode models")

        def cb(step, toks):
            if toks[0] is None:  # sequence finished (post-eos)
                return
            q.put({"event": "token", "step": step, "token": toks[0],
                   "text": m.tokenizer.decode([toks[0]])})

        with m.lock:
            res = m.engine.generate(
                [prompt], max_new_tokens=max_new, sampling=sp,
                eos_token_id=m.tokenizer.eos_token_id,
                stream_cb=cb, **gen_kw)
        q.put({"event": "done",
               "result": m.tokenizer.decode(res.tokens[0]),
               "tokens_per_s": res.decode_tokens_per_s})

    def inference_stream(self, body, _request=None):
        """SSE streaming decode — absent from the reference (SURVEY.md §2.3)."""
        stale = self._term_guard(_request)
        if stale:
            return stale
        if not self._try_begin_inference():
            return self._refuse_draining()
        try:
            return self._inference_stream_inner(body, _request)
        finally:
            self._end_inference()

    def _inference_stream_inner(self, body, _request=None):
        try:
            # validate up front so bad requests get a proper 400, matching
            # /inference; execution still re-preps inside the stream thread
            # (the lockstep leader relies on in-slot prep)
            m, _, _, _, _ = self._prep_inference(body)
        except (KeyError, ValueError) as e:
            return 400, {"status": "error", "message": str(e)}
        if m.batcher is None:
            ev = self.engine_stream_events(
                body, lambda fn: threading.Thread(target=fn,
                                                  daemon=True).start())
            return httpd.sse_stream(_request, ev)
        ctx = trace.current()   # submit happens on a helper thread below

        def events():
            import queue
            q: "queue.Queue" = queue.Queue()
            done = object()

            def run_batched():
                step = [0]

                def cb(token):
                    q.put({"event": "token", "step": step[0], "token": token,
                           "text": m.tokenizer.decode([token])})
                    step[0] += 1

                try:
                    _, prompt, sp, max_new, _gk = self._prep_inference(body)
                    pre = self._prefetch_kv(m, body, prompt)
                    req = m.batcher.submit(
                        prompt, max_new_tokens=max_new, sampling=sp,
                        eos_token_id=m.tokenizer.eos_token_id, stream_cb=cb,
                        seed=body.get("seed"),
                        kv_transfer_bytes=pre, trace_ctx=ctx,
                        adapter=body.get("adapter"))
                    self._note_prefix(m, body, prompt)
                    toks = req.wait(timeout=float(body.get("timeout", 300)))
                    q.put({"event": "done",
                           "result": m.tokenizer.decode(toks),
                           "ttft_ms": req.ttft_ms})
                except Exception as e:
                    q.put({"event": "error", "message": str(e)})
                q.put(done)

            threading.Thread(target=run_batched, daemon=True).start()
            while True:
                item = q.get()
                if item is done:
                    break
                yield item
            self.metrics.inc("requests_completed")

        return httpd.sse_stream(_request, events())

    def cancel(self, body, _request=None):
        """Cancel an in-flight tagged batched request, freeing its slot.

        The reference had no cancellation at all — a master-side timeout
        left the worker generating for nobody (SURVEY.md §2.3 one blocking
        request; the master's 120s timeout vs the worker's open-ended
        generate). Engine-mode requests are not cancellable mid-program
        (one jitted chunk runs to completion); the batcher drops the slot
        at its next step.

        Lease-fenced: a revived old leader's timeout path must not
        cancel a generation the CURRENT leader is waiting on — without
        the fence, its orphan-cancel would kill the live stream.
        """
        stale = self._term_guard(_request)
        if stale:
            return stale
        tag = body.get("request_tag")
        if not tag:
            return 400, {"status": "error", "message": "request_tag required"}
        with self._tagged_lock:
            req = self._tagged.get(str(tag))
        if req is None:
            return 404, {"status": "error",
                         "message": f"no in-flight request tagged {tag!r}"}
        req.cancel()
        self.metrics.inc("requests_cancelled")
        return {"status": "success",
                "message": f"cancel requested for {tag!r}"}

    # ---- profiling ----------------------------------------------------
    # The reference's only timing was wall-clock execution_time per request
    # (reference: worker/app.py:271,317; SURVEY.md §5.1). These endpoints
    # expose real device traces: XLA op timelines viewable in
    # TensorBoard/Perfetto, plus a live HBM profile.

    def profile_start(self, body):
        path = body.get("trace_dir") or "/tmp/dli_trace"
        import jax.profiler
        with self._profile_lock:   # check-then-act vs concurrent handlers
            if self._profile_dir is not None:
                return 409, {"status": "error",
                             "message": f"trace already running -> "
                                        f"{self._profile_dir}"}
            jax.profiler.start_trace(path)
            self._profile_dir = path
        return {"status": "success", "trace_dir": path}

    def profile_stop(self, body):
        import jax.profiler
        with self._profile_lock:
            if self._profile_dir is None:
                return 409, {"status": "error", "message": "no trace running"}
            jax.profiler.stop_trace()
            path, self._profile_dir = self._profile_dir, None
        return {"status": "success", "trace_dir": path,
                "message": "open with tensorboard --logdir or xprof"}

    def memory_profile(self, body):
        """Live device-memory profile (pprof protobuf), HBM ground truth."""
        import jax.profiler
        return (jax.profiler.device_memory_profile(), "application/protobuf")

    def ssh_setup(self, body):
        """Reference parity (worker/app.py:374-413): probe an SSH
        connection with the given credentials, then close it. Like the
        reference this is a connectivity TEST only — no tunnel is kept.
        Unlike the reference (which imported paramiko unconditionally but
        never declared it, SURVEY.md §5.9) the dependency is optional, and
        unlike the reference the endpoint demands worker auth: an open
        /ssh_setup is an SSRF/port-scan primitive and can be pointed at
        the operator's own key files."""
        if self.service.auth_key is None:
            return 403, {"status": "error",
                         "message": "/ssh_setup requires worker auth "
                                    "(set DLI_AUTH_ENABLED + DLI_AUTH_KEY)"}
        try:
            import paramiko
        except ImportError:
            return 501, {"status": "error",
                         "message": "paramiko not installed on this worker"}
        host = body.get("host")
        username = body.get("username")
        if not host or not username:
            return 400, {"status": "error",
                         "message": "host and username required"}
        client = paramiko.SSHClient()
        client.set_missing_host_key_policy(paramiko.AutoAddPolicy())
        try:
            kw = {"hostname": host, "port": int(body.get("port", 22)),
                  "username": username, "timeout": 10}
            if body.get("key_path"):
                kw["key_filename"] = body["key_path"]
            elif body.get("password"):
                kw["password"] = body["password"]
            else:
                return 400, {"status": "error",
                             "message": "password or key_path required"}
            client.connect(**kw)
            return {"status": "success",
                    "message": f"SSH connection to {host} verified"}
        except Exception as e:
            return 502, {"status": "error", "message": f"SSH failed: {e}"}
        finally:
            client.close()

    # ---- lifecycle ---------------------------------------------------

    def serve(self, host="0.0.0.0", port=8100, background=False):
        log.info("worker agent on %s:%d (devices: %s)", host, port,
                 jax.devices())
        return self.service.serve(host, port, background=background)


def _compile_cache_state() -> dict:
    """Where this process's persistent compile cache lives and how many
    entries it holds (utils/platform.enable_compilation_cache)."""
    d = jax.config.jax_compilation_cache_dir
    try:
        n = sum(1 for f in os.listdir(d) if f.endswith("-cache")) if d else 0
    except OSError:
        n = 0       # not created yet: nothing compiled so far
    return {"dir": d, "entries": n}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description="TPU worker agent")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8100)
    args = ap.parse_args(argv)
    from distributed_llm_inferencing_tpu.utils.platform import (
        ensure_backend)
    ensure_backend()
    WorkerAgent().serve(args.host, args.port)


if __name__ == "__main__":
    main()
