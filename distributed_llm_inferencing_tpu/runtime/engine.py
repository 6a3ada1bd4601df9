"""InferenceEngine: jitted, sharded prefill + decode with streaming.

TPU-native replacement for the reference's hot path — where the worker
called opaque ``model.generate()`` per request (reference:
worker/app.py:297-305), this engine owns the loop:

- **prefill**: one jitted call over a right-padded, bucketed prompt block
  (bucketing bounds XLA recompiles — the problem HF hid from the reference)
- **decode**: one jitted single-token step, compiled once per cache shape,
  with donated cache buffers so decoding is in-place in HBM
- **sampling** is fused into the decode program (ops/sampling.py)
- **sharding**: params/cache placed via parallel/sharding.py over any
  MeshSpec; the same engine runs single-chip or tp×dp×ep meshes unchanged
- **streaming**: tokens surface per step through a callback — the reference
  had no streaming at all (SURVEY.md §2.3)

Engine-level guards reject requests that exceed the context window instead
of silently clipping (models/transformer.py clips only as jit-safety).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.config import ModelConfig
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.ops.kvcache import KVCache, init_cache
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams, sample
from distributed_llm_inferencing_tpu.parallel import sharding as shd
from distributed_llm_inferencing_tpu.parallel.mesh import (
    MeshSpec, create_mesh, validate_spec)
from distributed_llm_inferencing_tpu.utils import clock, trace
from distributed_llm_inferencing_tpu.utils.metrics import Metrics

PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)


def _bucket(n: int) -> int:
    for b in PREFILL_BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds largest bucket")


@dataclasses.dataclass
class GenerateResult:
    tokens: List[List[int]]          # new tokens per sequence (eos-trimmed)
    prefill_ms: float
    decode_ms: float
    steps: int

    @property
    def decode_tokens_per_s(self) -> float:
        total = sum(len(t) for t in self.tokens)
        return total / (self.decode_ms / 1e3) if self.decode_ms > 0 else 0.0

    def cost(self) -> dict:
        """Engine-mode cost-ledger record, schema-compatible with the
        batcher's (runtime/batcher.py _cost_record). The engine serves
        one blocking generate at a time behind the per-model lock, so
        queue time is the caller's to measure — 0 here; a decode step
        is one weight-streaming pass."""
        total = sum(len(t) for t in self.tokens)
        return {
            "queue_ms": 0.0,
            "prefill_ms": round(self.prefill_ms, 3),
            "decode_ms": round(self.decode_ms, 3),
            "prefill_cached_tokens": 0,
            "prefill_uncached_tokens": 0,
            "decode_tokens": total,
            "weight_passes": self.steps,
            "engine_mode": True,
        }


class InferenceEngine:
    """Owns params on device + compiled step functions for one model."""

    def __init__(self, cfg: ModelConfig, params=None, *,
                 mesh_spec: Optional[MeshSpec] = None,
                 max_seq: Optional[int] = None,
                 seed: int = 0,
                 pipeline_microbatches: Optional[int] = None,
                 metrics: Optional[Metrics] = None):
        # the worker shares its registry so /metrics carries engine
        # timings; standalone engines keep their own
        self.metrics = metrics or Metrics()
        self.mesh_spec = mesh_spec or MeshSpec()
        self._n_micro = pipeline_microbatches
        if cfg.ssm is not None:
            # the engine's bucketed prefill, prefix reuse, speculation
            # and sharded caches carry K and V alone; a model with state
            # layers is served by the batcher (runtime/batcher.py), whose
            # cache manager keeps a state row a slot
            raise ValueError(
                f"{cfg.name}: state-space layers (cfg.ssm) are served by "
                "the continuous batcher (serving='batched'); the engine's "
                "dense-cache prefill / decode_step carry no recurrent "
                "state")
        validate_spec(self.mesh_spec, cfg)
        self.mesh = create_mesh(self.mesh_spec)
        # Pin the attention backend now that the program's device span is
        # known (pallas kernels are single-program; GSPMD partitions the
        # xla formulation on multi-device meshes).
        from distributed_llm_inferencing_tpu.models.transformer import (
            _cfg_backend)
        self.cfg = cfg = cfg.replace(
            attn_backend=_cfg_backend(cfg, self.mesh_spec.num_devices),
            # int4 pallas routing: row-parallel leaves stay on XLA when
            # this GSPMD program shards them over tp (config.py field doc)
            tp_row_sharded=self.mesh_spec.tp > 1,
            # MLA serves from the latent cache (the absorbed
            # formulation, transformer._mla_latent_attn) whenever the
            # mesh is eligible: cuts dense-cache bytes by
            # 2*H*head_dim/(kv_lora_rank+rope) (~19x on deepseek-proxy).
            # DLI_MLA_LATENT=0 opts out (A/B vs materialized).
            mla_latent_cache=(
                cfg.mla and cfg.kv_quant is None
                and cfg.sliding_window is None and cfg.attn_windows is None
                and cfg.attn_softcap is None
                and self.mesh_spec.sp == 1 and self.mesh_spec.pp == 1
                and os.environ.get("DLI_MLA_LATENT") != "0"))
        self.max_seq = min(max_seq or cfg.max_position_embeddings,
                           cfg.max_position_embeddings)
        # sequence parallelism shards the cache S axis: keep it divisible
        # (round DOWN — exceeding the model's position window would admit
        # positions past learned-embedding rows / the trained RoPE range)
        sp = self.mesh_spec.sp
        if sp > 1 and self.max_seq % sp:
            self.max_seq -= self.max_seq % sp

        if params is None:
            params = init_params(cfg, jax.random.PRNGKey(seed))
        else:
            from distributed_llm_inferencing_tpu.ops.quant import (
                maybe_quantize, maybe_quantize_embed)
            params = maybe_quantize_embed(maybe_quantize(params, cfg), cfg)
        with self.mesh:
            self.params = shd.shard_params(params, self.mesh, cfg, self.mesh_spec)

        self._cache_shardings = shd.named(
            self.mesh, shd.cache_specs(cfg, self.mesh_spec))
        self._prefill_fns = {}  # bucket -> compiled
        self._decode_fns = {}   # SamplingParams -> compiled
        # LoRA single-stream hook (models/lora.py): name -> adapter
        # (host numpy) and name -> cached params tree carrying its
        # delta pack. One adapter per generate() call.
        self._adapters = {}
        self._adapter_trees = {}

    # ---- compiled step builders -------------------------------------

    def _timed_first_call(self, fn):
        """Wrap a freshly-built jitted fn: jit compiles synchronously
        inside the first call (execution dispatches async), so timing
        that call observes ``engine_jit_compile`` to within one dispatch.
        Lives here — not at the call sites — so every compile-cache
        accessor reports compile time without re-deriving its key shape."""
        state = {"first": True}

        def wrapper(*args):
            if state.pop("first", None):
                t0 = time.perf_counter()
                out = fn(*args)
                self.metrics.observe("engine_jit_compile",
                                     time.perf_counter() - t0)
                return out
            return fn(*args)

        return wrapper

    def _build_prefill(self, s0: int):
        cfg = self.cfg
        # sp>1 routes prefill attention through the ring (parallel/ring.py);
        # pp>1 routes the whole stack through the pipelined executor
        mesh = self.mesh if self.mesh_spec.sp > 1 else None
        pp = self.mesh_spec.pp

        def fn(params, tokens, lengths, cache):
            if pp > 1:
                from distributed_llm_inferencing_tpu.parallel import pipeline
                logits, cache = pipeline.pipelined_prefill(
                    params, cfg, tokens, lengths, cache, mesh=self.mesh,
                    n_micro=pipeline.pick_n_micro(tokens.shape[0], pp,
                                                  self._n_micro))
            else:
                logits, cache = transformer.prefill(
                    params, cfg, tokens, lengths, cache, mesh=mesh)
            # gather last valid logit per sequence: [B,V]
            idx = jnp.maximum(lengths - 1, 0)
            last = jnp.take_along_axis(
                logits, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            return last, cache

        return self._timed_first_call(jax.jit(fn, donate_argnums=(3,)))

    # Chunk sizes for the scanned decode loop. Any max_new_tokens is a
    # greedy sum of these, so at most len(DECODE_CHUNKS) programs compile
    # per sampling config and the host syncs once per chunk, not per token
    # (the per-token dispatch+transfer pattern is what made the reference's
    # serving loop unshippable on an accelerator behind a network hop).
    # Powers of two keep the greedy cover tight: 63 remaining = 6 chunks,
    # which is what bounds per-chunk syncs on the streaming/eos path (the
    # non-streaming path queues every chunk and syncs once regardless).
    # The continuous batcher reuses this schedule (runtime/batcher.py).
    DECODE_CHUNKS = (64, 32, 16, 8, 4, 2, 1)
    # The incremental (streaming / eos-early-exit) path syncs and emits
    # only at chunk boundaries, and a chunk that straddles eos is wasted
    # compute — cap its chunk size so burst latency and eos overshoot
    # stay bounded while the fire-and-forget path uses the full 64.
    STREAM_CHUNK_MAX = 32

    def _decode_jitted(self, sp: SamplingParams, T: int):
        # per-instance cache (an lru_cache on the method would pin the
        # engine — and its HBM-resident params — in a class-global cache,
        # defeating /unload_model)
        fn = self._decode_fns.get((sp, T))
        if fn is None:
            cfg = self.cfg

            pp = self.mesh_spec.pp
            mesh, n_micro_req = self.mesh, self._n_micro

            def raw(params, tokens, cache, key):
                def step(carry, _):
                    cur, cache, key = carry
                    key, sub = jax.random.split(key)
                    if pp > 1:
                        from distributed_llm_inferencing_tpu.parallel import (
                            pipeline)
                        logits, cache = pipeline.pipelined_decode_step(
                            params, cfg, cur[:, None], cache, mesh=mesh,
                            n_micro=pipeline.pick_n_micro(
                                cur.shape[0], pp, n_micro_req))
                    else:
                        logits, cache = transformer.decode_step(
                            params, cfg, cur[:, None], cache,
                            mesh=(mesh if self.mesh_spec.sp > 1 else None))
                    nxt = sample(logits[:, 0], sub, sp)
                    return (nxt, cache, key), nxt

                (cur, cache, key), toks = jax.lax.scan(
                    step, (tokens, cache, key), length=T)
                return toks, cur, cache, key   # toks: [T, B]

            fn = self._timed_first_call(jax.jit(raw, donate_argnums=(2,)))
            # cap scaled to the chunk schedule: ~8 sampling configs' worth
            # of compiled programs before FIFO eviction
            if len(self._decode_fns) >= 8 * len(self.DECODE_CHUNKS):
                self._decode_fns.pop(next(iter(self._decode_fns)))
            self._decode_fns[(sp, T)] = fn
        return fn

    # ---- LoRA adapters (single-stream delta hook) ---------------------

    def load_adapter(self, adapter=None, *, name=None, source=None):
        """Make a LoRA adapter available to ``generate(adapter=...)``.

        Pass a ``models.lora.LoRAAdapter`` directly, or ``name`` +
        ``source`` (checkpoint dir, or a ``synth:`` URI for tests).
        The engine serves one adapter per request by swapping in a
        params tree whose layers carry the delta pack — the SAME
        ``_lora_apply`` hook the batcher's gathered path runs, so the
        single-stream and batched paths agree bitwise per request.
        """
        from distributed_llm_inferencing_tpu.models import lora as lora_mod
        if self.mesh_spec.pp > 1:
            raise ValueError("LoRA serving does not support pp > 1 "
                             "(the pipelined executor re-stages the "
                             "stacked layer tree without the delta pack)")
        if adapter is None:
            adapter = lora_mod.resolve(self.cfg, name, source)
        else:
            lora_mod._check_adapter(self.cfg, adapter)
        self._adapters[adapter.name] = adapter
        self._adapter_trees.pop(adapter.name, None)
        return adapter

    def unload_adapter(self, name: str) -> bool:
        self._adapter_trees.pop(name, None)
        return self._adapters.pop(name, None) is not None

    def adapter_stats(self) -> dict:
        """Resident-adapter advertisement for the worker's /health (the
        master's affinity scorer reads it from the node snapshot)."""
        return {"resident": sorted(self._adapters),
                "bytes": sum(a.nbytes for a in self._adapters.values())}

    def _params_for(self, adapter: Optional[str]):
        """Base params, or a shallow-copied tree whose layers carry the
        adapter's delta pack at slot 0. The dense forward passes no
        per-row ids, so ``_lora_apply`` gathers row 0 for every row —
        exactly this adapter. jit retraces once per adapter rank (the
        tree structure gains a "lora" subtree); the tree is cached so
        repeat requests reuse the committed device buffers."""
        if adapter is None:
            return self.params
        ad = self._adapters.get(adapter)
        if ad is None:
            raise ValueError(
                f"unknown adapter {adapter!r} (load_adapter first)")
        tree = self._adapter_trees.get(adapter)
        if tree is None:
            # per-layer {target: {"a": [1, din, r], "b": [1, r, dout]}}
            # with the alpha/rank scale folded into B (ops/lora.py doc)
            packs = [
                {t: {"a": a[None], "b": (b * ad.scale)[None]}
                 for t, (a, b) in lp.items()}
                for lp in ad.layers]
            tree = dict(self.params)
            stacked = {
                t: {k: jnp.asarray(np.stack([p[t][k] for p in packs]))
                    for k in ("a", "b")}
                for t in packs[0]}
            tree["layers"] = dict(tree["layers"], lora=stacked)
            self._adapter_trees[adapter] = tree
        return tree

    # ---- public API --------------------------------------------------

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int = 100,   # reference default, views.py:351
        sampling: Optional[SamplingParams] = None,
        seed: int = 0,
        eos_token_id: Optional[int] = None,
        stream_cb: Optional[Callable[[int, List[int]], None]] = None,
        speculative: Optional[str] = None,   # "ngram" (ops/speculative.py)
        spec_gamma: int = 4,
        adapter: Optional[str] = None,       # LoRA adapter name (load_adapter)
    ) -> GenerateResult:
        """Generate continuations for a batch of token-id prompts.

        stream_cb(step, tokens_this_step) fires after every decode step —
        the streaming surface the server layer exposes as SSE.

        ``speculative="ngram"`` turns on prompt-lookup speculative decoding
        (single sequence only): each dispatched program verifies
        ``spec_gamma`` self-drafted tokens, emitting 1..gamma+1 tokens per
        step — output distribution identical to plain decode (exact for
        greedy; leave-one-out rejection for sampling).
        """
        if speculative is not None:
            if adapter is not None:
                raise ValueError(
                    "LoRA adapters do not combine with speculative "
                    "decoding (the verify program has no delta hook)")
            return self._generate_speculative(
                prompts, max_new_tokens, sampling, seed, eos_token_id,
                stream_cb, speculative, spec_gamma)
        # raises on unknown adapter — a request NEVER silently serves
        # base weights (models/lora.py doc)
        params = self._params_for(adapter)
        cfg = self.cfg
        sp = sampling or SamplingParams()
        n_real = len(prompts)
        lens = [len(p) for p in prompts]
        if not lens or min(lens) < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            return GenerateResult(tokens=[[] for _ in range(n_real)],
                                  prefill_ms=0.0, decode_ms=0.0, steps=0)
        max_len = max(lens)
        if max_len + max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({max_len}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds engine max_seq {self.max_seq} "
                f"(context window {cfg.max_position_embeddings})")

        # pad batch to a dp-divisible size with dummy rows (trimmed below)
        dp = self.mesh_spec.dp
        B = -(-n_real // dp) * dp
        if B == 1 and jax.default_backend() == "cpu":
            # XLA-CPU strength-reduces M=1 dots whose weight operand is a
            # scan slice into naive kLoop fusions (~10-20x slower than the
            # dot kernel); a dummy second batch row keeps the real dot.
            # TPU/GPU never take this branch.
            B = 2
        prompts = list(prompts) + [[0]] * (B - n_real)
        lens = lens + [1] * (B - n_real)

        # bucket capped at cache capacity (max_len <= max_seq is guaranteed
        # by the guard above, so s0 >= max_len always holds)
        s0 = min(_bucket(max_len), self.max_seq)
        sp_deg = self.mesh_spec.sp
        if sp_deg > 1 and s0 % sp_deg:  # ring needs sp-divisible blocks
            s0 = min(s0 + sp_deg - s0 % sp_deg, self.max_seq)
        tokens = np.zeros((B, s0), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        lengths = jnp.asarray(lens, jnp.int32)

        with self.mesh:
            cache = init_cache(cfg, B, self.max_seq)
            cache = jax.device_put(cache, self._cache_shardings)

            prefill_fresh = s0 not in self._prefill_fns
            if prefill_fresh:
                self._prefill_fns[s0] = self._build_prefill(s0)
            t0 = time.perf_counter()
            wt0 = clock.now()
            last_logits, cache = self._prefill_fns[s0](
                params, jnp.asarray(tokens), lengths, cache)
            key = jax.random.PRNGKey(seed)
            key, sub = jax.random.split(key)
            cur = sample(last_logits, sub, sp)

            # Host syncs are the enemy: on a remote-attached chip one
            # device->host round trip costs tens of ms. Sync per chunk only
            # when the host must see tokens mid-flight (eos early-exit /
            # streaming); otherwise queue every chunk dispatch and sync ONCE.
            incremental = (eos_token_id is not None) or (stream_cb is not None)

            if incremental:
                cur.block_until_ready()
            t1 = time.perf_counter()
            wt1 = clock.now()

            steps = 1
            remaining = max_new_tokens - 1
            if not incremental:
                first_dev = cur          # prefill's sample (never donated)
                chunks_dev = []
                while remaining > 0:
                    T = next(c for c in self.DECODE_CHUNKS if c <= remaining)
                    decode = self._decode_jitted(sp, T)
                    toks_dev, cur, cache, key = decode(
                        params, cur, cache, key)
                    chunks_dev.append(toks_dev)
                    steps += T
                    remaining -= T
                # ONE sync for the whole request
                first, host_chunks = jax.device_get((first_dev, chunks_dev))
                toks_all = (np.concatenate(host_chunks, axis=0)
                            if host_chunks else np.zeros((0, B), np.int32))
                out = [[int(first[i])] + [int(t) for t in toks_all[:, i]]
                       for i in range(B)]
            else:
                out = [[int(cur[i])] for i in range(B)]
                done = [(i >= n_real) or
                        (eos_token_id is not None and out[i][0] == eos_token_id)
                        for i in range(B)]
                if stream_cb:
                    stream_cb(0, [int(cur[i]) for i in range(n_real)])

                # Without an eos stop-check the chunk schedule is data-
                # independent: keep a BOUNDED lookahead of dispatched
                # chunks (depth 2 — chunk N+1 launches before chunk N's
                # tokens transfer back, which is all the dispatch/
                # transfer overlap there is to win) rather than queueing
                # the whole generation: a stream_cb that dies mid-stream
                # (client disconnect) then wastes at most the in-flight
                # pair, not every remaining chunk. With eos the host
                # must see each chunk's tokens before dispatching more.
                pipelined: list = []
                rem_dispatch = remaining if eos_token_id is None else 0

                def dispatch_next():
                    nonlocal rem_dispatch, cur, cache, key
                    T = next(c for c in self.DECODE_CHUNKS
                             if c <= min(rem_dispatch,
                                         self.STREAM_CHUNK_MAX))
                    decode = self._decode_jitted(sp, T)
                    toks_dev, cur, cache, key = decode(
                        params, cur, cache, key)
                    pipelined.append((toks_dev, T))
                    rem_dispatch -= T

                while rem_dispatch > 0 and len(pipelined) < 2:
                    dispatch_next()

                while remaining > 0 and not all(done):
                    if pipelined:
                        toks_dev, T = pipelined.pop(0)
                        if rem_dispatch > 0:   # refill BEFORE blocking
                            dispatch_next()
                    else:
                        T = next(c for c in self.DECODE_CHUNKS
                                 if c <= min(remaining,
                                             self.STREAM_CHUNK_MAX))
                        decode = self._decode_jitted(sp, T)
                        toks_dev, cur, cache, key = decode(
                            params, cur, cache, key)
                    toks = np.asarray(toks_dev)    # [T, B] — one sync per chunk
                    for t in range(T):
                        # stream exactly what lands in `out` this step;
                        # finished sequences surface as None
                        emit = [None if done[i] else int(toks[t, i])
                                for i in range(n_real)]
                        for i in range(B):
                            if not done[i]:
                                out[i].append(int(toks[t, i]))
                                if (eos_token_id is not None
                                        and toks[t, i] == eos_token_id):
                                    done[i] = True
                        if stream_cb and any(e is not None for e in emit):
                            stream_cb(steps + t, emit)
                    steps += T
                    remaining -= T
            t2 = time.perf_counter()
            wt2 = clock.now()

        out = out[:n_real]  # drop dp-padding rows
        # trim trailing eos
        if eos_token_id is not None:
            out = [t[:-1] if t and t[-1] == eos_token_id else t for t in out]
        self._observe_generate(
            wt0, wt1, wt2, t1 - t0, t2 - t1, steps,
            {"model": cfg.name, "batch": n_real, "steps": steps},
            {"bucket": s0, "compiled": prefill_fresh},
            {"steps": steps, "incremental": incremental})
        return GenerateResult(
            tokens=out, prefill_ms=(t1 - t0) * 1e3,
            decode_ms=(t2 - t1) * 1e3, steps=steps)

    def _observe_generate(self, wt0, wt1, wt2, prefill_s, decode_s, steps,
                          gen_attrs, prefill_attrs, decode_attrs):
        """Shared metrics+trace epilogue for every generate path. Spans
        are retroactive (utils/trace.py record) and nest under the
        caller's span — the worker's /inference handler — via the
        contextvar; wall stamps keep master/worker timelines aligned
        while the perf_counter deltas feed the histograms."""
        self.metrics.observe("engine_prefill", prefill_s)
        self.metrics.observe("engine_decode", decode_s)
        self.metrics.inc("engine_decode_steps", steps)
        tracer = trace.get_tracer()
        g = tracer.record("engine.generate", wt0, wt2,
                          parent=trace.current(), attrs=gen_attrs)
        tracer.record("engine.prefill", wt0, wt1, parent=g,
                      attrs=prefill_attrs)
        tracer.record("engine.decode", wt1, wt2, parent=g,
                      attrs=decode_attrs)

    # ---- speculative decoding (ops/speculative.py) --------------------

    def _verify_jitted(self, sp: SamplingParams, g: int):
        fn = self._decode_fns.get(("spec", sp, g))
        if fn is None:
            cfg = self.cfg
            from distributed_llm_inferencing_tpu.ops import speculative

            def raw(params, cache, cur, drafts, key):
                return speculative.verify_step(params, cfg, cache, cur,
                                               drafts, key, sp)

            fn = self._timed_first_call(jax.jit(raw, donate_argnums=(1,)))
            if len(self._decode_fns) >= 8 * len(self.DECODE_CHUNKS):
                self._decode_fns.pop(next(iter(self._decode_fns)))
            self._decode_fns[("spec", sp, g)] = fn
        return fn

    def _generate_speculative(self, prompts, max_new_tokens, sampling, seed,
                              eos_token_id, stream_cb, mode, gamma):
        """Prompt-lookup speculative loop: one verify program per step,
        1..gamma+1 tokens per host sync. Single-sequence (speculation is a
        latency lever for individual streams; batched throughput comes
        from the continuous batcher)."""
        from distributed_llm_inferencing_tpu.ops import speculative
        if mode != "ngram":
            raise ValueError(f"unknown speculative mode {mode!r}")
        if len(prompts) != 1:
            raise ValueError("speculative decoding serves one sequence")
        if any(getattr(self.mesh_spec, ax) > 1 for ax in ("sp", "pp", "dp")):
            raise ValueError("speculative decoding supports tp/ep meshes")
        cfg = self.cfg
        sp = sampling or SamplingParams()
        gamma = max(1, int(gamma))
        prompt = list(map(int, prompts[0]))
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            return GenerateResult(tokens=[[]], prefill_ms=0.0, decode_ms=0.0,
                                  steps=0)
        if len(prompt) + max_new_tokens + gamma + 1 > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens})"
                f" + gamma ({gamma}) exceeds engine max_seq {self.max_seq}")

        s0 = min(_bucket(len(prompt)), self.max_seq)
        tokens = np.zeros((1, s0), np.int32)
        tokens[0, :len(prompt)] = prompt
        with self.mesh:
            cache = init_cache(cfg, 1, self.max_seq)
            cache = jax.device_put(cache, self._cache_shardings)
            prefill_fresh = s0 not in self._prefill_fns
            if prefill_fresh:
                self._prefill_fns[s0] = self._build_prefill(s0)
            t0 = time.perf_counter()
            wt0 = clock.now()
            last_logits, cache = self._prefill_fns[s0](
                self.params, jnp.asarray(tokens),
                jnp.asarray([len(prompt)], jnp.int32), cache)
            key = jax.random.PRNGKey(seed)
            key, sub = jax.random.split(key)
            cur = int(sample(last_logits, sub, sp)[0])
            t1 = time.perf_counter()
            wt1 = clock.now()

            hit_eos = eos_token_id is not None and cur == eos_token_id
            out: List[int] = [] if hit_eos else [cur]
            if stream_cb and not hit_eos:
                stream_cb(0, [cur])   # same contract as the plain path
            history = prompt + out
            steps = 1
            # Adaptive drafting (ops/speculative.py): a verify dispatch
            # costs one host sync per <= gamma+1 tokens, while a plain
            # chunk syncs once per <= STREAM_CHUNK_MAX — on a host where
            # dispatch dominates, drafting loses even at full acceptance
            # (BENCH_r05: 5.54 vs 17.04 tok/s). The controller measures
            # both arms and hands the loop to whichever is faster, so
            # ``speculative="ngram"`` can never stay slower than off.
            # Fresh per call — a request's output must stay a function
            # of (params, prompt, seed), never of neighbor requests —
            # with a SHORT probe cadence so even a few-dozen-token
            # generation measures the plain arm and can fall back
            # mid-request (probe schedules count chunks, so same-seed
            # reruns make identical decisions until both arms are
            # measured). DLI_SPEC_ADAPTIVE=0 pins always-draft
            # (parity tests / A/B).
            ctl = (speculative.AdaptiveSpecController(gamma, probe_every=8)
                   if os.environ.get("DLI_SPEC_ADAPTIVE", "1")
                   not in ("0", "false") else None)
            while len(out) < max_new_tokens and not hit_eos:
                g_now = ctl.choose() if ctl is not None else gamma
                p0 = time.perf_counter()
                if g_now == 0:
                    # plain fallback: same chunk trade as the streaming
                    # decode path (eos checked host-side per chunk)
                    rem = max_new_tokens - len(out)
                    T = next(c for c in self.DECODE_CHUNKS
                             if c <= min(rem, self.STREAM_CHUNK_MAX))
                    compiled = (sp, T) not in self._decode_fns
                    decode = self._decode_jitted(sp, T)
                    toks_dev, _, cache, key = decode(
                        self.params, jnp.asarray([out[-1]], jnp.int32),
                        cache, key)
                    emitted = [int(t) for t in np.asarray(toks_dev)[:, 0]]
                    steps += T
                else:
                    drafts = speculative.propose_ngram(history, g_now)
                    if drafts is None:
                        # no n-gram hit: verify a dummy draft — still
                        # emits >= 1 correct token for one dispatch
                        drafts = [history[-1]] * g_now
                    compiled = ("spec", sp, g_now) not in self._decode_fns
                    verify = self._verify_jitted(sp, g_now)
                    toks_dev, n_emit, cache, key = verify(
                        self.params, cache,
                        jnp.asarray([out[-1]], jnp.int32),
                        jnp.asarray([drafts], jnp.int32), key)
                    steps += 1
                    n = int(n_emit[0])
                    emitted = [int(t) for t in np.asarray(toks_dev)[0, :n]]
                # keep (and stream) only what the result will contain:
                # nothing past max_new_tokens, nothing at/after eos
                kept = []
                for t in emitted:
                    if eos_token_id is not None and t == eos_token_id:
                        hit_eos = True
                        break
                    kept.append(t)
                    if len(out) + len(kept) >= max_new_tokens:
                        break
                if ctl is not None:
                    dt = time.perf_counter() - p0
                    if g_now == 0:
                        ctl.record("plain", emitted=len(emitted),
                                   elapsed_s=dt, compiled=compiled)
                    else:
                        ctl.record("spec", emitted=len(emitted),
                                   elapsed_s=dt, drafted=g_now,
                                   accepted=len(emitted) - 1,
                                   compiled=compiled)
                out.extend(kept)
                history.extend(kept)
                if stream_cb:
                    # same contract as the plain path: one call per token,
                    # payload = that step's tokens per sequence ([t] here)
                    for j, t in enumerate(kept):
                        stream_cb(len(out) - len(kept) + j, [t])
            t2 = time.perf_counter()
            wt2 = clock.now()

        self._observe_generate(
            wt0, wt1, wt2, t1 - t0, t2 - t1, steps,
            {"model": cfg.name, "batch": 1, "steps": steps,
             "speculative": mode},
            {"bucket": s0, "compiled": prefill_fresh},
            {"steps": steps, "incremental": True})
        return GenerateResult(tokens=[out], prefill_ms=(t1 - t0) * 1e3,
                              decode_ms=(t2 - t1) * 1e3, steps=steps)

    # ---- introspection ----------------------------------------------

    def stats(self):
        from distributed_llm_inferencing_tpu.models.params import (
            param_bytes, param_count)
        return {
            "model": self.cfg.name,
            "mesh": self.mesh_spec.axis_sizes(),
            "params": param_count(self.params),
            "param_bytes": param_bytes(self.params),
            "max_seq": self.max_seq,
            "compiled_prefill_buckets": sorted(self._prefill_fns),
            "adapters": sorted(self._adapters),
        }
