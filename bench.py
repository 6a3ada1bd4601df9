"""Headline benchmark: GPT-2 decode tokens/sec/chip vs the reference stack.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

- ours: distributed_llm_inferencing_tpu engine (jitted prefill+decode, bf16)
  on the default JAX backend (the chip). With no chip and no explicit
  cpu request (--platform is not parsed here: DLI_PLATFORM=cpu or
  JAX_PLATFORMS=cpu) the bench exits non-zero and prints no result
  (utils/platform.ensure_backend); an asked-for CPU run carries
  {"platform": "cpu"}.
- baseline: the reference's serving stack — HF transformers ``generate()``
  on torch CPU (the reference's worker hot loop, worker/app.py:297-305) —
  measured fresh in the same process, same model config, same sampling
  params (top_p=0.95, top_k=50, temperature=0.8), same prompt/new-token
  counts. Both sides use random-init full-size gpt2 (125M) weights: no
  network access, and wall-clock is weight-value-independent.
  NOTE ``vs_baseline`` is a cross-stack AND cross-hardware multiplier
  (our TPU/JAX stack vs the reference's torch-CPU stack — the hardware
  each actually runs on); it is not a like-for-like chip comparison. The
  line carries ``baseline_stack`` so the number can't be misread.

Extra keys run in PRIORITY order (contract-critical first, long-tail
extras last) so a mid-run failure or the time budget can never cost the
headline numbers:
  batched_* — 8 concurrent gpt2 requests through the continuous batcher
              (runtime/batcher.py)
  llama_3_8b_int8|int4|int4_eq8_tokens_per_s — the north-star model
              (BASELINE.md config 2): int8, nibble-packed int4 via the
              pallas fused-unpack kernel (ops/pallas/quant_matmul.py),
              and int4 + int8-quantized embed/unembed tables
  batched_greedy_rep[_spec]_tokens_per_s — greedy x8 on a repetitive
              workload, plain vs on-device-drafted speculative decoding
  batched_stag_x32_* — 32 requests with Poisson arrivals over ~1s:
              honest TTFT/latency percentiles under staggered load
              (single-wave percentiles are degenerate — p50 == p95)
  prefill_chunk_stall_ms[_off] — max inter-token stall of an active
              decode stream while a long prompt admits, chunked prefill
              on vs off (the feature's entire point)
  moe_* — fits-on-one-chip MoE proxy (registry moe-proxy-8e): decode
              tok/s plus dense- vs capacity-dispatch prefill tok/s
              (BASELINE.md config 4's measurable stand-in)
  *_hbm_bw_util — bytes-per-token (= weight bytes at batch 1) x tok/s
              against the chip's spec HBM bandwidth
"""

import json
import os
import subprocess
import sys
import time

PROMPT_LEN = 16
NEW_TOKENS = 64
MODEL = "gpt2"

# spec HBM bandwidth by TPU generation (bytes/s), keyed on substrings of
# jax Device.device_kind
_HBM_BW = (
    ("v5 lite", 819e9), ("v5e", 819e9),
    ("v6 lite", 1640e9), ("v6e", 1640e9),
    ("v5p", 2765e9), ("v5", 819e9), ("v4", 1228e9),
)


# peak dense bf16 FLOP/s by TPU generation, same keying
_PEAK_FLOPS = (
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5p", 459e12), ("v5", 197e12), ("v4", 275e12),
)


def _chip_lookup(table, what):
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for sub, v in table:
        if sub in kind:
            return v
    raise ValueError(f"no {what} on record for device kind {kind!r}")


def _chip_bw():
    return _chip_lookup(_HBM_BW, "HBM bandwidth")


def _chip_flops():
    return _chip_lookup(_PEAK_FLOPS, "peak FLOP/s")


_PARTIAL_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_PARTIAL.json")


def _persist(result):
    """Keys captured so far, for whoever reads BENCH_PARTIAL.json after a
    run that never reached its final print."""
    with open(_PARTIAL_PATH, "w") as f:
        json.dump({**result, "partial": True, "ts": round(time.time())},
                  f, indent=1)


def bench_reference_stack():
    import torch
    import transformers
    torch.manual_seed(0)
    model = transformers.GPT2LMHeadModel(transformers.GPT2Config()).eval()
    prompt = torch.randint(0, 50257, (1, PROMPT_LEN))
    # explicit attention_mask + pad_token_id: without them HF warns per
    # call AND may behave differently around the (absent) pad token — the
    # baseline must measure exactly what we compare against, quietly
    kw = dict(do_sample=True, top_p=0.95, top_k=50, temperature=0.8,
              attention_mask=torch.ones_like(prompt),
              pad_token_id=model.config.eos_token_id)
    best = 0.0
    with torch.no_grad():
        model.generate(prompt, max_new_tokens=8, **kw)  # warmup
        for _ in range(3):   # best-of-3, same methodology as bench_ours
            t0 = time.perf_counter()
            out = model.generate(prompt, max_new_tokens=NEW_TOKENS, **kw)
            dt = time.perf_counter() - t0
            best = max(best, (out.shape[1] - PROMPT_LEN) / dt)
    return best


def _sampling():
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    return SamplingParams(temperature=0.8, top_k=50, top_p=0.95)


def bench_engine(model=MODEL, quant=None, new_tokens=NEW_TOKENS, repeats=3,
                 dtype=None, prompt_len=PROMPT_LEN, kv_quant=None,
                 embed_quant=None):
    """Best-of-N decode tok/s for one engine-mode model, batch 1.
    Returns (tok_s, weight_bytes) — weight bytes stream through the MXU
    every decode step, so they set the bandwidth roofline."""
    import numpy as np
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    cfg = get_config(model)
    if quant:
        cfg = cfg.replace(quant=quant)
    if dtype:
        cfg = cfg.replace(dtype=dtype)
    if kv_quant:
        cfg = cfg.replace(kv_quant=kv_quant)
    if embed_quant:
        cfg = cfg.replace(embed_quant=embed_quant)
    eng = InferenceEngine(cfg, max_seq=prompt_len + new_tokens + 16, seed=0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).tolist()
    sp = _sampling()
    # warmup/compile (same chunk programs as the timed runs)
    eng.generate([prompt], max_new_tokens=new_tokens, sampling=sp)
    best = 0.0
    for _ in range(repeats):   # best-of-N: host dispatch latency is
        # noisy run to run
        res = eng.generate([prompt], max_new_tokens=new_tokens, sampling=sp)
        total_ms = res.prefill_ms + res.decode_ms
        best = max(best, len(res.tokens[0]) / (total_ms / 1e3))
    return best, eng.stats()["param_bytes"]


def bench_speculative(new_tokens=NEW_TOKENS):
    """Prompt-lookup speculative decoding vs plain decode, same repetitive
    prompt (the workload class speculation targets — quoting/templated
    text). Returns (plain_tok_s, spec_tok_s)."""
    import numpy as np
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    cfg = get_config(MODEL)
    eng = InferenceEngine(cfg, max_seq=64 + new_tokens + 24, seed=0)
    rng = np.random.default_rng(0)
    prompt = (rng.integers(0, cfg.vocab_size, 8).tolist() * 8)[:64]
    sp = SamplingParams.greedy()

    def best_of(fn, n=3):
        fn()   # warmup/compile
        best = 0.0
        for _ in range(n):
            res = fn()
            ms = res.prefill_ms + res.decode_ms
            best = max(best, len(res.tokens[0]) / (ms / 1e3))
        return best

    plain = best_of(lambda: eng.generate(
        [prompt], max_new_tokens=new_tokens, sampling=sp))
    spec = best_of(lambda: eng.generate(
        [prompt], max_new_tokens=new_tokens, sampling=sp,
        speculative="ngram", spec_gamma=4))
    return plain, spec


def _control_plane_workers(n_workers, max_new=1):
    """Spin up in-proc batched workers (tiny-llama, 8 slots) and warm
    every program shape a loaded cluster dispatches. The admit/decode
    programs compile per power-of-two row bucket (1/2/4/8 with 8
    slots), so the warm drives each bucket DETERMINISTICALLY: one
    ``/inference_batch`` of exactly k sub-requests queues k rows under
    one lock (batcher.submit_many), and the admission pass takes them
    as one k-row wave. Burst-warming with concurrent singles instead
    leaves small buckets cold and a timed run then stalls 1-2s on each
    mid-benchmark XLA compile, which is exactly the noise a
    control-plane A/B can't afford."""
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent

    workers = []
    for _ in range(n_workers):
        agent = WorkerAgent()
        srv = agent.serve("127.0.0.1", 0, background=True)
        wport = srv.server_address[1]
        r = _rq.post(f"http://127.0.0.1:{wport}/load_model", json={
            "model_name": "tiny-llama", "allow_random_init": True,
            "dtype": "float32", "serving": "batched", "slots": 8,
            "kv_blocks": 256, "kv_block_size": 8, "max_seq": 64},
            timeout=600)
        assert r.status_code == 200, r.text
        workers.append((agent, wport))

    for _, wport in workers:
        for k in (8, 4, 2, 1):          # one wave per row bucket
            sub = {"prompt": "hi", "max_new_tokens": max_new,
                   "sampling": {"do_sample": False}}
            r = _rq.post(f"http://127.0.0.1:{wport}/inference_batch",
                         json={"model_name": "tiny-llama",
                               "requests": [dict(sub) for _ in range(k)]},
                         timeout=600)
            assert r.status_code == 200, r.text
        # and the plain single-request path (generic /inference handler)
        r = _rq.post(f"http://127.0.0.1:{wport}/inference", json={
            "model_name": "tiny-llama", "prompt": "hi",
            "max_new_tokens": max_new,
            "sampling": {"do_sample": False}}, timeout=600)
        assert r.status_code == 200, r.text
    return workers


def _goodput(done, wall):
    """SLO/goodput rollup over completed request rows. The master
    persists each request's cost-ledger record onto its row, so the
    bench evaluates the SAME per-request signal the master's SLO
    evaluator uses (runtime/tsdb.py cost_within_slo) — goodput is
    requests completing WITHIN the declared SLO per second, reported
    next to raw completed-req/s in every scenario."""
    from distributed_llm_inferencing_tpu.runtime import tsdb
    targets = tsdb.slo_targets()
    evaluated = good = 0
    for st in done:
        cost = st.get("cost")
        if isinstance(cost, str):
            try:
                cost = json.loads(cost)
            except ValueError:
                cost = None
        ok = tsdb.cost_within_slo(cost, targets)
        if ok is None:
            continue
        evaluated += 1
        good += bool(ok)
    return {
        "ttft_target_ms": targets["ttft_ms"],
        "itl_p95_target_ms": targets["itl_p95_ms"],
        "evaluated": evaluated,
        "within_slo": good,
        "attainment": (round(good / evaluated, 3) if evaluated else None),
        "goodput_req_per_s": round(good / max(wall, 1e-9), 2),
    }


def bench_control_plane(n_requests=160, concurrency=32, n_workers=2,
                        mode="batched", max_new=1, workers=None):
    """Control-plane saturation: master + in-proc batched workers, N
    requests from ``concurrency`` HTTP client threads. Reports
    sustained completed-requests/s, dispatch overhead (master-side time
    a request spends outside worker execution) p50/p95, and the RPC
    connection-reuse ratio off the pooled keep-alive sessions.

    ``mode="single"`` reproduces the pre-PR dispatcher shape — one
    claim per dispatch, a fresh TCP connection per RPC, the pre-PR
    default of 4 dispatcher threads — for the A/B the acceptance
    criterion compares (same workers, same client load). Pass
    ``workers`` (from _control_plane_workers) to A/B both modes
    against the same warm cluster; the caller then owns their shutdown.

    ``max_new`` defaults to 1 because this scenario measures the
    CONTROL plane: on CPU the per-token compute is linear in active
    rows, so long generations saturate the worker in every mode and
    hide the dispatch layer entirely (both shapes flatline at the same
    req/s). One token keeps the data plane a few ms per request and
    the dispatch overhead is what's left.
    """
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    own_workers = workers is None
    if own_workers:
        workers = _control_plane_workers(n_workers, max_new=max_new)
    if mode == "single":
        m = Master(":memory:", dispatcher_threads=4, dispatch_batch=1,
                   rpc_pool=False, health_interval=2.0)
    else:
        m = Master(":memory:", health_interval=2.0)   # shipped defaults
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    mport = msrv.server_address[1]
    base = f"http://127.0.0.1:{mport}"
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        m.start_background()
        done, failed, lock = [], [], _th.Lock()
        next_i = [0]

        def client():
            sess = _rq.Session()
            while True:
                with lock:
                    if next_i[0] >= n_requests:
                        return
                    i = next_i[0]
                    next_i[0] += 1
                rid = sess.post(f"{base}/api/inference/submit", json={
                    "model_name": "tiny-llama", "prompt": "hi",
                    "max_new_tokens": max_new,
                    "sampling": {"do_sample": False,
                                 "allow_random_init": True},
                }).json()["request_id"]
                # status polls back off 20ms -> 200ms: a fixed fast
                # cadence costs ~20 polls per completion and the poll
                # storm (32 clients x HTTP parse + store read each)
                # starves the very dispatch path being measured —
                # throttling BOTH modes toward the same ceiling and
                # hiding the control-plane delta
                poll = 0.02
                while True:
                    st = sess.get(
                        f"{base}/api/inference/status/{rid}"
                    ).json()["request"]
                    if st["status"] in ("completed", "failed"):
                        with lock:
                            (done if st["status"] == "completed"
                             else failed).append(st)
                        break
                    time.sleep(poll)
                    poll = min(0.2, poll * 1.5)

        t0 = time.time()
        threads = [_th.Thread(target=client) for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.time() - t0
        snap = m.metrics.snapshot()
        c = snap["counters"]
        created = c.get("master_rpc_conns_created", 0)
        reused = c.get("master_rpc_conns_reused", 0)
        overhead = snap["timings"].get("master_dispatch_overhead", {})
        batch_sz = snap["timings"].get("master_dispatch_batch_size", {})
        return {
            "mode": mode,
            "requests": n_requests,
            "concurrency": concurrency,
            "workers": n_workers,
            "completed": len(done),
            "failed": len(failed),
            "completed_req_per_s": round(len(done) / max(wall, 1e-9), 2),
            "wall_s": round(wall, 2),
            "dispatch_overhead_ms_p50": round(
                overhead.get("p50", 0.0) * 1e3, 1),
            "dispatch_overhead_ms_p95": round(
                overhead.get("p95", 0.0) * 1e3, 1),
            "dispatch_batch_size_mean": round(batch_sz.get("mean", 1.0), 2),
            "rpc_conns_created": created,
            "rpc_conns_reused": reused,
            "rpc_conn_reuse_ratio": round(
                reused / max(1.0, created + reused), 3),
            "sched_picks": {k[len("scheduler_pick_"):]: int(v)
                            for k, v in c.items()
                            if k.startswith("scheduler_pick_")},
            "slo": _goodput(done, wall),
        }
    finally:
        m.stop()
        if own_workers:
            for agent, _ in workers:
                agent.service.shutdown()


def _prefix_sys(g: int) -> str:
    """64-char shared 'system prompt' for group g: 8 whole 8-token blocks
    with the byte tokenizer, 4 whole 16-byte digest chunks."""
    return f"<{g:03d}>" + "s" * 59


def _prefix_prompt(g: int, i: int) -> str:
    """Group-shared system prefix + a 15-char per-request tail (the tail
    never block-aligns into the shared prefix)."""
    return _prefix_sys(g) + f"|u{i:04d}|" + "t" * 7


_PREFIX_DIGEST_CHUNK = 16   # bytes; 64-char sys prefix = 4 whole chunks


def _prefix_cache_workers(n_workers, kv_host_mb, kv_blocks=64):
    """In-proc batched workers for the prefix-cache scenario: small KV
    pool (eviction pressure is part of the workload), host arena sized by
    ``kv_host_mb`` (0 = tier off), and a staged warm that compiles both
    admission shapes the timed run dispatches — cold full-prompt tails
    and warm shared-prefix tails — per power-of-two wave bucket, using
    warm-only prompt groups so the timed groups start radix-cold."""
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent

    workers = []
    for _ in range(n_workers):
        agent = WorkerAgent()
        srv = agent.serve("127.0.0.1", 0, background=True)
        wport = srv.server_address[1]
        r = _rq.post(f"http://127.0.0.1:{wport}/load_model", json={
            "model_name": "tiny-llama", "allow_random_init": True,
            "dtype": "float32", "serving": "batched", "slots": 8,
            "kv_blocks": kv_blocks, "kv_block_size": 8, "max_seq": 128,
            "kv_host_mb": kv_host_mb,
            "kv_digest_chunk": _PREFIX_DIGEST_CHUNK}, timeout=600)
        assert r.status_code == 200, r.text

        def wave(subs):
            rr = _rq.post(f"http://127.0.0.1:{wport}/inference_batch",
                          json={"model_name": "tiny-llama",
                                "requests": subs}, timeout=600)
            assert rr.status_code == 200, rr.text

        for k in (8, 4, 2, 1):
            # cold shape: k DISTINCT warm groups in one wave (no same-
            # wave shared prefix, so all k admit as one k-row bucket)
            wave([{"prompt": _prefix_prompt(900 + k * 10 + j, j),
                   "max_new_tokens": 4, "sampling": {"do_sample": False}}
                  for j in range(k)])
            # warm shape: same groups again, new tails -> shared-prefix
            # admissions (small tail bucket, deep prefix bucket)
            wave([{"prompt": _prefix_prompt(900 + k * 10 + j, 100 + j),
                   "max_new_tokens": 4, "sampling": {"do_sample": False}}
                  for j in range(k)])
        # plain single-request path
        r = _rq.post(f"http://127.0.0.1:{wport}/inference", json={
            "model_name": "tiny-llama", "prompt": _prefix_prompt(990, 0),
            "max_new_tokens": 4, "sampling": {"do_sample": False}},
            timeout=600)
        assert r.status_code == 200, r.text
        workers.append((agent, wport))
    return workers


def bench_prefix_cache(n_requests=96, concurrency=8, n_workers=2,
                       groups=6, tier_on=True, workers=None):
    """Shared-system-prompt serving through a live master: ``groups``
    request families share a 64-char system prefix within the family,
    submitted interleaved (round-robin over groups) from ``concurrency``
    client threads — the workload where prefix-blind routing scatters a
    family over every worker and each pays full prefill.

    ``tier_on`` toggles the WHOLE cluster prefix tier: affinity routing
    (master ``prefix_weight``) plus the workers' host arena + digest
    advertisement (``kv_host_mb``). Reports completed/failed, client
    latency percentiles, the cluster-wide prefill cached-token fraction
    (tokens served from the radix/arena tiers vs run through prefill),
    affinity pick counts, and arena offload/restore traffic.
    """
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    own_workers = workers is None
    if own_workers:
        workers = _prefix_cache_workers(n_workers,
                                        kv_host_mb=64 if tier_on else 0)
    m = Master(":memory:", health_interval=1.0,
               prefix_weight=None if tier_on else 0.0)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        m.start_background()
        time.sleep(1.2)   # one health sweep: queue/digest state is fresh
        done, failed, lats, lock = [], [], [], _th.Lock()
        next_i = [0]

        def client():
            sess = _rq.Session()
            while True:
                with lock:
                    if next_i[0] >= n_requests:
                        return
                    i = next_i[0]
                    next_i[0] += 1
                t0 = time.time()
                rid = sess.post(f"{base}/api/inference/submit", json={
                    "model_name": "tiny-llama",
                    "prompt": _prefix_prompt(i % groups, i),
                    "max_new_tokens": 4,
                    "sampling": {"do_sample": False,
                                 "allow_random_init": True},
                }).json()["request_id"]
                poll = 0.02
                while True:
                    st = sess.get(
                        f"{base}/api/inference/status/{rid}"
                    ).json()["request"]
                    if st["status"] in ("completed", "failed"):
                        with lock:
                            lats.append(time.time() - t0)
                            (done if st["status"] == "completed"
                             else failed).append(st)
                        break
                    time.sleep(poll)
                    poll = min(0.2, poll * 1.5)

        t0 = time.time()
        threads = [_th.Thread(target=client) for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.time() - t0
        wc = {}
        for agent, _ in workers:
            for k, v in agent.metrics.snapshot()["counters"].items():
                wc[k] = wc.get(k, 0.0) + v
        cached = wc.get("prefill_cached_tokens", 0.0)
        uncached = wc.get("prefill_uncached_tokens", 0.0)
        mc = m.metrics.snapshot()["counters"]
        lats.sort()
        return {
            "tier": "on" if tier_on else "off",
            "requests": n_requests, "groups": groups,
            "completed": len(done), "failed": len(failed),
            "wall_s": round(wall, 2),
            "completed_req_per_s": round(len(done) / max(wall, 1e-9), 2),
            "latency_ms_p50": round(
                lats[len(lats) // 2] * 1e3, 1) if lats else None,
            "latency_ms_p95": round(
                lats[min(len(lats) - 1, int(len(lats) * 0.95))] * 1e3,
                1) if lats else None,
            "prefill_cached_tokens": int(cached),
            "prefill_uncached_tokens": int(uncached),
            "prefill_cached_fraction": round(
                cached / max(1.0, cached + uncached), 3),
            "affinity_picks": int(
                mc.get("scheduler_pick_prefix_affinity", 0)),
            "kvtier_offloaded_blocks": int(
                wc.get("kvtier_offloaded_blocks", 0)),
            "kvtier_restored_tokens": int(
                wc.get("kvtier_restored_tokens", 0)),
            "radix_hits": int(wc.get("radix_prefix_hits", 0)),
            "radix_misses": int(wc.get("radix_prefix_misses", 0)),
            "slo": _goodput(done, wall),
        }
    finally:
        m.stop()
        if own_workers:
            for agent, _ in workers:
                agent.service.shutdown()


def _prefix_cache_scenario(argv, opt, smoke):
    """--scenario prefix_cache [--smoke|--ab]: the tier A/B runs each leg
    against a FRESH worker set (cache state is the measured object; a
    shared warm cluster would leak leg 1's radix contents into leg 2).
    The speedup is prefill-tokens-saved: cached fraction on / off."""
    if smoke:
        n, conc, nw, groups = (opt("--requests", 24),
                               opt("--concurrency", 4), 2, 8)
    else:
        # 3 members per prefix family: the off leg's prefix-blind
        # scatter then pays a whole redundant prefix prefill per extra
        # worker a family lands on (2P vs 1P of reusable prefix for a
        # 3-member family on 2 nodes), and family members arrive far
        # enough apart that the radix has evicted the prefix in between
        # — the host arena (on leg) restores it, the off leg re-prefills
        n, conc, nw, groups = (opt("--requests", 96),
                               opt("--concurrency", 8),
                               opt("--workers", 2), opt("--groups", 32))
    result = {"scenario": "prefix_cache", "smoke": smoke}
    if "--ab" in argv:
        off = bench_prefix_cache(n, conc, nw, groups, tier_on=False)
        on = bench_prefix_cache(n, conc, nw, groups, tier_on=True)
        result.update(off=off, on=on)
        base_frac = off["prefill_cached_fraction"]
        result["prefill_saved_x"] = round(
            on["prefill_cached_fraction"] / max(base_frac, 1e-3), 2)
        if off.get("latency_ms_p50") and on.get("latency_ms_p50"):
            result["latency_p50_x"] = round(
                off["latency_ms_p50"] / max(on["latency_ms_p50"], 1e-3), 2)
    else:
        result.update(bench_prefix_cache(n, conc, nw, groups, tier_on=True))
    print(json.dumps(result))
    if smoke:
        run = result.get("on", result)
        ok = (run.get("completed") == n and run.get("failed") == 0
              and run.get("affinity_picks", 0) > 0
              and run.get("prefill_cached_fraction", 0) > 0.15)
        if not ok:
            print("prefix-cache smoke FAILED", file=sys.stderr)
            return 1
        print(f"prefix-cache smoke ok: cached fraction "
              f"{run['prefill_cached_fraction']}, "
              f"affinity picks {run['affinity_picks']}", file=sys.stderr)
    return 0


# ---- multi-LoRA adapter serving ---------------------------------------

# synth: adapters at scale ~0.8: strong enough that the rank-r delta
# actually flips greedy argmax on the random-init tiny model (the
# checkpoint-realistic 0.05 default produces a ~0.25% relative delta
# that greedy decoding never sees — the A/B would be vacuous)
_LORA_ADAPTERS = (("ad-alpha", "synth:rank=4,seed=3,scale=0.8"),
                  ("ad-beta", "synth:rank=8,seed=9,scale=0.8"))


def _lora_workers(n_workers):
    """In-proc batched workers for the multi-LoRA scenario. The warm
    inference compiles the base (``use_lora=False``) admission/decode
    shapes; the first adapter wave pays the one LoRA-program compile."""
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent

    workers = []
    for _ in range(n_workers):
        agent = WorkerAgent()
        srv = agent.serve("127.0.0.1", 0, background=True)
        wport = srv.server_address[1]
        r = _rq.post(f"http://127.0.0.1:{wport}/load_model", json={
            "model_name": "tiny-llama", "allow_random_init": True,
            "dtype": "float32", "serving": "batched", "slots": 4,
            "kv_blocks": 128, "kv_block_size": 8, "max_seq": 128},
            timeout=600)
        assert r.status_code == 200, r.text
        rr = _rq.post(f"http://127.0.0.1:{wport}/inference", json={
            "model_name": "tiny-llama", "prompt": "warm the base path",
            "max_new_tokens": 4, "sampling": {"do_sample": False}},
            timeout=600)
        assert rr.status_code == 200, rr.text
        workers.append((agent, wport))
    return workers


def bench_multi_lora_smoke(n_requests=24, concurrency=4, n_workers=2):
    """Mixed-adapter serving through a live master: register two
    adapters in the replicated registry, interleave base / ad-alpha /
    ad-beta submits, and verify the full control-plane story — lazy
    dispatch-time loads (``dli_adapter_lazy_loads_total``), adapter-
    affinity picks after residency lands, the adapter-loaded /
    adapter-evicted decision trail in ``/api/events``, and zero
    failures (an adapter problem FAILS the request, never silently
    serves base weights)."""
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    workers = _lora_workers(n_workers)
    m = Master(":memory:", health_interval=1.0)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        for name, source in _LORA_ADAPTERS:
            r = _rq.post(f"{base}/api/adapters/register", json={
                "adapter": name, "source": source,
                "model_name": "tiny-llama"}).json()
            assert r["status"] == "success", r
        m.start_background()
        time.sleep(1.2)   # one health sweep: snapshots are fresh
        done, failed, lock = [], [], _th.Lock()
        next_i = [0]
        rotation = (None,) + tuple(n for n, _ in _LORA_ADAPTERS)

        def client():
            sess = _rq.Session()
            while True:
                with lock:
                    if next_i[0] >= n_requests:
                        return
                    i = next_i[0]
                    next_i[0] += 1
                body = {"model_name": "tiny-llama",
                        "prompt": f"<q{i:03d}> tell me about item {i}",
                        "max_new_tokens": 4,
                        "sampling": {"do_sample": False,
                                     "allow_random_init": True}}
                adapter = rotation[i % len(rotation)]
                if adapter:
                    body["adapter"] = adapter
                rid = sess.post(f"{base}/api/inference/submit",
                                json=body).json()["request_id"]
                poll = 0.02
                while True:
                    st = sess.get(
                        f"{base}/api/inference/status/{rid}"
                    ).json()["request"]
                    if st["status"] in ("completed", "failed"):
                        with lock:
                            (done if st["status"] == "completed"
                             else failed).append(st)
                        break
                    time.sleep(poll)
                    poll = min(0.2, poll * 1.5)

        t0 = time.time()
        threads = [_th.Thread(target=client) for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.time() - t0
        mc = m.metrics.snapshot()["counters"]
        loaded_evts = _rq.get(f"{base}/api/events",
                              params={"type": "adapter-loaded"}).json()
        resident = _rq.get(f"{base}/api/adapters").json()
        return {
            "requests": n_requests, "completed": len(done),
            "failed": len(failed), "wall_s": round(wall, 2),
            "affinity_picks": int(
                mc.get("scheduler_pick_adapter_affinity", 0)),
            "lazy_loads": int(mc.get("adapter_lazy_loads", 0)),
            "load_failures": int(mc.get("adapter_load_failures", 0)),
            "adapter_loaded_events": int(loaded_evts.get("count", 0)),
            "residency": resident.get("residency", {}),
        }
    finally:
        m.stop()
        for agent, _ in workers:
            agent.service.shutdown()


def bench_multi_lora_ab(n_requests=18, tokens=24):
    """The tentpole's zero-cost-mixing claim, measured on direct
    in-proc batchers sharing ONE base param tree: a mixed-adapter
    stream (base + two adapters interleaved in the same waves) must
    sustain >= 0.9x the tokens-per-weight-pass of a base-only stream —
    batching is preserved, adapters never split the wave — and every
    adapter's greedy output must be bitwise-equal to a dedicated
    single-adapter batcher's (the gathered per-slot delta is exact,
    not an approximation)."""
    import jax
    import jax.numpy as jnp
    import numpy as _np
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)

    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = _np.random.default_rng(23)
    prompts = [rng.integers(0, 256, 6 + (i % 5)).tolist()
               for i in range(n_requests)]
    rotation = (None,) + tuple(n for n, _ in _LORA_ADAPTERS)

    def mk():
        return ContinuousBatcher(cfg, params, num_blocks=256, block_size=8,
                                 slots=4, max_seq=96)

    def run(b, assign):
        counters = b.metrics.snapshot()["counters"]
        t0 = (counters.get("batcher_tokens_emitted", 0),
              counters.get("batcher_weight_passes", 0))
        reqs = [b.submit(prompts[i], max_new_tokens=tokens,
                         sampling=SamplingParams.greedy(), seed=700 + i,
                         adapter=ad)
                for i, ad in assign]
        for _ in range(6000):
            b.step()
            if all(r.done.is_set() for r in reqs):
                break
        for r in reqs:
            assert r.error is None, r.error
        counters = b.metrics.snapshot()["counters"]
        emitted = counters.get("batcher_tokens_emitted", 0) - t0[0]
        passes = counters.get("batcher_weight_passes", 0) - t0[1]
        return {(i, ad): r.tokens for (i, ad), r in zip(assign, reqs)}, \
            emitted / max(passes, 1)

    # base-only leg: every request on the shared base weights
    _, base_tpp = run(mk(), [(i, None) for i in range(n_requests)])
    # mixed leg: base + both adapters interleaved in the same waves
    mixed = mk()
    for name, source in _LORA_ADAPTERS:
        mixed.load_adapter(name, source)
    assign = [(i, rotation[i % len(rotation)]) for i in range(n_requests)]
    mixed_out, mixed_tpp = run(mixed, assign)
    # dedicated legs: one batcher per adapter serving ONLY that
    # adapter's slice of the workload — the bitwise reference
    bitwise_equal = True
    for name, source in _LORA_ADAPTERS:
        ded = mk()
        ded.load_adapter(name, source)
        sub = [(i, ad) for i, ad in assign if ad == name]
        ded_out, _ = run(ded, sub)
        for key in sub:
            if ded_out[key] != mixed_out[key]:
                bitwise_equal = False
    return {
        "requests": n_requests, "tokens_each": tokens,
        "base_tokens_per_pass": round(base_tpp, 3),
        "mixed_tokens_per_pass": round(mixed_tpp, 3),
        "mixing_cost_x": round(mixed_tpp / max(base_tpp, 1e-9), 3),
        "bitwise_equal_vs_dedicated": bitwise_equal,
    }


def _multi_lora_scenario(argv, opt, smoke):
    """--scenario multi_lora [--smoke|--ab]: multi-adapter serving.
    ``--ab`` gates mixed-adapter batching efficiency (>= 0.9x base
    tokens-per-weight-pass) and per-adapter bitwise equality against
    dedicated single-adapter batchers; ``--smoke`` gates the routed
    path — adapter-affinity picks > 0, lazy load -> serve, the
    adapter-loaded trail in /api/events, zero failures. Writes
    /tmp/dli_bench_multi_lora.json for the CI artifact."""
    result = {"scenario": "multi_lora", "smoke": smoke}
    rc = 0
    if "--ab" in argv:
        ab = bench_multi_lora_ab(opt("--requests", 18),
                                 opt("--tokens", 24))
        result["ab"] = ab
        ok = (ab["mixing_cost_x"] >= 0.9
              and ab["bitwise_equal_vs_dedicated"])
        if not ok:
            print("multi-lora A/B FAILED", file=sys.stderr)
            rc = 1
    if smoke or "--ab" not in argv:
        run = bench_multi_lora_smoke(opt("--requests", 24),
                                     opt("--concurrency", 4),
                                     opt("--workers", 2))
        result.update(run)
        if smoke:
            ok = (run["completed"] == result["requests"]
                  and run["failed"] == 0
                  and run["affinity_picks"] > 0
                  and run["lazy_loads"] > 0
                  and run["adapter_loaded_events"] > 0)
            if not ok:
                print("multi-lora smoke FAILED", file=sys.stderr)
                rc = 1
            else:
                print(f"multi-lora smoke ok: affinity picks "
                      f"{run['affinity_picks']}, lazy loads "
                      f"{run['lazy_loads']}, loaded events "
                      f"{run['adapter_loaded_events']}", file=sys.stderr)
    with open("/tmp/dli_bench_multi_lora.json", "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return rc


_DISAGG_MODEL = "tiny-llama-long"     # 1k-context tiny llama (registry)


def _disagg_prompt_long(i):
    """~770 byte-tokens (96 full 8-token blocks), unique per request —
    shared prefixes would let the radix/affinity tiers hide exactly the
    prefill interference this scenario measures. At this length a
    prefill program costs tens of decode steps of compute, so colocated
    prefill visibly stalls co-resident decode streams."""
    return f"<L{i:03d}>" + \
        "The quick brown fox jumps over the lazy dog. " * 17


def _disagg_prompt_short(i):
    return f"<s{i:03d}> please continue the story"


def _disagg_workers(roles):
    """In-proc batched workers for the disaggregation scenario, one per
    role. Warm compiles the long-admission, short-admission, and decode
    shapes the timed run dispatches; a (prefill, decode) pair also warms
    the export -> /kv_fetch -> restore path end to end."""
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent

    workers = []
    for i, role in enumerate(roles):
        agent = WorkerAgent(role=role)
        srv = agent.serve("127.0.0.1", 0, background=True)
        wport = srv.server_address[1]
        r = _rq.post(f"http://127.0.0.1:{wport}/load_model", json={
            "model_name": _DISAGG_MODEL, "allow_random_init": True,
            "dtype": "float32", "serving": "batched", "slots": 2,
            "kv_blocks": 1280, "kv_block_size": 8, "max_seq": 1024,
            # both legs run UNCHUNKED prefill: chunked prefill is the
            # orthogonal interference mitigation (it bounds a stall at
            # the cost of prefill efficiency); the A/B isolates what
            # DISAGGREGATION removes — on the decode pool a transferred
            # prompt's admission is a block scatter plus a tail-only
            # prefill no matter how long the prompt is
            "prefill_chunk": 0,
            # latency-tier decode: 8-token chunk cap so inter-token gaps
            # track steps — a 64-token mega-chunk would deliver a whole
            # short request as one burst and hide every stall from the
            # ITL percentiles (same cap both legs)
            "decode_chunk_cap": 8}, timeout=600)
        assert r.status_code == 200, r.text
        for prompt, mx in ((_disagg_prompt_long(900 + i), 1),
                           (_disagg_prompt_short(900 + i), 24)):
            rr = _rq.post(f"http://127.0.0.1:{wport}/inference", json={
                "model_name": _DISAGG_MODEL, "prompt": prompt,
                "max_new_tokens": mx, "sampling": {"do_sample": False}},
                timeout=600)
            assert rr.status_code == 200, rr.text
        workers.append((agent, wport))
    if "prefill" in roles and "decode" in roles:
        pport = workers[roles.index("prefill")][1]
        dport = workers[roles.index("decode")][1]
        prompt = _disagg_prompt_long(990)
        rr = _rq.post(f"http://127.0.0.1:{pport}/inference", json={
            "model_name": _DISAGG_MODEL, "prompt": prompt,
            "max_new_tokens": 1, "kv_export": True,
            "sampling": {"do_sample": False}}, timeout=600)
        assert rr.status_code == 200, rr.text
        rr = _rq.post(f"http://127.0.0.1:{dport}/inference", json={
            "model_name": _DISAGG_MODEL, "prompt": prompt,
            "max_new_tokens": 1,
            "kv_source": {"url": f"http://127.0.0.1:{pport}",
                          "model": _DISAGG_MODEL},
            "sampling": {"do_sample": False}}, timeout=600)
        assert rr.status_code == 200, rr.text
    return workers


def _pct(vals, q):
    if not vals:
        return None
    vals = sorted(vals)
    return round(vals[min(len(vals) - 1, int(len(vals) * q))], 1)


def bench_disagg(n_long=16, n_short=24, long_clients=4, short_clients=2,
                 disagg=True):
    """Long-prompt/short-decode interference through a live master
    (FlowKV's disaggregation workload). Two closed-loop client pools:
    ``long_clients`` keep unique ~114-token prefills in flight on both
    legs (the background pressure), while ``short_clients`` stream
    decode-heavy requests at a modest rate and MEASURE — worker-side
    TTFT (queue+prefill ms from the cost ledger) and decode ITL p95.
    The short pool is deliberately far below saturation: the scenario
    measures the interference a co-resident prefill inflicts on a
    decode stream, not raw fleet capacity (on this CPU box a tiny
    model's capacity story favors whichever leg has more decode slots;
    the accelerator-relevant signal is the stall a prefill program puts
    into a decode stream's token gaps, which disaggregation removes).
    ``disagg`` toggles the fleet's role split — (prefill, decode) pools
    with cross-node KV transfer vs the colocated (mixed, mixed)
    baseline."""
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    roles = ("prefill", "decode") if disagg else ("mixed", "mixed")
    workers = _disagg_workers(roles)
    m = Master(":memory:", health_interval=1.0, disagg_min_prompt=64)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        m.start_background()
        time.sleep(1.2)   # one health sweep: roles + digests are fresh
        done, failed, lock = [], [], _th.Lock()
        short_next = [0]

        def run_one(sess, kind, i):
            body = {"model_name": _DISAGG_MODEL,
                    "sampling": {"do_sample": False,
                                 "allow_random_init": True}}
            if kind == "long":
                # prefill-dominated: one sampled token, all prompt — the
                # canonical long-prompt ingest (summarization/RAG) shape
                body.update(prompt=_disagg_prompt_long(i),
                            max_new_tokens=1)
            else:
                body.update(prompt=_disagg_prompt_short(i),
                            max_new_tokens=24)
            rid = sess.post(f"{base}/api/inference/submit",
                            json=body).json()["request_id"]
            poll = 0.02
            while True:
                st = sess.get(f"{base}/api/inference/status/{rid}"
                              ).json()["request"]
                if st["status"] in ("completed", "failed"):
                    st["_kind"] = kind
                    with lock:
                        (done if st["status"] == "completed"
                         else failed).append(st)
                    return
                time.sleep(poll)
                poll = min(0.2, poll * 1.5)

        # Arrival shapes match the phenomenon under test. Long prompts
        # arrive in synchronized BURSTS of ``long_clients`` (batch
        # ingest / RAG pipelines are bursty): during a burst every
        # colocated node is prefilling at once, so the queue-aware
        # scheduler has no idle node to dodge to — which is exactly the
        # regime FlowKV disaggregates away. The short stream is paced
        # (closed loop + think time) below saturation: its TTFT/ITL
        # then measure collision probability with prefill work, not
        # queue-drain luck.
        def long_pump():
            i = 0
            while i < n_long:
                burst = min(long_clients, n_long - i)
                ts = [_th.Thread(target=run_one,
                                 args=(_rq.Session(), "long", i + j))
                      for j in range(burst)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=600)
                i += burst
                time.sleep(0.25)

        def short_client():
            sess = _rq.Session()
            while True:
                with lock:
                    if short_next[0] >= n_short:
                        return
                    i = short_next[0]
                    short_next[0] += 1
                run_one(sess, "short", i)
                time.sleep(0.12)

        t0 = time.time()
        threads = ([_th.Thread(target=long_pump)]
                   + [_th.Thread(target=short_client)
                      for _ in range(short_clients)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.time() - t0
        short_ttft, short_itl, long_e2e = [], [], []
        for st in done:
            cost = st.get("cost")
            if isinstance(cost, str):
                try:
                    cost = json.loads(cost)
                except ValueError:
                    cost = None
            if st["_kind"] == "long":
                if st.get("completed_at") and st.get("created_at"):
                    long_e2e.append(
                        (st["completed_at"] - st["created_at"]) * 1e3)
                continue
            if not cost:
                continue
            short_ttft.append(cost["queue_ms"] + cost["prefill_ms"])
            if cost.get("itl_p95_ms") is not None:
                short_itl.append(cost["itl_p95_ms"])
        wc = {}
        for agent, _ in workers:
            for k, v in agent.metrics.snapshot()["counters"].items():
                wc[k] = wc.get(k, 0.0) + v
        mc = m.metrics.snapshot()["counters"]
        n = n_long + n_short
        return {
            "mode": "disagg" if disagg else "colocated",
            "requests": n, "long": n_long, "short": n_short,
            "completed": len(done), "failed": len(failed),
            "wall_s": round(wall, 2),
            "ttft_ms_p50": _pct(short_ttft, 0.5),
            "ttft_ms_p95": _pct(short_ttft, 0.95),
            "itl_p95_ms_p50": _pct(short_itl, 0.5),
            "itl_p95_ms_p95": _pct(short_itl, 0.95),
            "long_e2e_ms_p50": _pct(long_e2e, 0.5),
            "kv_transfer_blocks": int(wc.get("kv_transfer_blocks", 0)),
            "kv_transfer_bytes": int(wc.get("kv_transfer_bytes", 0)),
            "kv_transfer_failures": int(
                wc.get("kv_transfer_failures", 0)),
            "kvtier_exported_blocks": int(
                wc.get("kvtier_exported_blocks", 0)),
            "disagg_transfers": int(
                mc.get("scheduler_disagg_transfer", 0)),
            "disagg_recomputes": int(
                mc.get("scheduler_disagg_recompute", 0)),
            "disagg_prefill_failed": int(
                mc.get("disagg_prefill_failed", 0)),
            "role_picks": {
                "prefill": int(mc.get("scheduler_pick_role_prefill", 0)),
                "decode": int(mc.get("scheduler_pick_role_decode", 0))},
            "slo": _goodput(done, wall),
        }
    finally:
        m.stop()
        for agent, _ in workers:
            agent.service.shutdown()


def bench_disagg_probe(disagg=True, rounds=6):
    """Controlled interference probe: what does a LONG-PROMPT ARRIVAL
    cost a decode stream already running on the target node? Per round:
    a probe short (64 decode tokens) streams on the target node; mid-
    decode, a long prompt lands on that node together with a second
    short. Measured: the in-flight short's worst inter-token gap (the
    stall the long's admission injects into its decode) and the
    arriving short's worker-side TTFT.

    ``disagg=True`` stages the long's prefill on a prefill-role peer
    first (kv_export — in steady state phase 1 happened earlier on the
    prefill pool) and the arrival is the decode-role dispatch with a
    ``kv_source`` hint: admission is a block scatter + tail-only
    prefill. ``disagg=False`` is the colocated arrival: a cold full
    prefill on the busy node — the fleet-busy case where queue-aware
    routing has no idle node to dodge to. Deterministic sequencing
    makes this the low-variance twin of the open workload's percentile
    comparison."""
    import threading as _th
    import requests as _rq

    roles = ("prefill", "decode") if disagg else ("mixed",)
    workers = _disagg_workers(roles)
    tgt = workers[-1][1]        # decode node / the colocated node
    pport = workers[0][1]
    try:
        def infer(port, body):
            body.setdefault("sampling", {"do_sample": False})
            body["model_name"] = _DISAGG_MODEL
            r = _rq.post(f"http://127.0.0.1:{port}/inference", json=body,
                         timeout=600)
            assert r.status_code == 200, r.text
            return r.json()

        stalls, ttfts, fails = [], [], [0]
        for k in range(rounds):
            long_p = _disagg_prompt_long(600 + k)
            body_long = {"prompt": long_p, "max_new_tokens": 1}
            if disagg:
                infer(pport, {"prompt": long_p, "max_new_tokens": 1,
                              "kv_export": True})
                body_long["kv_source"] = {
                    "url": f"http://127.0.0.1:{pport}",
                    "model": _DISAGG_MODEL}
            out = {}

            def run(name, port, body):
                try:
                    out[name] = infer(port, body)
                except AssertionError:
                    fails[0] += 1

            a = _th.Thread(target=run, args=("A", tgt, {
                "prompt": _disagg_prompt_short(600 + k),
                "max_new_tokens": 64}))
            a.start()
            time.sleep(0.1)         # A is mid-decode when the long lands
            lt = _th.Thread(target=run, args=("long", tgt, body_long))
            bt = _th.Thread(target=run, args=("B", tgt, {
                "prompt": _disagg_prompt_short(700 + k),
                "max_new_tokens": 8}))
            lt.start()
            # B arrives strictly AFTER the long's admission began — a
            # simultaneous submit would race the FIFO queue and
            # sometimes measure B in FRONT of the long
            time.sleep(0.04)
            bt.start()
            for t in (a, lt, bt):
                t.join(timeout=600)
            if len(out) == 3:
                stalls.append(out["A"]["cost"]["itl_max_ms"])
                cb = out["B"]["cost"]
                ttfts.append(cb["queue_ms"] + cb["prefill_ms"])
        return {
            "mode": "disagg" if disagg else "colocated",
            "rounds": rounds, "failed": fails[0],
            "probe_stall_ms_p50": _pct(stalls, 0.5),
            "probe_short_ttft_ms_p50": _pct(ttfts, 0.5),
        }
    finally:
        for agent, _ in workers:
            agent.service.shutdown()


def bench_disagg_compression(host_dtype="native", rounds=3):
    """One (prefill, decode) pair under ``DLI_KV_HOST_DTYPE=
    host_dtype``: export ``rounds`` unique long prompts on the prefill
    node, pull each over the wire to the decode node (direct
    ``kv_source`` dispatch), and return the wire/restore counters plus
    every greedy completion. Run once per dtype and compare: the int8
    leg must ship >=3x fewer wire bytes than native at zero transfer
    failures with identical greedy outputs (the ``--ab`` compression
    gate). Counters are diffed against the post-warmup snapshot so the
    warm-path transfer in ``_disagg_workers`` doesn't pollute the
    measurement."""
    import requests as _rq

    prev = os.environ.get("DLI_KV_HOST_DTYPE")
    os.environ["DLI_KV_HOST_DTYPE"] = host_dtype
    try:
        workers = _disagg_workers(("prefill", "decode"))
    finally:
        if prev is None:
            os.environ.pop("DLI_KV_HOST_DTYPE", None)
        else:
            os.environ["DLI_KV_HOST_DTYPE"] = prev
    (pagent, pport), (dagent, dport) = workers
    base0 = {}
    for agent in (pagent, dagent):
        for k, v in agent.metrics.snapshot()["counters"].items():
            base0[k] = base0.get(k, 0.0) + v
    try:
        outs, fails = [], 0
        for k in range(rounds):
            prompt = _disagg_prompt_long(800 + k)
            r = _rq.post(f"http://127.0.0.1:{pport}/inference", json={
                "model_name": _DISAGG_MODEL, "prompt": prompt,
                "max_new_tokens": 1, "kv_export": True,
                "sampling": {"do_sample": False}}, timeout=600)
            if r.status_code != 200:
                fails += 1
                continue
            r = _rq.post(f"http://127.0.0.1:{dport}/inference", json={
                "model_name": _DISAGG_MODEL, "prompt": prompt,
                "max_new_tokens": 8,
                "kv_source": {"url": f"http://127.0.0.1:{pport}",
                              "model": _DISAGG_MODEL},
                "sampling": {"do_sample": False}}, timeout=600)
            if r.status_code != 200:
                fails += 1
                continue
            outs.append([int(t) for t in r.json()["tokens"]])
        wc = {}
        for agent in (pagent, dagent):
            for k, v in agent.metrics.snapshot()["counters"].items():
                wc[k] = wc.get(k, 0.0) + v
        delta = {k: wc.get(k, 0.0) - base0.get(k, 0.0) for k in wc}
        gauges = dagent.metrics.snapshot()["gauges"]
        return {
            "host_dtype": host_dtype, "rounds": rounds, "failed": fails,
            "tokens": outs,
            "kv_wire_sent_bytes": int(delta.get("kv_wire_sent_bytes", 0)),
            "kv_wire_raw_bytes": int(delta.get("kv_wire_raw_bytes", 0)),
            "kv_transfer_blocks": int(delta.get("kv_transfer_blocks", 0)),
            "kv_transfer_failures": int(
                delta.get("kv_transfer_failures", 0)),
            "kv_prefetch_coalesced": int(
                delta.get("kv_prefetch_coalesced", 0)),
            "kv_restore_overlap_ratio": round(float(
                gauges.get("kv_restore_overlap_ratio", 0.0)), 3),
        }
    finally:
        for agent, _ in workers:
            agent.service.shutdown()


def _disagg_scenario(argv, opt, smoke):
    """--scenario disagg [--smoke|--ab]: disaggregated prefill/decode
    pools vs the colocated baseline. The smoke gates zero failures plus
    at least one real cross-node transfer; the A/B additionally reports
    the short stream's TTFT p50 and decode ITL p95 improvement ratios
    (colocated / disaggregated — above 1.0 means disaggregation wins)
    and runs the compression legs (native vs DLI_KV_HOST_DTYPE=int8
    through the same transfer path), gating >=3x fewer wire bytes at
    zero failures with greedy outputs matching the native leg. Writes
    /tmp/dli_bench_disagg.json for the CI artifact."""
    if smoke:
        n_long, n_short, lc, sc = (opt("--long", 4), opt("--short", 8),
                                   2, 2)
    else:
        n_long, n_short, lc, sc = (opt("--long", 24), opt("--short", 36),
                                   opt("--long-clients", 4),
                                   opt("--short-clients", 2))
    result = {"scenario": "disagg", "smoke": smoke}
    if "--ab" in argv:
        # the open workload (failures, transfers, tail percentiles
        # under stochastic arrivals) plus the controlled interference
        # probe (the low-variance measurement of what one long-prompt
        # arrival costs a decode stream — the ratio the acceptance
        # criteria gate on; open-workload MEDIANS at this CPU scale
        # measure queue luck, see bench_disagg's docstring)
        colo = bench_disagg(n_long, n_short, lc, sc, disagg=False)
        dis = bench_disagg(n_long, n_short, lc, sc, disagg=True)
        p_colo = bench_disagg_probe(disagg=False)
        p_dis = bench_disagg_probe(disagg=True)
        # compression leg: same transfer path twice, native vs int8
        # arena storage — wire bytes must shrink >=3x at zero failures
        # with greedy outputs matching the native leg token-for-token
        c_nat = bench_disagg_compression("native")
        c_q8 = bench_disagg_compression("int8")
        result.update(colocated=colo, disagg=dis,
                      probe_colocated=p_colo, probe_disagg=p_dis,
                      compress_native=c_nat, compress_int8=c_q8)
        if c_q8.get("kv_wire_sent_bytes"):
            result["wire_bytes_x"] = round(
                c_nat.get("kv_wire_sent_bytes", 0)
                / max(c_q8["kv_wire_sent_bytes"], 1), 2)
        result["greedy_match"] = (bool(c_nat.get("tokens"))
                                  and c_nat.get("tokens")
                                  == c_q8.get("tokens"))
        if p_colo.get("probe_short_ttft_ms_p50") \
                and p_dis.get("probe_short_ttft_ms_p50"):
            result["ttft_p50_x"] = round(
                p_colo["probe_short_ttft_ms_p50"]
                / max(p_dis["probe_short_ttft_ms_p50"], 1e-3), 2)
        if p_colo.get("probe_stall_ms_p50") \
                and p_dis.get("probe_stall_ms_p50"):
            result["itl_stall_x"] = round(
                p_colo["probe_stall_ms_p50"]
                / max(p_dis["probe_stall_ms_p50"], 1e-3), 2)
        if colo.get("itl_p95_ms_p95") and dis.get("itl_p95_ms_p95"):
            result["workload_itl_p95_x"] = round(
                colo["itl_p95_ms_p95"]
                / max(dis["itl_p95_ms_p95"], 1e-3), 2)
        ok = (colo.get("failed") == 0 and dis.get("failed") == 0
              and p_colo.get("failed") == 0 and p_dis.get("failed") == 0
              and dis.get("kv_transfer_blocks", 0) >= 1
              and result.get("ttft_p50_x", 0) > 1.0
              and result.get("itl_stall_x", 0) > 1.0
              and c_nat.get("failed") == 0 and c_q8.get("failed") == 0
              and c_nat.get("kv_transfer_failures") == 0
              and c_q8.get("kv_transfer_failures") == 0
              and result.get("wire_bytes_x", 0) >= 3.0
              and result["greedy_match"])
        print(json.dumps(result))
        try:
            with open("/tmp/dli_bench_disagg.json", "w") as f:
                json.dump(result, f, indent=1)
        except OSError:
            pass
        if not ok:
            print("disagg A/B gate FAILED", file=sys.stderr)
            return 1
        print(f"disagg A/B ok: arriving-short TTFT p50 "
              f"{result['ttft_p50_x']}x, in-flight decode stall "
              f"{result['itl_stall_x']}x, workload ITL tail "
              f"{result.get('workload_itl_p95_x')}x, int8 wire bytes "
              f"{result['wire_bytes_x']}x smaller (greedy outputs "
              f"match), 0 failures all legs", file=sys.stderr)
        return 0
    result.update(bench_disagg(n_long, n_short, lc, sc, disagg=True))
    print(json.dumps(result))
    try:
        with open("/tmp/dli_bench_disagg.json", "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass
    if smoke:
        run = result
        n = n_long + n_short
        ok = (run.get("completed") == n and run.get("failed") == 0
              and run.get("kv_transfer_blocks", 0) >= 1
              and run.get("disagg_transfers", 0) >= 1)
        if not ok:
            print("disagg smoke FAILED", file=sys.stderr)
            return 1
        print(f"disagg smoke ok: {run['kv_transfer_blocks']} blocks "
              f"({run['kv_transfer_bytes']} B) transferred across "
              f"{run['disagg_transfers']} disaggregated dispatches, "
              f"0 failures", file=sys.stderr)
    return 0


_REBAL_MODEL = "tiny-llama"          # short-prompt uniform mix: tiny ctx


def _rebalance_workers(roles):
    """In-proc batched tiny-llama workers for the rebalance scenario
    (uniform short-prompt mix), warmed for the short admission +
    decode shapes the run dispatches."""
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent

    workers = []
    for i, role in enumerate(roles):
        agent = WorkerAgent(role=role)
        srv = agent.serve("127.0.0.1", 0, background=True)
        wport = srv.server_address[1]
        r = _rq.post(f"http://127.0.0.1:{wport}/load_model", json={
            "model_name": _REBAL_MODEL, "allow_random_init": True,
            "dtype": "float32", "serving": "batched", "slots": 2,
            "kv_blocks": 96, "kv_block_size": 8, "max_seq": 128,
            "decode_chunk_cap": 8}, timeout=600)
        assert r.status_code == 200, r.text
        rr = _rq.post(f"http://127.0.0.1:{wport}/inference", json={
            "model_name": _REBAL_MODEL,
            "prompt": _disagg_prompt_short(900 + i),
            "max_new_tokens": 24, "sampling": {"do_sample": False}},
            timeout=600)
        assert rr.status_code == 200, rr.text
        workers.append((agent, wport))
    return workers


def bench_rebalance_uniform(mode, n=120, clients=6, ramp=24,
                            max_new=24):
    """Uniform short-prompt mix through a live master — the workload
    BENCH_r07 showed static disaggregation LOSING on (goodput dropped
    8.23->5.31 req/s because the strict prefill node idles while the
    decode node serves everything). Three fleet modes:

    - ``colocated``: (mixed, mixed), the baseline both pools serve;
    - ``static``:    (prefill, decode), roles pinned — the strand;
    - ``elastic``:   (prefill, decode) + the rebalancer: sustained
      queue-depth divergence flips the idle prefill worker into the
      decode pool, converging to the colocated topology.

    A ``ramp`` of untimed requests runs first so every mode measures
    its STEADY state (for elastic that includes rebalancer
    convergence — the flip itself is the ramp's business; static gets
    the same ramp and stays stranded). Goodput = completed measured
    requests / measured wall.

    CPU-box caveat: every in-proc worker shares ONE
    CPU, so per-node capacity is not additive and stranding a node
    cannot shrink fleet throughput here the way BENCH_r07's
    8.23->5.31 req/s drop shows on real per-node hardware. The
    substrate-valid strand evidence is the rebalancer's own detection
    — sustained decode-pool queue divergence against an idle strict
    prefill node, answered by a role flip — plus elastic goodput >=
    colocated (elasticity costs nothing and converges the static
    topology to the colocated one, which on per-node hardware IS the
    recovered capacity)."""
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    roles = ("mixed", "mixed") if mode == "colocated" \
        else ("prefill", "decode")
    workers = _rebalance_workers(roles)
    m = Master(":memory:", health_interval=0.5,
               rebalance=(mode == "elastic"),
               rebalance_interval_s=0.3, rebalance_sustain_s=1.2,
               rebalance_ratio=2.0, tsdb_step_s=0.3)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        m.start_background()
        time.sleep(1.2)          # one health sweep: roles fresh
        done, failed, lock = [], [], _th.Lock()
        nxt = [-ramp]            # negative ids are the untimed ramp

        def run_one(sess, i):
            body = {"model_name": _REBAL_MODEL,
                    "prompt": _disagg_prompt_short(1000 + i),
                    "max_new_tokens": max_new,
                    "sampling": {"do_sample": False,
                                 "allow_random_init": True}}
            rid = sess.post(f"{base}/api/inference/submit",
                            json=body).json()["request_id"]
            poll = 0.02
            while True:
                st = sess.get(f"{base}/api/inference/status/{rid}"
                              ).json()["request"]
                if st["status"] in ("completed", "failed"):
                    if i >= 0:   # ramp requests are not measured
                        with lock:
                            (done if st["status"] == "completed"
                             else failed).append(st)
                    return
                time.sleep(poll)
                poll = min(0.2, poll * 1.5)

        t_start = [None]

        def client():
            sess = _rq.Session()
            while True:
                with lock:
                    if nxt[0] >= n:
                        return
                    i = nxt[0]
                    nxt[0] += 1
                    if i == 0:   # ramp done: the measured window opens
                        t_start[0] = time.time()
                run_one(sess, i)

        threads = [_th.Thread(target=client) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        wall = time.time() - (t_start[0] or time.time())
        mc = m.metrics.snapshot()["counters"]
        return {
            "mode": mode, "requests": n, "ramp": ramp,
            "completed": len(done), "failed": len(failed),
            "wall_s": round(wall, 2),
            "goodput_req_s": round(len(done) / max(wall, 1e-6), 2),
            "role_flips": int(mc.get("rebalancer_role_flips", 0)),
            "migrations": int(mc.get("requests_migrated", 0)),
            "slo": _goodput(done, wall),
        }
    finally:
        m.stop()
        for agent, _ in workers:
            agent.service.shutdown()


def bench_rebalance_chaos(n=10):
    """Kill a decode worker mid-wave (FailSafe leg): long-prompt
    disaggregated requests, the decode node dies while serving, and
    every request must still complete with output identical to an
    undisturbed reference run — zero lost, zero duplicated tokens —
    with recovery paid as a KV re-fetch (the persisted kv_source), not
    a re-prefill. Reports recovered-vs-cold prefill cost so the
    "cheaper than one re-prefill" claim is measured, not asserted."""
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    workers = _disagg_workers(("prefill", "decode", "decode"))
    (pre_a, _), (d1_a, d1p), (d2_a, d2p) = workers
    m = Master(":memory:", health_interval=0.5, disagg_min_prompt=64,
               infer_timeout=30)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        m.start_background()
        time.sleep(1.2)

        def run_wave(tag, kill=False):
            out, lock = {}, _th.Lock()
            killed = [None]

            def one(sess, i):
                body = {"model_name": _DISAGG_MODEL,
                        "prompt": _disagg_prompt_long(i),
                        "max_new_tokens": 8,
                        "sampling": {"do_sample": False,
                                     "allow_random_init": True}}
                rid = sess.post(f"{base}/api/inference/submit",
                                json=body).json()["request_id"]
                poll = 0.02
                while True:
                    st = sess.get(
                        f"{base}/api/inference/status/{rid}"
                    ).json()["request"]
                    if st["status"] in ("completed", "failed"):
                        with lock:
                            out[i] = st
                        return
                    time.sleep(poll)
                    poll = min(0.2, poll * 1.5)

            def killer():
                # kill decode node d1 the moment it is serving an
                # in-flight request — mid-stream by construction (the
                # _processing window is the phase-2 dispatch itself)
                deadline = time.time() + 30
                while time.time() < deadline:
                    if any(nd["port"] == d1p
                           for nd in list(m._processing.values())):
                        killed[0] = d1p
                        d1_a.service.shutdown()
                        return
                    time.sleep(0.003)

            kt = _th.Thread(target=killer) if kill else None
            if kt is not None:
                kt.start()       # armed BEFORE the first submit: a
                # warm-cache wave can finish in well under a second
            ts = [_th.Thread(target=one, args=(_rq.Session(), i))
                  for i in range(n)]
            for j, t in enumerate(ts):
                t.start()
                if j < len(ts) - 1:
                    # staggered arrivals: the wave spans long enough
                    # that work remains in flight when the node dies
                    time.sleep(0.12)
            for t in ts:
                t.join(timeout=600)
            if kt is not None:
                kt.join(timeout=600)
            return out, killed[0]

        # chaos FIRST, on the cold fleet: every long prompt actually
        # disaggregates (a warm fleet's prefix advertisements would
        # price recompute cheaper and skip the kv_source hint this leg
        # exists to exercise). The greedy reference wave runs after —
        # output is node-independent, so the comparison stands.
        chaos, killed_port = run_wave("chaos", kill=True)
        ref, _ = run_wave("ref")
        assert all(st["status"] == "completed" for st in ref.values())
        mismatched = [i for i in range(n)
                      if chaos.get(i, {}).get("result")
                      != ref[i]["result"]]
        failed = [i for i, st in chaos.items()
                  if st["status"] != "completed"]
        recovered, rec_prefill, cold_prefill = 0, [], []
        for i, st in chaos.items():
            cost = st.get("cost")
            if isinstance(cost, str):
                try:
                    cost = json.loads(cost)
                except ValueError:
                    cost = None
            refc = ref[i].get("cost")
            if isinstance(refc, str):
                try:
                    refc = json.loads(refc)
                except ValueError:
                    refc = None
            if st.get("attempts", 0) >= 1 and cost:
                recovered += 1
                rec_prefill.append(cost.get("prefill_ms") or 0)
                cached = (cost.get("prefill_cached_tokens") or 0)
                uncached = (cost.get("prefill_uncached_tokens") or 0)
                cold_prefill.append(
                    ((refc or {}).get("prefill_ms") or 0, cached,
                     uncached))
        rec_cached = sum(c for _, c, _ in cold_prefill)
        rec_uncached = sum(u for _, _, u in cold_prefill)
        surv = d2_a if killed_port == d1p else d1_a
        sc = {}
        for k, v in surv.metrics.snapshot()["counters"].items():
            sc[k] = v
        return {
            "requests": n, "killed_port": killed_port,
            "completed": sum(1 for st in chaos.values()
                             if st["status"] == "completed"),
            "failed": len(failed),
            "mismatched_outputs": len(mismatched),
            "recovered_requests": recovered,
            # the FailSafe claim, measured: tokens of the recovered
            # attempts' prefill served from cache/transfer vs recomputed
            "recovered_prefill_cached_tokens": rec_cached,
            "recovered_prefill_uncached_tokens": rec_uncached,
            "recovered_prefill_ms_p50": _pct(rec_prefill, 0.5),
            "survivor_kv_transfer_blocks": int(
                sc.get("kv_transfer_blocks", 0)),
        }
    finally:
        m.stop()
        for agent, _ in workers:
            try:
                agent.service.shutdown()
            except Exception:
                pass


def _rebalance_scenario(argv, opt, smoke):
    """--scenario rebalance [--smoke|--ab]: elastic rebalancing + live
    migration. The smoke gates one proactive role flip on the uniform
    mix plus kill-mid-wave recovery with zero lost/duplicated tokens;
    the A/B adds the colocated/static legs (the BENCH_r07 strand),
    gating elastic goodput >= 0.95x colocated, and re-runs the
    interference probe to show the disaggregation wins survive
    elasticity. Writes the result JSON to /tmp/dli_bench_rebalance.json
    for the CI artifact."""
    result = {"scenario": "rebalance", "smoke": smoke}
    if smoke:
        n, clients, ramp, n_chaos = (opt("--requests", 60), 6, 20,
                                     opt("--chaos-requests", 6))
    else:
        # saturating shape: enough closed-loop clients that the decode
        # pool queues (the rebalancer's divergence signal is real) and
        # the hot-node shedding leg engages
        n, clients, ramp, n_chaos = (opt("--requests", 160),
                                     opt("--clients", 14),
                                     opt("--ramp", 30),
                                     opt("--chaos-requests", 10))
    if "--ab" in argv:
        mx = opt("--max-new", 32)
        colo = bench_rebalance_uniform("colocated", n, clients, ramp,
                                       max_new=mx)
        static = bench_rebalance_uniform("static", n, clients, ramp,
                                         max_new=mx)
        elastic = bench_rebalance_uniform("elastic", n, clients, ramp,
                                          max_new=mx)
        chaos = bench_rebalance_chaos(n_chaos)
        p_colo = bench_disagg_probe(disagg=False)
        p_dis = bench_disagg_probe(disagg=True)
        result.update(colocated=colo, static=static, elastic=elastic,
                      chaos=chaos, probe_colocated=p_colo,
                      probe_disagg=p_dis)
        g = lambda leg: leg.get("goodput_req_s") or 0.0  # noqa: E731
        result["static_vs_colocated_x"] = round(
            g(static) / max(g(colo), 1e-6), 3)
        result["elastic_vs_colocated_x"] = round(
            g(elastic) / max(g(colo), 1e-6), 3)
        if p_colo.get("probe_short_ttft_ms_p50") \
                and p_dis.get("probe_short_ttft_ms_p50"):
            result["ttft_p50_x"] = round(
                p_colo["probe_short_ttft_ms_p50"]
                / max(p_dis["probe_short_ttft_ms_p50"], 1e-3), 2)
        if p_colo.get("probe_stall_ms_p50") \
                and p_dis.get("probe_stall_ms_p50"):
            result["itl_stall_x"] = round(
                p_colo["probe_stall_ms_p50"]
                / max(p_dis["probe_stall_ms_p50"], 1e-3), 2)
        # BENCH_r07's probe wins must survive elasticity (within 20%)
        try:
            with open(os.path.join(os.path.dirname(__file__),
                                   "BENCH_r07.json")) as f:
                r07 = json.load(f)
            result["r07_ttft_p50_x"] = r07.get("ttft_p50_x")
            result["r07_itl_stall_x"] = r07.get("itl_stall_x")
        except Exception:
            r07 = {}
        ok = (all(leg.get("failed") == 0
                  for leg in (colo, static, elastic))
              and elastic.get("completed") == n
              and elastic.get("role_flips", 0) >= 1
              and result.get("elastic_vs_colocated_x", 0) >= 0.95
              and chaos.get("failed") == 0
              and chaos.get("mismatched_outputs") == 0
              and chaos.get("recovered_requests", 0) >= 1
              and chaos.get("recovered_prefill_cached_tokens", 0) > 0
              and result.get("ttft_p50_x", 0) > 1.0
              and result.get("itl_stall_x", 0) > 1.0)
        if r07.get("ttft_p50_x") and r07.get("itl_stall_x"):
            preserved = (
                result.get("ttft_p50_x", 0)
                >= 0.8 * float(r07["ttft_p50_x"])
                and result.get("itl_stall_x", 0)
                >= 0.8 * float(r07["itl_stall_x"]))
            result["probe_vs_r07_preserved"] = preserved
            ok = ok and preserved
    else:
        elastic = bench_rebalance_uniform("elastic", n, clients, ramp)
        chaos = bench_rebalance_chaos(n_chaos)
        result.update(elastic=elastic, chaos=chaos)
        ok = (elastic.get("failed") == 0
              and elastic.get("completed") == n
              and elastic.get("role_flips", 0) >= 1
              and chaos.get("failed") == 0
              and chaos.get("mismatched_outputs") == 0
              and chaos.get("recovered_requests", 0) >= 1)
    print(json.dumps(result))
    try:
        with open("/tmp/dli_bench_rebalance.json", "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass
    if not ok:
        print("rebalance gate FAILED", file=sys.stderr)
        return 1
    if "--ab" in argv:
        print(f"rebalance A/B ok: elastic "
              f"{result['elastic_vs_colocated_x']}x colocated goodput "
              f"(static {result['static_vs_colocated_x']}x), "
              f"{result['elastic']['role_flips']} flip(s), chaos "
              f"{chaos['recovered_requests']} recovered / 0 lost, "
              f"probe TTFT {result.get('ttft_p50_x')}x stall "
              f"{result.get('itl_stall_x')}x", file=sys.stderr)
    else:
        print(f"rebalance smoke ok: {elastic['role_flips']} flip(s), "
              f"goodput {elastic['goodput_req_s']} req/s, chaos "
              f"{chaos['recovered_requests']} recovered, 0 failures, "
              f"0 mismatches", file=sys.stderr)
    return 0


def _plan_workers(delay_s):
    """Heterogeneous 3-worker fleet for the planner scenario: three
    identical in-proc tiny-llama workers, one throttled via a
    server-side latency fault on its /inference point — the same
    injection surface the chaos gates use, so the slowdown is visible
    exactly where the planner must see it (the master's latency EWMA
    and the node's tok/s TSDB series), not hardcoded into the model."""
    workers = _rebalance_workers(("mixed", "mixed", "mixed"))
    agent0, _ = workers[0]
    agent0.service.faults.arm(
        [{"point": "/inference", "mode": "latency", "delay_s": delay_s}],
        seed=0, replace=True)
    return workers


def bench_plan_hetero(planned, workers, delay_s, n=36, clients=4,
                      ramp=12, max_new=24, bound_s=None):
    """One leg of the planner A/B on the live heterogeneous fleet.

    ``planned=False`` is the naive-uniform baseline: every node serves
    mixed, the scheduler spreads work across all three — closed-loop
    clients that land on the throttled worker sit out its injected
    delay, wasting concurrency the fast nodes never see.
    ``planned=True`` asks ``POST /api/plans/auto`` for a decision after
    the warmup ramp has taught the master its EWMAs/TSDB rates, then
    lets the rebalancer steer roles to the planner's target (the
    throttled node quarantined into the strict prefill pool, out of
    the short-prompt dispatch path).

    Goodput = measured requests completing within ``bound_s`` / wall.
    The bound is derived from the leg's own ramp when not given (p25
    of ramp e2e — a fast-node service time — plus half the injected
    delay): fast completions clear it, throttled ones cannot."""
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    m = Master(":memory:", health_interval=0.5, rebalance=planned,
               rebalance_interval_s=0.3, rebalance_sustain_s=0.8,
               rebalance_ratio=2.0, tsdb_step_s=0.3)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        m.start_background()
        time.sleep(1.2)          # one health sweep: roles fresh
        done, failed, lock = [], [], _th.Lock()

        def run_one(sess, i, sink=None):
            body = {"model_name": _REBAL_MODEL,
                    "prompt": _disagg_prompt_short(3000 + i),
                    "max_new_tokens": max_new,
                    "sampling": {"do_sample": False,
                                 "allow_random_init": True}}
            t0 = time.time()
            rid = sess.post(f"{base}/api/inference/submit",
                            json=body).json()["request_id"]
            poll = 0.02
            while True:
                st = sess.get(f"{base}/api/inference/status/{rid}"
                              ).json()["request"]
                if st["status"] in ("completed", "failed"):
                    el = time.time() - t0
                    if sink is not None:
                        with lock:
                            sink.append((st["status"], el))
                    return
                time.sleep(poll)
                poll = min(0.2, poll * 1.5)

        def wave(count, sink):
            nxt = [0]

            def client():
                sess = _rq.Session()
                while True:
                    with lock:
                        if nxt[0] >= count:
                            return
                        i = nxt[0]
                        nxt[0] += 1
                    run_one(sess, i, sink)

            ts = [_th.Thread(target=client) for _ in range(clients)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=900)

        ramp_rows = []
        wave(ramp, ramp_rows)    # untimed: teaches EWMAs + TSDB rates
        if bound_s is None:
            els = sorted(el for _, el in ramp_rows) or [0.5]
            bound_s = els[len(els) // 4] * 2.0 + delay_s * 0.5
        decision = None
        if planned:
            time.sleep(1.0)      # a few TSDB steps past the ramp
            # the quarantine signal (the throttled node's latency EWMA
            # crossing the SLO bound) can lag the ramp when its last
            # throttled completion raced the telemetry sweep; the
            # search is deterministic on settled inputs, so give the
            # signal a bounded window to land before measuring
            for attempt in range(3):
                r = _rq.post(f"{base}/api/plans/auto", json={
                    "model_name": _REBAL_MODEL,
                    "est_prompt_tokens": 8,
                    "est_decode_tokens": max_new,
                    "slo_e2e_ms": bound_s * 1e3,
                    "force": attempt > 0}).json()
                assert r.get("status") == "success", r
                decision = r["decision"]
                if (decision.get("chosen") or {}).get("prefill_nodes"):
                    break
                time.sleep(2.0)
            # the rebalancer steers toward the planner's target split;
            # wait for the quarantine flip to land before measuring
            deadline = time.time() + 15.0
            while time.time() < deadline:
                st = _rq.get(f"{base}/api/nodes/status").json()["nodes"]
                if any(nd.get("role") == "prefill" for nd in st):
                    break
                time.sleep(0.25)
        rows = []
        t0 = time.time()
        wave(n, rows)
        wall = time.time() - t0
        completed = [el for s2, el in rows if s2 == "completed"]
        within = sum(1 for el in completed if el <= bound_s)
        roles = {nd["name"]: nd.get("role")
                 for nd in _rq.get(f"{base}/api/nodes/status"
                                   ).json()["nodes"]}
        leg = {
            "mode": "planned" if planned else "naive-uniform",
            "requests": n, "ramp": ramp, "clients": clients,
            "completed": len(completed),
            "failed": len(rows) - len(completed),
            "wall_s": round(wall, 2),
            "bound_s": round(bound_s, 3),
            "within_bound": within,
            "goodput_req_s": round(within / max(wall, 1e-6), 2),
            "req_per_s": round(len(completed) / max(wall, 1e-6), 2),
            "roles": roles,
        }
        if decision is not None:
            chosen = decision.get("chosen") or {}
            leg["planner"] = {
                "plan_id": decision.get("plan_id"),
                "mesh": chosen.get("mesh"),
                "role_split": chosen.get("role_split"),
                "prefill_nodes": chosen.get("prefill_nodes"),
                "score_goodput_req_s":
                    chosen.get("score_goodput_req_s"),
                "candidates": decision.get("candidates"),
                "scored": decision.get("scored"),
                # the fitted classes (rates, latencies) explain WHY the
                # split was chosen — keep them in the CI artifact
                "classes": (decision.get("inputs") or {}).get("classes"),
            }
        return leg
    finally:
        m.stop()


def _plan_scenario(argv, opt, smoke):
    """--scenario plan [--smoke|--ab]: heterogeneity-aware planner on a
    live fleet — three workers, one throttled by an injected /inference
    latency fault. The A/B runs naive-uniform first (also calibrating
    the shared within-bound SLO from its ramp), then the planner leg,
    gating planner goodput >= 1.15x naive (DLI_BENCH_PLAN_MIN_X) with
    zero failures on both legs. The smoke runs the planner leg only
    and gates the full decision->steering path: a persisted decision,
    the throttled worker steered into the prefill pool, zero failures.
    Writes /tmp/dli_bench_plan.json for the CI artifact."""
    # 6s ≈ 60x a fast-node service time: deep enough that requests
    # landing on the throttled worker bust the SLO bound AND strand a
    # closed-loop client, which is the regime where quarantining it
    # (what the planner chooses) measurably beats keeping its capacity
    delay_s = opt("--delay", 6.0, float)
    if smoke:
        n, ramp = opt("--requests", 10), 8
    else:
        n, ramp = opt("--requests", 36), opt("--ramp", 12)
    clients = opt("--clients", 4)
    result = {"scenario": "plan", "smoke": smoke, "delay_s": delay_s}
    workers = _plan_workers(delay_s)
    try:
        if "--ab" in argv:
            naive = bench_plan_hetero(False, workers, delay_s, n=n,
                                      clients=clients, ramp=ramp)
            planned = bench_plan_hetero(True, workers, delay_s, n=n,
                                        clients=clients, ramp=ramp,
                                        bound_s=naive["bound_s"])
            result.update(naive=naive, planned=planned)
            result["planned_vs_naive_x"] = round(
                planned["goodput_req_s"]
                / max(naive["goodput_req_s"], 1e-6), 3)
            min_x = float(os.environ.get("DLI_BENCH_PLAN_MIN_X", "1.15"))
            result["min_x"] = min_x
            ok = (naive["failed"] == 0 and planned["failed"] == 0
                  and naive["completed"] == n
                  and planned["completed"] == n
                  and planned.get("planner") is not None
                  and result["planned_vs_naive_x"] >= min_x)
        else:
            planned = bench_plan_hetero(True, workers, delay_s, n=n,
                                        clients=clients, ramp=ramp)
            result.update(planned=planned)
            pl = planned.get("planner") or {}
            ok = (planned["failed"] == 0
                  and planned["completed"] == n
                  and pl.get("plan_id") is not None
                  and "prefill" in planned["roles"].values())
    finally:
        for agent, _ in workers:
            agent.service.shutdown()
    print(json.dumps(result))
    try:
        with open("/tmp/dli_bench_plan.json", "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass
    if not ok:
        print("plan gate FAILED", file=sys.stderr)
        return 1
    if "--ab" in argv:
        print(f"plan A/B ok: planner {result['planned_vs_naive_x']}x "
              f"naive-uniform goodput "
              f"({result['planned']['goodput_req_s']} vs "
              f"{result['naive']['goodput_req_s']} req/s within "
              f"{result['naive']['bound_s']}s), 0 failures both legs",
              file=sys.stderr)
    else:
        print(f"plan smoke ok: plan {planned['planner']['plan_id']} "
              f"chosen ({planned['planner']['scored']} candidates "
              f"scored), throttled worker steered to prefill, "
              f"goodput {planned['goodput_req_s']} req/s, 0 failures",
              file=sys.stderr)
    return 0


def _free_port():
    from distributed_llm_inferencing_tpu.utils.platform import free_port
    return free_port()


def bench_ha_failover(n=16, lease_ms=1000.0, clients=4, max_new=8):
    """Kill-the-leader chaos gate (docs/robustness.md "Replicated
    control plane"): a live 2-master (leader subprocess + in-proc
    standby) / 2-worker fleet under load, SIGKILL the lease-holding
    master mid-wave, and require:

    - the standby holds the lease within 2 lease intervals;
    - every acked request reaches exactly one terminal state — zero
      lost (the submit barrier replicated the row before the client
      saw the id), zero duplicated (worker-side generation executions
      == requests, the idempotency-tag accounting: a re-dispatch of
      the dead leader's in-flight work joins/replays, never re-runs);
    - dashboard/API reads stay live on the survivor THROUGHOUT (a
      poller hits /api/nodes/status + the dashboard page every 250ms
      across the kill);
    - the takeover is reconstructable from the replicated journal
      alone: the survivor's /api/events serves the leader-era
      node-added records (replication) plus its own lease-acquired +
      takeover-recovery records.

    The leader is a REAL subprocess killed with SIGKILL — no flush, no
    goodbye, dead sockets — which is exactly the failure ROADMAP item
    4 names."""
    import os as _os
    import signal as _sig
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    lease_s = lease_ms / 1e3
    workers = _rebalance_workers(("mixed", "mixed"))
    lport = _free_port()
    leader_base = f"http://127.0.0.1:{lport}"
    standby = Master(":memory:", ha_peers=[leader_base],
                     ha_lease_ms=lease_ms, ha_repl_barrier=True,
                     health_interval=0.5, rebalance=False,
                     dispatcher_threads=2, tsdb_step_s=0.5)
    ssrv = standby.service.serve("127.0.0.1", 0, background=True)
    standby_base = f"http://127.0.0.1:{ssrv.server_address[1]}"
    env = dict(_os.environ,
               DLI_HA_PEERS=standby_base,
               DLI_HA_LEASE_MS=str(lease_ms),
               DLI_HA_REPL_BARRIER="1",
               JAX_PLATFORMS="cpu")
    log_path = "/tmp/dli_ha_leader.log"
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "distributed_llm_inferencing_tpu.runtime.master",
         "--host", "127.0.0.1", "--port", str(lport),
         "--db", ":memory:", "--ha-leader"],
        env=env, stdout=open(log_path, "w"), stderr=subprocess.STDOUT)
    dash_errors = [0]
    stop_poll = _th.Event()
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                if _rq.get(f"{leader_base}/health",
                           timeout=2).status_code == 200:
                    break
            except Exception:
                time.sleep(0.2)
        else:
            raise RuntimeError("leader subprocess never came up "
                               f"(see {log_path})")
        # arm the standby's takeover monitor only now that the leader
        # is up: a slow leader boot must not hand the standby the lease
        # before the run even starts
        standby.start_background()
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{leader_base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        # worker-side execution baseline AFTER warm, BEFORE the wave:
        # the duplicate gate is exact (executions delta == requests)
        def worker_execs():
            return sum(int(a.metrics.snapshot()["counters"]
                           .get("requests_completed", 0))
                       for a, _ in workers)

        base_execs = worker_execs()

        def dash_poll():
            # the survivor must serve reads THROUGHOUT the incident
            while not stop_poll.is_set():
                for path in ("/api/nodes/status", "/"):
                    try:
                        r = _rq.get(standby_base + path, timeout=3)
                        if r.status_code != 200:
                            dash_errors[0] += 1
                    except Exception:
                        dash_errors[0] += 1
                stop_poll.wait(0.25)

        poller = _th.Thread(target=dash_poll, daemon=True)
        poller.start()
        acked, lock = [], _th.Lock()
        entry = [leader_base]
        nxt = [0]

        def entry_refresh(sess):
            for base in (standby_base, leader_base):
                try:
                    r = sess.get(f"{base}/api/leader", timeout=2).json()
                    if r.get("is_leader"):
                        return base
                    if r.get("leader"):
                        return r["leader"]
                except Exception:
                    continue
            return None

        def submit_one(sess, i):
            # client_tag: the submit idempotency key — a retry whose
            # ack died with the leader dedupes onto the committed row
            # instead of enqueueing a second request (which would
            # honestly generate twice and fail the exactly-once gate)
            body = {"model_name": _REBAL_MODEL,
                    "prompt": _disagg_prompt_short(3000 + i),
                    "max_new_tokens": max_new,
                    "client_tag": f"ha-bench-{_os.getpid()}-{i}",
                    "sampling": {"do_sample": False,
                                 "allow_random_init": True}}
            stop_at = time.time() + 120
            while time.time() < stop_at:
                base = entry[0]
                try:
                    r = sess.post(f"{base}/api/inference/submit",
                                  json=body, timeout=15,
                                  allow_redirects=False)
                except Exception:
                    # the leader died under us: rediscover the entry
                    got = entry_refresh(sess)
                    if got:
                        entry[0] = got
                    time.sleep(0.1)
                    continue
                if r.status_code == 307:
                    loc = r.headers.get("Location") or ""
                    entry[0] = loc.rsplit("/api/", 1)[0] or entry[0]
                    continue
                if r.status_code == 200:
                    j = r.json()
                    if j.get("status") == "success":
                        return j["request_id"]
                time.sleep(0.1)
            raise TimeoutError(f"request {i} never acked")

        def client():
            sess = _rq.Session()
            while True:
                with lock:
                    if nxt[0] >= n:
                        return
                    i = nxt[0]
                    nxt[0] += 1
                rid = submit_one(sess, i)
                with lock:
                    acked.append(rid)
                time.sleep(0.05)      # stretch the wave past the kill

        kill_at = [None]
        takeover_s = [None]

        def killer():
            # mid-wave, with work demonstrably in flight: the standby's
            # REPLICA shows the claims (claims replicate), so polling
            # the survivor proves in-flight state exists at the kill
            armed_at = None
            stop_at = time.time() + 120
            while time.time() < stop_at:
                with lock:
                    k = len(acked)
                if k >= max(2, n // 3):
                    armed_at = armed_at or time.time()
                    try:
                        counts = _rq.get(
                            standby_base + "/api/inference/recent",
                            timeout=2).json().get("counts", {})
                    except Exception:
                        counts = {}
                    if counts.get("processing") or \
                            time.time() - armed_at > 3.0:
                        break
                time.sleep(0.02)
            kill_at[0] = time.time()
            _os.kill(proc.pid, _sig.SIGKILL)
            t0 = time.time()
            while time.time() - t0 < 60:
                try:
                    if _rq.get(standby_base + "/api/ha",
                               timeout=2).json().get("is_leader"):
                        takeover_s[0] = round(time.time() - kill_at[0],
                                              3)
                        return
                except Exception:
                    pass
                time.sleep(0.05)

        kt = _th.Thread(target=killer, daemon=True)
        kt.start()
        threads = [_th.Thread(target=client) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        kt.join(timeout=600)
        proc.wait(timeout=30)
        # every acked request terminal on the survivor — zero lost
        results = {}
        stop_at = time.time() + 300
        for rid in list(acked):
            while time.time() < stop_at:
                try:
                    st = _rq.get(
                        f"{standby_base}/api/inference/status/{rid}",
                        timeout=5).json()
                except Exception:
                    # a transient survivor hiccup must not crash the
                    # gate (or hang it: the artifact JSON still needs
                    # to be written for CI)
                    time.sleep(0.2)
                    continue
                req = st.get("request")
                if req is None:
                    results[rid] = {"status": "lost"}
                    break
                if req["status"] in ("completed", "failed"):
                    results[rid] = req
                    break
                time.sleep(0.1)
            else:
                results[rid] = {"status": "timeout"}
        stop_poll.set()
        poller.join(timeout=10)
        execs = worker_execs() - base_execs
        ha = _rq.get(standby_base + "/api/ha").json()

        def ev_count(etype):
            try:
                return _rq.get(standby_base + "/api/events",
                               params={"type": etype},
                               timeout=5).json().get("count", 0)
            except Exception:
                return -1

        recov = _rq.get(standby_base + "/api/events",
                        params={"type": "takeover-recovery"},
                        timeout=5).json()
        recovered = sum(int((e.get("data") or {}).get("recovered") or 0)
                        for e in recov.get("events", []))
        return {
            "requests": n, "acked": len(acked),
            "completed": sum(1 for r in results.values()
                             if r["status"] == "completed"),
            "failed": sum(1 for r in results.values()
                          if r["status"] == "failed"),
            "lost": sum(1 for r in results.values()
                        if r["status"] in ("lost", "timeout")),
            "worker_executions": execs,
            "takeover_s": takeover_s[0],
            "lease_s": lease_s,
            "takeover_within_2_leases": (takeover_s[0] is not None
                                         and takeover_s[0]
                                         <= 2 * lease_s),
            "survivor_term": ha.get("term"),
            "recovered_at_takeover": recovered,
            "dashboard_errors": dash_errors[0],
            "events_lease_acquired": ev_count("lease-acquired"),
            "events_takeover_recovery": ev_count("takeover-recovery"),
            # leader-era records served from the REPLICATED journal:
            # the survivor never added a node itself
            "events_node_added_replicated": ev_count("node-added"),
        }
    finally:
        stop_poll.set()
        try:
            proc.kill()
        except Exception:
            pass
        standby.stop()
        for agent, _ in workers:
            try:
                agent.service.shutdown()
            except Exception:
                pass


def _ha_scenario(argv, opt, smoke):
    """--scenario ha [--smoke]: the replicated-control-plane chaos
    gate. Writes the result JSON to /tmp/dli_bench_ha.json for the CI
    artifact. Gates: takeover within 2 lease intervals, zero
    lost/failed/duplicated requests, survivor dashboard reads clean,
    and the takeover reconstructable from the replicated journal."""
    result = {"scenario": "ha", "smoke": smoke}
    n = opt("--requests", 12 if smoke else 24)
    # 2x the lease is both the takeover gate AND the barrier budget: on
    # a CPU-contended box (2 masters + 2 workers + clients sharing
    # cores) a sub-second budget flakes on scheduler stalls, not on
    # replication
    lease_ms = opt("--lease-ms", 1500.0, float)
    run = bench_ha_failover(n=n, lease_ms=lease_ms,
                            clients=opt("--clients", 4))
    result.update(run)
    ok = (run["acked"] == n
          and run["completed"] == n
          and run["failed"] == 0 and run["lost"] == 0
          and run["worker_executions"] == n
          and run["takeover_within_2_leases"]
          and run["dashboard_errors"] == 0
          and run["events_lease_acquired"] >= 1
          and run["events_takeover_recovery"] >= 1
          and run["events_node_added_replicated"] >= 2)
    print(json.dumps(result))
    try:
        with open("/tmp/dli_bench_ha.json", "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass
    if not ok:
        print("ha gate FAILED", file=sys.stderr)
        return 1
    print(f"ha ok: takeover {run['takeover_s']}s "
          f"(lease {run['lease_s']}s), {run['completed']}/{n} exactly "
          f"once ({run['worker_executions']} worker executions), "
          f"{run['recovered_at_takeover']} recovered at takeover, "
          f"dashboard clean", file=sys.stderr)
    return 0


def bench_decode_speed_leg(model, n_requests, new_tokens, prompt_len,
                           wave_on, repeats=2):
    """One decode-speed leg through the in-proc continuous batcher on a
    draft-friendly (repetitive) greedy workload. Returns the leg's
    artifact: tok/s, the batcher-histogram percentiles, and the
    amortization ratio NORMALIZED PER SLOT — burst submission of
    n_requests == slots equal-budget requests keeps occupancy ~full, so
    plain decode reads ~1.0 tokens/weight-pass/slot and accepted wave
    drafts push it past it (the headline
    ``dli_decode_tokens_per_weight_pass`` signal, per slot)."""
    tput, stats = bench_batched(
        model=model, n_requests=n_requests, new_tokens=new_tokens,
        prompt_len=prompt_len, repeats=repeats, repetitive=True,
        speculative="ngram" if wave_on else None)
    slots = stats.get("active_slots") or n_requests
    tpwp = stats.get("tokens_per_weight_pass")
    leg = {
        "tokens_per_s": round(tput, 2),
        "tokens_per_weight_pass": tpwp,
        "tokens_per_weight_pass_per_slot": (
            round(tpwp / slots, 3) if tpwp else None),
        "slots": slots,
        "failed": 0,   # bench_batched raises on any failed request
    }
    for key in ("itl_ms_p50", "itl_ms_p95", "latency_ms_p50",
                "spec_mode", "spec_fallbacks", "spec_wave_dispatches",
                "spec_accepted_tokens"):
        if stats.get(key) is not None:
            leg[key] = stats[key]
    return leg


def _decode_speed_scenario(argv, opt, smoke):
    """--scenario decode_speed [--smoke|--ab]: raw decode throughput.

    Two measurements, both CPU-runnable (random-init weights — the
    measured object is the serving machinery, not the checkpoint):

    - **batched A/B**: wave-level speculation on vs plain continuous
      batching on a draft-friendly workload, gated on the per-slot
      tokens-per-weight-pass amortization (wave on must clear it, plain
      must sit ~1.0) at zero failed requests.
    - **single-stream spec-vs-plain**: the BENCH_r05 regression gate —
      speculative single-stream must be >= plain tok/s within tolerance,
      or the per-request arbitration must have measurably fallen back
      (the 5.54-vs-17.04 inversion, where always-on drafting halved
      single-stream throughput, must stay gone).
    """
    model = (argv[argv.index("--model") + 1] if "--model" in argv
             else "tiny-llama")
    if smoke:
        n, toks, plen, reps = opt("--requests", 4), 48, 24, 1
    else:
        n, toks, plen, reps = (opt("--requests", 8),
                               opt("--tokens", 96), opt("--prompt", 32), 2)
    result = {"scenario": "decode_speed", "smoke": smoke, "model": model}
    try:
        if "--ab" in argv or smoke:
            off = bench_decode_speed_leg(model, n, toks, plen, False,
                                         repeats=reps)
            on = bench_decode_speed_leg(model, n, toks, plen, True,
                                        repeats=reps)
            result.update(batched_off=off, batched_on=on)
            base = off.get("tokens_per_weight_pass_per_slot") or 1.0
            result["amortization_x"] = round(
                (on.get("tokens_per_weight_pass_per_slot") or 0.0)
                / max(base, 1e-6), 2)
        else:
            result.update(batched_on=bench_decode_speed_leg(
                model, n, toks, plen, True, repeats=reps))
        # single-stream arbitration gate (spec must never lose to plain
        # for long: either it holds within tolerance — 0.85, the honest
        # CPU-box bar where verify width is real compute, not spare MXU;
        # the r05 inversion was 0.33 — or the controller measurably
        # bailed). Longer budget than the batched legs: single-stream
        # speculation is a steady-state trade and short bursts
        # under-sample acceptance.
        s_toks = max(toks, 96)
        s_plain = bench_decode_speed_leg(model, 1, s_toks, plen, False,
                                         repeats=reps)
        s_spec = bench_decode_speed_leg(model, 1, s_toks, plen, True,
                                        repeats=reps)
        result.update(single_plain=s_plain, single_spec=s_spec)
        fell_back = (s_spec.get("spec_mode") == "plain"
                     or (s_spec.get("spec_fallbacks") or 0) > 0)
        result["single_stream_ok"] = bool(
            s_spec["tokens_per_s"] >= 0.85 * s_plain["tokens_per_s"]
            or fell_back)
    except RuntimeError as e:       # a failed request fails the scenario
        result["error"] = str(e)
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    if smoke or "--ab" in argv:
        on = result["batched_on"]
        bar = 1.2 if smoke else 1.5
        ok = (result["single_stream_ok"]
              and (on.get("tokens_per_weight_pass_per_slot") or 0) > bar
              and (result["batched_off"]
                   ["tokens_per_weight_pass_per_slot"] or 0) < 1.1)
        if not ok:
            print("decode-speed gate FAILED", file=sys.stderr)
            return 1
        print(f"decode-speed ok: wave "
              f"{on['tokens_per_weight_pass_per_slot']} tok/pass/slot "
              f"(plain {result['batched_off']['tokens_per_weight_pass_per_slot']}), "
              f"single-stream spec {result['single_spec']['tokens_per_s']} "
              f"vs plain {result['single_plain']['tokens_per_s']} tok/s",
              file=sys.stderr)
    return 0


def _sim_scale_scenario(argv, opt, smoke):
    """--scenario sim_scale [--smoke]: the cluster observatory's SCALE
    gate (docs/simulator.md). Every leg routes its requests through the
    REAL ``_pick_node``/breaker/``Store`` on the virtual clock:

    - **scale** — DLI_SIM_NODES x DLI_SIM_REQUESTS diurnal arrivals;
      gated on <120s wall, every request completed, zero starved, empty
      invariant-violation list;
    - **adversarial** — bursty/tie/heavy-tail arrivals with three nodes
      failing mid-run; breakers must open AND recover, every request
      must reach a terminal state, invariants stay clean;
    - **determinism** — two identically-seeded runs must produce the
      SAME decision-journal hash (the bit-for-bit replay bar);
    - **sublinear** — per-pick cost at 4x the fleet must stay <2x (the
      sampled scheduler's O(sample) bar).

    Writes /tmp/dli_bench_sim.json for the CI artifact."""
    from tools.dlisim import SimConfig, run_sim

    nodes = opt("--nodes", int(os.environ.get("DLI_SIM_NODES", 1000)))
    reqs = opt("--requests",
               int(os.environ.get("DLI_SIM_REQUESTS", 100_000)))
    seed = opt("--seed", int(os.environ.get("DLI_SIM_SEED", 42)))
    wall_budget = opt("--wall-budget", 120.0, float)
    result = {"scenario": "sim_scale", "smoke": smoke,
              "nodes": nodes, "requests": reqs, "seed": seed}
    failures = []

    def leg(name, rep):
        entry = {k: getattr(rep, k) for k in (
            "completed", "failed", "starved", "wall_s", "sim_s",
            "pick_us_mean", "pick_us_p95", "goodput_req_per_s",
            "ttft_ms_p50", "queue_depth_mean", "journal_hash")}
        entry["violations"] = rep.violations[:20]
        entry["breaker"] = rep.breaker
        result[name] = entry
        if rep.violations:
            failures.append(f"{name}: {len(rep.violations)} invariant "
                            f"violation(s)")
        if rep.starved:
            failures.append(f"{name}: {rep.starved} starved request(s)")
        return rep

    scale = leg("scale", run_sim(SimConfig(
        nodes=nodes, requests=reqs, duration_s=600.0,
        arrival="diurnal", seed=seed)))
    if scale.completed != reqs or scale.failed:
        failures.append(f"scale: {scale.completed}/{reqs} completed, "
                        f"{scale.failed} failed (healthy fleet)")
    if scale.wall_s >= wall_budget:
        failures.append(f"scale: wall {scale.wall_s}s >= "
                        f"{wall_budget}s budget")

    adv_n = max(8, nodes // 5)
    adv_r = max(1000, reqs // 5)
    adv = leg("adversarial", run_sim(SimConfig(
        nodes=adv_n, requests=adv_r, duration_s=600.0,
        arrival="adversarial", seed=seed,
        fail_nodes=[(0, 60.0, 180.0), (1, 90.0, 240.0),
                    (2, 120.0, 210.0)])))
    if adv.completed + adv.failed != adv_r:
        failures.append(f"adversarial: {adv.completed}+{adv.failed} "
                        f"terminal != {adv_r} submitted")
    if not adv.breaker.get("opened"):
        failures.append("adversarial: no breaker ever opened despite "
                        "three mid-run node failures")
    if not adv.breaker.get("closed"):
        failures.append("adversarial: no breaker recovered (half-open "
                        "probe -> closed) after nodes returned")

    twin_cfg = dict(nodes=50, requests=2000, duration_s=120.0,
                    arrival="bursty", seed=seed)
    t1 = run_sim(SimConfig(**twin_cfg))
    t2 = run_sim(SimConfig(**twin_cfg))
    result["determinism"] = {"hash_a": t1.journal_hash,
                             "hash_b": t2.journal_hash}
    if t1.journal_hash != t2.journal_hash:
        failures.append("determinism: identically-seeded runs diverged "
                        f"({t1.journal_hash[:12]} != "
                        f"{t2.journal_hash[:12]})")

    # sub-linearity: the sampled scheduler's per-pick cost must not
    # track fleet size. ~4x the nodes may cost at most 2x the pick —
    # in practice both fleets sample the same DLI_SCHED_SAMPLE
    # candidates and the ratio sits near 1. The small fleet stays
    # ABOVE the sampling cap on purpose: comparing a sampled pick
    # against a below-cap full scan would measure the cap, not the
    # scaling.
    from distributed_llm_inferencing_tpu.runtime.master import (
        SCHED_SAMPLE)
    small_n = min(nodes, max(2 * SCHED_SAMPLE, nodes // 4))
    small = run_sim(SimConfig(nodes=small_n,
                              requests=10_000, duration_s=60.0,
                              arrival="diurnal", seed=seed))
    ratio = (round(scale.pick_us_mean / small.pick_us_mean, 2)
             if small.pick_us_mean else None)
    result["sublinear"] = {"small_nodes": small_n,
                           "small_pick_us_mean": small.pick_us_mean,
                           "scale_pick_us_mean": scale.pick_us_mean,
                           "ratio": ratio}
    if ratio is None or ratio >= 2.0:
        failures.append(f"sublinear: pick cost ratio {ratio} at 4x "
                        f"fleet (>= 2.0)")

    result["failures"] = failures
    print(json.dumps(result))
    try:
        with open("/tmp/dli_bench_sim.json", "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass
    if failures:
        print("sim_scale gate FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    print(f"sim_scale ok: {reqs} requests / {nodes} nodes in "
          f"{scale.wall_s}s wall (pick {scale.pick_us_mean}us mean, "
          f"sublinear ratio {ratio}), adversarial "
          f"{adv.breaker['opened']} breaker-opens all terminal, "
          f"determinism twin hash {t1.journal_hash[:12]}",
          file=sys.stderr)
    return 0


def _sim_calibrate_scenario(argv, opt, smoke):
    """--scenario sim_calibrate [--smoke]: the observatory's
    CALIBRATION gate (docs/simulator.md). Runs a small REAL cluster
    (master + in-proc batched worker), captures its arrival trace from
    the ``request-submitted`` journal and its cost-ledger rows, fits
    the synthetic worker model from them, replays the EXACT trace
    through the simulator, and gates on the sim-vs-real divergence of
    goodput / TTFT p50 / queue depth staying within the documented
    tolerances (DLI_SIM_TOL_*). Divergence report lands at
    /tmp/dli_sim_calibration.json either way — CI keeps a history of
    how faithful the sim is."""
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master
    from tools.dlisim import (DEFAULT_MODEL, SimConfig,
                              arrival_trace_from_events,
                              divergence_report, fit_worker_model,
                              run_sim)

    n = opt("--requests", 48)
    conc = opt("--concurrency", 6)
    max_new = opt("--max-new", 8)
    tolerances = {
        "goodput_req_per_s": float(
            os.environ.get("DLI_SIM_TOL_GOODPUT", 0.5)),
        "ttft_ms_p50": float(os.environ.get("DLI_SIM_TOL_TTFT", 0.75)),
        "queue_depth_mean": float(
            os.environ.get("DLI_SIM_TOL_QUEUE", 1.0)),
    }
    result = {"scenario": "sim_calibrate", "smoke": smoke,
              "requests": n, "tolerances": tolerances}

    workers = _control_plane_workers(1, max_new=max_new)
    m = Master(":memory:", health_interval=2.0)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    mport = msrv.server_address[1]
    base = f"http://127.0.0.1:{mport}"
    done, failed, lock = [], [], _th.Lock()
    next_i = [0]
    queue_samples = []
    sampling = _th.Event()

    def qsampler():
        # the real-side queue_pending series, same signal the sim
        # samples at its health cadence
        while not sampling.wait(0.1):
            c = m.store.counts()
            queue_samples.append(c.get("pending", 0))

    def prompt_for(i):
        # varied prompt sizes so the fitted prefill rate sees a spread
        # and the replayed trace isn't one degenerate length — but
        # bounded well under the worker's max_seq=64 (byte tokenizer:
        # chars ~= tokens) with max_new on top, and short enough that
        # CPU prefill keeps most requests inside the 2s TTFT SLO on
        # BOTH sides (a goodput of ~zero makes relative error
        # meaningless)
        return f"r{i:02d}:" + "x" * (8 + (i * 5) % 24)

    def client():
        sess = _rq.Session()
        while True:
            with lock:
                if next_i[0] >= n:
                    return
                i = next_i[0]
                next_i[0] += 1
            rid = sess.post(f"{base}/api/inference/submit", json={
                "model_name": "tiny-llama", "prompt": prompt_for(i),
                "max_new_tokens": max_new,
                "sampling": {"do_sample": False,
                             "allow_random_init": True},
            }).json()["request_id"]
            poll = 0.02
            while True:
                st = sess.get(
                    f"{base}/api/inference/status/{rid}"
                ).json()["request"]
                if st["status"] in ("completed", "failed"):
                    with lock:
                        (done if st["status"] == "completed"
                         else failed).append(st)
                    break
                time.sleep(poll)
                poll = min(0.2, poll * 1.5)

    try:
        r = _rq.post(f"{base}/api/nodes/add", json={
            "name": "w0", "host": "127.0.0.1",
            "port": workers[0][1]}).json()
        assert r["status"] == "success", r
        m.start_background()
        qt = _th.Thread(target=qsampler, daemon=True)
        qt.start()
        t0 = time.time()
        threads = [_th.Thread(target=client) for _ in range(conc)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.time() - t0
        sampling.set()
        qt.join(timeout=5)
        trace_rows = m.store.query_events(etype="request-submitted",
                                          limit=10 * n)
    finally:
        m.stop()
        for agent, _ in workers:
            agent.service.shutdown()

    costs = [st.get("cost") for st in done if st.get("cost")]
    ttfts = []
    for cost in costs:
        if isinstance(cost, str):
            try:
                cost = json.loads(cost)
            except ValueError:
                continue
        q = cost.get("queue_ms") or 0.0
        p = cost.get("prefill_ms")
        if p is not None:
            ttfts.append(q + p)
    ttfts.sort()
    real = {
        "completed": len(done), "failed": len(failed),
        "wall_s": round(wall, 2),
        "goodput_req_per_s": _goodput(done, wall)["goodput_req_per_s"],
        "ttft_ms_p50": (round(ttfts[len(ttfts) // 2], 2)
                        if ttfts else None),
        "queue_depth_mean": (round(sum(queue_samples)
                                   / len(queue_samples), 2)
                             if queue_samples else None),
    }
    trace = arrival_trace_from_events(trace_rows)
    model = fit_worker_model(costs, base=DEFAULT_MODEL)
    result["fitted_model"] = {
        "prefill_ms_per_token": round(model.prefill_ms_per_token, 4),
        "decode_ms_per_token": round(model.decode_ms_per_token, 4),
        "overhead_ms": round(model.overhead_ms, 3),
        "source": model.source,
    }
    rep = run_sim(SimConfig(nodes=1, requests=len(trace),
                            arrivals=trace, slots_per_node=8,
                            model=model, health_interval_s=2.0,
                            seed=opt("--seed", 42)))
    sim = {
        "completed": rep.completed, "failed": rep.failed,
        "goodput_req_per_s": rep.goodput_req_per_s,
        "ttft_ms_p50": rep.ttft_ms_p50,
        "queue_depth_mean": rep.queue_depth_mean,
    }
    div = divergence_report(real, sim, tolerances)
    result.update({"real": real, "sim": sim, "divergence": div,
                   "trace_requests": len(trace)})
    ok = (div["ok"] and len(done) == n and not failed
          and len(trace) == n and rep.completed == len(trace))
    print(json.dumps(result))
    try:
        with open("/tmp/dli_sim_calibration.json", "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass
    if not ok:
        print("sim_calibrate gate FAILED: "
              + json.dumps(div["metrics"]), file=sys.stderr)
        return 1
    print(f"sim_calibrate ok: {len(trace)}-request trace replayed, "
          + ", ".join(
              f"{k} real {v['real']} vs sim {v['sim']} "
              f"(rel_err {v['rel_err']}, tol {v['tolerance']})"
              for k, v in div["metrics"].items()
              if v["ok"] is not None),
          file=sys.stderr)
    return 0


def _overload_leg(workers, master_kw, capacity, duration, max_arrivals,
                  drain_timeout, max_new=48):
    """One open-loop overload storm against a fresh master over an
    already-warm worker set (caller owns worker shutdown). OPEN-loop on
    purpose: a closed loop self-throttles to whatever the cluster
    serves and can never push it past capacity, so the front door would
    have nothing to refuse. ``max_new=48`` (vs the control_plane
    scenario's 1) keeps the DATA plane the bottleneck: short
    generations drain as fast as HTTP submits arrive through the same
    master process, and a generator that shares the server's ceiling
    cannot outrun it — the workers must be warmed with the SAME token
    count or the first storm wave measures an XLA compile stall. Arrival times follow a diurnal ramp —
    ``rate(t) = capacity * (0.5 + 3.5 sin^2(pi t/D))`` — starting under
    capacity and peaking at 4x mid-window; submits round-robin the
    three SLO classes and four tenants (``X-DLI-Tenant`` header, the
    way a real client declares itself).

    The latency-tier SLO rollup folds the MASTER-side pending wait
    (``started_at - created_at``) into the cost record's ``queue_ms``
    before evaluating: the worker's ledger starts at its own submit, so
    under a master-side backlog — the exact thing this scenario
    manufactures — the raw record would score a request that sat 60s in
    the master queue as within-SLO."""
    import math
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    times = []
    t = 0.0
    while t < duration and len(times) < max_arrivals:
        rate = capacity * (0.5 + 3.5 * math.sin(
            math.pi * t / duration) ** 2)
        times.append(t)
        t += 1.0 / max(rate, 1e-6)

    classes = ("latency", "throughput", "batch")
    stats = {"submitted": 0, "accepted": 0, "rejected_429": 0,
             "rejected_no_retry_after": 0, "rejected_by_reason": {},
             "unexpected_status": 0, "transport_errors": 0,
             "accepted_by_class": {c: 0 for c in classes}}
    m = Master(":memory:", **master_kw)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    lock = _th.Lock()
    next_i = [0]
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        m.start_background()
        t0 = time.time()

        def submitter():
            sess = _rq.Session()
            while True:
                with lock:
                    if next_i[0] >= len(times):
                        return
                    i = next_i[0]
                    next_i[0] += 1
                delay = t0 + times[i] - time.time()
                if delay > 0:
                    time.sleep(delay)
                try:
                    r = sess.post(f"{base}/api/inference/submit", json={
                        "model_name": "tiny-llama", "prompt": "hi",
                        "max_new_tokens": max_new,
                        "slo_class": classes[i % 3],
                        "sampling": {"do_sample": False,
                                     "allow_random_init": True}},
                        headers={"X-DLI-Tenant": f"t{i % 4}"},
                        timeout=30)
                except Exception:
                    with lock:
                        stats["transport_errors"] += 1
                    continue
                try:
                    body = r.json()
                except ValueError:
                    body = {}
                with lock:
                    stats["submitted"] += 1
                    if r.status_code == 429:
                        stats["rejected_429"] += 1
                        if not r.headers.get("Retry-After"):
                            stats["rejected_no_retry_after"] += 1
                        reason = body.get("reason", "?")
                        stats["rejected_by_reason"][reason] = \
                            stats["rejected_by_reason"].get(reason, 0) + 1
                    elif r.status_code == 200 and \
                            body.get("status") == "success":
                        stats["accepted"] += 1
                        stats["accepted_by_class"][classes[i % 3]] += 1
                    else:
                        stats["unexpected_status"] += 1

        threads = [_th.Thread(target=submitter) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        submit_wall = time.time() - t0

        # drain: every ADMITTED request must reach a terminal state
        # before the rows are scored (bounded — the control-off leg
        # owes ~4x capacity and may time out; recorded, gated only on
        # the control leg)
        deadline = time.time() + drain_timeout
        while time.time() < deadline:
            c = m.store.counts()
            if not (c.get("pending", 0) or c.get("processing", 0)):
                break
            time.sleep(0.2)
        wall = time.time() - t0

        # the ladder must also walk back DOWN once the storm passes
        # (one rung per hold window) before the event trail is read
        if master_kw.get("overload"):
            deadline = time.time() + 30.0
            while time.time() < deadline and m._overload_level:
                time.sleep(0.25)

        rows = [dict(r) for r in m.store._all("SELECT * FROM requests")]
        done, failed = [], []
        for r in rows:
            cost = r.get("cost")
            if isinstance(cost, str):
                try:
                    cost = json.loads(cost)
                except ValueError:
                    cost = None
            if isinstance(cost, dict) and r.get("started_at"):
                wait_ms = max(0.0, (float(r["started_at"])
                                    - float(r["created_at"]))) * 1e3
                cost = dict(cost,
                            queue_ms=float(cost.get("queue_ms") or 0.0)
                            + wait_ms)
                r = dict(r, cost=cost)
            (done if r["status"] == "completed"
             else failed if r["status"] == "failed"
             else []).append(r)
        done_latency = [r for r in done if r["slo_class"] == "latency"]

        ev = _rq.get(f"{base}/api/events",
                     params={"type": "overload-level",
                             "limit": 1000}).json()
        ladder = [{"level": e["data"].get("level"),
                   "prev_level": e["data"].get("prev_level"),
                   "direction": e["data"].get("direction"),
                   "queue_depth": e["data"].get("queue_depth"),
                   "burn_rate": e["data"].get("burn_rate")}
                  for e in ev.get("events", [])]
        counters = m.metrics.snapshot()["counters"]
        return {
            "arrivals": len(times),
            "duration_s": round(duration, 1),
            "submit_wall_s": round(submit_wall, 2),
            "wall_s": round(wall, 2),
            **stats,
            "completed": len(done),
            "admitted_failed": len(failed),
            "admitted_unfinished": len(rows) - len(done) - len(failed),
            "admit_rejected_total": int(
                counters.get("admit_rejected", 0)),
            "shed": {k[len("shed_"):]: int(v)
                     for k, v in counters.items()
                     if k.startswith("shed_")},
            "overload_level_max": max(
                [0] + [e["level"] for e in ladder
                       if e["level"] is not None]),
            "ladder_up": sum(1 for e in ladder
                             if e["direction"] == "up"),
            "ladder_down": sum(1 for e in ladder
                               if e["direction"] == "down"),
            "ladder": ladder[:60],
            "slo_latency": _goodput(done_latency, wall),
            "slo_all": _goodput(done, wall),
        }
    finally:
        m.stop()


def _overload_capacity_probe(workers, n=150, max_new=48):
    """SATURATED serving capacity: blast ``n`` open-loop submits at a
    plain master and measure the steady-state completion slope off the
    store — from the 25%-drained mark to fully drained, so neither the
    submit burst nor the batch ramp-up dilutes the estimate. Both
    matter: the closed-loop control_plane harness throttles on its own
    status polls, and a lightly-loaded drain measures partial batch
    occupancy — the worker's throughput RISES with queue depth, so
    either low-ball makes the storm scale itself to a rate the cluster
    absorbs without ever overloading."""
    import threading as _th
    import requests as _rq
    from distributed_llm_inferencing_tpu.runtime.master import Master

    m = Master(":memory:", health_interval=2.0)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        for i, (_, wport) in enumerate(workers):
            r = _rq.post(f"{base}/api/nodes/add", json={
                "name": f"w{i}", "host": "127.0.0.1",
                "port": wport}).json()
            assert r["status"] == "success", r
        m.start_background()
        lock = _th.Lock()
        left = [n]

        def blast():
            sess = _rq.Session()
            while True:
                with lock:
                    if left[0] <= 0:
                        return
                    left[0] -= 1
                sess.post(f"{base}/api/inference/submit", json={
                    "model_name": "tiny-llama", "prompt": "hi",
                    "max_new_tokens": max_new,
                    "sampling": {"do_sample": False,
                                 "allow_random_init": True}},
                    timeout=30)

        t0 = time.time()
        threads = [_th.Thread(target=blast) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        mark = None              # (time, completed) at the 25% mark
        deadline = time.time() + 120
        while time.time() < deadline:
            c = m.store.counts()
            done = c.get("completed", 0) + c.get("failed", 0)
            if mark is None and done >= n // 4:
                mark = (time.time(), done)
            if done >= n:
                break
            time.sleep(0.05)
        if mark and done > mark[1] and time.time() > mark[0]:
            return (done - mark[1]) / (time.time() - mark[0])
        return n / max(time.time() - t0, 1e-6)
    finally:
        m.stop()


def _overload_scenario(argv, opt, smoke):
    """--scenario overload [--smoke] [--ab]: the overload front door's
    proof gate (docs/robustness.md "Overload control"). Two halves:

    - **real cluster** — a short closed-loop probe measures serving
      capacity, then an open-loop diurnal generator (_overload_leg)
      ramps submits to ~4x that capacity with mixed SLO classes and
      tenants. Gates: every refusal was an honest 429 carrying
      Retry-After; zero ADMITTED requests failed or stranded; the
      degradation ladder walked up AND back down, and the whole walk
      chains consistently from ``/api/events?type=overload-level``
      alone (each transition's prev_level = the previous transition's
      level, starting at 0 and ending at 0). ``--ab`` repeats the
      identical storm with the front door OFF (unbounded queue, no
      ladder) and gates latency-tier goodput(on) >= 1.5x goodput(off).
    - **simulated fleet** — the same admission/ladder/claim code at
      1000 nodes on the virtual clock (tools/dlisim --overload), run
      twice: byte-identical journal hashes, refusals present, ladder
      engaged, zero starved/violations, and the claim-wave
      anti-starvation bound holds (docs/simulator.md).

    Writes /tmp/dli_bench_overload.json for the CI artifact."""
    import math
    from distributed_llm_inferencing_tpu.runtime.state import (
        CLAIM_AGING_S)
    from tools.dlisim import SimConfig, run_sim

    ab = "--ab" in argv
    seed = opt("--seed", 42)
    nw = opt("--workers", 1 if smoke else 2)
    duration = opt("--duration", 8.0 if smoke else 20.0, float)
    max_arrivals = opt("--max-arrivals", 2400 if smoke else 8000)
    result = {"scenario": "overload", "smoke": smoke, "ab": ab,
              "seed": seed}
    failures = []

    workers = _control_plane_workers(nw, max_new=48)
    try:
        capacity = max(2.0, _overload_capacity_probe(
            workers, n=100 if smoke else 200))
        result["capacity_req_per_s"] = round(capacity, 2)

        # queue threshold ~1s of backlog at capacity: the ladder
        # engages while a latency request behind the queue can still
        # make its TTFT target; the hard cap is 4 rungs deeper
        qthr = max(8.0, capacity)
        on_kw = dict(health_interval=0.5,
                     admit_max_pending=int(4 * qthr),
                     overload=True, overload_burn=0.0,
                     overload_queue=qthr, overload_hold_s=1.0,
                     overload_interval_s=0.25)
        on = _overload_leg(workers, on_kw, capacity, duration,
                           max_arrivals, drain_timeout=60.0)
        result["control_on"] = on

        # honesty: every refusal an explicit 429 + Retry-After, and no
        # submit ever failed any other way
        if on["rejected_no_retry_after"]:
            failures.append(f"control_on: {on['rejected_no_retry_after']}"
                            " 429(s) without Retry-After")
        if on["transport_errors"] or on["unexpected_status"]:
            failures.append(
                f"control_on: {on['transport_errors']} transport "
                f"error(s) + {on['unexpected_status']} non-200/429 "
                "response(s) — refusals must be honest 429s")
        if on["rejected_429"] == 0:
            failures.append("control_on: a 4x-capacity storm produced "
                            "zero refusals (front door never engaged)")
        # admitted work is owed: none may fail or strand
        if on["admitted_failed"] or on["admitted_unfinished"]:
            failures.append(
                f"control_on: {on['admitted_failed']} admitted "
                f"request(s) failed, {on['admitted_unfinished']} never "
                "reached a terminal state")
        # the full ladder walk, from the journal alone
        if on["ladder_up"] == 0 or on["ladder_down"] == 0:
            failures.append(
                f"control_on: ladder walked up {on['ladder_up']}x / "
                f"down {on['ladder_down']}x (need both)")
        lvl = 0
        for e in on["ladder"]:
            if e["prev_level"] != lvl or e["queue_depth"] is None:
                failures.append(
                    "control_on: overload-level event trail does not "
                    f"chain (prev_level {e['prev_level']} at walked "
                    f"level {lvl}, queue_depth {e['queue_depth']}) — "
                    "the walk must reconstruct from /api/events alone")
                break
            lvl = e["level"]
        if lvl != 0 and not any(f.startswith("control_on: overload")
                                for f in failures):
            failures.append(f"control_on: ladder ended at rung {lvl}, "
                            "never walked back to 0")

        if ab:
            off_kw = dict(health_interval=0.5, admit_rate=0.0,
                          admit_max_pending=0, overload=False)
            off = _overload_leg(workers, off_kw, capacity, duration,
                                max_arrivals,
                                drain_timeout=60.0 if smoke else 120.0)
            result["control_off"] = off
            g_on = on["slo_latency"]["goodput_req_per_s"]
            g_off = off["slo_latency"]["goodput_req_per_s"]
            result["latency_goodput_ratio"] = (
                round(g_on / g_off, 2) if g_off else None)
            if g_off and g_on / g_off < 1.5:
                failures.append(
                    f"ab: latency-tier goodput {g_on} req/s with the "
                    f"front door vs {g_off} without — ratio "
                    f"{g_on / g_off:.2f} < 1.5")
    finally:
        for agent, _ in workers:
            agent.service.shutdown()

    # -- simulated fleet: the same front door at 1000 nodes, twice ----
    sim_nodes = 200 if smoke else 1000
    sim_reqs = 4000 if smoke else 20_000
    sim_cfg = dict(nodes=sim_nodes, requests=sim_reqs, duration_s=120.0,
                   arrival="diurnal", seed=seed, slo_mix=True,
                   overload=True, admit_max_pending=100,
                   overload_queue=30.0, overload_hold_s=10.0,
                   claim_interval_s=1.0, dispatch_batch=64)
    s1 = run_sim(SimConfig(**sim_cfg))
    s2 = run_sim(SimConfig(**sim_cfg))
    bound = (math.ceil(2 * CLAIM_AGING_S / sim_cfg["claim_interval_s"])
             + math.ceil(sim_cfg["admit_max_pending"]
                         / sim_cfg["dispatch_batch"])
             + s1.waves_frozen + 2)
    result["sim"] = {
        "nodes": sim_nodes, "requests": sim_reqs,
        "completed": s1.completed, "rejected": s1.rejected,
        "rejected_by_reason": s1.rejected_by_reason, "shed": s1.shed,
        "overload_level_max": s1.overload_level_max,
        "claim_waves": s1.claim_waves,
        "waves_frozen": s1.waves_frozen,
        "starvation_max_waves": s1.starvation_max_waves,
        "starvation_bound": bound, "starved": s1.starved,
        "violations": s1.violations[:20], "wall_s": s1.wall_s,
        "hash_a": s1.journal_hash, "hash_b": s2.journal_hash,
    }
    if s1.journal_hash != s2.journal_hash:
        failures.append("sim: identically-seeded overload runs diverged "
                        f"({s1.journal_hash[:12]} != "
                        f"{s2.journal_hash[:12]})")
    if s1.violations or s1.starved:
        failures.append(f"sim: {len(s1.violations)} invariant "
                        f"violation(s), {s1.starved} starved")
    if not s1.rejected or not s1.overload_level_max:
        failures.append(f"sim: {s1.rejected} refusals at ladder max "
                        f"{s1.overload_level_max} — the overload sweep "
                        "never engaged the front door")
    if s1.starvation_max_waves > bound:
        failures.append(
            f"sim: an admitted request sat {s1.starvation_max_waves} "
            f"claim waves > anti-starvation bound {bound} "
            "(aging + bounded queue must cap the wait)")

    result["failures"] = failures
    print(json.dumps(result))
    try:
        with open("/tmp/dli_bench_overload.json", "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass
    if failures:
        print("overload gate FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    on = result["control_on"]
    print(f"overload ok: {on['rejected_429']}/{on['submitted']} honest "
          f"429s at 4x capacity, ladder to rung "
          f"{on['overload_level_max']} and back, latency goodput "
          f"{on['slo_latency']['goodput_req_per_s']} req/s"
          + (f" ({result['latency_goodput_ratio']}x control-off)"
             if ab else "")
          + f"; sim {sim_nodes} nodes: {s1.rejected} refusals, "
          f"starvation {s1.starvation_max_waves} <= {bound} waves, "
          f"twin hash {s1.journal_hash[:12]}", file=sys.stderr)
    return 0


def _scenario_main(argv):
    """`bench.py --scenario {control_plane|prefix_cache|multi_lora
    |decode_speed|disagg|rebalance|plan|ha|overload|sim_scale
    |sim_calibrate}
    [--smoke|--ab] [--requests N] [--concurrency C] [--workers W]` —
    standalone scenario entry, one JSON line on stdout, nonzero rc on
    smoke/gate failure."""
    def opt(name, default, cast=int):
        return cast(argv[argv.index(name) + 1]) if name in argv else default

    name = argv[argv.index("--scenario") + 1]
    if name == "sim_scale":
        # pure virtual-clock simulation: no workers, no JAX
        return _sim_scale_scenario(argv, opt, "--smoke" in argv)
    # every other scenario spins fresh worker sets per leg: the persistent
    # compilation cache lets later legs (and repeat CI runs) reuse compiled
    # executables instead of re-paying cold XLA compiles that would dwarf
    # the measured window
    from distributed_llm_inferencing_tpu.utils.platform import (
        enable_compilation_cache)
    enable_compilation_cache()
    scenarios = {
        "decode_speed": _decode_speed_scenario,
        "prefix_cache": _prefix_cache_scenario,
        "multi_lora": _multi_lora_scenario,
        "disagg": _disagg_scenario,
        "rebalance": _rebalance_scenario,
        "plan": _plan_scenario,
        "ha": _ha_scenario,
        "overload": _overload_scenario,
        "sim_calibrate": _sim_calibrate_scenario,
    }
    if name in scenarios:
        return scenarios[name](argv, opt, "--smoke" in argv)
    if name != "control_plane":
        print(json.dumps({"error": f"unknown scenario {name!r}"}))
        return 2
    smoke = "--smoke" in argv
    max_new = opt("--max-new", 1)
    if smoke:
        n, conc, nw = opt("--requests", 24), opt("--concurrency", 8), 1
    else:
        # 320 requests ≈ a ~15s sustained window: long enough that the
        # pooled sessions' ramp-up (one socket per concurrent RPC per
        # node) amortizes below 10% of RPCs, which is what the reuse
        # acceptance bar measures
        n, conc, nw = (opt("--requests", 320), opt("--concurrency", 32),
                       opt("--workers", 2))
    result = {"scenario": "control_plane", "smoke": smoke}
    if "--ab" in argv:
        # one warm cluster, both dispatcher shapes: the delta is the
        # control plane, not worker state
        workers = _control_plane_workers(nw, max_new=max_new)
        try:
            single = bench_control_plane(n, conc, nw, mode="single",
                                         max_new=max_new, workers=workers)
            batched = bench_control_plane(n, conc, nw, mode="batched",
                                          max_new=max_new, workers=workers)
        finally:
            for agent, _ in workers:
                agent.service.shutdown()
        result.update(single=single, batched=batched)
        if single["completed_req_per_s"] > 0:
            result["speedup"] = round(
                batched["completed_req_per_s"]
                / single["completed_req_per_s"], 2)
    else:
        result.update(bench_control_plane(n, conc, nw, mode="batched",
                                          max_new=max_new))
    print(json.dumps(result))
    if smoke:
        # under --ab the per-run stats are nested; gate on the batched leg
        run = result.get("batched", result)
        ok = (run.get("completed") == n and run.get("failed") == 0
              and run.get("rpc_conn_reuse_ratio", 0) > 0.5
              # cost-ledger plumbing: every completed request's row must
              # carry an evaluable cost record (worker -> master -> row)
              and run.get("slo", {}).get("evaluated") == n)
        if not ok:
            print("control-plane smoke FAILED", file=sys.stderr)
            return 1
        print(f"control-plane smoke ok: "
              f"{run['completed_req_per_s']} req/s, "
              f"reuse {run['rpc_conn_reuse_ratio']}, "
              f"goodput {run['slo']['goodput_req_per_s']} req/s "
              f"(attainment {run['slo']['attainment']})", file=sys.stderr)
    return 0


def bench_batched(model=MODEL, quant=None, n_requests=8,
                  new_tokens=NEW_TOKENS, dtype=None, repeats=2,
                  prompt_len=PROMPT_LEN, kv_quant=None,
                  speculative=None, repetitive=False, stagger_s=None):
    """Aggregate throughput + TTFT/latency percentiles: n concurrent
    requests through the continuous batcher (the serving path the
    reference fully serialized, reference worker/Dockerfile:47).

    Drives ``step()`` synchronously (no scheduler thread) so the timed
    region is pure serving work, and warms with an identically-shaped
    workload first so the exact wave/chunk programs the timed run
    launches are already compiled.

    ``stagger_s``: spread submissions as Poisson arrivals over roughly
    this many seconds instead of one burst — admission then happens
    across many waves, so TTFT/latency percentiles reflect load instead
    of a single wave's degenerate p50 == p95.
    """
    import numpy as np
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    from distributed_llm_inferencing_tpu.utils.metrics import Metrics

    cfg = get_config(model)
    if quant:
        cfg = cfg.replace(quant=quant)
    if dtype:
        cfg = cfg.replace(dtype=dtype)
    if kv_quant:
        cfg = cfg.replace(kv_quant=kv_quant)
    max_seq = prompt_len + new_tokens + 16
    slots = min(n_requests, 32)
    blocks = max(256, n_requests * (-(-max_seq // 16)) + 32)
    met = Metrics()   # percentiles come from the batcher's own histograms
    b = ContinuousBatcher(cfg, num_blocks=blocks, block_size=16,
                          slots=slots, max_seq=max_seq, seed=0,
                          speculative=speculative, metrics=met)
    rng = np.random.default_rng(0)
    # the speculative comparison measures greedy on BOTH arms (greedy is
    # the accelerated mode, and the baseline must match it); repetitive
    # prompts are the workload class prompt-lookup drafting targets
    sp = (SamplingParams.greedy() if (speculative or repetitive)
          else _sampling())

    def mk_prompt():
        if repetitive:
            base = rng.integers(0, cfg.vocab_size, 4).tolist()
            return (base * (prompt_len // 4 + 1))[:prompt_len]
        return rng.integers(0, cfg.vocab_size, prompt_len).tolist()

    def run(seed_base):
        # fresh prompts every run: same buckets/shapes (compiled programs
        # reused), no radix hits from a previous run's inserts
        prompts = [mk_prompt() for _ in range(n_requests)]
        offs = None
        if stagger_s:
            gaps = np.random.default_rng(seed_base).exponential(
                stagger_s / n_requests, n_requests)
            offs = np.cumsum(gaps)
        reqs = []
        nxt = 0
        t0 = time.perf_counter()
        deadline = t0 + 600
        while True:
            now = time.perf_counter() - t0
            while nxt < n_requests and (offs is None or offs[nxt] <= now):
                reqs.append(b.submit(prompts[nxt],
                                     max_new_tokens=new_tokens, sampling=sp,
                                     seed=seed_base + nxt))
                nxt += 1
            busy = b.step()
            if not busy and nxt < n_requests:
                time.sleep(0.001)   # idle until the next Poisson arrival
            assert time.perf_counter() < deadline, \
                "batched bench did not converge"
            if nxt >= n_requests and all(r.done.is_set() for r in reqs):
                break
        dt = time.perf_counter() - t0
        for r in reqs:
            if r.error:
                raise RuntimeError(f"batched request failed: {r.error}")
        return sum(len(r.tokens) for r in reqs) / dt, reqs

    # AOT-compile the decode-program space FIRST (the workload warmup
    # then runs on the installed executables — one compile per program),
    # then run a workload warmup for the admission-wave programs. A
    # speculative trajectory's chunk sequence is acceptance-dependent,
    # so workload warmup alone cannot cover the space and a tail-chunk
    # variant would pay its XLA compile inside a measured rep (this is
    # exactly how the BENCH_r05 5.54-vs-17.04 "speculative regression"
    # happened — the spec leg was billed for compiles the plain leg
    # amortized)
    b.warm_decode_programs()
    run(1)
    best, stats = 0.0, {}
    for rep in range(repeats):
        met.reset_timings()   # percentiles cover exactly this rep's run
        c0 = met.snapshot()["counters"]   # counters are monotone: deltas
        tput, reqs = run(1000 * (rep + 1))
        if tput > best:
            best = tput
            # sourced from the scheduler's own histograms
            # (runtime/batcher.py observes ttft / inter-token pacing /
            # e2e latency per request), not bench-side ad-hoc timers
            snap = met.snapshot()
            t, c1 = snap["timings"], snap["counters"]

            def q(name, p):
                e = t.get(name)
                return round(e[p] * 1e3, 1) if e else None

            def delta(name):
                return c1.get(name, 0) - c0.get(name, 0)

            passes = delta("batcher_weight_passes")
            stats = {
                "ttft_ms_p50": q("batcher_ttft", "p50"),
                "ttft_ms_p95": q("batcher_ttft", "p95"),
                "itl_ms_p50": q("batcher_inter_token", "p50"),
                "itl_ms_p95": q("batcher_inter_token", "p95"),
                "latency_ms_p50": q("batcher_e2e_latency", "p50"),
                "latency_ms_p95": q("batcher_e2e_latency", "p95"),
                # amortization: tokens per weight-streaming pass over the
                # whole rep (== mean decode batch occupancy) — continuous
                # batching's reason to exist, now measurable per run
                "tokens_per_weight_pass": (
                    round(delta("batcher_tokens_emitted") / passes, 2)
                    if passes else None),
            }
            if speculative:
                # controllers live on the requests
                # (BatchRequest._spec_ctl) — aggregate the best rep's
                # verdicts
                ctls = [r._spec_ctl for r in reqs
                        if r._spec_ctl is not None]
                if ctls:
                    stats["spec_mode"] = (
                        "spec" if any(c.mode == "spec" for c in ctls)
                        else "plain")
                    stats["spec_fallbacks"] = sum(
                        c.fallbacks for c in ctls)
                stats["spec_wave_dispatches"] = \
                    b.stats()["spec_wave"]["dispatches"]
                stats["spec_accepted_tokens"] = int(
                    delta("spec_wave_accepted_tokens")) or None
            stats["active_slots"] = slots
    return best, stats


def bench_prefill_chunk_stall(model=MODEL, dtype=None, chunk=32,
                              long_len=1536):
    """How long one huge prompt stalls co-running decode — the number
    chunked prefill exists to bound. An active request streams tokens
    while a ``long_len``-token prompt admits; returns the active
    stream's max inter-token gap (ms). Compare chunk=32 vs chunk=None."""
    import numpy as np
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)

    cfg = get_config(model)
    if dtype:
        cfg = cfg.replace(dtype=dtype)
    bs = 16
    max_seq = long_len + 96
    blocks = 2 * (-(-max_seq // bs)) + 32
    rng = np.random.default_rng(0)
    sp = SamplingParams.greedy()

    b = ContinuousBatcher(cfg, num_blocks=blocks, block_size=bs,
                          slots=2, max_seq=max_seq, seed=0,
                          prefill_chunk=chunk)
    # small decode chunks: the stream callback fires per chunk, so the
    # measured max-gap must be admission stall, not chunk duration
    b.DECODE_CHUNKS = (8, 4, 2, 1)

    def run(seed_base):
        # fresh prompts each run: no radix hits, so every run drives the
        # same (already compiled after run 1) admission/chunk programs
        stamps = []
        a = b.submit(rng.integers(0, cfg.vocab_size, 16).tolist(),
                     max_new_tokens=64, sampling=sp, seed=seed_base,
                     stream_cb=lambda tok: stamps.append(
                         time.perf_counter()))
        # let the short stream start, then the long prompt arrives
        while len(a.tokens) < 4:
            b.step()
        long = b.submit(rng.integers(0, cfg.vocab_size, long_len).tolist(),
                        max_new_tokens=2, sampling=sp, seed=seed_base + 1)
        guard = 0
        while not (a.done.is_set() and long.done.is_set()):
            b.step()
            guard += 1
            assert guard < 10_000
        for r in (a, long):
            if r.error:
                raise RuntimeError(r.error)
        gaps = [(t1 - t0) * 1e3 for t0, t1 in zip(stamps, stamps[1:])]
        return max(gaps)

    run(1)   # warmup: compiles the admission + chunk programs
    return min(run(100), run(200))


def bench_moe_prefill(prompt_len=512, dtype=None):
    """MoE prefill throughput (tok/s through prefill) on the
    fits-on-one-chip proxy (registry moe-proxy-8e)."""
    import numpy as np
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    cfg = get_config("moe-proxy-8e").replace(quant="int8")
    if dtype:
        cfg = cfg.replace(dtype=dtype)
    eng = InferenceEngine(cfg, max_seq=prompt_len + 24, seed=0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).tolist()
    sp = _sampling()
    eng.generate([prompt], max_new_tokens=2, sampling=sp)   # warmup
    best = 0.0
    for _ in range(2):
        res = eng.generate([prompt], max_new_tokens=2, sampling=sp)
        best = max(best, prompt_len / (res.prefill_ms / 1e3))
    return best


def bench_prefill_mfu(model=MODEL, prompt_len=512, dtype=None, repeats=3,
                      quant=None):
    """Prefill MFU: achieved matmul FLOP/s over the chip's peak bf16
    FLOP/s. Prefill is compute-roofed (decode is bandwidth-roofed — the
    ``*_hbm_bw_util`` keys cover that side); forward FLOPs use the
    ``2 * matmul_params * tokens`` lower bound (attention FLOPs excluded;
    embed/unembed excluded because prefill gathers the one last-position
    logit row), so the reported MFU slightly understates the machine.
    ``quant`` is for models whose bf16 weights don't fit in HBM (the FLOP
    count is quant-independent). Returns (prefill_tok_s, param_count)."""
    import numpy as np
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    cfg = get_config(model)
    if dtype:
        cfg = cfg.replace(dtype=dtype)
    if quant:
        cfg = cfg.replace(quant=quant)
    eng = InferenceEngine(cfg, max_seq=prompt_len + 24, seed=0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).tolist()
    sp = _sampling()
    eng.generate([prompt], max_new_tokens=2, sampling=sp)   # warmup
    best = 0.0
    for _ in range(repeats):
        res = eng.generate([prompt], max_new_tokens=2, sampling=sp)
        best = max(best, prompt_len / (res.prefill_ms / 1e3))
    # count only the per-token matmul params: the token embedding is a
    # gather and the unembed runs for ONE position per sequence in prefill
    # (engine gathers last_logits), so 2*total_params*tokens would inflate
    # the MFU — the opposite bias of the attention-FLOPs exclusion
    from distributed_llm_inferencing_tpu.models.params import param_count
    body = {k: v for k, v in eng.params.items()
            if k not in ("embed", "lm_head")}
    return best, param_count(body)


def _reclaim():
    """Drop dead device buffers between extras — consecutive 8B benches
    otherwise overlap two weight sets in HBM and RESOURCE_EXHAUST."""
    import gc
    gc.collect()


BENCH_BUDGET_S = float(os.environ.get("DLI_BENCH_BUDGET_S", 2400))
_T0 = time.time()


def _over_budget(what):
    """Extras are skipped past the budget so the contract line always
    prints well before any driver-side timeout."""
    if time.time() - _T0 > BENCH_BUDGET_S:
        print(f"{what} skipped: bench budget exhausted "
              f"({time.time() - _T0:.0f}s > {BENCH_BUDGET_S:.0f}s)",
              file=sys.stderr)
        return True
    return False


def run_all(platform):
    result = {
        "metric": "gpt2_decode_tokens_per_s_per_chip",
        "value": 0.0,
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "baseline_stack": "hf-transformers-torch-cpu-in-process "
                          "(cross-stack, cross-hardware)",
        "platform": platform,
    }
    # bf16 is software-emulated on host CPU; use f32 there so the cpu
    # number reflects the machine, not the emulation
    dtype = "float32" if platform == "cpu" else None
    bw = None if platform == "cpu" else _chip_bw()
    peak = None if platform == "cpu" else _chip_flops()
    on_tpu = platform != "cpu"

    def util(key, tok_s, pbytes):
        if bw:
            result[key] = round(pbytes * tok_s / bw, 3)

    def mfu(key, tok_s, params):
        if peak:
            result[key] = round(2.0 * params * tok_s / peak, 3)

    # ---- priority 1: the contract headline -------------------------------
    # On TPU: the framework's native bf16 serving config. On an asked-for
    # CPU platform: the framework's recommended CPU serving config —
    # int8 weight-only + int8 embed table, f32 activations/accumulate.
    # The reference stack has no quantized CPU path at all (reference
    # worker/app.py:297-305 is stock HF f32 generate); the like-for-like
    # f32 comparison is reported alongside as gpt2_f32_tokens_per_s /
    # vs_baseline_f32 so the cross-precision multiplier can't be
    # misread.
    if on_tpu:
        ours, pbytes = bench_engine(dtype=dtype)
    else:
        ours, pbytes = bench_engine(quant="int8", embed_quant="int8",
                                    dtype="float32")
        result["ours_config"] = (
            "int8 weight-only + int8 embed (f32 activations; baseline is "
            "the reference's f32 stack — see vs_baseline_f32 for "
            "same-precision)")
        result["gpt2_int8_tokens_per_s"] = round(ours, 2)
    result["value"] = round(ours, 2)
    util("gpt2_hbm_bw_util", ours, pbytes)
    print(f"ours: {ours:.2f} tok/s [{platform}]", file=sys.stderr)
    _persist(result)

    # ---- priority 1b (cpu): f32, the like-for-like arm of vs_baseline_f32
    if not on_tpu:
        try:
            f32, _ = bench_engine(dtype="float32")
            result["gpt2_f32_tokens_per_s"] = round(f32, 2)
            print(f"gpt2 f32 (like-for-like): {f32:.2f} tok/s",
                  file=sys.stderr)
        except Exception as e:
            print(f"cpu f32 bench skipped: {e!r}", file=sys.stderr)
        _persist(result)

    # ---- priority 2: batched x8 (the >=3x-engine bar) --------------------
    try:
        tput, pstats = bench_batched(dtype=dtype)
        result["batched_throughput_tokens_per_s"] = round(tput, 2)
        result.update({f"batched_{k}": v for k, v in pstats.items()})
        print(f"batched x8: {tput:.2f} tok/s {pstats}", file=sys.stderr)
    except Exception as e:  # extras never break the contract line
        print(f"batched bench skipped: {e!r}", file=sys.stderr)
    _persist(result)

    # ---- priority 3: the north-star model, int8 then int4 ----------------
    # (llama-3-8b, BASELINE.md config 2 — int4 is the pallas kernel's
    # make-or-break model-level number, so it runs BEFORE any long tail)
    if on_tpu:
        for key, kw in (
                ("llama_3_8b_int8", dict(quant="int8")),
                ("llama_3_8b_int4", dict(quant="int4")),
                ("llama_3_8b_int4_eq8", dict(quant="int4",
                                             embed_quant="int8")),
        ):
            _reclaim()
            if _over_budget(key):
                break
            try:
                ll, llb = bench_engine("llama-3-8b", new_tokens=32,
                                       repeats=2, **kw)
                result[f"{key}_tokens_per_s"] = round(ll, 2)
                util(f"{key}_hbm_bw_util", ll, llb)
                print(f"{key}: {ll:.2f} tok/s", file=sys.stderr)
            except Exception as e:
                print(f"{key} skipped: {e!r}", file=sys.stderr)
            _persist(result)

    # ---- priority 3b: prefill MFU (the compute-roofline axis) ------------
    if on_tpu and peak and not _over_budget("prefill mfu"):
        for mkey, mmodel, mq in (("gpt2", MODEL, None),
                                 ("llama_3_8b", "llama-3-8b", "int8")):
            _reclaim()
            try:
                ptok, pcount = bench_prefill_mfu(mmodel, quant=mq)
                result[f"{mkey}_prefill_tokens_per_s"] = round(ptok, 1)
                mfu(f"{mkey}_prefill_mfu", ptok, pcount)
                print(f"{mkey} prefill: {ptok:.1f} tok/s "
                      f"mfu={result.get(f'{mkey}_prefill_mfu')}",
                      file=sys.stderr)
            except Exception as e:
                print(f"{mkey} prefill mfu skipped: {e!r}", file=sys.stderr)
            _persist(result)

    # ---- priority 4: MoE proxy (BASELINE.md config 4 stand-in) -----------
    # (above the serving long tail: these keys have never produced a
    # number on any platform, so they outrank re-measuring variants)
    if on_tpu and not _over_budget("moe proxy"):
        _reclaim()
        try:
            md, mdb = bench_engine("moe-proxy-8e", quant="int8",
                                   new_tokens=32, repeats=2)
            result["moe_decode_tokens_per_s"] = round(md, 2)
            util("moe_decode_hbm_bw_util", md, mdb)
            print(f"moe decode: {md:.2f} tok/s", file=sys.stderr)
            _reclaim()
            pf = bench_moe_prefill()
            result["moe_prefill_tokens_per_s"] = round(pf, 2)
            print(f"moe prefill: {pf:.2f} tok/s", file=sys.stderr)
            _reclaim()
        except Exception as e:
            print(f"moe proxy skipped: {e!r}", file=sys.stderr)
        _persist(result)

    # ---- priority 4b: deepseek proxy (MLA latent attention + sigmoid
    # group-routed MoE + shared experts + mixed dense-prefix stack) ------
    if on_tpu and not _over_budget("deepseek proxy"):
        _reclaim()
        try:
            dd, ddb = bench_engine("deepseek-proxy", quant="int8",
                                   new_tokens=32, repeats=2)
            result["deepseek_decode_tokens_per_s"] = round(dd, 2)
            util("deepseek_decode_hbm_bw_util", dd, ddb)
            print(f"deepseek decode: {dd:.2f} tok/s", file=sys.stderr)
        except Exception as e:
            print(f"deepseek proxy skipped: {e!r}", file=sys.stderr)
        _persist(result)

    # ---- priority 5: batched speculative pair ----------------------------
    if on_tpu and not _over_budget("batched speculative"):
        for tag, spec in (("", None), ("_spec", "ngram")):
            _reclaim()
            try:
                tput, pstats = bench_batched(repeats=1, speculative=spec,
                                             repetitive=True)
                result[f"batched_greedy_rep{tag}_tokens_per_s"] = round(
                    tput, 2)
                if spec:
                    # the adaptive verdict must reach the artifact: a
                    # speculative regression with no mode/fallback
                    # evidence is undiagnosable after the fact
                    result.update(
                        {f"batched_greedy_rep_spec_{k}": v
                         for k, v in pstats.items()
                         if k.startswith("spec_")})
                print(f"batched greedy repetitive{tag}: {tput:.2f} tok/s "
                      f"{ {k: v for k, v in pstats.items() if k.startswith('spec_')} }",
                      file=sys.stderr)
            except Exception as e:
                print(f"batched spec{tag} bench skipped: {e!r}",
                      file=sys.stderr)
            _persist(result)

    # ---- priority 6: long-context kv8 pair -------------------------------
    if on_tpu and not _over_budget("long-ctx kv8"):
        for tag, kvq in (("", None), ("_kv8", "int8")):
            _reclaim()
            try:
                tput, pstats = bench_batched(
                    n_requests=16, repeats=1, prompt_len=256, kv_quant=kvq)
                result[f"batched_x16_long{tag}_tokens_per_s"] = round(tput, 2)
                print(f"batched x16 long-ctx{tag}: {tput:.2f} tok/s {pstats}",
                      file=sys.stderr)
            except Exception as e:
                print(f"batched long-ctx{tag} skipped: {e!r}", file=sys.stderr)
            _persist(result)

    # ---- priority 7: staggered-arrival percentiles (p50 != p95) ----------
    if on_tpu and not _over_budget("staggered x32"):
        _reclaim()
        try:
            tput, pstats = bench_batched(n_requests=32, repeats=2,
                                         stagger_s=1.0)
            result["batched_stag_x32_tokens_per_s"] = round(tput, 2)
            result.update(
                {f"batched_stag_x32_{k}": v for k, v in pstats.items()})
            print(f"batched staggered x32: {tput:.2f} tok/s {pstats}",
                  file=sys.stderr)
        except Exception as e:
            print(f"staggered x32 skipped: {e!r}", file=sys.stderr)
        _persist(result)

    # ---- priority 8: chunked-prefill stall A/B ---------------------------
    if on_tpu and not _over_budget("prefill-chunk A/B"):
        _reclaim()
        try:
            on = bench_prefill_chunk_stall(chunk=32)
            off = bench_prefill_chunk_stall(chunk=None)
            result["prefill_chunk_stall_ms"] = round(on, 1)
            result["prefill_chunk_stall_ms_off"] = round(off, 1)
            print(f"prefill-chunk stall: on={on:.1f} ms off={off:.1f} ms",
                  file=sys.stderr)
        except Exception as e:
            print(f"prefill-chunk A/B skipped: {e!r}", file=sys.stderr)
        _persist(result)

    # ---- long tail: scaling + other model families -----------------------
    if on_tpu and not _over_budget("batched x16/x32"):
        for n in (16, 32):
            _reclaim()
            try:
                tput, pstats = bench_batched(n_requests=n, repeats=1)
                result[f"batched_x{n}_tokens_per_s"] = round(tput, 2)
                result[f"batched_x{n}_latency_ms_p50"] = pstats[
                    "latency_ms_p50"]
                print(f"batched x{n}: {tput:.2f} tok/s {pstats}",
                      file=sys.stderr)
            except Exception as e:
                print(f"batched x{n} bench skipped: {e!r}", file=sys.stderr)
            _persist(result)
    if on_tpu and not _over_budget("big-model extras"):
        _reclaim()
        try:
            xl, xlb = bench_engine("gpt2-xl", quant="int8", new_tokens=32,
                                   repeats=2)
            result["gpt2_xl_int8_tokens_per_s"] = round(xl, 2)
            util("gpt2_xl_int8_hbm_bw_util", xl, xlb)
            print(f"gpt2-xl int8: {xl:.2f} tok/s", file=sys.stderr)
        except Exception as e:
            print(f"gpt2-xl bench skipped: {e!r}", file=sys.stderr)
        _persist(result)
        _reclaim()
        try:
            if _over_budget("gpt2-xl int4+eq8"):
                raise RuntimeError("budget")
            # tied-head family full quant story: int4 matmuls (pallas
            # kernel) + int8 embedding table (the 161 MB/token unembed)
            xq, xqb = bench_engine("gpt2-xl", quant="int4",
                                   embed_quant="int8", new_tokens=32,
                                   repeats=2)
            result["gpt2_xl_int4_eq8_tokens_per_s"] = round(xq, 2)
            util("gpt2_xl_int4_eq8_hbm_bw_util", xq, xqb)
            print(f"gpt2-xl int4+eq8: {xq:.2f} tok/s", file=sys.stderr)
        except Exception as e:
            print(f"gpt2-xl int4+eq8 bench skipped: {e!r}", file=sys.stderr)
        _reclaim()
        try:
            if _over_budget("llama-3-8b batched"):
                raise RuntimeError("budget")
            llt, llst = bench_batched("llama-3-8b", quant="int8",
                                      new_tokens=32, repeats=1)
            result["llama_3_8b_int8_batched_tokens_per_s"] = round(llt, 2)
            result.update(
                {f"llama_3_8b_int8_batched_{k}": v for k, v in llst.items()})
            print(f"llama-3-8b int8 batched x8: {llt:.2f} tok/s",
                  file=sys.stderr)
        except Exception as e:
            print(f"llama-3-8b batched bench skipped: {e!r}", file=sys.stderr)
        _persist(result)
        _reclaim()
        try:
            # ALiBi family on the flash kernels (BLOOM/Falcon-RW/MPT were
            # previously second-class on the fast paths — the kernels now
            # carry the linear bias in-tile, ops/pallas/flash_attention.py)
            if _over_budget("falcon-rw-1b"):
                raise RuntimeError("budget")
            fr, frb = bench_engine("falcon-rw-1b", quant="int8",
                                   new_tokens=32, repeats=2)
            result["falcon_rw_1b_int8_tokens_per_s"] = round(fr, 2)
            util("falcon_rw_1b_int8_hbm_bw_util", fr, frb)
            print(f"falcon-rw-1b int8 (alibi): {fr:.2f} tok/s",
                  file=sys.stderr)
        except Exception as e:
            print(f"falcon-rw-1b bench skipped: {e!r}", file=sys.stderr)
        _persist(result)
        _reclaim()
        try:
            # BASELINE.md config 3: Mistral-7B (sliding-window attn)
            if _over_budget("mistral-7b"):
                raise RuntimeError("budget")
            ms, msb = bench_engine("mistral-7b", quant="int8",
                                   new_tokens=32, repeats=2)
            result["mistral_7b_int8_tokens_per_s"] = round(ms, 2)
            util("mistral_7b_int8_hbm_bw_util", ms, msb)
            print(f"mistral-7b int8: {ms:.2f} tok/s", file=sys.stderr)
        except Exception as e:
            print(f"mistral-7b bench skipped: {e!r}", file=sys.stderr)
    _reclaim()
    try:
        if _over_budget("speculative"):
            raise RuntimeError("budget")
        plain, spec = bench_speculative()
        result["speculative_tokens_per_s"] = round(spec, 2)
        result["speculative_plain_tokens_per_s"] = round(plain, 2)
        print(f"speculative ngram: {spec:.2f} vs plain {plain:.2f} tok/s",
              file=sys.stderr)
    except Exception as e:
        print(f"speculative bench skipped: {e!r}", file=sys.stderr)
    _persist(result)
    baseline = bench_reference_stack()
    print(f"reference stack (HF torch CPU): {baseline:.2f} tok/s",
          file=sys.stderr)
    if baseline > 0:
        result["vs_baseline"] = round(ours / baseline, 3)
        if "gpt2_f32_tokens_per_s" in result:
            result["vs_baseline_f32"] = round(
                result["gpt2_f32_tokens_per_s"] / baseline, 3)
    _persist(result)
    return result


def main():
    global _T0
    if "--scenario" in sys.argv:
        # standalone scenario mode (CI smokes, operator A/Bs): no
        # headline artifact — one JSON line and an exit code
        sys.exit(_scenario_main(sys.argv))
    from distributed_llm_inferencing_tpu.utils.platform import ensure_backend
    platform = ensure_backend()   # exits non-zero without a chip
    _T0 = time.time()   # backend init must not eat the extras budget
    print(json.dumps(run_all(platform)))


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    main()
