"""Disaggregated prefill/decode pools + cross-node KV transfer.

Covers the acceptance-critical invariants:
- the KV wire's frame codec round-trips and rejects every corruption
  class (bad magic, truncated stream, over-cap lengths, spec drift),
- ``POST /kv_fetch`` streams exactly the arena blocks asked for,
  reports missing digests, honors the size cap, and stays auth-gated,
- a decode continued from transferred KV is BITWISE identical to a cold
  prefill (greedy and sampled),
- chaos on the transfer wire (mid-stream disconnect, corrupt frames,
  injected 500, dead peer) degrades to recompute with identical output
  and never fails or corrupts the request — and costs at most one
  breaker strike,
- role-aware routing: strict pools, the mixed default's full backward
  compatibility, the sticky-retry pin surviving the role filter, and
  the >90%-full arena prefill avoidance,
- worker-side peer sessions reuse keep-alive sockets (created/reused
  accounting) and tear down on connection faults.
"""

import json
import time

import numpy as np
import pytest
import requests as rq

from distributed_llm_inferencing_tpu.runtime import kvwire
from distributed_llm_inferencing_tpu.runtime.master import Master
from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
from conftest import stop_worker

# ~100 byte-tokens: long enough for many full 8-token blocks, short
# enough that "<mode> "-prefixed variants + 8 new tokens fit max_seq 128
LONG_PROMPT = "The quick brown fox jumps over the lazy dog. " * 2 + "Go."
SHORT_PROMPT = "hi there"


# ---- frame codec units --------------------------------------------------

def _pages():
    return [np.arange(24, dtype=np.float32).reshape(2, 3, 4),
            np.arange(6, dtype=np.int8).reshape(6),
            np.arange(4, dtype=np.float16).reshape(2, 2)]


def test_frame_roundtrip():
    frames = (kvwire.encode_frame("d1", _pages())
              + kvwire.encode_frame("d2", [np.ones((3,), np.int32)])
              + kvwire.encode_end(2, ["gone"], truncated=1,
                                  served_bytes=84))
    # feed in awkward chunk sizes: the reader must reassemble across
    # chunk boundaries
    chunks = [frames[i:i + 7] for i in range(0, len(frames), 7)]
    blocks, end = kvwire.decode_frames(chunks)
    assert set(blocks) == {"d1", "d2"}
    for got, want in zip(blocks["d1"], _pages()):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    assert end == {"end": True, "served": 2, "served_bytes": 84,
                   "missing": ["gone"], "missing_count": 1,
                   "truncated": 1}
    # the missing LIST is capped so the end-frame header can never blow
    # the decoder's MAX_HDR_BYTES; the count stays exact
    big = kvwire.encode_end(0, [f"{i:016x}" for i in range(4096)])
    _, end = kvwire.decode_frames([big])
    assert end["missing_count"] == 4096 and len(end["missing"]) == 256


@pytest.mark.parametrize("mangle", ["magic", "truncate", "hdr_cap",
                                    "payload_cap", "spec_short", "garbage"])
def test_frame_corruption_raises(mangle):
    import struct
    good = kvwire.encode_frame("d", _pages()) + kvwire.encode_end(1, [])
    if mangle == "magic":
        bad = b"XXXX" + good[4:]
    elif mangle == "truncate":
        bad = good[:len(good) // 2]     # stream ends before the end frame
    elif mangle == "hdr_cap":
        bad = kvwire.MAGIC + struct.pack(">II", 1 << 20, 0)
    elif mangle == "payload_cap":
        bad = kvwire.MAGIC + struct.pack(">II", 2, 1 << 30) + b"{}"
    elif mangle == "spec_short":
        # header promises more page bytes than the payload carries
        hdr = json.dumps({"digest": "d", "pages": [
            {"dtype": "<f4", "shape": [64]}]}).encode()
        bad = kvwire.MAGIC + struct.pack(">II", len(hdr), 8) + hdr + b"\0" * 8
    else:
        bad = b"#!<<injected corrupt body; not JSON>>"
    with pytest.raises(kvwire.WireError):
        kvwire.decode_frames([bad])


def test_decode_frames_byte_cap():
    frames = kvwire.encode_frame("d", [np.zeros((1024,), np.float32)])
    with pytest.raises(kvwire.WireError):
        kvwire.decode_frames([frames], max_total_bytes=64)


# ---- quantized (kvq8) frames --------------------------------------------

def _q8_record():
    from distributed_llm_inferencing_tpu.ops import kvblock_quant as kvq
    rng = np.random.default_rng(9)
    return kvq.quantize_block(
        [rng.standard_normal((2, 8, 2, 4)).astype(np.float32),
         rng.integers(0, 7, (5,)).astype(np.int8)])


def test_kvq8_frame_roundtrip():
    from distributed_llm_inferencing_tpu.ops import kvblock_quant as kvq
    rec = _q8_record()
    frames = (kvwire.encode_stored("q1", rec)
              + kvwire.encode_end(1, [], served_bytes=len(rec)))
    chunks = [frames[i:i + 7] for i in range(0, len(frames), 7)]
    blocks, _ = kvwire.decode_frames(chunks)
    got = blocks["q1"]
    assert kvq.is_quantized_block(got)
    for a, b in zip(kvq.dequantize_block(got), kvq.dequantize_block(rec)):
        np.testing.assert_array_equal(a, b)
    # stored/logical accounting dispatches on the representation
    assert kvwire.stored_nbytes(rec) == kvq.stored_nbytes(rec)
    assert kvwire.logical_nbytes(rec) > kvwire.stored_nbytes(rec)
    pages = _pages()
    assert kvwire.stored_nbytes(pages) == sum(p.nbytes for p in pages)


def _reframe(frame, mutate_hdr=None, mutate_payload=None):
    """Unpack one encoded frame, apply mutations, re-pack with
    consistent lengths — corruption the length prefixes can't catch,
    so the VALIDATION layer has to."""
    import struct
    hl, pl = struct.unpack(">II", frame[4:12])
    hdr = json.loads(frame[12:12 + hl])
    payload = frame[12 + hl:12 + hl + pl]
    if mutate_hdr:
        hdr = mutate_hdr(hdr)
    if mutate_payload:
        payload = mutate_payload(payload, hdr)
    h = json.dumps(hdr).encode()
    return (kvwire.MAGIC + struct.pack(">II", len(h), len(payload))
            + h + payload)


@pytest.mark.parametrize("mangle", [
    "quant_scheme", "meta_missing", "meta_count", "bad_dtype",
    "scale_truncated", "nonfinite_scale"])
def test_kvq8_frame_corruption_raises(mangle):
    """Quantized-frame corruption classes — bad scale lengths, dtype
    drift, truncated scale payloads, NaN scales — all raise WireError
    (-> recompute on the fetching side), never crash or yield a record
    that would silently poison a dequant."""
    rec = _q8_record()
    frame = kvwire.encode_stored("q", rec)
    q_nbytes = rec["pages"][0]["q"].nbytes

    def hdr_mut(hdr):
        if mangle == "quant_scheme":
            hdr["quant"] = "kvq9"
        elif mangle == "meta_missing":
            del hdr["meta"]
        elif mangle == "meta_count":
            hdr["meta"] = hdr["meta"] + [{"kind": "raw"}]
        elif mangle == "bad_dtype":
            hdr["meta"][0]["dtype"] = "int64"
        elif mangle == "scale_truncated":
            # scale page shorter than the q page's (layers, heads)
            hdr["pages"][1]["shape"] = [1, 2]
        return hdr

    def payload_mut(payload, hdr):
        if mangle == "scale_truncated":
            return payload[:q_nbytes + 8]   # 1x2 float32 scales
        if mangle == "nonfinite_scale":
            import struct
            return (payload[:q_nbytes] + struct.pack("<f", float("nan"))
                    + payload[q_nbytes + 4:])
        return payload

    bad = _reframe(frame, hdr_mut, payload_mut) + kvwire.encode_end(1, [])
    with pytest.raises(kvwire.WireError):
        kvwire.decode_frames([bad])


# ---- live workers -------------------------------------------------------

def _mk_worker(role="mixed", **load_kw):
    agent = WorkerAgent(role=role)
    srv = agent.serve("127.0.0.1", 0, background=True)
    port = srv.server_address[1]
    body = {"model_name": "tiny-llama", "allow_random_init": True,
            "dtype": "float32", "serving": "batched", "slots": 4,
            "kv_blocks": 64, "kv_block_size": 8, "max_seq": 128}
    body.update(load_kw)
    r = rq.post(f"http://127.0.0.1:{port}/load_model", json=body,
                timeout=600)
    assert r.status_code == 200, r.text
    return agent, port


def _infer(port, prompt, max_new=6, seed=11, do_sample=False, **extra):
    body = {"model_name": "tiny-llama", "prompt": prompt,
            "max_new_tokens": max_new, "seed": seed,
            "sampling": {"do_sample": do_sample, "temperature": 0.8,
                         "top_k": 20}}
    body.update(extra)
    r = rq.post(f"http://127.0.0.1:{port}/inference", json=body,
                timeout=600)
    assert r.status_code == 200, r.text
    return r.json()


def _counters(agent):
    return agent.metrics.snapshot()["counters"]


@pytest.fixture(scope="module")
def prefill_worker():
    agent, port = _mk_worker(role="prefill")
    yield agent, port
    stop_worker(agent)


def test_health_reports_role_and_occupancy(prefill_worker):
    agent, port = prefill_worker
    h = rq.get(f"http://127.0.0.1:{port}/health").json()
    assert h["role"] == "prefill"
    assert "arena_occupancy" in h
    _infer(port, LONG_PROMPT, kv_export=True)
    h = rq.get(f"http://127.0.0.1:{port}/health").json()
    assert h["arena_occupancy"] is not None and h["arena_occupancy"] > 0
    # the scheduler stats carry the occupancy fraction per model too
    kv = h["loaded_models"][0]["scheduler"]["kvtier"]
    assert 0 < kv["occupancy"] <= 1


def test_bad_role_rejected():
    with pytest.raises(ValueError):
        WorkerAgent(role="gpu")


def test_kv_fetch_endpoint_serves_exported_blocks(prefill_worker):
    agent, port = prefill_worker
    res = _infer(port, LONG_PROMPT, kv_export=True)
    m = agent.models["tiny-llama"]
    bs = m.batcher.block_size
    prompt_toks = m.tokenizer.encode(LONG_PROMPT)
    digs = m.batcher.kvtier.block_digests(
        prompt_toks[:len(prompt_toks) // bs * bs])
    assert digs and all(m.batcher.kvtier.arena.peek(d) for d in digs)
    r = rq.post(f"http://127.0.0.1:{port}/kv_fetch",
                json={"model_name": "tiny-llama",
                      "digests": digs + ["feedfacefeedface"]},
                stream=True, timeout=30)
    assert r.status_code == 200
    assert "octet-stream" in r.headers["Content-Type"]
    blocks, end = kvwire.decode_frames(r.iter_content(chunk_size=4096))
    assert set(blocks) == set(digs)
    assert end["served"] == len(digs) and end["truncated"] == 0
    assert end["served_bytes"] > 0      # honest partial-fetch sizing
    assert end["missing"] == ["feedfacefeedface"]
    # frames carry the exact arena bytes
    for d in digs:
        arena_pages = m.batcher.kvtier.arena.peek_pages(d)
        for got, want in zip(blocks[d], arena_pages):
            np.testing.assert_array_equal(got, np.asarray(want))
    assert res["tokens"]   # the export pass still answered normally


def test_kv_fetch_validation(prefill_worker):
    _, port = prefill_worker
    url = f"http://127.0.0.1:{port}/kv_fetch"
    assert rq.post(url, json={"model_name": "nope",
                              "digests": ["d"]}).status_code == 404
    assert rq.post(url, json={"model_name": "tiny-llama",
                              "digests": []}).status_code == 400
    assert rq.post(url, json={"model_name": "tiny-llama",
                              "digests": [1, 2]}).status_code == 400
    assert rq.post(url, json={
        "model_name": "tiny-llama",
        "digests": ["d"] * (kvwire.MAX_DIGESTS + 1)}).status_code == 400


def test_kv_fetch_size_cap(prefill_worker, monkeypatch):
    from distributed_llm_inferencing_tpu.runtime import worker as worker_mod
    agent, port = prefill_worker
    _infer(port, LONG_PROMPT, kv_export=True)
    m = agent.models["tiny-llama"]
    toks = m.tokenizer.encode(LONG_PROMPT)
    bs = m.batcher.block_size
    digs = m.batcher.kvtier.block_digests(toks[:len(toks) // bs * bs])
    # cap below one frame: everything truncates, nothing served
    monkeypatch.setattr(worker_mod, "KV_FETCH_MAX_MB", 1e-6)
    r = rq.post(f"http://127.0.0.1:{port}/kv_fetch",
                json={"model_name": "tiny-llama", "digests": digs},
                stream=True, timeout=30)
    blocks, end = kvwire.decode_frames(r.iter_content(chunk_size=4096))
    assert not blocks and end["truncated"] == len(digs)
    assert end["served"] == 0 and end["served_bytes"] == 0
    # cap fitting exactly one frame: the terminal frame reports the
    # blocks AND bytes actually served, so the peer can size its
    # recompute fallback to the true shortfall
    one = len(kvwire.encode_stored(
        digs[0], m.batcher.kvtier.arena.peek_stored(digs[0])))
    monkeypatch.setattr(worker_mod, "KV_FETCH_MAX_MB", one / (1 << 20))
    r = rq.post(f"http://127.0.0.1:{port}/kv_fetch",
                json={"model_name": "tiny-llama", "digests": digs},
                stream=True, timeout=30)
    blocks, end = kvwire.decode_frames(r.iter_content(chunk_size=4096))
    assert len(blocks) == 1 and end["served"] == 1
    assert end["served_bytes"] == one
    assert end["truncated"] == len(digs) - 1


@pytest.fixture(scope="module")
def trio():
    """(src prefill, dst decode, cold mixed) worker trio shared by the
    bitwise and chaos tests — each test uses a distinct prompt family so
    one test's radix/arena state can't mask another's transfer."""
    src = _mk_worker(role="prefill")
    dst = _mk_worker(role="decode")
    cold = _mk_worker(role="mixed")
    yield src, dst, cold
    for a, _ in (src, dst, cold):
        stop_worker(a)


def test_transferred_decode_bitwise_identical(trio):
    """The headline guarantee: decode continued from fetched KV emits
    the exact tokens a cold single-node run emits — greedy AND sampled."""
    (src, src_port), (dst, dst_port), (cold, cold_port) = trio
    for do_sample, seed in ((False, 11), (True, 12)):
        # cold reference on a worker that never saw the prompt
        ref = _infer(cold_port, LONG_PROMPT, max_new=8, seed=seed,
                     do_sample=do_sample)
        # disaggregated: prefill+export on src, decode on dst with a
        # kv_source hint back at src
        _infer(src_port, LONG_PROMPT, max_new=1, seed=seed,
               do_sample=do_sample, kv_export=True)
        before = _counters(dst).get("kv_transfer_blocks", 0)
        got = _infer(dst_port, LONG_PROMPT, max_new=8, seed=seed,
                     do_sample=do_sample,
                     kv_source={"url": f"http://127.0.0.1:{src_port}",
                                "model": "tiny-llama"})
        assert got["tokens"] == ref["tokens"], (do_sample, seed)
        assert got["result"] == ref["result"]
        transferred = _counters(dst)["kv_transfer_blocks"] - before
        if do_sample:
            # second pass, same prompt: the first already parked the
            # blocks locally, so no new transfer is required
            assert got["cost"]["prefill_cached_tokens"] > 0
        else:
            assert transferred > 0      # the KV really crossed nodes
            assert got["cost"]["kv_transfer_bytes"] > 0


def test_peer_session_reuse_and_teardown():
    """PR 4 treatment on the worker-side peer sessions: the second fetch
    rides the pooled keep-alive socket (reused climbs, created doesn't),
    and a dead peer purges the session so the next dial is fresh."""
    src, src_port = _mk_worker(role="prefill")
    dst, _dst_port = _mk_worker(role="decode")
    try:
        _infer(src_port, LONG_PROMPT, kv_export=True)
        m = src.models["tiny-llama"]
        toks = m.tokenizer.encode(LONG_PROMPT)
        bs = m.batcher.block_size
        digs = m.batcher.kvtier.block_digests(toks[:len(toks) // bs * bs])
        client = dst.peer_client()
        url = f"http://127.0.0.1:{src_port}"
        got = client.fetch(url, "tiny-llama", digs)
        assert set(got) == set(digs)
        c = _counters(dst)
        assert c["worker_peer_conns_created"] == 1
        client.fetch(url, "tiny-llama", digs[:1])
        c = _counters(dst)
        assert c["worker_peer_conns_created"] == 1
        assert c["worker_peer_conns_reused"] >= 1
        # dead peer: the fetch fails loudly and the session is purged
        stop_worker(src)
        with pytest.raises(Exception):
            client.fetch(url, "tiny-llama", digs[:1])
        assert url not in client._sessions
    finally:
        stop_worker(dst)
        stop_worker(src)


def test_restore_from_peer_rejects_mismatched_pages():
    """A peer serving a different cache layout must degrade to
    recompute, not crash the scheduler thread in the restore scatter."""
    import jax
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    class BadFetcher:
        calls = 0

        def fetch(self, url, model, digests):
            self.calls += 1
            return {d: [np.zeros((3, 5), np.float64)] for d in digests}

    fetcher = BadFetcher()
    b = ContinuousBatcher(cfg, params, num_blocks=32, block_size=8,
                          slots=2, max_seq=128, kv_fetcher=fetcher)
    prompt = list(range(40))
    ref = b.submit(list(prompt), max_new_tokens=6,
                   sampling=SamplingParams.greedy(), seed=5)
    for _ in range(200):
        b.step()
        if ref.done.is_set():
            break
    cold = ref.wait()
    b2 = ContinuousBatcher(cfg, params, num_blocks=32, block_size=8,
                           slots=2, max_seq=128, kv_fetcher=fetcher)
    r2 = b2.submit(list(prompt), max_new_tokens=6,
                   sampling=SamplingParams.greedy(), seed=5,
                   kv_source={"url": "http://peer", "model": "tiny-llama"})
    for _ in range(200):
        b2.step()
        if r2.done.is_set():
            break
    assert r2.wait() == cold            # recompute, identical output
    assert fetcher.calls == 1           # one peer RPC per request
    c = b2.metrics.snapshot()["counters"]
    assert c["kv_transfer_failures"] >= 1
    assert c["kv_transfer_blocks"] == 0


# ---- chaos on the transfer wire ----------------------------------------

@pytest.mark.parametrize("mode", ["disconnect", "corrupt", "error",
                                  "timeout"])
def test_chaos_kv_fetch_degrades_to_recompute(trio, mode):
    """Killing/corrupting the KV source mid-fetch never fails or
    corrupts the decode request: output stays bitwise identical to a
    cold prefill (no duplicated or lost tokens) and the failure is
    surfaced in kv_transfer_failures. ``timeout`` arms the CLIENT-side
    ``rpc:/kv_fetch`` point (the decode node's own fault injector);
    the rest are server-side on the source."""
    (src, src_port), (dst, dst_port), (cold, cold_port) = trio
    prompt = f"<{mode}> {LONG_PROMPT}"    # per-mode prompt family: an
    # earlier mode's recompute left ITS prompt radix-cached on dst
    try:
        ref = _infer(cold_port, prompt, max_new=8, seed=21)
        _infer(src_port, prompt, max_new=1, seed=21, kv_export=True)
        if mode == "timeout":
            dst.service.faults.arm([{"point": "rpc:/kv_fetch",
                                     "mode": "timeout", "times": 1}],
                                   seed=0)
        else:
            src.service.faults.arm([{"point": "/kv_fetch", "mode": mode,
                                     "times": 1}], seed=0)
        fails0 = _counters(dst).get("kv_transfer_failures", 0)
        blocks0 = _counters(dst).get("kv_transfer_blocks", 0)
        got = _infer(dst_port, prompt, max_new=8, seed=21,
                     kv_source={"url": f"http://127.0.0.1:{src_port}",
                                "model": "tiny-llama"})
        assert got["tokens"] == ref["tokens"]
        c = _counters(dst)
        assert c["kv_transfer_failures"] - fails0 >= 1
        assert c["kv_transfer_blocks"] - blocks0 == 0
    finally:
        src.service.faults.clear()
        dst.service.faults.clear()


def test_chaos_disagg_source_death_no_breaker_storm():
    """Full master-driven flow with the prefill node crashing before
    the fetch: the decode request completes by recompute, and the chaos
    costs AT MOST one breaker strike (the transfer failure itself is a
    worker-to-worker affair the master's breaker never sees)."""
    src, src_port = _mk_worker(role="prefill")
    dst, dst_port = _mk_worker(role="decode")
    m = Master(":memory:", health_interval=30.0, disagg_min_prompt=64)
    msrv = m.service.serve("127.0.0.1", 0, background=True)
    base = f"http://127.0.0.1:{msrv.server_address[1]}"
    try:
        for i, p in enumerate((src_port, dst_port)):
            r = rq.post(f"{base}/api/nodes/add",
                        json={"name": f"w{i}", "host": "127.0.0.1",
                              "port": p}).json()
            assert r["status"] == "success", r
        m.start_background()
        # the decode-side fetch will hit a dead listener: sever the
        # source right after its prefill pass via a crash fault
        src.service.faults.arm([{"point": "/kv_fetch", "mode": "crash",
                                 "times": 1}], seed=0)
        rid = rq.post(f"{base}/api/inference/submit", json={
            "model_name": "tiny-llama", "prompt": LONG_PROMPT,
            "max_new_tokens": 6,
            "sampling": {"do_sample": False,
                         "allow_random_init": True}}).json()["request_id"]
        deadline = time.time() + 240
        while time.time() < deadline:
            st = rq.get(f"{base}/api/inference/status/{rid}"
                        ).json()["request"]
            if st["status"] in ("completed", "failed"):
                break
            time.sleep(0.2)
        assert st["status"] == "completed", st
        assert _counters(dst)["kv_transfer_failures"] >= 1
        strikes = [n["consecutive_failures"]
                   for n in m.store.list_nodes()]
        assert max(strikes) <= 1
        mc = m.metrics.snapshot()["counters"]
        assert mc["scheduler_disagg_transfer"] >= 1
    finally:
        m.stop()
        stop_worker(src)
        stop_worker(dst)


# ---- int8 wire tier + single-flight prefetch ----------------------------

def test_int8_worker_transfer_greedy_match_and_compression(
        trio, monkeypatch):
    """End-to-end int8 transfer between live workers: the decode
    continued from quantized fetched KV emits the exact greedy tokens
    of a cold native run, the wire ships >=3.5x fewer bytes than the
    logical pages, and the arena advertises honest stored bytes."""
    monkeypatch.setenv("DLI_KV_HOST_DTYPE", "int8")
    src, src_port = _mk_worker(role="prefill")
    dst, dst_port = _mk_worker(role="decode")
    (_, _), (_, _), (cold, cold_port) = trio   # native cold reference
    prompt = f"<q8> {LONG_PROMPT}"
    try:
        ref = _infer(cold_port, prompt, max_new=8, seed=31)
        _infer(src_port, prompt, max_new=1, seed=31, kv_export=True)
        got = _infer(dst_port, prompt, max_new=8, seed=31,
                     kv_source={"url": f"http://127.0.0.1:{src_port}",
                                "model": "tiny-llama"})
        assert got["tokens"] == ref["tokens"]
        sc, dc = _counters(src), _counters(dst)
        assert sc["kv_wire_sent_bytes"] > 0
        assert sc["kv_wire_sent_bytes"] < sc["kv_wire_raw_bytes"] / 3.5
        assert dc["kv_transfer_failures"] == 0
        assert dc["kv_transfer_blocks"] > 0
        # transfer accounting counts STORED (compressed) wire bytes
        assert dc["kv_transfer_bytes"] == sc["kv_wire_sent_bytes"]
        st = src.models["tiny-llama"].batcher.kvtier.stats()
        assert st["dtype"] == "int8"
        assert st["logical_bytes"] > st["bytes"] * 3.5
    finally:
        stop_worker(src)
        stop_worker(dst)


def test_single_flight_prefetch_coalesces():
    """Seeded concurrent prefetches of the same digest set coalesce
    onto ONE wire transfer: the first caller leads, the rest register
    as waiters (kv_prefetch_coalesced), and every caller finds the
    blocks arena-resident afterward."""
    import threading
    import jax
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = list(range(40))
    # source batcher: run + export so its arena holds the blocks
    b1 = ContinuousBatcher(cfg, params, num_blocks=32, block_size=8,
                           slots=2, max_seq=128)
    r = b1.submit(list(prompt), max_new_tokens=2,
                  sampling=SamplingParams.greedy(), seed=5,
                  kv_export=True)
    for _ in range(200):
        b1.step()
        if r.done.is_set():
            break
    r.wait()
    bs = b1.block_size
    digs = b1.kvtier.block_digests(prompt[:len(prompt) // bs * bs])
    served = {d: tuple(np.asarray(p)
                       for p in b1.kvtier.arena.peek_pages(d))
              for d in digs}

    class Peer:
        calls = 0

        def fetch(self, url, model, digests):
            self.calls += 1
            return {d: served[d] for d in digests if d in served}

    fetcher = Peer()
    b2 = ContinuousBatcher(cfg, params, num_blocks=32, block_size=8,
                           slots=2, max_seq=128, kv_fetcher=fetcher)
    b2._wire_overlap = False
    started, release = threading.Event(), threading.Event()
    wire_calls = []
    orig = b2._wire_fetch

    def gated(url, model, want, progress=None):
        wire_calls.append(list(want))
        started.set()
        release.wait(30)    # hold the leader in flight so the waiters
        return orig(url, model, want, progress=progress)   # must queue

    b2._wire_fetch = gated
    src = {"url": "http://peer", "model": "tiny-llama"}
    results = []

    def prefetch():
        results.append(b2.prefetch_kv(list(prompt), src))

    leader = threading.Thread(target=prefetch)
    leader.start()
    assert started.wait(10)
    waiters = [threading.Thread(target=prefetch) for _ in range(4)]
    for t in waiters:
        t.start()
    # every waiter must have REGISTERED (seen the in-flight entry and
    # counted itself) before the leader is released — that is the race
    # the registry exists for
    deadline = time.time() + 10
    while time.time() < deadline:
        c = b2.metrics.snapshot()["counters"]
        if c.get("kv_prefetch_coalesced", 0) >= 4:
            break
        time.sleep(0.01)
    release.set()
    leader.join(timeout=30)
    for t in waiters:
        t.join(timeout=30)
    c = b2.metrics.snapshot()["counters"]
    assert c["kv_prefetch_coalesced"] == 4
    assert fetcher.calls == 1           # exactly one wire transfer
    assert len(wire_calls) == 1
    want = digs[:(len(prompt) - 1) // bs]
    assert wire_calls[0] == want        # the deduped union, in order
    assert all(b2.kvtier.arena.peek(d) for d in want)
    assert sorted(results, reverse=True)[0] > 0     # leader got bytes
    assert sorted(results)[:4] == [0, 0, 0, 0]      # waiters shared


# ---- role-aware routing -------------------------------------------------

def _role_master(roles, runtime=None):
    """Master with synthetic nodes declaring ``roles`` (no live workers
    — routing units only)."""
    m = Master(":memory:", dispatcher_threads=0)
    for i, role in enumerate(roles):
        nid = m.store.add_node(f"n{i}", "127.0.0.1", 9000 + i,
                               is_active=True)
        m.store.update_node(nid, info={
            "role": role, "arena_occupancy": 0.1,
            "loaded_models": [{"name": "mod", "scheduler": {
                "queued": 0, "blocks_free": 10,
                "kvtier": {"occupancy": 0.1}}}]})
        m._note_runtime(nid, json.loads(
            m.store.get_node(nid)["info"]))
        if runtime and i in runtime:
            m._node_runtime[nid].update(runtime[i])
    return m


def test_pick_node_role_pools():
    m = _role_master(["prefill", "decode", "mixed"])
    try:
        ids = {n["name"]: n["id"]
               for n in m.store.list_nodes()}
        picked = {m._pick_node("mod", role="decode")["id"]
                  for _ in range(12)}
        assert ids["n0"] not in picked          # strict prefill excluded
        picked = {m._pick_node("mod", role="prefill")["id"]
                  for _ in range(12)}
        assert ids["n1"] not in picked          # strict decode excluded
        # no compatible node at all -> fall back to everyone
        m2 = _role_master(["prefill", "prefill"])
        assert m2._pick_node("mod", role="decode") is not None
        m2.stop()
        # mixed fleet: role filter is a no-op, counters untouched
        m3 = _role_master(["mixed", "mixed"])
        m3._pick_node("mod", role="decode")
        assert m3.metrics.snapshot()["counters"][
            "scheduler_pick_role_decode"] == 0
        m3.stop()
    finally:
        m.stop()


def test_pick_node_role_keeps_sticky_pin():
    """A timeout retry pinned to the node that holds its in-flight
    generation must reach it even when the role filter would drop it."""
    m = _role_master(["prefill", "decode"])
    try:
        pid = m.store.list_nodes()[0]["id"]
        n = m._pick_node("mod", role="decode", prefer=pid)
        assert n["id"] == pid
    finally:
        m.stop()


def test_pick_node_avoids_full_arena_for_prefill():
    m = _role_master(["prefill", "prefill"],
                     runtime={0: {"arena_occ": 0.97},
                              1: {"arena_occ": 0.2}})
    try:
        nodes = m.store.list_nodes()
        for _ in range(6):
            assert m._pick_node("mod", role="prefill")["id"] \
                == nodes[1]["id"]
        c = m.metrics.snapshot()["counters"]
        assert c["scheduler_pick_arena_full_avoided"] >= 1
        # both full: better a full arena than no prefill at all
        m._node_runtime[nodes[1]["id"]]["arena_occ"] = 0.99
        assert m._pick_node("mod", role="prefill") is not None
    finally:
        m.stop()


def test_plan_disagg_decisions():
    m = _role_master(["prefill", "decode"])
    try:
        snapshot = m.store.list_nodes(active_only=True)

        def req(prompt, attempts=0, excluded=None):
            return {"id": 1, "model_name": "mod", "prompt": prompt,
                    "attempts": attempts,
                    "excluded_nodes": excluded or [],
                    "sampling": {}}
        m._disagg_min_prompt = 64
        plan = m._plan_disagg(req("x" * 100), snapshot)
        assert plan is not None
        (pn, dn) = plan
        assert m._node_role(pn) == "prefill" and m._node_role(dn) == "decode"
        # reservations were taken — release for the next checks
        with m._inflight_lock:
            m._inflight.clear()
        # short prompt / retries / disabled policy never disaggregate
        assert m._plan_disagg(req("x" * 10), snapshot) is None
        assert m._plan_disagg(req("x" * 100, attempts=1), snapshot) is None
        assert m._plan_disagg(req("x" * 100, excluded=[1]), snapshot) is None
        m._disagg = False
        assert m._plan_disagg(req("x" * 100), snapshot) is None
        m._disagg = True
        # a prefill node WITHOUT a host arena (engine-serving or
        # kv_host_mb=0) cannot export: the plan must refuse instead of
        # silently double-prefilling every long prompt
        for n in snapshot:
            n.pop("_can_export", None)
        pid = snapshot[0]["id"]
        saved = m._node_runtime[pid]
        m._node_runtime[pid] = {"queue": 0, "free_blocks": 10,
                                "arena_occ": None, "at": time.time(),
                                "models": {}}
        m.store.update_node(pid, info={"role": "prefill",
                                       "arena_occupancy": None,
                                       "loaded_models": []})
        snap2 = m.store.list_nodes(active_only=True)
        assert m._plan_disagg(req("x" * 100), snap2) is None
        with m._inflight_lock:
            m._inflight.clear()
        m._node_runtime[pid] = saved
        # a warm decode node tips the decision to recompute-by-affinity
        from distributed_llm_inferencing_tpu.runtime.kvtier import (
            PrefixDigestIndex)
        idx = PrefixDigestIndex(chunk=16)
        idx.note("x" * 100, 25)
        dn_id = snapshot[1]["id"]
        m._node_runtime[dn_id]["models"]["mod"]["digests"] = \
            idx.advertise()
        before = m.metrics.snapshot()["counters"][
            "scheduler_disagg_recompute"]
        assert m._plan_disagg(req("x" * 100), snapshot) is None
        after = m.metrics.snapshot()["counters"][
            "scheduler_disagg_recompute"]
        assert after == before + 1
    finally:
        m.stop()


def test_mixed_fleet_never_disaggregates():
    m = _role_master(["mixed", "mixed"])
    try:
        snapshot = m.store.list_nodes(active_only=True)
        req = {"id": 1, "model_name": "mod", "prompt": "x" * 4096,
               "attempts": 0, "excluded_nodes": [], "sampling": {}}
        assert m._plan_disagg(req, snapshot) is None
        c = m.metrics.snapshot()["counters"]
        assert c["scheduler_disagg_transfer"] == 0
        assert c["scheduler_disagg_recompute"] == 0
    finally:
        m.stop()


def test_node_status_reports_role_and_arena():
    m = _role_master(["prefill", "decode"],
                     runtime={0: {"arena_occ": 0.5}})
    try:
        nodes = m.api_node_status({})["nodes"]
        assert [n["role"] for n in nodes] == ["prefill", "decode"]
        assert nodes[0]["arena_occupancy"] == 0.5
    finally:
        m.stop()
