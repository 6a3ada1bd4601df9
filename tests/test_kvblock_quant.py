"""Per-(layer, head) int8 KV-block quantization: the differential suite.

`DLI_KV_HOST_DTYPE=int8` trades the arena/wire tier's bit-exactness for
~3.9x density, so it is gated by its own evidence rather than riding the
bitwise pins:

- quantize -> dequantize error stays inside the per-(layer, head)
  half-step bound on every supported logical dtype,
- decode-step LOGITS computed against a quantize-roundtripped paged
  cache stay within a small max-abs-err of the native cache on registry
  models, with the greedy argmax unchanged,
- a greedy decode continued from int8-quantized transferred blocks
  emits the exact tokens of a cold native run (the end-to-end twin of
  ``test_disagg.py``'s bitwise pin),
- wire flattening round-trips, and ``block_from_wire`` rejects every
  malformed-meta class (the payload came off a socket),
- the arena's byte accounting is honest in int8 mode: ``occupancy``
  counts stored bytes, ``logical_bytes`` what they restore to.

Native mode is deliberately NOT touched here — its bitwise guarantees
stay pinned by the unmodified tests in ``test_kvtier.py`` and
``test_disagg.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops import kvblock_quant as kvq
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    init_paged_cache)
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
from distributed_llm_inferencing_tpu.runtime.batcher import (
    ContinuousBatcher)
from distributed_llm_inferencing_tpu.runtime.kvtier import HostKVArena
from conftest import jitted

BS = 8


def _page(rng, dtype=np.float32, L=2, bs=BS, H=2, D=4, scale=1.0):
    return (rng.standard_normal((L, bs, H, D)) * scale).astype(dtype)


# ---- numeric bounds -----------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "float16", "bfloat16"])
def test_roundtrip_bounded_error(dtype):
    import ml_dtypes
    np_dtype = (np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16"
                else np.dtype(dtype))
    rng = np.random.default_rng(0)
    page = _page(rng, np.float32).astype(np_dtype)
    e = kvq.quantize_page(page)
    assert e["kind"] == "q8" and e["q"].dtype == np.int8
    assert e["scale"].shape == (page.shape[0], page.shape[-2])
    deq = kvq.dequantize_page(e)
    assert deq.dtype == page.dtype and deq.shape == page.shape
    # per-(layer, head) half-step bound: |x - deq| <= scale/2 plus the
    # logical dtype's own rounding on the way back
    f = np.asarray(page, np.float32)
    err = np.abs(f - np.asarray(deq, np.float32))
    bound = e["scale"][:, None, :, None] * 0.55 + np.abs(f) * 1e-2
    assert np.all(err <= bound), float(err.max())


def test_scale_varies_per_layer_and_head():
    """A hot head must not inflate a quiet head's quantization step —
    the per-(layer, head) granularity is the scheme's whole point."""
    rng = np.random.default_rng(1)
    page = _page(rng)
    page[0, :, 0, :] *= 100.0           # one hot (layer, head)
    e = kvq.quantize_page(page)
    assert e["scale"][0, 0] > 50 * e["scale"][0, 1]
    deq = kvq.dequantize_page(e)
    quiet_err = np.abs(page[0, :, 1, :] - deq[0, :, 1, :]).max()
    assert quiet_err <= e["scale"][0, 1] * 0.55


def test_raw_passthrough():
    """Integer pages (kv-quantized device caches) and low-rank float
    leaves (their scale planes) must pass through bit-identically —
    re-quantizing either would be lossy-on-lossy."""
    rng = np.random.default_rng(2)
    pages = [rng.integers(-127, 127, (2, BS, 2, 4)).astype(np.int8),
             rng.standard_normal((2, BS, 2)).astype(np.float32)]  # 3D
    rec = kvq.quantize_block(pages)
    assert all(e["kind"] == "raw" for e in rec["pages"])
    for got, want in zip(kvq.dequantize_block(rec), pages):
        np.testing.assert_array_equal(got, want)


def test_accounting_and_specs():
    rng = np.random.default_rng(3)
    pages = [_page(rng), _page(rng)]
    rec = kvq.quantize_block(pages)
    logical = sum(p.nbytes for p in pages)
    assert kvq.logical_nbytes(rec) == logical
    assert kvq.stored_nbytes(rec) < logical / 3.5
    assert kvq.logical_specs(rec) == [(p.shape, p.dtype) for p in pages]
    assert kvq.is_quantized_block(rec)
    assert not kvq.is_quantized_block(tuple(pages))


# ---- wire flattening / untrusted-meta validation ------------------------

def test_wire_roundtrip():
    rng = np.random.default_rng(4)
    pages = [_page(rng), rng.integers(0, 5, (3,)).astype(np.int32)]
    rec = kvq.quantize_block(pages)
    back = kvq.block_from_wire(kvq.wire_meta(rec), kvq.wire_arrays(rec))
    for got, want in zip(kvq.dequantize_block(back),
                         kvq.dequantize_block(rec)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mangle", [
    "kind", "dtype", "q_dtype", "scale_dtype", "scale_shape",
    "nonfinite", "short", "long", "low_rank"])
def test_block_from_wire_rejects(mangle):
    """Every malformed-meta class raises ValueError (the codec maps it
    to WireError -> recompute) — a socket payload is never trusted."""
    rng = np.random.default_rng(5)
    rec = kvq.quantize_block([_page(rng)])
    meta, arrs = kvq.wire_meta(rec), kvq.wire_arrays(rec)
    if mangle == "kind":
        meta = [{"kind": "zstd"}]
    elif mangle == "dtype":
        meta = [{"kind": "q8", "dtype": "int64"}]
    elif mangle == "q_dtype":
        arrs = [arrs[0].astype(np.int16), arrs[1]]
    elif mangle == "scale_dtype":
        arrs = [arrs[0], arrs[1].astype(np.float64)]
    elif mangle == "scale_shape":
        # truncated scale payload: fewer scales than (layers, heads)
        arrs = [arrs[0], arrs[1][:1]]
    elif mangle == "nonfinite":
        bad = arrs[1].copy()
        bad.flat[0] = np.nan
        arrs = [arrs[0], bad]
    elif mangle == "short":
        arrs = arrs[:1]
    elif mangle == "long":
        arrs = arrs + [arrs[1]]
    else:   # a q page too low-rank to carry (layer, head) axes
        arrs = [arrs[0][0], arrs[1]]
    with pytest.raises(ValueError):
        kvq.block_from_wire(meta, arrs)


# ---- arena accounting in int8 mode --------------------------------------

def test_arena_int8_density_and_honest_bytes():
    rng = np.random.default_rng(6)
    pages = tuple(_page(rng) for _ in range(2))
    logical = sum(p.nbytes for p in pages)
    native = HostKVArena(capacity_bytes=1 << 20)
    q8 = HostKVArena(capacity_bytes=1 << 20, dtype="int8")
    assert native.put("d", pages) and q8.put("d", pages)
    sn, sq = native.stats(), q8.stats()
    assert sn["bytes"] == logical == sn["logical_bytes"]
    assert sq["bytes"] < logical / 3.5      # occupancy counts STORED
    assert sq["logical_bytes"] == logical
    assert sq["dtype"] == "int8"
    # restore path: logical pages out, bounded error
    got = q8.get("d")
    assert [g.shape for g in got] == [p.shape for p in pages]
    rec = q8.peek_stored("d")
    assert kvq.is_quantized_block(rec)
    # a quantized record fetched from an int8 peer stores as-is in a
    # NATIVE arena too (cross-mode transfer)
    assert native.put("q", rec)
    assert native.stats()["bytes"] > logical  # d native + q stored
    assert [g.shape for g in native.get("q")] == [p.shape for p in pages]


def test_arena_rejects_bad_dtype():
    with pytest.raises(ValueError):
        HostKVArena(capacity_bytes=1024, dtype="fp4")


# ---- logit differential on registry models ------------------------------

@pytest.mark.parametrize("model", ["tiny-llama", "tiny-gpt2"])
def test_decode_logits_bounded_vs_native_restore(model):
    """Decode-step logits against a quantize-roundtripped paged cache
    stay within a small max-abs-err of the native cache, and the greedy
    argmax is unchanged — the numeric core of the int8 quality gate."""
    cfg = get_config(model).replace(dtype="float32", attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 21).tolist()
    t = -(-len(prompt) // BS) * BS
    n_blocks = t // BS
    my_blocks = list(range(1, 1 + n_blocks))
    tokens = np.zeros((1, t), np.int32)
    tokens[0, :len(prompt)] = prompt
    paged = init_paged_cache(cfg, 16, BS, dtype=jnp.float32)
    last, paged = jitted(transformer.paged_prefill_tail)(
        params, cfg, jnp.asarray(tokens),
        jnp.asarray([len(prompt)], jnp.int32),
        jnp.asarray(my_blocks, jnp.int32),
        jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), paged)

    def roundtrip(leaf):
        a = np.array(leaf)              # [L, NB, bs, H, D]
        for b in my_blocks:
            a[:, b] = kvq.dequantize_page(kvq.quantize_page(a[:, b]))
        return jnp.asarray(a)

    paged_q = paged._replace(k=roundtrip(paged.k), v=roundtrip(paged.v))
    block_tables = np.zeros((1, 8), np.int32)
    block_tables[0, :n_blocks] = my_blocks
    block_tables[0, n_blocks] = 1 + n_blocks
    context_lens = np.asarray([len(prompt)], np.int32)
    toks = np.asarray([int(jnp.argmax(last[0]))], np.int32)
    ln, _ = jitted(transformer.paged_decode_step)(
        params, cfg, jnp.asarray(toks), paged,
        jnp.asarray(block_tables), jnp.asarray(context_lens))
    lq, _ = jitted(transformer.paged_decode_step)(
        params, cfg, jnp.asarray(toks), paged_q,
        jnp.asarray(block_tables), jnp.asarray(context_lens))
    err = float(jnp.max(jnp.abs(lq[0] - ln[0])))
    assert err < 0.25, err
    assert int(jnp.argmax(lq[0])) == int(jnp.argmax(ln[0]))


# ---- end-to-end: greedy decode from int8-transferred blocks -------------

def test_greedy_decode_from_quantized_transfer_matches_cold():
    """A greedy decode continued from int8-quantized transferred KV
    emits the exact tokens of a cold native run, at zero transfer
    failures — the end-to-end acceptance gate for int8 mode. (Wire
    overlap is irrelevant to the numerics; the blocking fetch path
    keeps the fake peer simple.)"""
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = list(range(40))

    def run(b, **kw):
        r = b.submit(list(prompt), max_new_tokens=6,
                     sampling=SamplingParams.greedy(), seed=5, **kw)
        for _ in range(300):
            b.step()
            if r.done.is_set():
                break
        return r.wait()

    b1 = ContinuousBatcher(cfg, params, num_blocks=32, block_size=BS,
                           slots=2, max_seq=128)
    cold = run(b1)
    run(b1, kv_export=True)     # park the prompt's blocks in the arena
    digs = b1.kvtier.block_digests(prompt[:len(prompt) // BS * BS])
    assert digs and all(b1.kvtier.arena.peek(d) for d in digs)
    records = {d: kvq.quantize_block(
        [np.asarray(p) for p in b1.kvtier.arena.peek_pages(d)])
        for d in digs}

    class QuantPeer:
        calls = 0

        def fetch(self, url, model, digests):
            self.calls += 1
            return {d: records[d] for d in digests if d in records}

    fetcher = QuantPeer()
    b2 = ContinuousBatcher(cfg, params, num_blocks=32, block_size=BS,
                           slots=2, max_seq=128, kv_fetcher=fetcher)
    b2._wire_overlap = False
    got = run(b2, kv_source={"url": "http://peer", "model": "tiny-llama"})
    assert got == cold
    assert fetcher.calls == 1
    c = b2.metrics.snapshot()["counters"]
    # the restore leaves the final block to the tail prefill (its last
    # position's KV is never fetchable), so limit = (n-1)//bs blocks
    assert c["kv_transfer_blocks"] == (len(prompt) - 1) // BS
    assert c["kv_transfer_failures"] == 0
    assert c["kv_transfer_bytes"] < sum(
        kvq.logical_nbytes(r) for r in records.values()) / 3.5
