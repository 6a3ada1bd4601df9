"""Pallas interpret-mode parity suite: every hand-written kernel diffed
against its XLA reference formulation on CPU.

The kernels (ops/pallas/) are the TPU-compiled fast path; the XLA
formulations are the always-available oracle. This suite pins them
together in tier-1 so a kernel edit can't silently diverge: odd shapes,
batch > 1, masked tails (context lengths mid-block), every quantized
weight form, and the end-to-end batcher greedy parity for the paged
pool kernel the decode chunks take where the shape allows.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.attention import attend_prefill
from distributed_llm_inferencing_tpu.ops.pallas import flash_attention
from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


# ---- quant_matmul: int4 dequant-GEMV kernel vs the XLA unpack ---------

def _q4_ref(x, p4, scale, chunks=1):
    from distributed_llm_inferencing_tpu.ops.quant import unpack_int4
    w = unpack_int4(np.asarray(p4), chunks).astype(np.float32)
    return np.asarray(x, np.float32) @ w * np.asarray(scale, np.float32)


@pytest.mark.parametrize("rows,din,dout", [
    (1, 64, 128),      # decode GEMV
    (4, 64, 192),      # batch > 1, dout off the 128 tile
    (8, 128, 384),     # tile boundary + ragged final block
    (3, 96, 160),      # odd-ish everything (din still even)
])
def test_q4_matmul_matches_xla_unpack(rows, din, dout):
    from distributed_llm_inferencing_tpu.ops.pallas.quant_matmul import (
        q4_matmul)
    from distributed_llm_inferencing_tpu.ops.quant import (
        quantize_weight_int4)
    rng = np.random.default_rng(rows * din)
    w = rng.normal(size=(din, dout)).astype(np.float32)
    leaf = quantize_weight_int4(jnp.asarray(w))
    x = rng.normal(size=(rows, din)).astype(np.float32)
    ref = _q4_ref(x, leaf["p4"], leaf["scale"])
    out = q4_matmul(jnp.asarray(x), leaf["p4"], leaf["scale"],
                    interpret=True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_q4_matmul_row_chunked_matches_xla_unpack():
    """The row-parallel (chunk-local packed) variant: single-device body
    must honor the chunked layout, matching the unpack reference."""
    from distributed_llm_inferencing_tpu.ops.pallas.quant_matmul import (
        q4_matmul_row)
    from distributed_llm_inferencing_tpu.ops.quant import (
        quantize_weight_int4, repack_int4_rows)
    rng = np.random.default_rng(7)
    din, dout, chunks = 128, 256, 2
    w = rng.normal(size=(din, dout)).astype(np.float32)
    leaf = repack_int4_rows(quantize_weight_int4(jnp.asarray(w)), chunks)
    x = rng.normal(size=(2, din)).astype(np.float32)
    ref = _q4_ref(x, leaf["p4"], leaf["scale"], chunks=chunks)
    out = q4_matmul_row(jnp.asarray(x), leaf["p4"], leaf["scale"],
                        interpret=True, chunks=chunks)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


# ---- flash_attention: odd shapes beyond test_pallas_attention's -------

@pytest.mark.parametrize("B,S,H,Hkv,hd", [
    (3, 40, 6, 2, 16),     # odd batch, S with no pow2 block fit
    (1, 24, 2, 1, 8),      # tiny head_dim (tiny-llama shape), MQA
])
def test_flash_prefill_odd_shapes(B, S, H, Hkv, hd):
    rng = np.random.default_rng(B * S)
    q, k, v = (_rand(rng, B, S, H, hd), _rand(rng, B, S, Hkv, hd),
               _rand(rng, B, S, Hkv, hd))
    ref = attend_prefill(q, k, v, backend="xla")
    out = flash_attention(q, k, v, block_q=16, block_kv=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---- paged_attend: the decode chunk's pool kernel vs the two-segment ---
# ---- attend over gathered K and V ---------------------------------------

# K and V planes of 8 or 16 heads of 128, with and without a window; then
# one plane of shared rows (a latent pool: hkv 1, no window), 128 and 640
# wide, the query narrower than the row as MLA's is; each with the side
# rows of a chunk of 1 and of 8 passes. Then heads that fill half or a
# quarter of a tile's sublanes (4: falcon-h1's 20 query heads over them, a
# group that is no power of two, and trinity's 32; 2), the 4 with every
# chunk size's side rows (4 rows at a chunk of one: half a tile). Last,
# flat rows (``hd`` a pair: a K head's width and a V head's; ``hkv`` the
# K/V heads side by side in a position's one row of each plane): 2 heads
# of 192 / 128 -> rows of 384 / 256 columns, mimo-v2.5's 4 -> 768 /
# 512 under 64 query heads, and 4 of 128 / 128 under 20
_PAGED_SHAPES = [
    (g, hkv, hd, window, side_rows)
    for g, hkv, hd, window in [
        (g, hkv, 128, window) for window in (None, 9)
        for g, hkv in ((1, 8), (4, 8), (1, 16), (4, 16))
    ] + [(4, 1, 128, None), (32, 1, 640, None)]
    for side_rows in (1, 8)
] + [(5, 4, 128, window, side_rows) for window in (None, 9)
     for side_rows in (1, 2, 4, 8)
] + [(8, 4, 128, None, 1), (8, 4, 128, 9, 2), (8, 4, 128, None, 4),
     (8, 4, 128, 9, 8), (3, 2, 128, None, 1), (3, 2, 128, 9, 8)
] + [(g, hkv, (192, 128), None, side_rows)
     for g, hkv in ((2, 2), (16, 4)) for side_rows in (1, 8)
     # ... and falcon-h1's as one device stores them since PR 49: K and V
     # rows both 4 x 128 = 512 wide, 5 query heads a K/V head (p @ V a
     # head at a time takes 5 rows of p), and a window
] + [(5, 4, (128, 128), window, side_rows)
     for window, side_rows in ((None, 1), (None, 8), (9, 8))]


@pytest.mark.parametrize("g,hkv,hd,window,side_rows", _PAGED_SHAPES)
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_paged_attend_matches_two_segment_attend(monkeypatch, dtype, g, hkv,
                                                 hd, window, side_rows):
    """The kernel, interpreted, against ops/attention.attend over (the
    gathered pool, the chunk's side rows): a plane of a stack taken by a
    traced index (a looped model's u * L + l) and by a constant one
    (layers held one by one), ragged lengths with a dead
    slot (length 0 and, apart from it, a slot that is not live), a length
    on a block boundary and one at the table's end, a window shorter
    than a context, every pass t of the side rows. Work items of four
    pages in steps of two and tail steps of one, so a slot's walk takes
    several items and ends on a short one, in steps of both widths.
    With one K/V head the plane is a latent pool's: its rows are K and V
    at once (handed in as both), zeros past the query's width, and the
    reference is attend with the rows' own columns as K and V. Flat rows
    (a model with layer kinds): K and V planes of one row a position,
    its heads side by side and V's narrower than K's, the query
    zero-expanded to K's row as the decode chunk expands it, a context
    of less than a page; the reference is attend over the same rows
    viewed as heads."""
    from distributed_llm_inferencing_tpu.models.transformer import (
        _flat_rows_q)
    from distributed_llm_inferencing_tpu.ops import attention
    from distributed_llm_inferencing_tpu.ops.pallas import paged_attention
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        gather_seq, head_rows)
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(g * 100 + hkv + side_rows)
    flat = isinstance(hd, tuple)
    shared = hkv == 1
    planes, nb, bs, mb = (2, 20, 4, 4) if shared else (3, 48, 4, 7)
    h = g * hkv
    if flat:   # the planes hold one "head": the row
        (hd, vd), heads, hkv = hd, hkv, 1
    qw = hd - 24 if shared else vd if flat else hd
    lens = np.asarray([0, 13, 3 if flat else 8, mb * bs, 5,
                       17][:4 if shared else 6], np.int32)
    live = np.asarray([0, 1, 1, 1, 0, 1][:len(lens)], bool)
    if shared:     # the empty slot first, then a dead one that holds rows
        lens, live = lens[[0, 2, 1, 3]], np.asarray([1, 0, 1, 1], bool)
    r = len(lens)

    def rows(*lead, v=False):
        if flat:
            return _rand(rng, *lead, 1, heads * (vd if v else hd)).astype(dt)
        x = _rand(rng, *lead, hkv, hd)
        return (x * (jnp.arange(hd) < qw)).astype(dt)
    k_planes = rows(planes, nb, bs)
    v_planes = k_planes if shared else rows(planes, nb, bs, v=True)
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))[:r * mb]
                     .reshape(r, mb).astype(np.int32))
    side_k = rows(r, side_rows)
    side_v = side_k if shared else rows(r, side_rows, v=True)
    q = _rand(rng, r, 1, h, hd if flat else qw).astype(dt)
    cl = jnp.asarray(lens)
    # (pages a tail step, a step, an item) = (1, 2, 4)
    n_planes = 1 if shared else 2
    page = bs * dt.itemsize * (heads * (hd + vd) if flat
                               else n_planes * hkv * hd)
    monkeypatch.setattr(paged_attention, "_TAIL_BYTES", page)
    monkeypatch.setattr(paged_attention, "_STEP_BYTES", 2 * page)
    monkeypatch.setattr(paged_attention, "_ITEM_BYTES", 4 * page)
    # every slot's state as one value, and (the wide latent rows) a
    # slot at a time
    monkeypatch.setattr(paged_attention, "_STATE_AT_ONCE", 64 * 1024)
    # an item's 4 pages started and awaited a page a loop iteration, or
    # (a shared plane, 4 and 16 heads) in groups of 3 + 1 and in bulk
    monkeypatch.setattr(paged_attention, "_LOOP_PAGES",
                        2 if shared or flat or hkv in (4, 16) else 4)
    monkeypatch.setattr(paged_attention, "_GROUP", 3)
    items = sum(
        -(-(-(-n // bs) - (max(n - window + 1, 0) // bs if window else 0))
           // 4) for n, a in zip(lens, live) if a)

    def both(plane, t):
        # (the walk inside the program: made eagerly, its dozen small
        # operations each compile, a second of every case)
        walk = paged_attention.pool_walk(
            cl, jnp.asarray(live), k_planes, mb, sliding_window=window,
            n_planes=n_planes, v_planes=v_planes)
        out = paged_attention.paged_attend(
            _flat_rows_q(q, heads, k_planes) if flat else q, k_planes,
            v_planes, plane, bt, cl, cl + t, walk,
            (side_k, side_v, t), sliding_window=window, scale=0.11,
            v_head_dim=vd if flat else None, interpret=True)
        pool_pos = jnp.broadcast_to(jnp.arange(mb * bs), (r, mb * bs))
        side_pos = cl[:, None] + jnp.arange(side_rows)[None, :]

        def seg(x, w):   # a segment's rows as attend takes them
            return head_rows(x, heads, w) if flat else x[..., :qw]
        ref = attention.attend(
            q, (seg(gather_seq(k_planes, bt, plane), hd), seg(side_k, hd)),
            (seg(gather_seq(v_planes, bt, plane), vd if flat else hd),
             seg(side_v, vd if flat else hd)),
            (cl + t)[:, None], (pool_pos, side_pos),
            (pool_pos < cl[:, None],
             jnp.broadcast_to(jnp.arange(side_rows) <= t, (r, side_rows))),
            sliding_window=window, scale=0.11)
        return out, ref, walk.count[0]

    tol = 2e-5 if dtype == "float32" else 1e-2
    # one program a case: the plane's index traced (a scanned stack) or,
    # for a shared plane with one side row, a constant of the trace
    # (layers held one by one)
    constant = shared and side_rows == 1
    run = jax.jit(both, static_argnums=0 if constant else ())
    for plane, t in ((planes - 1, 0), (planes - 1 if constant else 1,
                                       side_rows - 1)):
        out, ref, count = run(plane if constant else jnp.int32(plane),
                              jnp.int32(t))
        assert int(count) == items
        out, ref = (np.asarray(x, np.float32) for x in (out, ref))
        # a slot the walk leaves out reads its side rows alone: what the
        # chunk never emits; the live ones are the comparison
        np.testing.assert_allclose(out[live][..., :qw], ref[live],
                                   rtol=tol, atol=tol)
        assert np.isfinite(out).all() and not out[..., qw:].any()


def _serve(cfg, params, prompts, new=10):
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    b = ContinuousBatcher(cfg, params, seed=0, slots=4, num_blocks=64,
                          block_size=4, max_seq=64, prefill_chunk=4,
                          decode_chunk_cap=8, kv_host_mb=0)
    greedy = SamplingParams.greedy()
    reqs = [b.submit(p, max_new_tokens=new, sampling=greedy, seed=0)
            for p in prompts]
    while b.inflight():
        b.step()
    assert all(r.error is None for r in reqs)
    c = b.metrics.snapshot()["counters"]
    from distributed_llm_inferencing_tpu.utils import trace
    span = [s for s in trace.get_tracer().spans()
            if s.name == "batcher.decode_chunk"][-1]
    return ([r.tokens for r in reqs],
            c.get("batcher_pool_kernel_passes", 0),
            c["batcher_weight_passes"], span.attrs["pool_kernel"])


def test_paged_attend_supported_shapes():
    """Pools the kernel reads as they lie: K/V heads of whole lanes that
    fill a tile's 8 sublanes or divide them (falcon-h1's and trinity's
    4; 2; MQA's and a latent pool's 1), bf16 or float32."""
    from distributed_llm_inferencing_tpu.ops.pallas.paged_attention import (
        supported)
    for hkv in (1, 2, 4, 8, 16):
        assert supported(hkv, 128, jnp.bfloat16)
    assert supported(4, 256, jnp.float32) and supported(1, 640, jnp.bfloat16)
    # mimo-v2.5's flat rows: one row a position in each plane
    assert supported(1, 768, jnp.bfloat16) and supported(1, 512, jnp.bfloat16)
    assert not supported(3, 128, jnp.bfloat16)
    assert not supported(12, 128, jnp.bfloat16)
    assert not supported(4, 64, jnp.bfloat16)
    assert not supported(4, 128, jnp.int8)


# heads of the width and count the kernel reads as they lie (supported):
# one (8, 128) tile of K/V heads a position
_KERNEL_HEADS = dict(num_heads=8, num_kv_heads=8, head_dim=128)


@pytest.mark.parametrize("model,shape,kernel", [
    ("tiny-llama", dict(_KERNEL_HEADS, num_heads=16), True),   # G = 2
    ("tiny-llama", dict(_KERNEL_HEADS, sliding_window=6), True),
    ("tiny-ouro", dict(_KERNEL_HEADS, loop_steps=2), True),   # planes u * L + l
    ("tiny-llama", {}, False),                 # 4 heads of 8: not its shape
    # a latent plane, its 40-wide rows stored 128 wide; 4 layers held 1 by 1
    ("tiny-kanana", {}, True),
    ("tiny-afmoe", {}, False),                 # windows a layer, held 1 by 1
    # falcon-h1's heads: 20 over 4 (G = 5), half a tile of K/V heads a
    # position, beside the state layers' planes in the chunk's carry
    ("tiny-falcon-h1", dict(num_heads=20, num_kv_heads=4, head_dim=128),
     True),
    ("tiny-falcon-h1", {}, False),             # 2 heads of 24: not its shape
    # mimo-v2's full layers: flat rows, 2 x 24 -> 128 columns of K and 2
    # value heads of 128 -> 256 of V; the windowed layers keep their ring
    ("tiny-mimo-v2", dict(v_head_dim=128), True),
    ("tiny-mimo-v2", {}, False),               # values of 16: no whole lanes
])
def test_batcher_pool_kernel_where_the_shape_allows(monkeypatch, model,
                                                    shape, kernel):
    """The batcher with its kernel pin interpreted (on a one-device TPU
    it pins "pallas"): a dense, a looped and an MLA model (a latent
    pool, MoE layers held one by one) whose pool the kernel reads as it
    lies emit the XLA form's greedy tokens, every decode pass counted in
    ``batcher_pool_kernel_passes``; a pool of another shape and a
    windowed MoE model's keep the XLA form and count none. The in-loop
    gather is the XLA form compared with (the chip's: toy pools are
    otherwise pre-gathered)."""
    from distributed_llm_inferencing_tpu.models import transformer
    from distributed_llm_inferencing_tpu.runtime import batcher
    monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    cfg = get_config(model).replace(dtype="float32", attn_backend="xla",
                                    **shape)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (9, 5, 13)]
    monkeypatch.setattr(batcher, "_expert_backend",
                        lambda *a, **k: "pallas_interpret")
    # (one set of weights for both runs: drawing them is seconds here)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks, passes, weight_passes, attr = _serve(cfg, params, prompts)
    assert weight_passes > 0 and attr == int(kernel)
    assert passes == (weight_passes if kernel else 0)
    if kernel:
        monkeypatch.setattr(batcher, "_expert_backend",
                            lambda *a, **k: "xla")
        assert _serve(cfg, params, prompts)[:2] == (toks, 0)
