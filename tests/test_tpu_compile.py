"""Mosaic compiles of the Pallas kernels at mistral-7b's real shapes.

Interpret mode (every other Pallas test in the suite) cannot see what the
TPU compiler refuses: a block off the (8, 128) tiling, more scoped VMEM
than a kernel may have. libtpu compiles for a chip that is described and
not attached, so these tests lower each kernel for one device of a
``v5e:2x2`` topology — shapes only, nothing runs — and fail with whatever
the chip's compiler would raise.

The topology is described inside a module-scoped fixture (never at
import time: one process at a time may load libtpu, and every xdist
worker imports every test file), and every compile happens in the test's
own process. Keep these tests in this one file.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_llm_inferencing_tpu.models.registry import get_config

# mistral-7b (models/registry.py): the widths chip_smoke.py serves
CFG = get_config("mistral-7b")
H, HKV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
D, I, WINDOW = CFG.hidden_size, CFG.intermediate_size, CFG.sliding_window
# the smoke's serving shape (chip_smoke.py LOAD): slots, block size, pool
# blocks (+1 reserved dummy) and block-table width max_seq / block_size
SLOTS, BS, NB, MB = 8, 16, 1025, 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_on_chip(topo):
    """compile_on_chip(fn, (shape, dtype) pytree..., kernel=True) -> the
    compiled program. Arguments are ShapeDtypeStructs placed on one
    described device (``donate``: the positions of those the program may
    write in place); the persistent compile cache is off around the
    compile (an entry written for a described chip cannot be read back
    without one, and warns). ``kernel`` says whether the program has to
    hold a Pallas kernel (a ``tpu_custom_call``) or is plain XLA."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def struct(leaf):
        shape, dtype = leaf
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(fn, *args, kernel=True, donate=()):
        args = [jax.tree.map(struct, a,
                             is_leaf=lambda x: isinstance(x, tuple))
                for a in args]
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            compiled = jax.jit(fn, donate_argnums=donate).lower(
                *args).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
        assert ("tpu_custom_call" in compiled.as_text()) == kernel, (
            "kernel missing from the program" if kernel
            else "a kernel in a program that should be plain XLA")
        return compiled

    return run


# prefill: the batcher's tail buckets (1..512 blocks of 16 tokens) give
# power-of-two lengths; a short one, the prefill-chunk length and a long
# one cover the block-picking branches
@pytest.mark.parametrize("batch,seq", [(1, 16), (1, 512), (4, 512),
                                       (1, 2048)])
def test_flash_attention_compiles(compile_on_chip, batch, seq):
    from distributed_llm_inferencing_tpu.ops.pallas import flash_attention
    compile_on_chip(
        functools.partial(flash_attention, sliding_window=WINDOW),
        ((batch, seq, H, HD), BF16), ((batch, seq, HKV, HD), BF16),
        ((batch, seq, HKV, HD), BF16))


@pytest.mark.parametrize("cache_len", [2048, 8192])
def test_flash_decode_compiles(compile_on_chip, cache_len):
    from distributed_llm_inferencing_tpu.ops.pallas import flash_decode
    compile_on_chip(
        functools.partial(flash_decode, sliding_window=WINDOW),
        ((SLOTS, 1, H, HD), BF16), ((SLOTS, cache_len, HKV, HD), BF16),
        ((SLOTS, cache_len, HKV, HD), BF16), ((SLOTS,), jnp.int32))


def test_flash_kernels_compile_with_alibi(compile_on_chip):
    """The ALiBi slopes ride SMEM beside the lengths; same tiling rule."""
    from distributed_llm_inferencing_tpu.ops.attention import alibi_slopes
    from distributed_llm_inferencing_tpu.ops.pallas import (
        flash_attention, flash_decode)
    heads, hd = 32, 64    # falcon-rw-1b's attention shape
    compile_on_chip(
        lambda q, k, v: flash_attention(q, k, v, alibi=alibi_slopes(heads)),
        *[((1, 512, heads, hd), BF16)] * 3)
    compile_on_chip(
        lambda q, k, v, n: flash_decode(q, k, v, n,
                                        alibi=alibi_slopes(heads)),
        ((SLOTS, 1, heads, hd), BF16), ((SLOTS, 1024, heads, hd), BF16),
        ((SLOTS, 1024, heads, hd), BF16), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("side_rows", [1, 2, 4, 8])
def test_paged_attend_compiles_over_a_latent_plane(compile_on_chip,
                                                   side_rows):
    """ops/pallas/paged_attention.py at the kanana cell's decode shape:
    64 slots of 32 heads over ONE plane of shared 640-wide rows (K and V
    at once), 10,241 blocks of 16, block tables of 160 columns, the
    query 576 wide, and the side rows of each of the cell's chunk sizes.
    A chunk of one pass hands the kernel ONE side row a slot: Mosaic
    refuses a bf16 product with a one-row operand ('vector.broadcast'
    ... same element type), which no interpreted test shows (PERF.md
    section 6, PR 42). The call asks for its own VMEM (21 MiB: the
    whole-block q, output and softmax state)."""
    from distributed_llm_inferencing_tpu.ops.pallas import paged_attention
    slots, heads, mb = 64, 32, 160

    def attend(q, rows, bt, cl, side, plane, t):
        walk = paged_attention.pool_walk(cl, cl > 0, rows, mb, n_planes=1)
        return paged_attention.paged_attend(
            q, rows, rows, plane, bt, cl, cl + t, walk, (side, side, t),
            scale=192 ** -0.5)

    compile_on_chip(
        attend, ((slots, 1, heads, 576), BF16),
        ((7, 10241, BS, 1, 640), BF16), ((slots, mb), jnp.int32),
        ((slots,), jnp.int32), ((slots, side_rows, 1, 640), BF16),
        ((), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("side_rows", [1, 2, 4, 8])
@pytest.mark.parametrize("rows", ["tiles", "flat"])
def test_paged_attend_compiles_over_four_head_planes(compile_on_chip,
                                                     side_rows, rows):
    """ops/pallas/paged_attention.py at the falcon-h1 cell's decode
    shape: 64 slots of 20 query heads (2.5 tiles of sublanes, a group of
    5) over K and V planes of 4 heads, block tables of 64 columns, and
    the side rows of each of the cell's chunk sizes (a chunk of one
    pass: 4 rows, half a tile, or one flat row).

    ``tiles``: the heads an axis, `[6, 4097, 16, 4, 128]` (the cell's
    pool until PR 49; a mesh's shard never reaches the kernel). XLA lays
    4-head planes out in (4, 128) tiles; two of them hold the bytes of
    one (8, 128) tile in the same order, so the flat view the kernel
    reads, `[6, 4097, 64, 128]`, must be a `bitcast` of the stored plane
    and nothing plane-sized may be copied (PERF.md section 6, PR 43).

    ``flat``: a position's heads side by side, `[6, 4097, 16, 1, 512]`
    (ops/paged_kvcache.heads_in_rows: the cell's pool since PR 49), q
    zero-expanded to a row of 512, K and V rows the same width, `p @ V`
    a K/V head's 5 query heads at a time: Mosaic takes the 5-row slices
    of p, and no plane is copied."""
    from distributed_llm_inferencing_tpu.models.transformer import (
        _flat_rows_q)
    from distributed_llm_inferencing_tpu.ops.pallas import paged_attention
    slots, heads, hkv, mb = 64, 20, 4, 64
    flat = rows == "flat"
    row = (1, hkv * HD) if flat else (hkv, HD)
    planes = (6, 4097, BS) + row
    assert paged_attention.supported(*row, BF16)
    assert paged_attention._pages(BS, *row, 2, mb) == (16, 32, 32)

    def attend(q, k, v, bt, cl, side_k, side_v, plane, t):
        walk = paged_attention.pool_walk(cl, cl > 0, k, mb, v_planes=v)
        if flat:
            return paged_attention.paged_attend(
                _flat_rows_q(q, hkv, k), k, v, plane, bt, cl, cl + t, walk,
                (side_k, side_v, t), scale=HD ** -0.5, v_head_dim=HD)
        return paged_attention.paged_attend(
            q, k, v, plane, bt, cl, cl + t, walk, (side_k, side_v, t))

    text = compile_on_chip(
        attend, ((slots, 1, heads, HD), BF16), (planes, BF16),
        (planes, BF16), ((slots, mb), jnp.int32), ((slots,), jnp.int32),
        ((slots, side_rows) + row, BF16),
        ((slots, side_rows) + row, BF16), ((), jnp.int32),
        ((), jnp.int32)).as_text()
    assert "paged_pool_attend" in text
    if not flat:
        view = [ln for ln in text.splitlines()
                if re.search(r"= bf16\[6,4097,64,128\]", ln)]
        assert len(view) == 2 and all(" bitcast(" in ln for ln in view), view
    assert not re.findall(r"= bf16\[6,4097,16,(?:4,128|1,512)\]\S* "
                          r"(?!parameter|bitcast)\S+\(", text)


def test_ssm_state_step_writes_the_plane_in_place(compile_on_chip):
    """ops/pallas/ssm_step.py at falcon-h1-34b's cell: 6 layers, 64
    slots and the dummy row, 32 heads of 128 x 256 float32 in 2 groups,
    under a layer scan that carries the donated plane: the kernel
    compiles, the 1.6 GB plane is aliased to the result and no copy of
    it (nor of a layer of it) is made."""
    from distributed_llm_inferencing_tpu.ops.pallas import ssm_step
    layers, slots, heads, p, n, g = 6, 64, 32, 128, 256, 2
    assert ssm_step.supported(heads, g, p, n, jnp.float32)
    assert not ssm_step.supported(4, 2, 16, 16, jnp.float32)   # the toy's

    def passes(plane, decay, dtx, b, c):
        def layer(carry, li):
            plane, acc = carry
            plane, y = ssm_step.ssm_step(plane, li, decay, dtx, b, c)
            return (plane, acc + y), None
        return jax.lax.scan(
            layer, (plane, jnp.zeros((slots, heads, p), jnp.float32)),
            jnp.arange(layers))[0]
    f32 = jnp.float32
    compiled = compile_on_chip(
        passes, ((layers, slots + 1, heads, p, n), f32),
        ((slots, heads), f32), ((slots, heads, p), f32),
        ((slots, g, n), f32), ((slots, g, n), f32), donate=(0,))
    mem = compiled.memory_analysis()
    plane_bytes = layers * (slots + 1) * heads * p * n * 4
    assert mem.alias_size_in_bytes >= plane_bytes
    assert mem.temp_size_in_bytes < 2 ** 20
    text = compiled.as_text()
    assert "ssm_state_step" in text
    assert not [ln for ln in text.splitlines()
                if (" copy(" in ln or "copy-start(" in ln)
                and ("f32[6,65,2,16,128,256]" in ln
                     or "f32[6,65,32,128,256]" in ln)]


# up/gate (4096 x 14336) and down (14336 x 4096); f32 activations take
# the sign-extending kernel variant
@pytest.mark.parametrize("din,dout,act", [(D, I, BF16), (I, D, BF16),
                                          (I, D, jnp.float32)])
def test_q4_matmul_compiles(compile_on_chip, din, dout, act):
    from distributed_llm_inferencing_tpu.ops.pallas.quant_matmul import (
        q4_matmul)
    compile_on_chip(
        q4_matmul, ((SLOTS, din), act), ((din // 2, dout), jnp.uint8),
        ((dout,), jnp.float32))


def test_decode_chunk_attention_copies_no_kv(compile_on_chip):
    """The XLA decode attention of the benchmark's cells (16 slots, the
    gathered pool of 2048 positions + the chunk's 8-row side buffer, 32
    query over 8 kv heads of 128, bf16) reads K and V as stored. Before
    PR 25 this program broadcast both to f32[16,2056,8,4,128] through HBM
    (674 MB of temporaries, 87 % of the decode pass on the chip: PERF.md
    section 6); it fails if anything K-sized comes back in f32."""
    from distributed_llm_inferencing_tpu.ops.attention import attend
    slots, pool, side = 16, 2048, 8

    def decode_attention(q, kp, vp, sk, sv, cl, t):
        pool_pos = jnp.broadcast_to(jnp.arange(pool, dtype=jnp.int32),
                                    (slots, pool))
        side_pos = cl[:, None] + jnp.arange(side, dtype=jnp.int32)[None]
        side_valid = jnp.broadcast_to(
            jnp.arange(side, dtype=jnp.int32)[None] <= t, (slots, side))
        return attend(q, (kp, sk), (vp, sv), (cl + t)[:, None],
                      (pool_pos, side_pos),
                      (pool_pos < cl[:, None], side_valid),
                      sliding_window=WINDOW)

    compiled = compile_on_chip(
        decode_attention, ((slots, 1, H, HD), BF16),
        ((slots, pool, HKV, HD), BF16), ((slots, pool, HKV, HD), BF16),
        ((slots, side, HKV, HD), BF16), ((slots, side, HKV, HD), BF16),
        ((slots,), jnp.int32), ((), jnp.int32), kernel=False)
    kv_elems = slots * (pool + side) * HKV * HD
    wide = [m for m in set(re.findall(r"f32\[([\d,]+)\]",
                                      compiled.as_text()))
            if math.prod(map(int, m.split(","))) >= kv_elems]
    assert not wide, f"K- or V-sized f32 arrays in the program: {wide}"
    assert compiled.memory_analysis().temp_size_in_bytes <= 64 * 2 ** 20



# kanana-2-30b-a3b (models/registry.py) at the benchmark cell's decode
# shape: 64 slots, 2560 pool positions, one shared 576-wide latent row
KANANA = get_config("kanana-2-30b-a3b")


def test_latent_decode_attention_reads_the_rows_as_stored(compile_on_chip):
    """The absorbed MLA decode attention of the kanana cell: 32 query
    heads over ONE kv head whose rows (576 bf16: rope key + latent) are
    K and V at once, gathered pool + the chunk's side buffer. Nothing
    row-sized may come back in f32, and no 512-wide copy of the rows
    (the value slice) may be cut: the context is taken over the whole
    row and its first 64 columns dropped after."""
    from distributed_llm_inferencing_tpu.ops.attention import attend
    slots, pool, side, heads = 64, 2560, 8, KANANA.num_heads
    width = KANANA.kv_lora_rank + KANANA.qk_rope_head_dim
    assert width == 576

    def decode_attention(q_eff, rows, side_rows, cl, t):
        pool_pos = jnp.broadcast_to(jnp.arange(pool, dtype=jnp.int32),
                                    (slots, pool))
        side_pos = cl[:, None] + jnp.arange(side, dtype=jnp.int32)[None]
        side_valid = jnp.broadcast_to(
            jnp.arange(side, dtype=jnp.int32)[None] <= t, (slots, side))
        ctx = attend(q_eff, (rows, side_rows), (rows, side_rows),
                     (cl + t)[:, None], (pool_pos, side_pos),
                     (pool_pos < cl[:, None], side_valid),
                     scale=KANANA.qk_head_dim ** -0.5)
        return ctx[..., KANANA.qk_rope_head_dim:]

    compiled = compile_on_chip(
        decode_attention, ((slots, 1, heads, width), BF16),
        ((slots, pool, 1, width), BF16), ((slots, side, 1, width), BF16),
        ((slots,), jnp.int32), ((), jnp.int32), kernel=False)
    text = compiled.as_text()
    row_elems = slots * pool * width
    wide = [m for m in set(re.findall(r"f32\[([\d,]+)\]", text))
            if math.prod(map(int, m.split(","))) >= row_elems]
    assert not wide, f"row-sized f32 arrays in the program: {wide}"
    copies = [m for m in set(re.findall(r"bf16\[([\d,]+)\]", text))
              if m.endswith(f",{KANANA.kv_lora_rank}")
              and math.prod(map(int, m.split(","))) >= row_elems // 2]
    assert not copies, f"a value-slice copy of the rows: {copies}"
    assert compiled.memory_analysis().temp_size_in_bytes <= 96 * 2 ** 20


@pytest.mark.parametrize("model,tokens,kernel", [
    ("kanana-2-30b-a3b", 64, "expert_stream_matmul"),
    ("trinity-mini", 64, "expert_stream_matmul"),
    ("kanana-2-30b-a3b", 128, "ragged-dot"),     # the smallest admit program
    ("kanana-2-30b-a3b", 2048, "ragged-dot"),
    ("trinity-mini", 1024, "ragged-dot")])
def test_expert_dispatch_is_a_grouped_matmul(compile_on_chip, model, tokens,
                                             kernel):
    """The one expert dispatch as the batcher pins it for a one-device
    TPU program (``expert_matmul: pallas``), at the kanana and trinity
    cells' decode pass (64 tokens: 384 / 512 rows over 128 experts) and
    at admit programs' sizes: three grouped matmuls that take the
    experts' weights as they are, no [N, k, E, C] one-hots and no float
    copy of the weights (a dense or capacity form would need gigabytes
    of temporaries here). At decode size they are the streaming kernel
    of ops/pallas/grouped_matmul.py at both cells' widths (16-row tiles
    on the (8, 128) tiling, two 3-4 MiB weight blocks + rows + output
    inside the scoped VMEM it asks for); from the smallest admit program
    up, ``lax.ragged_dot``'s own custom calls, as before PR 32."""
    from distributed_llm_inferencing_tpu.models import transformer
    cfg = get_config(model).replace(moe_shared_experts=0,
                                    expert_matmul="pallas")
    E, d, i = cfg.num_experts, cfg.hidden_size, cfg.expert_intermediate_size
    lp = {"router": {"w": ((d, E), BF16), "bias": ((E,), jnp.float32)},
          "experts": {"gate": {"w": ((E, d, i), BF16)},
                      "up": {"w": ((E, d, i), BF16)},
                      "down": {"w": ((E, i, d), BF16)}}}
    compiled = compile_on_chip(
        lambda x, lp, valid: transformer._moe(x, lp, cfg, valid=valid),
        ((tokens, d), BF16), lp, ((tokens,), jnp.bool_))
    calls = re.findall(r"%(\S+?)(?:\.\d+)? = \S+ custom-call\([^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"",
                       compiled.as_text())
    assert sorted(calls) == [kernel] * 3 or sorted(calls) == [
        kernel + "-none"] * 3, calls
    rows = tokens * cfg.num_experts_per_tok
    # rows in and out of the experts, in bf16 and once in f32 for the
    # combine: nowhere near an expert-weights-sized temporary
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 16 * rows * d + 32 * 2 ** 20


def _serving_shapes(cfg, bs, blocks, held=False, slots=0):
    """(shape, dtype) trees of a model's parameters and of its pool of
    ``blocks`` + 1 blocks of ``bs`` (the last the reserved one), as
    [a list of planes, the state layers' planes of ``slots`` + 1 rows by
    field name: empty without cfg.ssm]. ``held``: MoE layers a list of
    per-layer trees, as the batcher holds them (``_unstack_layers``)."""
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        init_paged_cache)

    def shapes(tree):
        return jax.tree.map(lambda s: (s.shape, s.dtype), tree)

    params = shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    if held:   # (a model with layer kinds: each kind's stack)
        for name in ("layers", "layers_full"):
            if name in params:
                n = jax.tree.leaves(
                    params[name],
                    is_leaf=lambda x: isinstance(x, tuple))[0][0][0]
                one = jax.tree.map(lambda s: (s[0][1:], s[1]), params[name],
                                   is_leaf=lambda x: isinstance(x, tuple))
                params[name] = [one] * n
    cache = jax.eval_shape(
        lambda: init_paged_cache(cfg, blocks + 1, bs, slots=slots))
    state = {} if cfg.ssm is None else {"ssm": cache.ssm, "conv": cache.conv}
    if cfg.swa is not None:
        state = {"ring_k": cache.ring_k, "ring_v": cache.ring_v}
    # (a list: compile_on_chip takes every tuple for a (shape, dtype) leaf)
    return params, [shapes(list(cache.planes())), shapes(state)]


def _decode_chunk(compile_on_chip, cfg, k, slots, bs, blocks, mb,
                  kernel=True, held=False, donate=False):
    """``paged_decode_chunk`` compiled for the described chip:
    ``k`` passes over ``slots`` slots, a pool of ``blocks`` + 1 blocks of
    ``bs`` (the last the reserved one), ``mb`` block-table columns.
    ``held`` as in _serving_shapes; ``donate``: the pool is donated, as
    the batcher's ``_decode_jit`` donates it."""
    from distributed_llm_inferencing_tpu.models import transformer
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache)
    params, pool = _serving_shapes(cfg, bs, blocks, held, slots)

    def chunk(params, pool, tokens, bt, ints, floats, ds):
        cl, seeds, steps, tks, budget, eos = ints
        return transformer.paged_decode_chunk(
            params, cfg, k, tokens, PagedKVCache(*pool[0], **pool[1]), bt,
            cl, seeds, steps, floats[0], tks, floats[1], ds, budget, eos,
            blocks)

    return compile_on_chip(
        chunk, params, pool, ((slots,), jnp.int32),
        ((slots, mb), jnp.int32), ((6, slots), jnp.int32),
        ((2, slots), jnp.float32), ((slots,), jnp.bool_),
        kernel=kernel, donate=(1,) if donate else ())


def _decode_chunk_text(*args, **kw):
    return _decode_chunk(*args, **kw).as_text()


def _admit_text(compile_on_chip, cfg, t, pb, wave, bs, blocks, kernel=True,
                held=False, slots=0):
    """_decode_chunk_text's twin for an admit program: the text of
    ``paged_prefill_tail`` over a wave of ``wave`` tails of ``t`` tokens,
    ``pb`` prefix blocks a row, the pool donated as ``_admit_jit``
    donates it."""
    from distributed_llm_inferencing_tpu.models import transformer
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache)
    params, pool = _serving_shapes(cfg, bs, blocks, held, slots)

    def admit(params, pool, tokens, tail_blocks, prefix_blocks, lens):
        return transformer.paged_prefill_tail(
            params, cfg, tokens, lens[0], tail_blocks, prefix_blocks,
            lens[1], PagedKVCache(*pool[0], **pool[1]),
            slots=lens[2] if pool[1] else None)

    return compile_on_chip(
        admit, params, pool, ((wave, t), jnp.int32),
        ((wave, t // bs), jnp.int32), ((wave, pb), jnp.int32),
        ((2 + bool(pool[1]), wave), jnp.int32), kernel=kernel,
        donate=(1,)).as_text()


def test_decode_chunk_sorts_nothing_vocabulary_sized(compile_on_chip):
    """A decode chunk of the kanana cell (64 slots over 128,256 logits,
    the dense layer and one expert layer, the latent pool) samples
    without sorting the vocabulary: `sample_batch`'s full tier, which
    every `top_k` 0 row takes, finds its thresholds by a search
    (`ops/sampling.py: nucleus_thresholds`). Before PR 28 this program
    held `sort(f32[64,128256])`, a third of the pass on the chip
    (PERF.md section 6). The expert dispatch's sort of its 384 (token,
    choice) pairs stays."""
    cfg = KANANA.replace(num_layers=2, attn_backend="xla",
                         mla_latent_cache=True)
    text = _decode_chunk_text(compile_on_chip, cfg, k=1, slots=64, bs=16,
                              blocks=640, mb=160)
    sorts = re.findall(r"= \(?([^=\n]*?)\)? sort\(", text)
    assert sorts, "the expert dispatch's sort should be in the program"
    wide = [s for s in sorts
            if any(int(d) >= cfg.vocab_size
                   for dims in re.findall(r"\[([\d,]+)\]", s)
                   for d in dims.split(","))]
    assert not wide, f"a sort over the vocabulary: {wide}"


def test_decode_chunk_copies_no_layers_pool_out_of_the_stack(compile_on_chip):
    """The decode-sat cell's decode chunk (mistral-7b, int8 weights, 16
    slots, 8 passes, the pool of 1025 blocks gathered in the loop): under
    the layer scan each rung's branch gathers by (layer, block) from the
    stacked pool where it lies. While the scan handed the layer's slice
    to the lax.switch, the program held two `dynamic-slice` fusions with
    a result of `bf16[1025,16,8,128]`, a copy of every layer's K and V
    pool on every pass: 3 ms of a 17.9 ms pass on the chip (PERF.md
    section 6, PR 36). tests/test_decode_gather.py holds the same of the
    jaxpr at toy widths."""
    text = _decode_chunk_text(
        compile_on_chip, CFG.replace(quant="int8", attn_backend="xla"),
        k=8, slots=16, bs=BS, blocks=NB - 1, mb=MB, kernel=False)
    assert "conditional(" in text, "the rungs' lax.switch should be there"
    plane = f"bf16[{NB},{BS},{HKV},{HD}]"
    made = re.findall(rf"%(\S+) = {re.escape(plane)}\S* (?!parameter|"
                      rf"get-tuple-element)(\S+?)\(", text)
    assert not made, f"one layer's plane is materialized: {made[:4]}"


@functools.lru_cache(maxsize=None)
def _pool_sized():
    """scripts/compile_serving_programs.py's ``pool_sized``, whose count
    its report prints for every program: (name, operation) of the
    instructions that yield a pool-sized or plane-sized array."""
    import importlib.util
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "compile_serving_programs", pathlib.Path(__file__).parent.parent
        / "scripts" / "compile_serving_programs.py")
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    return script.pool_sized


# model -> (config as the batcher pins it on a one-device TPU, slots,
# block size, pool blocks, block-table columns, an admit program's
# (tail, prefix blocks, wave), the plane copies its admit program and
# its decode chunk may keep)
def _cells():
    pins = dict(attn_backend="xla", expert_matmul="pallas",
                pool_kernel="pallas")
    return {
        # benchmarks/chip/configs/mistral-7b-int8.json
        "mistral": (CFG.replace(quant="int8", **pins),
                    16, BS, NB - 1, MB, (512, 0, 2), (0, 0)),
        # .../ouro-2.6b.json: 192 planes, the widest admit wave
        "ouro": (get_config("ouro-2.6b").replace(**pins),
                 8, 16, 320, 40, (256, 0, 8), (0, 0)),
        # .../kanana-2-30b-a3b-l7.json: the latent pool
        "kanana": (KANANA.replace(num_layers=7, mla_latent_cache=True,
                                  **pins),
                   64, 16, 10240, 160, (128, 0, 1), (0, 0)),
        # .../trinity-mini-l5.json. Its 4 K/V heads of 128 lie side by
        # side in ONE row of 512 (ops/paged_kvcache.heads_in_rows): the
        # planes arrive in (8, 128) tiles of (positions, columns), which
        # the wave's write of whole blocks and the chunk's gather take
        # where they lie. With the heads an axis they arrived in
        # (4, 128) tiles and XLA re-tiled either plane once a chunk of 8
        # and, in a wave, there and back: 1.007 GB a plane a copy, four
        # an admit program, two a chunk, until PR 49
        "trinity": (get_config("trinity-mini").replace(
            num_layers=5, dense_prefix_layers=1,
            attn_windows=(2048,) * 4 + (None,),
            rope_layers=(1, 1, 1, 1, 0), **pins),
            64, 16, 12288, 576, (512, 128, 1), (0, 0)),
        # .../falcon-h1-34b-l6.json: 20 query heads over 4 K/V heads,
        # the state planes of 65 rows beside the pool. Flat rows of 512
        # as trinity's (its admit programs kept trinity's four copies
        # until PR 49); its decode chunk reads them by the kernel, q
        # zero-expanded to a row, p @ V a K/V head's 5 query heads at a
        # time
        "falcon-h1": (get_config("falcon-h1-34b").replace(
            num_layers=6, **pins), 64, 16, 4096, 64, (512, 1, 8), (0, 0)),
    }


@pytest.mark.parametrize("model,program", [
    (model, program)
    for model in ("mistral", "kanana", "trinity", "ouro", "falcon-h1")
    for program in ("admit", "decode-chunk-8")
    # a looped model's wave writes a step's tails behind that step, a
    # block at a time (write_blocks(first_plane=)): updates in place,
    # not the one scatter a plane this test counts
    if (model, program) != ("ouro", "admit")])
def test_no_serving_program_copies_the_pool(compile_on_chip, model, program):
    """The cells' admit programs and decode chunks of 8 passes read the
    stacked pool where it lies, by (layer, block), and write it once, in
    place: in the program text nothing but the scatter that writes a
    donated plane yields an array with as many elements as the pool's
    plane or as one layer of it. Before PR 38 kanana's chunk held
    `fusion.1095` (the pool sliced into its layers), `copy.956`-`962`
    (each re-laid out) and `copy.922` / `copy.927` (the whole pool, to
    the scatter's layout and back): 19 ms a chunk on the chip; every
    admit program sliced the pool into layers, stacked the layers'
    outputs into a fresh buffer and copied that into the donated one
    (mistral: `copy.123`, `copy.124`). PERF.md section 6, PR 38.

    Since PR 40 the decode chunks of a scanned stack whose pool the
    Pallas kernel reads as it lies (mistral, Ouro) hold it
    (`paged_pool_attend`, one call in the scanned layer body) in place of the
    ladder's `conditional`, and with it nothing of a rung's K or V size,
    `bf16[slots, rung, Hkv, 128]`: the gather's copy that the pass wrote
    and read back (20.4 of Ouro's 56 ms pass). Ouro's chunk's transient
    falls from 1.23 GiB to 1.13 by those copies and no further: the rest
    is `copy.111`-`113`, the q, k and v weights `bf16[48,2048,2048]`
    re-laid out once a program (ROADMAP S10). Since PR 42 kanana's chunk
    holds it too, seven call sites (the dense layer's scan of one and six
    layers held one by one) over the latent plane as it is stored,
    `bf16[7,10241,16,1,640]`: no `bf16[64,2560,1,640]` (or 576) of
    gathered rows, no copy of the plane. Since PR 43 falcon-h1's chunk
    holds it, one call in the scanned layer body over planes of 4 K/V
    heads (the state planes are the carry's, written in place by
    `ssm_state_step`): until PR 49 `bf16[6,4097,16,4,128]` in (4, 128)
    tiles, whose flat view was a bitcast, since then flat rows,
    `bf16[6,4097,16,1,512]` (ops/paged_kvcache.heads_in_rows), read
    under q zero-expanded to a row: no `bf16[64,768,4,128]` or
    `bf16[64,768,1,512]` of a rung. trinity's chunk and every admit
    program have no such call.

    Since PR 49 no cell's program keeps a copy of a plane: trinity's and
    falcon-h1's 4-head planes were re-tiled whole around the wave's
    write (four `copy` an admit program) and ahead of trinity's chunk
    of 8 (two), 1.007 GB and 0.403 GB a copy; a position's heads side
    by side in one row leave one `fusion(scatter)` a plane."""
    cfg, slots, bs, blocks, mb, (t, pb, wave), kept = _cells()[model]
    held = cfg.is_moe
    kept = kept[program != "admit"]
    pool_kernel = program != "admit" and model != "trinity"
    if program == "admit":
        text = _admit_text(compile_on_chip, cfg, t, pb, wave, bs, blocks,
                           kernel=held, held=held, slots=slots)
    else:
        chunk = _decode_chunk(compile_on_chip, cfg, 8, slots, bs, blocks,
                              mb, kernel=held or pool_kernel, held=held,
                              donate=True)
        text = chunk.as_text()
    assert ("paged_pool_attend" in text) == pool_kernel
    _, (pool, _) = _serving_shapes(cfg, bs, blocks)
    if pool_kernel:
        from distributed_llm_inferencing_tpu.models.transformer import (
            _pool_ladder)
        switches = [ln for ln in text.splitlines()
                    if " conditional(" in ln and "/sample/" not in ln]
        assert not switches, f"the ladder's switch is built: {switches[:2]}"
        rungs = "|".join(str(m * bs) for m in _pool_ladder(mb))
        heads, width = ((1, r"\d+") if cfg.mla_latent_cache
                        else (cfg.num_kv_heads, cfg.head_dim))
        if model == "falcon-h1":   # a position's heads in one row
            assert pool[0][0] == (6, 4097, 16, 1, 512), pool
            heads, width = r"(?:1|4)", r"(?:128|512)"
        made = re.findall(rf"= (bf16\[{slots},(?:{rungs}),"
                          rf"{heads},{width}\])", text)
        assert not made, f"a rung of K or V is materialized: {made[:4]}"
        if model == "ouro":
            assert chunk.memory_analysis().temp_size_in_bytes \
                < 1.15 * 2 ** 30
    made = _pool_sized()(text, [jax.ShapeDtypeStruct(*p) for p in pool])
    writes = [m for m in made if m[1] in ("fusion(scatter)", "scatter")]
    assert len(writes) == len(pool), f"one write a plane: {made}"
    rest = [m for m in made if m not in writes]
    assert [op for _, op in rest] == ["copy"] * kept, (
        f"the pool, or a layer of it, is materialized: {rest}")


@pytest.mark.parametrize("program", ["admit", "decode-chunk-8"])
def test_mimo_programs_fit_the_chip_and_copy_neither_cache(compile_on_chip,
                                                           program):
    """benchmarks/chip/configs/mimo-v2.5-l7.json: 7 layers of two kinds
    held one by one, 16 of 256 experts, 64 slots, the full layers' pool of
    20,480 blocks (a position's 4 heads in one row: 768 and 512 columns)
    and the windowed layers' rings of 65 rows (1536 and 1024). The widest
    admit wave the byte bounds let through (2 rows of 2048) and the chunk
    of 8 passes fit the chip's 16 GB with their arguments, and write pool
    and ring once each, in place: nothing else in the program yields an
    array the size of a plane of either or of one layer of it (with the
    heads as an axis of 4 the admit programs held four pool-sized copies,
    PERF.md section 6, PR 45). The chunk's experts take the streaming
    kernel, a wave's lax.ragged_dot. The chunk's two full layers read the
    pool by the paged kernel, its flat rows as they lie (Mosaic takes the
    kernel at 64 query heads over rows of 768 and 512 columns); no copy
    of every slot's gathered block table is made beside the pool, so the
    chunk's transient stays under what the gather's took (1.44 GiB; PR
    46's reads 0.97); an admit program holds no such kernel."""
    cfg = get_config("mimo-v2.5").replace(
        num_layers=7, vocab_size=19072, experts_held=(0, 16),
        swa={"pattern": (0, 1, 1, 1, 1, 1, 0), "num_kv_heads": 8,
             "rope_theta": 1e4, "sinks": True},
        attn_backend="xla", expert_matmul="pallas", pool_kernel="pallas")
    slots, bs, blocks, mb = 64, 16, 20480, 320
    if program == "admit":
        from distributed_llm_inferencing_tpu.models import transformer
        from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
            PagedKVCache)
        params, pool = _serving_shapes(cfg, bs, blocks, True, slots)
        t, pb, wave = 2048, 1, 2

        def admit(params, pool, tokens, tail_blocks, prefix_blocks, lens):
            return transformer.paged_prefill_tail(
                params, cfg, tokens, lens[0], tail_blocks, prefix_blocks,
                lens[1], PagedKVCache(*pool[0], **pool[1]), slots=lens[2])
        compiled = compile_on_chip(
            admit, params, pool, ((wave, t), jnp.int32),
            ((wave, t // bs), jnp.int32), ((wave, pb), jnp.int32),
            ((3, wave), jnp.int32), kernel=True, donate=(1,))
    else:
        compiled = _decode_chunk(compile_on_chip, cfg, 8, slots, bs, blocks,
                                 mb, kernel=True, held=True, donate=True)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    assert ("expert_stream_matmul" in text) == (program != "admit")
    assert ("paged_pool_attend" in text) == (program != "admit")
    if program != "admit":
        gathered = re.findall(
            rf"= (bf16\[(?:{slots},{mb * bs}|{slots * mb},{bs}),"
            r"(?:1,)?(?:768|512)\])", text)
        assert not gathered, f"the block tables' rows gathered: {gathered[:4]}"
        assert mem.temp_size_in_bytes < 1.1 * 2 ** 30
    _, (pool, ring) = _serving_shapes(cfg, bs, blocks, True, slots)
    planes = [jax.ShapeDtypeStruct(*p) for p in pool + list(ring.values())]
    made = _pool_sized()(text, planes)
    writes = [m for m in made if m[1] in ("fusion(scatter)", "scatter")]
    assert len(writes) == 4, f"one write a plane of pool and ring: {made}"
    copies = [m for m in made if m[1] in ("copy", "fusion", "transpose")]
    assert not copies, f"a plane, or a layer of one, is copied: {copies}"
