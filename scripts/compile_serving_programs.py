#!/usr/bin/env python3
"""Compile the batcher's whole admit and decode-chunk programs for a
described TPU — no chip, no arrays — before spending a chip call.

    JAX_PLATFORMS=cpu python scripts/compile_serving_programs.py [tp ...]
    JAX_PLATFORMS=cpu MODEL=kanana python scripts/compile_serving_programs.py 1
    JAX_PLATFORMS=cpu MODEL=trinity python scripts/compile_serving_programs.py 1
    JAX_PLATFORMS=cpu MODEL=mistral-cell python scripts/compile_serving_programs.py 1
    JAX_PLATFORMS=cpu MODEL=ouro python scripts/compile_serving_programs.py 1
    JAX_PLATFORMS=cpu MODEL=falcon-h1 python scripts/compile_serving_programs.py 1
    JAX_PLATFORMS=cpu MODEL=mimo python scripts/compile_serving_programs.py 1

For each tensor-parallel width given (default: 1 and 4) the real jitted
programs of ``runtime/batcher.py`` are lowered for ``v5e:2x2`` at
chip_smoke.py's serving shape (mistral-7b int8, 32 layers; ``LAYERS=2``
compiles as long: the stack is one scan), with ``MODEL=mistral-cell`` at
the benchmark cells' (16 slots, chunks of 8 passes), with
``MODEL=kanana`` at its cell's (kanana-2-30b-a3b bf16, 7 layers, 64
slots, the latent pool of 10,240 blocks) or ``MODEL=trinity`` at its cell's
(trinity-mini bf16, 5 layers, 64 slots, 12,288 blocks, contexts to 9216:
the 2-row admit over a 512-block prefix and the decode chunks with the
windowed read) or ``MODEL=ouro`` at its cell's (ouro-2.6b bf16 whole, 48
layers run 4 times, 8 slots, 192 planes of 321 blocks) or
``MODEL=falcon-h1`` at its cell's (falcon-h1-34b bf16, 6 layers, 64 slots,
4096 blocks, the state planes of 65 rows: the widest admit waves the
token bound lets through and the decode chunks) or ``MODEL=mimo`` at its
cell's (mimo-v2.5 bf16, 7 layers of two kinds, 16 of 256 experts, 64
slots, the full layers' pool of 20,480 blocks and the rings of 65 rows),
with shapes from
``jax.eval_shape`` and shardings from ``parallel/sharding.py``. Prints
what ``compiled.memory_analysis()`` says each device must hold, the
collectives in the program text, and every instruction that yields a
pool-sized or plane-sized array (``pool_sized``: a program that reads
the pool where it lies has one scatter a plane and nothing else, which
since PR 49 holds for every target: trinity's and falcon-h1's pools of
4 K/V heads lie as flat rows of 512, ops/paged_kvcache.heads_in_rows,
and falcon-h1's programs list the state plane's update beside the
scatters). With ``TEXT_DIR=<dir>`` each program's
text goes there too, less what names source lines (each instruction's
``metadata={...}``, the tables of files, functions and stack frames at
the top, and the call-site locations inside a Mosaic kernel's
serialized module, in whose place goes the hash of the module printed
without them), so that two trees' programs compare with ``diff -r``.
What the chip's compiler refuses, it refuses here. A compile that passes
is not a chip run: this gives bytes, never a time. It loads libtpu, so
run it while no test run needs ``tests/test_tpu_compile.py``.
"""

import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from distributed_llm_inferencing_tpu.models.params import init_params  # noqa: E402
from distributed_llm_inferencing_tpu.models.registry import get_config  # noqa: E402
from distributed_llm_inferencing_tpu.ops.paged_kvcache import init_paged_cache  # noqa: E402
from distributed_llm_inferencing_tpu.parallel import sharding as shd  # noqa: E402
from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec, create_mesh  # noqa: E402
from distributed_llm_inferencing_tpu.runtime.batcher import (  # noqa: E402
    ContinuousBatcher, _expert_backend)

# model, quant, depth (a layer count, or the cell's overrides), slots,
# block, blocks, max_seq, admit shapes as (tail, prefix blocks, wave),
# decode chunk sizes
TARGETS = {
    "mistral": ("mistral-7b", "int8", 0, 8, 16, 1024, 2048,   # chip_smoke.py
                ((512, 0, 1), (512, 32, 1), (32, 0, 2)), (32, 1)),
    # benchmarks/chip/configs/mistral-7b-int8.json: 16 slots, its widest
    # admit wave, the 2-row one and the smallest; the decode chunks
    "mistral-cell": ("mistral-7b", "int8", 0, 16, 16, 1024, 2048,
                     ((512, 0, 16), (512, 0, 2), (128, 0, 1)), (8, 1)),
    # benchmarks/chip/configs/kanana-2-30b-a3b-l7.json: its widest admit
    # programs, its smallest (6 rows an expert: lax.ragged_dot, like
    # every admit program) and its decode chunks (the streaming kernel)
    "kanana": ("kanana-2-30b-a3b", None, 7, 64, 16, 10240, 2560,
               ((512, 0, 64), (128, 0, 64), (512, 0, 1), (128, 0, 1)),
               (8, 1)),
    # benchmarks/chip/configs/trinity-mini-l5.json: the widest admit the
    # wave bound lets through, a chunk of a prefix's first admission
    "trinity": ("trinity-mini", None, {
        "num_layers": 5, "dense_prefix_layers": 1,
        "attn_windows": (2048, 2048, 2048, 2048, None),
        "rope_layers": (1, 1, 1, 1, 0)}, 64, 16, 12288, 9216,
        ((512, 512, 2), (512, 128, 1)), (8, 1)),
    # benchmarks/chip/configs/ouro-2.6b.json: 48 layers run 4 times, 192
    # planes of 321 blocks; its widest admit wave (8 rows of 256: a step's
    # tail rows are 0.8 GB, written a step at a time), the probes' 512
    # tail over a cached prefix, and the decode chunks
    "ouro": ("ouro-2.6b", None, 0, 8, 16, 320, 640,
             ((256, 0, 8), (256, 0, 4), (128, 0, 8), (128, 0, 1), (512, 0, 1),
              (16, 32, 8)), (8, 1)),
    # benchmarks/chip/configs/falcon-h1-34b-l6.json: 6 layers, 64 slots
    # and their state rows; the widest admit waves under the token bound
    # (4,096 tokens as bucketed), the smallest, and the decode chunks
    "falcon-h1": ("falcon-h1-34b", None, 6, 64, 16, 4096, 1024,
                  ((128, 1, 32), (256, 1, 16), (512, 1, 8), (128, 1, 1)),
                  (8, 1)),
    # benchmarks/chip/configs/mimo-v2.5-l7.json: 7 layers of two kinds,
    # 16 of 256 experts, 64 slots and their rings beside the full
    # layers' pool; the widest admit waves the byte bounds let through,
    # the second chunk of a chunked 4096 prompt, and the decode chunks
    "mimo": ("mimo-v2.5", None, {
        "num_layers": 7, "vocab_size": 19072, "experts_held": (0, 16),
        "swa": {"pattern": (0, 1, 1, 1, 1, 1, 0), "num_kv_heads": 8,
                "rope_theta": 1e4, "sinks": True}}, 64, 16, 20480, 5120,
        ((2048, 1, 2), (1024, 1, 4), (512, 1, 8), (256, 1, 16),
         (2048, 128, 1), (256, 1, 1)), (8, 1)),
}
TARGET = os.environ.get("MODEL", "mistral")
(MODEL, QUANT, DEPTH, SLOTS, BLOCK, BLOCKS, MAX_SEQ, ADMIT,
 DECODE) = TARGETS[TARGET]
TEXT_DIR = os.environ.get("TEXT_DIR")
GIB = 2.0 ** 30


def _kernel_hash(match):
    """A Mosaic kernel's serialized module (``"body": "<base64>"`` in its
    custom call's backend_config) holds the source locations of its call
    sites, line numbers of the model code included: replace it by the
    hash of the module printed without locations."""
    import base64
    import hashlib
    from jax._src.interpreters.mlir import make_ir_context
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    with make_ir_context() as ctx:
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        asm = ir.Module.parse(base64.b64decode(match.group(1))) \
            .operation.get_asm(enable_debug_info=False)
    return '"body":"sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()


def scrub(text):
    """The program text less what names source lines."""
    text = re.sub(r"^FileNames\n.*?^StackFrames\n(?:\d+ \{[^\n]*\}\n)*", "",
                  text, flags=re.S | re.M)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.sub(r'"body": *"([A-Za-z0-9+/=]+)"', _kernel_hash, text)


def pool_sized(text, paged):
    """(name, operation) of every instruction of a program's text that
    yields an array with as many elements as a plane of the pool
    ``paged`` or as one layer of it: a program that reads the pool where
    it lies and writes it once, in place, has the one scatter a plane
    and nothing else. Parameters, get-tuple-elements and bitcasts yield
    no new array, a tuple other than a fusion's (a loop's state, a
    conditional's operands) only hands arrays on, and what a fusion
    computes inside is the fusion's one result (a fusion around a
    scatter is named ``fusion(scatter)``)."""
    counts = set()
    for plane in jax.tree.leaves(paged):   # as one device holds it
        shape = (plane.sharding.shard_shape(plane.shape)
                 if getattr(plane, "sharding", None) else plane.shape)
        counts |= {math.prod(shape), math.prod(shape[1:])}
    fused = set(re.findall(r" fusion\(.*calls=%([^\s,)]+)", text))
    scatters, found, inside = set(), [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            inside = head.group(1)
            continue
        made = re.match(r"\s+(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(", line)
        if not made:
            continue
        name, shapes, op = made.groups()
        if op == "scatter":
            scatters.add(inside)
        if inside in fused or op in ("parameter", "get-tuple-element",
                                     "bitcast") or (
                shapes.startswith("(") and op != "fusion"):
            continue
        if any(math.prod(int(d) for d in dims.split(",")) in counts
               for dims in re.findall(r"\[([\d,]+)\]", shapes)):
            calls = re.search(r"calls=%([^\s,)]+)", line)
            found.append((name, op, calls and calls.group(1)))
    return [(name, "fusion(scatter)" if called in scatters else op)
            for name, op, called in found]


def report(name, lowered, t0, paged):
    try:
        compiled = lowered.compile()
    except jax.errors.JaxRuntimeError as e:   # what the chip would refuse
        print(f"{name}: REFUSED: " + " | ".join(
            line.strip() for line in str(e).splitlines()[:8] if line.strip()),
            flush=True)
        return
    mem, text = compiled.memory_analysis(), compiled.as_text()
    sized = pool_sized(text, paged)
    if TEXT_DIR:
        os.makedirs(TEXT_DIR, exist_ok=True)
        with open(os.path.join(
                TEXT_DIR, re.sub(r"[^A-Za-z0-9=_]+", "_", name)), "w") as f:
            f.write(scrub(text))
    print(f"{name}: {time.time() - t0:.1f}s to compile; per device "
          f"arguments {mem.argument_size_in_bytes / GIB:.2f} GiB, "
          f"transient {mem.temp_size_in_bytes / GIB:.2f} GiB "
          f"({mem.temp_size_in_bytes} B), "
          f"output {mem.output_size_in_bytes / GIB:.2f} GiB "
          f"(aliased {mem.alias_size_in_bytes / GIB:.2f}); "
          f"all-reduce {text.count('all-reduce(')}, "
          f"all-gather {text.count('all-gather(')}, "
          f"pallas calls {text.count('tpu_custom_call')} (ragged-dot "
          f"{len(re.findall(r'%ragged-dot[^ ]* = ', text))}, "
          f"expert_stream_matmul "
          f"{len(re.findall(r'%expert_stream_matmul[^ ]* = ', text))}, "
          f"paged_pool_attend "
          f"{len(re.findall(r'%paged_pool_attend[^ ]* = ', text))}); "
          f"pool- or plane-sized arrays made {len(sized)}: "
          f"{' '.join(f'{n}({op})' for n, op in sized)}",
          flush=True)


def main(widths):
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    layers = DEPTH if isinstance(DEPTH, dict) else int(
        os.environ.get("LAYERS", DEPTH))
    for tp in widths:
        spec = MeshSpec(tp=tp)
        mesh = create_mesh(spec, topo.devices)
        cfg = get_config(MODEL).replace(quant=QUANT)
        if layers:
            cfg = cfg.replace(**(layers if isinstance(layers, dict)
                                 else {"num_layers": layers}))
        # what ContinuousBatcher.__init__ pins (on the described TPU)
        cfg = cfg.replace(attn_backend="xla", tp_row_sharded=tp > 1,
                          mla_latent_cache=cfg.mla,
                          expert_matmul=_expert_backend(spec.num_devices,
                                                        "tpu"),
                          pool_kernel=_expert_backend(spec.num_devices,
                                                      "tpu"))
        print(f"--- {MODEL} {QUANT}, {cfg.num_layers} layers, tp={tp}, "
              f"attn_backend={cfg.attn_backend}", flush=True)

        def described(tree, specs):
            return jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                tree, shd.named(mesh, specs))

        params = described(
            jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))),
            shd.param_specs(cfg, spec))
        if cfg.is_moe:
            # the batcher holds MoE layers one by one (_unstack_layers:
            # each kind's stack of a model with layer kinds)
            for name in ("layers", "layers_full"):
                if name in params:
                    n = jax.tree.leaves(params[name])[0].shape[0]
                    params[name] = [jax.tree.map(
                        lambda s: jax.ShapeDtypeStruct(
                            s.shape[1:], s.dtype, sharding=NamedSharding(
                                mesh, P(*s.sharding.spec[1:]))),
                        params[name]) for _ in range(n)]
        paged = described(
            jax.eval_shape(lambda: init_paged_cache(
                cfg, BLOCKS + 1, BLOCK, slots=SLOTS, devices=mesh.size)),
            shd.paged_cache_specs(cfg, spec))
        replicated = NamedSharding(mesh, P())

        def arr(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=replicated)

        # the batcher's program builders need only these attributes; its
        # constructor would place real parameters on jax.devices()
        b = object.__new__(ContinuousBatcher)
        b.cfg, b.block_size, b.mesh_spec, b.mesh = cfg, BLOCK, spec, mesh
        b._dummy, b._prefill_fns, b._decode_fns = 0, {}, {}
        mb = MAX_SEQ // BLOCK
        with mesh:
            for t, pb, wave in ADMIT:
                # ... + a slot row a wave row, for a model with a per-slot
                # cache (state layers, ring layers)
                n_ints = wave * (t + t // BLOCK + pb + 6 + cfg.slot_cache)
                t0 = time.time()
                report(f"{TARGET} tp={tp} admit tail={t} prefix_blocks={pb} "
                       f"wave={wave}",
                       b._admit_jit(t, pb, wave).lower(
                           params, arr((n_ints,), jnp.int32),
                           arr((2, wave), jnp.float32), paged), t0, paged)
            for k in DECODE:
                t0 = time.time()
                report(f"{TARGET} tp={tp} decode chunk k={k}",
                       b._decode_jit(k, SLOTS, mb).lower(
                           params, arr((SLOTS,), jnp.int32),
                           arr((SLOTS * (mb + 7),), jnp.int32),
                           arr((2, SLOTS), jnp.float32), paged), t0, paged)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]] or [1, 4])
