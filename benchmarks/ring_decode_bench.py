"""Ring-decode vs dense-GSPMD decode step: evidence on a virtual mesh.

parallel/ring.py routes sp-sharded decode through an explicit
flash-decoding combine (per-shard online-softmax partials + one
pmax/psum of O(B*H*hd) bytes). One real chip can't host an sp mesh, so
this harness compares the full ``transformer.decode_step`` with the ring
path against the dense-under-GSPMD fallback on an
``--xla_force_host_platform_device_count`` CPU mesh, reporting compiled
collective bytes (the traffic that would ride ICI) plus relative
wall-clock and output equality.

MEASURED FINDING (recorded so the ring.py claim stays honest): at the
scales this harness can run, XLA's partitioner discovers an equivalent
combine-of-partials pattern for the dense formulation — collective
traffic parity and bit-identical outputs. The explicit ring-decode
path's value is therefore the *guarantee* of that communication shape
(GSPMD's choice is heuristic and scale/layout-dependent), not a measured
win over it; wall-clock on CPU memcpy collectives is noise either way.

Usage: python benchmarks/ring_decode_bench.py [S] [sp]
Prints one JSON line with ring_ms / dense_ms / *_collective_bytes /
speedup / max_abs_diff.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(seq_len: int = 32768, sp: int = 8):
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={sp}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_llm_inferencing_tpu.parallel.mesh import (
        MeshSpec, create_mesh)

    import numpy as np
    from distributed_llm_inferencing_tpu.models import transformer
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
    from distributed_llm_inferencing_tpu.parallel import sharding as shd

    # The claim under test lives in the FULL decode step (ring.py:20-26):
    # in isolation GSPMD already partitions a lone attention well, but
    # inside the real program (cache scatter + QKV/O matmuls around it)
    # the dense fallback's resharding shows up. Same model step, same
    # sp-sharded cache; only mesh= routing differs.
    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    B = 1
    spec = MeshSpec(sp=sp)
    mesh = create_mesh(spec)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    with mesh:
        params = shd.shard_params(params, mesh, cfg, spec)
        cache = init_cache(cfg, B, seq_len, dtype=jnp.float32)
        cache = jax.device_put(cache,
                               shd.named(mesh, shd.cache_specs(cfg, spec)))
        # pretend the cache is full to seq_len - 1 (realistic long decode)
        cache = cache._replace(
            lengths=jnp.full((B,), seq_len - 1, jnp.int32))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (B, 1)),
            jnp.int32)

        ring = jax.jit(lambda p, t, c: transformer.decode_step(
            p, cfg, t, c, mesh=mesh)[0])
        dense = jax.jit(lambda p, t, c: transformer.decode_step(
            p, cfg, t, c, mesh=None)[0])   # GSPMD dense fallback

        def collective_bytes(fn):
            """Bytes produced by cross-device collectives in the compiled
            HLO — the traffic that would ride ICI on a real slice. This is
            the number the ring claim is about: the dense formulation
            gathers cache shards; the ring combines O(B*H*hd) partials."""
            import re
            txt = fn.lower(params, tokens, cache).compile().as_text()
            dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                        "s8": 1, "u8": 1, "pred": 1, "f64": 8}
            total = 0
            for m in re.finditer(
                    r"=\s+(?:\([^)]*\)\s+)?(\w+)\[([\d,]*)\][^=]*"
                    r"(all-gather|all-reduce|collective-permute|"
                    r"reduce-scatter|all-to-all)\(", txt):
                dt, shape = m.group(1), m.group(2)
                n = 1
                for d in filter(None, shape.split(",")):
                    n *= int(d)
                total += n * dt_bytes.get(dt, 4)
            # tuple-shaped collectives: count their tuple elements too
            for m in re.finditer(
                    r"=\s+\(([^)]+)\)\s+(?:all-gather|all-reduce|"
                    r"collective-permute|reduce-scatter|all-to-all)\(", txt):
                for el in m.group(1).split(", "):
                    em = re.match(r"(\w+)\[([\d,]*)\]", el.strip())
                    if em:
                        n = 1
                        for d in filter(None, em.group(2).split(",")):
                            n *= int(d)
                        total += n * dt_bytes.get(em.group(1), 4)
            return total

        def best(fn, n=5):
            jax.block_until_ready(fn(params, tokens, cache))
            t_best = 1e9
            for _ in range(n):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(params, tokens, cache))
                t_best = min(t_best, time.perf_counter() - t0)
            return t_best * 1e3

        ring_ms, dense_ms = best(ring), best(dense)
        out_r = ring(params, tokens, cache)
        out_d = dense(params, tokens, cache)
        err = float(jnp.max(jnp.abs(out_r - out_d)))
        rb, db = collective_bytes(ring), collective_bytes(dense)
        print(json.dumps({
            "seq_len": seq_len, "sp": sp, "batch": B, "model": cfg.name,
            "ring_ms": round(ring_ms, 2), "dense_ms": round(dense_ms, 2),
            "ring_collective_bytes": rb,
            "dense_collective_bytes": db,
            "collective_traffic_ratio": round(db / rb, 1) if rb else None,
            "speedup": round(dense_ms / ring_ms, 2) if ring_ms else None,
            "max_abs_diff": err,
            "note": "virtual CPU mesh: wall-clock is relative evidence "
                    "only; collective bytes are what would ride ICI",
        }))


if __name__ == "__main__":
    s = int(sys.argv[1]) if len(sys.argv) > 1 else 32768
    sp = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    main(s, sp)
