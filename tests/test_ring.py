"""Ring attention (sequence parallelism) tests on the 8-device CPU mesh.

Golden property: the sp-sharded ring (parallel/ring.py) must match the
dense single-device attention (ops/attention.py:attend) and the full
transformer prefill must be invariant to sp.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.attention import attend
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.parallel import ring, sharding as shd
from distributed_llm_inferencing_tpu.parallel.mesh import (
    MeshSpec, create_mesh, validate_spec)


def _dense_ref(q, k, v, lengths, sliding_window=None):
    B, S = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = pos < lengths[:, None]
    return np.asarray(attend(q, k, v, pos, pos, valid,
                             sliding_window=sliding_window))


@pytest.mark.parametrize("spec,window", [
    (MeshSpec(sp=4), None),
    (MeshSpec(sp=8), None),
    (MeshSpec(dp=2, sp=2, tp=2), None),
    (MeshSpec(sp=4), 7),            # sliding window crosses chunk bounds
])
def test_ring_matches_dense(spec, window):
    rng = np.random.default_rng(0)
    B, S, H, Hkv, hd = 4, 32, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    lengths = jnp.asarray([S, S - 5, 17, 1], jnp.int32)  # ragged
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    ref = _dense_ref(q, k, v, lengths, window)
    mesh = create_mesh(spec)
    with mesh:
        got = jax.jit(lambda q, k, v: ring.ring_attend_prefill(
            q, k, v, pos, lengths, mesh=mesh, sliding_window=window)
        )(q, k, v)
    # rows past a sequence's length attend nothing (ring emits zeros;
    # dense path emits an arbitrary uniform average) — compare valid rows
    mask = np.asarray(pos < lengths[:, None])[..., None, None]
    np.testing.assert_allclose(np.where(mask, np.asarray(got), 0),
                               np.where(mask, ref, 0), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("spec", [
    MeshSpec(sp=4),
    MeshSpec(dp=2, sp=2, tp=2),
])
def test_prefill_invariant_to_sp(spec):
    """Full-model prefill logits with sp sharding == single-device logits."""
    cfg = get_config("tiny-llama").replace(dtype="float32")
    validate_spec(spec, cfg)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S = 2, 16
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, S)),
        jnp.int32)
    lengths = jnp.asarray([S, S - 3], jnp.int32)

    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    ref, _ = transformer.prefill(params, cfg, tokens, lengths, cache)
    ref = np.asarray(ref)

    mesh = create_mesh(spec)
    with mesh:
        sp_params = shd.shard_params(params, mesh, cfg, spec)
        cache = init_cache(cfg, B, S, dtype=jnp.float32)
        cache = jax.device_put(
            cache, shd.named(mesh, shd.cache_specs(cfg, spec)))
        got, _ = jax.jit(lambda p, t, l, c: transformer.prefill(
            p, cfg, t, l, c, mesh=mesh))(sp_params, tokens, lengths, cache)
    got = np.asarray(got)
    # compare logits at valid positions only (padding rows are garbage on
    # both sides but not necessarily the same garbage)
    pos = np.arange(S)[None, :]
    valid = (pos < np.asarray(lengths)[:, None])[..., None]
    np.testing.assert_allclose(np.where(valid, got, 0),
                               np.where(valid, ref, 0),
                               atol=2e-4, rtol=2e-4)


def test_ring_then_decode_end_to_end():
    """Prefill via ring (sp=4), then greedy decode steps; tokens must match
    the single-device engine exactly."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    cfg = get_config("tiny-llama").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = np.random.default_rng(3).integers(
        1, cfg.vocab_size, 21).tolist()
    sp_eng = InferenceEngine(cfg, params, mesh_spec=MeshSpec(sp=4),
                             max_seq=64)
    ref_eng = InferenceEngine(cfg, params, max_seq=64)
    g = SamplingParams.greedy()
    got = sp_eng.generate([prompt], max_new_tokens=12, sampling=g)
    ref = ref_eng.generate([prompt], max_new_tokens=12, sampling=g)
    assert got.tokens == ref.tokens


def test_ring_rejects_kv_replication():
    mesh = create_mesh(MeshSpec(sp=2, tp=4))
    q = jnp.zeros((1, 8, 4, 8))
    k = jnp.zeros((1, 8, 1, 8))  # 1 kv head < tp=4 -> replication needed
    pos = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="kv"):
        ring.ring_attend_prefill(q, k, k, pos, jnp.ones((1,), jnp.int32),
                                 mesh=mesh)


# ---- ring decode (flash-decoding combine over sp) -----------------------


@pytest.mark.parametrize("spec,window", [
    (MeshSpec(sp=4), None),
    (MeshSpec(sp=8), None),
    (MeshSpec(dp=2, sp=2, tp=2), None),
    (MeshSpec(sp=4), 7),
])
def test_ring_decode_matches_dense(spec, window):
    """One-token attention over an sp-sharded cache == dense attention."""
    rng = np.random.default_rng(1)
    B, S, H, Hkv, hd = 4, 32, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    lengths = jnp.asarray([S, S - 5, 17, 1], jnp.int32)  # ragged

    # dense reference: query sits at position length-1
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = pos < lengths[:, None]
    ref = np.asarray(attend(q, k, v, (lengths - 1)[:, None], pos, valid,
                            sliding_window=window))

    mesh = create_mesh(spec)
    with mesh:
        got = jax.jit(lambda q, k, v, l: ring.ring_attend_decode(
            q, k, v, l, mesh=mesh, sliding_window=window))(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5, rtol=1e-5)


def test_sp_tp_decode_trajectory_matches_dense():
    """sp=2 x tp=2 engine: full greedy trajectory == single-device engine."""
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    cfg = get_config("tiny-llama").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = np.random.default_rng(7).integers(
        1, cfg.vocab_size, 19).tolist()
    sp_eng = InferenceEngine(cfg, params, mesh_spec=MeshSpec(sp=2, tp=2),
                             max_seq=64)
    ref_eng = InferenceEngine(cfg, params, max_seq=64)
    g = SamplingParams.greedy()
    got = sp_eng.generate([prompt], max_new_tokens=12, sampling=g)
    ref = ref_eng.generate([prompt], max_new_tokens=12, sampling=g)
    assert got.tokens == ref.tokens


def test_ring_decode_bench_harness_runs():
    """The perf-evidence harness (benchmarks/ring_decode_bench.py) stays
    runnable and its two formulations stay numerically aligned."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "ring_decode_bench.py"), "256", "2"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["seq_len"] == 256 and line["sp"] == 2
    assert line["max_abs_diff"] < 1e-4
    assert line["ring_collective_bytes"] > 0


def test_ring_alibi_matches_dense():
    """sp + ALiBi: the ring carries the linear bias (slopes shard over tp
    with the heads) — prefill and decode must match the dense xla path."""
    import jax
    from distributed_llm_inferencing_tpu.ops.attention import (
        alibi_slopes, attend_decode, attend_prefill)
    from distributed_llm_inferencing_tpu.parallel.mesh import (
        MeshSpec, create_mesh)
    from distributed_llm_inferencing_tpu.parallel.ring import (
        ring_attend_decode, ring_attend_prefill)

    rng = np.random.default_rng(11)
    B, S, H, Hkv, hd = 2, 32, 4, 4, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    lengths = jnp.asarray([S, S - 5], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    sl = alibi_slopes(H)
    mesh = create_mesh(MeshSpec(sp=2, tp=2))

    # reference: the dense formulation with per-sequence validity masks
    # (what the ring sees)
    valid = pos < lengths[:, None]
    from distributed_llm_inferencing_tpu.ops.attention import attend
    ref = attend(q, k, v, pos, pos, valid, alibi=sl)
    got = ring_attend_prefill(q, k, v, pos, lengths, mesh=mesh, alibi=sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    qd = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    refd = attend_decode(qd, k, v, lengths, backend="xla", alibi=sl)
    gotd = ring_attend_decode(qd, k, v, lengths, mesh=mesh, alibi=sl)
    np.testing.assert_allclose(np.asarray(gotd), np.asarray(refd),
                               rtol=2e-5, atol=2e-5)


def test_sp_pp_engine_matches_dense():
    """sp × pp (the 70B-long-context corner): the pipelined executor
    routes per-stage attention through the ring path via a nested
    shard_map on the abstract context mesh — greedy decode must match
    the single-device engine exactly, with and without tp."""
    import jax
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    cfg = get_config("tiny-llama").replace(dtype="float32",
                                           attn_backend="xla")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = np.random.default_rng(0).integers(0, 256, 11).tolist()
    g = SamplingParams.greedy()
    ref = InferenceEngine(cfg, params, max_seq=64).generate(
        [prompt, prompt[:7]], max_new_tokens=6, sampling=g).tokens
    for spec in (MeshSpec(pp=2, sp=2), MeshSpec(pp=2, sp=2, tp=2)):
        got = InferenceEngine(cfg, params, mesh_spec=spec,
                              max_seq=64).generate(
            [prompt, prompt[:7]], max_new_tokens=6, sampling=g).tokens
        assert got == ref, (spec, got, ref)
