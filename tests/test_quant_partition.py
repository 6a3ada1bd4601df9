"""GSPMD partitioning for the int4 pallas kernel (ops/pallas/quant_matmul).

The kernel's value is the llama-8B-tp / 70B-pp+tp regimes, so it must run
INSIDE multi-device GSPMD programs — these tests pin the partitioning
rule on a CPU mesh (pallas interpret mode): column-parallel (dout over
tp) runs per-shard and matches the XLA unpack bit-for-bit at f32 tile
sizes, row-parallel leaves keep the XLA path (supported() hint), and an
int4 model on a tp=2 engine matches its tp=1 twin.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_llm_inferencing_tpu.ops.pallas import quant_matmul as qm
from distributed_llm_inferencing_tpu.ops.quant import (
    quantize_weight_int4, unpack_int4)


def _leaf(din, dout, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((din, dout)), jnp.float32)
    return quantize_weight_int4(w)


def _ref(x, leaf):
    return x @ (unpack_int4(leaf["p4"]).astype(jnp.float32)
                * leaf["scale"][None, :])


def test_q4_matmul_partitions_column_parallel():
    leaf = _leaf(64, 256)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("tp",))
    p4 = jax.device_put(leaf["p4"], NamedSharding(mesh, P(None, "tp")))
    sc = jax.device_put(leaf["scale"], NamedSharding(mesh, P("tp")))
    xr = jax.device_put(x, NamedSharding(mesh, P(None, None)))
    out = jax.jit(lambda a, p, s: qm.q4_matmul(a, p, s, interpret=True))(
        xr, p4, sc)
    # the rule shards the OUTPUT channel axis — no resharding collective
    # on the weight, result lands tp-sharded
    assert out.sharding.spec == P(None, "tp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, leaf)),
                               rtol=1e-4, atol=1e-4)


def test_q4_matmul_batch_sharded_rows():
    leaf = _leaf(64, 128)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("dp",))
    xr = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    out = jax.jit(lambda a: qm.q4_matmul(a, leaf["p4"], leaf["scale"],
                                         interpret=True))(xr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, leaf)),
                               rtol=1e-4, atol=1e-4)


def test_supported_gates(monkeypatch):
    monkeypatch.setenv("DLI_INT4_PALLAS", "interpret")
    assert qm.supported(1, 64, 128)
    # row-sharded leaves keep XLA regardless of platform/mode
    assert not qm.supported(1, 64, 128, row_sharded=True)
    monkeypatch.setenv("DLI_INT4_PALLAS", "never")
    assert not qm.supported(1, 64, 128)
    monkeypatch.setenv("DLI_INT4_PALLAS", "auto")
    # CPU backend without interpret: XLA fallback
    assert not qm.supported(1, 64, 128)


def test_int4_engine_tp2_matches_tp1(monkeypatch):
    """Whole-model check: an int4 engine on a tp=2 mesh (kernel engaged
    via interpret mode, column-parallel per-shard; row-parallel leaves on
    XLA) greedy-decodes identically to the single-device engine."""
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.models import convert
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    monkeypatch.setenv("DLI_INT4_PALLAS", "interpret")
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=96, n_positions=64, n_embd=128, n_layer=2,
        n_head=4)).eval()

    def mk(spec):
        cfg, params = convert.load_hf_model(hf, dtype=jnp.float32)
        cfg = cfg.replace(dtype="float32", name="tiny-int4", quant="int4")
        return InferenceEngine(cfg, params, mesh_spec=spec, max_seq=64)

    prompt = [3, 17, 52, 9]
    g = SamplingParams.greedy()
    a = mk(None).generate([prompt], max_new_tokens=8, sampling=g).tokens[0]
    b = mk(MeshSpec(tp=2)).generate([prompt], max_new_tokens=8,
                                    sampling=g).tokens[0]
    assert a == b


def test_q4_row_parallel_matches_reference():
    """Row-parallel (din-sharded) leaves: after the chunk-local repack
    (ops/quant.py repack_int4_rows) each shard's slice is self-contained,
    the kernel runs locally and one psum combines partials."""
    from distributed_llm_inferencing_tpu.ops.quant import repack_int4_rows
    leaf = _leaf(64, 256, seed=3)
    ch = repack_int4_rows(leaf, 2)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("tp",))
    p4 = jax.device_put(ch["p4"], NamedSharding(mesh, P("tp", None)))
    sc = jax.device_put(ch["scale"], NamedSharding(mesh, P(None)))
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "tp")))
    out = jax.jit(lambda a, p, s: qm.q4_matmul_row(
        a, p, s, interpret=True, chunks=2))(xs, p4, sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, leaf)),
                               rtol=1e-4, atol=1e-4)


def test_chunked_repack_preserves_values():
    from distributed_llm_inferencing_tpu.ops.quant import (
        dequantize_weight, repack_int4_rows, unpack_int4)
    leaf = _leaf(96, 160, seed=4)
    for chunks in (2, 4):
        ch = repack_int4_rows(leaf, chunks)
        np.testing.assert_array_equal(
            np.asarray(unpack_int4(ch["p4"], chunks)),
            np.asarray(unpack_int4(leaf["p4"])))
        np.testing.assert_array_equal(np.asarray(dequantize_weight(ch)),
                                      np.asarray(dequantize_weight(leaf)))


def test_int4_engine_tp2_row_and_col_kernels(monkeypatch):
    """Whole model on tp=2 with BOTH kernel modes engaged — q/k/v/up
    column-partitioned, o/down row-partitioned via the shard-time repack
    (parallel/sharding.py shard_params) — matches the tp=1 engine."""
    import torch
    import transformers
    from distributed_llm_inferencing_tpu.models import convert
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine

    monkeypatch.setenv("DLI_INT4_PALLAS", "interpret")
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=96, n_positions=64, n_embd=128, n_layer=2,
        n_head=4)).eval()

    def mk(spec):
        cfg, params = convert.load_hf_model(hf, dtype=jnp.float32)
        cfg = cfg.replace(dtype="float32", name="tiny-int4rc", quant="int4")
        return InferenceEngine(cfg, params, mesh_spec=spec, max_seq=64)

    tp2 = mk(MeshSpec(tp=2))
    # the shard-time repack actually engaged on the row-parallel leaves
    assert "chunked" in tp2.params["layers"]["o"]
    assert "chunked" in tp2.params["layers"]["down"]
    assert "chunked" not in tp2.params["layers"]["up"]
    g = SamplingParams.greedy()
    a = mk(None).generate([[3, 17, 52, 9]], max_new_tokens=8,
                          sampling=g).tokens[0]
    b = tp2.generate([[3, 17, 52, 9]], max_new_tokens=8,
                     sampling=g).tokens[0]
    assert a == b
