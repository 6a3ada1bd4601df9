"""Native checkpoint save/restore (models/checkpoint.py).

Covers: roundtrip fidelity, HF→native conversion parity, sharded restore
straight into NamedSharding placements, and the worker's
native_checkpoint load path.
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import requests

from distributed_llm_inferencing_tpu.models import checkpoint
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from conftest import jitted, stop_worker


def tree_equal(a, b):
    flat_a, _ = jax.tree.flatten(a)
    flat_b, _ = jax.tree.flatten(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_roundtrip(tmp_path):
    cfg = get_config("tiny-llama").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    checkpoint.save_checkpoint(str(tmp_path / "ck"), cfg, params)
    cfg2, params2 = checkpoint.load_checkpoint(str(tmp_path / "ck"))
    assert cfg2 == cfg
    tree_equal(params, params2)


def test_hf_convert_parity(tmp_path):
    torch = pytest.importorskip("torch")
    import transformers
    from distributed_llm_inferencing_tpu.models.convert import load_hf_model
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=97, n_positions=64, n_embd=32, n_layer=2, n_head=4)).eval()
    hf.save_pretrained(tmp_path / "hf")
    checkpoint.convert_hf_to_native(str(tmp_path / "hf"),
                                    str(tmp_path / "native"))
    cfg_direct, params_direct = load_hf_model(str(tmp_path / "hf"))
    cfg_native, params_native = checkpoint.load_checkpoint(
        str(tmp_path / "native"))
    assert cfg_native.family == cfg_direct.family == "gpt2"
    tree_equal(params_direct, params_native)


def test_tokenizer_travels_with_native_checkpoint(tmp_path):
    """convert copies tokenizer artifacts; the worker only uses a dir as a
    tokenizer source when artifacts exist (else byte-level fallback)."""
    from distributed_llm_inferencing_tpu.utils.tokenizer import has_tokenizer
    cfg = get_config("tiny-llama").replace(dtype="float32")
    checkpoint.save_checkpoint(
        str(tmp_path / "ck"), cfg,
        init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    assert not has_tokenizer(str(tmp_path / "ck"))   # weights-only dir
    (tmp_path / "ck" / "tokenizer.json").write_text("{}")
    assert has_tokenizer(str(tmp_path / "ck"))
    assert not has_tokenizer(None)


def test_sharded_restore(tmp_path):
    """Leaves restore directly into their mesh placement, and the sharded
    model computes the same logits as the host-restored one."""
    from distributed_llm_inferencing_tpu.models import transformer
    from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
    from distributed_llm_inferencing_tpu.parallel.mesh import (
        MeshSpec, create_mesh)

    cfg = get_config("tiny-llama").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    checkpoint.save_checkpoint(str(tmp_path / "ck"), cfg, params)

    spec = MeshSpec(tp=2, dp=2)
    mesh = create_mesh(spec)
    cfg2, sharded = checkpoint.load_checkpoint(
        str(tmp_path / "ck"), mesh=mesh, mesh_spec=spec)
    # attention projections must actually live sharded over tp
    qw = sharded["layers"]["q"]["w"]
    assert len(qw.sharding.device_set) == 4

    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    lens = jnp.full((2,), 8, jnp.int32)

    def fwd(p):
        cache = init_cache(cfg, 2, 16, dtype=jnp.float32)
        logits, _ = jitted(transformer.prefill)(p, cfg, toks, lens, cache)
        return logits

    with mesh:
        got = jax.jit(fwd)(sharded)
    want = jax.jit(fwd)(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_cli_convert_and_worker_load(tmp_path):
    out = str(tmp_path / "native-gpt2")
    r = subprocess.run(
        [sys.executable, "-m", "distributed_llm_inferencing_tpu", "convert",
         "--model_name", "tiny-gpt2", "--allow_random_init",
         "--dtype", "float32", "--out", out],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    assert "saved native checkpoint" in r.stdout

    from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
    agent = WorkerAgent()
    srv = agent.serve(host="127.0.0.1", port=0, background=True)
    port = srv.server_address[1]
    try:
        resp = requests.post(
            f"http://127.0.0.1:{port}/load_model",
            json={"model_name": "m", "native_checkpoint": out,
                  "max_seq": 64}, timeout=300)
        assert resp.status_code == 200, resp.text
        resp = requests.post(
            f"http://127.0.0.1:{port}/inference",
            json={"model_name": "m", "prompt_tokens": [1, 2, 3],
                  "max_new_tokens": 4, "sampling": {"do_sample": False}},
            timeout=300)
        assert resp.status_code == 200, resp.text
        assert len(resp.json()["tokens"]) == 4
    finally:
        stop_worker(agent)


def test_generate_cli_loads_native_checkpoint(tmp_path, capsys):
    """`generate --checkpoint_path <native dir>` auto-detects the Orbax
    layout (params/ subdir) and serves it without torch — same surface
    the worker uses, now from the CLI."""
    import jax
    from distributed_llm_inferencing_tpu import __main__ as cli
    from distributed_llm_inferencing_tpu.models import checkpoint
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.models.registry import get_config

    cfg = get_config("tiny-llama").replace(dtype="float32")
    checkpoint.save_checkpoint(
        str(tmp_path / "native"), cfg,
        init_params(cfg, jax.random.PRNGKey(0)))
    cli.main(["--platform", "cpu", "generate",
              "--checkpoint_path", str(tmp_path / "native"),
              "--prompt", "ab", "--max_new_tokens", "4", "--greedy"])
    out = capsys.readouterr().out
    assert len(out.strip()) > 0


def test_roundtrip_per_layer_windows(tmp_path):
    """attn_windows survives config.json (tuple -> list -> tuple) and the
    int32 ``attn_window`` leaf restores with its dtype intact."""
    cfg = get_config("tiny-llama").replace(
        dtype="float32", sliding_window=None,
        attn_windows=(None, 3, None, 3))
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    checkpoint.save_checkpoint(str(tmp_path / "ck"), cfg, params)
    cfg2, params2 = checkpoint.load_checkpoint(str(tmp_path / "ck"))
    assert cfg2 == cfg
    assert cfg2.attn_windows == (None, 3, None, 3)
    assert params2["layers"]["attn_window"].dtype == jnp.int32
    tree_equal(params, params2)
