"""Paged KV cache correctness: paged serving path ≡ dense path.

The golden property (the one the reference never checked for its shards,
SURVEY.md §4): a sequence decoded through paged blocks — including via a
shared cached prefix — produces the same tokens/logits as the dense-cache
path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
    flat_pool, heads_in_rows, init_paged_cache)
from conftest import (
    assert_flat_rows_serve_the_same, flat_rows_scenario, jitted)

BS = 8  # block size for tests


def _cfg(name):
    return get_config(name).replace(dtype="float32", attn_backend="xla")


def _dense_greedy(cfg, params, prompt, n_new):
    """Reference trajectory via the dense cache."""
    s0 = 32
    cache = init_cache(cfg, 1, 128, dtype=jnp.float32)
    tokens = np.zeros((1, s0), np.int32)
    tokens[0, :len(prompt)] = prompt
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    logits, cache = jitted(transformer.prefill)(
        params, cfg, jnp.asarray(tokens), lengths, cache)
    last = logits[0, len(prompt) - 1]
    out, traj = [], [last]
    cur = jnp.argmax(last)[None]
    out.append(int(cur[0]))
    for _ in range(n_new - 1):
        logits, cache = jitted(transformer.decode_step)(
            params, cfg, cur[:, None], cache)
        traj.append(logits[0, 0])
        cur = jnp.argmax(logits[0, 0])[None]
        out.append(int(cur[0]))
    return out, traj


def _paged_greedy(cfg, params, prompt, n_new, *, num_blocks=32, slots=4,
                  slot=1):
    """Same trajectory via paged blocks, request parked in slot `slot`."""
    paged = init_paged_cache(cfg, num_blocks, BS, dtype=jnp.float32)
    # block 0 is the dummy; the request owns blocks 1..n
    t = -(-len(prompt) // BS) * BS  # pad tail to block multiple
    n_blocks = t // BS
    my_blocks = list(range(1, 1 + n_blocks))
    max_blocks = 8
    tokens = np.zeros((1, t), np.int32)
    tokens[0, :len(prompt)] = prompt

    last, paged = jitted(transformer.paged_prefill_tail)(
        params, cfg, jnp.asarray(tokens), jnp.asarray([len(prompt)], jnp.int32),
        jnp.asarray(my_blocks, jnp.int32),
        jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), paged)

    block_tables = np.zeros((slots, max_blocks), np.int32)
    block_tables[slot, :n_blocks] = my_blocks
    # growth room: one extra block for decode past the prompt blocks
    extra = 1 + n_blocks + slot  # arbitrary distinct id
    block_tables[slot, n_blocks] = extra
    context_lens = np.zeros((slots,), np.int32)
    context_lens[slot] = len(prompt)

    out, traj = [], [last[0]]
    cur_tok = int(jnp.argmax(last[0]))
    out.append(cur_tok)
    toks = np.zeros((slots,), np.int32)
    for _ in range(n_new - 1):
        toks[slot] = cur_tok
        logits, paged = jitted(transformer.paged_decode_step)(
            params, cfg, jnp.asarray(toks), paged,
            jnp.asarray(block_tables), jnp.asarray(context_lens))
        traj.append(logits[slot])
        cur_tok = int(jnp.argmax(logits[slot]))
        out.append(cur_tok)
        context_lens[slot] += 1
    return out, traj


@pytest.mark.parametrize("model", ["tiny-gpt2", "tiny-llama", "tiny-mixtral"])
def test_paged_equals_dense(model):
    cfg = _cfg(model)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 13).tolist()   # straddles blocks
    n_new = 10
    dense_toks, dense_traj = _dense_greedy(cfg, params, prompt, n_new)
    paged_toks, paged_traj = _paged_greedy(cfg, params, prompt, n_new)
    assert dense_toks == paged_toks
    for i, (d, p) in enumerate(zip(dense_traj, paged_traj)):
        np.testing.assert_allclose(d, p, rtol=2e-4, atol=2e-4,
                                   err_msg=f"step {i}")


def test_sliding_window_paged():
    cfg = _cfg("tiny-llama").replace(sliding_window=8)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 11).tolist()
    dense_toks, _ = _dense_greedy(cfg, params, prompt, 12)
    paged_toks, _ = _paged_greedy(cfg, params, prompt, 12)
    assert dense_toks == paged_toks


def test_prefix_reuse_matches_full_prefill():
    """Tail prefill over a cached prefix ≡ full prefill of the whole prompt."""
    cfg = _cfg("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, 2 * BS).tolist()  # 2 full blocks
    tail_a = rng.integers(0, cfg.vocab_size, 5).tolist()
    tail_b = rng.integers(0, cfg.vocab_size, 7).tolist()

    paged = init_paged_cache(cfg, 32, BS, dtype=jnp.float32)

    # Request A: no prefix cached yet — prefill the whole prompt
    prompt_a = shared + tail_a
    t_a = -(-len(prompt_a) // BS) * BS
    blocks_a = list(range(1, 1 + t_a // BS))
    toks_a = np.zeros((1, t_a), np.int32)
    toks_a[0, :len(prompt_a)] = prompt_a
    last_a, paged = jitted(transformer.paged_prefill_tail)(
        params, cfg, jnp.asarray(toks_a),
        jnp.asarray([len(prompt_a)], jnp.int32),
        jnp.asarray(blocks_a, jnp.int32),
        jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), paged)

    # Request B: first 2 blocks (len(shared) tokens) come from the radix
    # cache (blocks_a[:2]); only B's tail is computed.
    prompt_b = shared + tail_b
    tail_len = len(prompt_b) - len(shared)
    t_b = -(-tail_len // BS) * BS
    blocks_b = list(range(10, 10 + t_b // BS))
    toks_b = np.zeros((1, t_b), np.int32)
    toks_b[0, :tail_len] = prompt_b[len(shared):]
    last_b, paged = jitted(transformer.paged_prefill_tail)(
        params, cfg, jnp.asarray(toks_b),
        jnp.asarray([tail_len], jnp.int32),
        jnp.asarray(blocks_b, jnp.int32),
        jnp.asarray([blocks_a[:2]], jnp.int32),
        jnp.asarray([len(shared)], jnp.int32), paged)

    # Oracle: full prefill of B's whole prompt, fresh blocks
    paged2 = init_paged_cache(cfg, 32, BS, dtype=jnp.float32)
    t_full = -(-len(prompt_b) // BS) * BS
    toks_full = np.zeros((1, t_full), np.int32)
    toks_full[0, :len(prompt_b)] = prompt_b
    last_full, _ = jitted(transformer.paged_prefill_tail)(
        params, cfg, jnp.asarray(toks_full),
        jnp.asarray([len(prompt_b)], jnp.int32),
        jnp.asarray(list(range(1, 1 + t_full // BS)), jnp.int32),
        jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), paged2)

    np.testing.assert_allclose(np.asarray(last_b[0]), np.asarray(last_full[0]),
                               rtol=2e-4, atol=2e-4)


# ---- which pools store a position's heads in one row (heads_in_rows) ----

@pytest.mark.parametrize("model,kw,devices,row", [
    # trinity-mini's and falcon-h1's 4 K/V heads of 128, and 2: flat
    ("tiny-llama", dict(num_kv_heads=4, head_dim=128), 1, 512),
    ("tiny-llama", dict(num_kv_heads=2, head_dim=128), 1, 256),
    ("tiny-falcon-h1", dict(num_heads=20, num_kv_heads=4, head_dim=128), 1,
     512),                                       # beside the state planes
    # a tile's 8 sublanes filled, or more: the heads keep their axis
    ("tiny-llama", dict(num_kv_heads=8, head_dim=128), 1, None),
    ("tiny-ouro", dict(num_heads=16, num_kv_heads=16, head_dim=128), 1, None),
    # heads that are no whole lanes; an int8 pool (its scale planes have
    # a head axis and no width); a latent pool (one shared row already)
    ("tiny-llama", {}, 1, None),
    ("tiny-llama", dict(num_kv_heads=4, head_dim=128, kv_quant="int8"), 1,
     None),
    ("tiny-kanana", dict(mla_latent_cache=True), 1, None),
    # a mesh shards the head axis (parallel/sharding.paged_cache_specs)
    ("tiny-llama", dict(num_kv_heads=4, head_dim=128), 4, None),
    ("tiny-llama", dict(num_kv_heads=2, head_dim=128), 2, None),
    # one head (MQA): the same shape either way
    ("tiny-llama", dict(num_kv_heads=1, head_dim=128), 1, None),
])
def test_which_pools_store_heads_side_by_side(model, kw, devices, row):
    """The rule is read from the pool's shape (init_paged_cache), not
    from a model's name: an unquantized one-device pool of fewer K/V
    heads than a tile's 8 sublanes, each whole lanes wide, stores a
    position's heads in ONE row; every other pool keeps
    [L, NB, bs, Hkv, hd]. The bytes a token are the same either way."""
    cfg = get_config(model).replace(**kw)
    paged = init_paged_cache(cfg, 6, BS, slots=2, devices=devices)
    by_heads = init_paged_cache(cfg, 6, BS, slots=2, devices=8)
    assert heads_in_rows(cfg, devices) == flat_pool(cfg, paged) \
        == (row is not None)
    assert paged.bytes_per_token == by_heads.bytes_per_token
    if row is None:
        assert paged.k.shape == by_heads.k.shape
        assert paged.k.shape[3:] == (
            (1, paged.k.shape[4]) if cfg.mla_latent_cache
            else (cfg.num_kv_heads, cfg.head_dim))
    else:
        assert paged.k.shape == paged.v.shape == (
            cfg.num_layers, 6, BS, 1, row)
    assert (paged.ssm is None) == (cfg.ssm is None)


@pytest.mark.parametrize("form", ["pre-gathered", "in-loop", "kernel",
                                  "speculative"])
def test_flat_rows_serve_what_the_heads_axis_serves(form, monkeypatch):
    """conftest.flat_rows_scenario over a pool of 4 K/V heads of 128 as
    one device holds it (rows of 512) and as a mesh does (the heads an
    axis): a wave's write, a prefix hit, a chunked prompt's next chunk,
    then the decode chunk's read in each of its forms and its one write.
    Tokens exactly, logits and every plane to a float32's rounding (each
    query head zero-expanded to the row: the other heads' columns add
    exact zeros, in a sum of another length)."""
    cfg = _cfg("tiny-llama").replace(
        head_dim=128,
        pool_kernel="pallas_interpret" if form == "kernel" else "xla")
    if form != "pre-gathered":
        monkeypatch.setattr(transformer, "_PREGATHER_MAX_BYTES", 0)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    flat, by_heads = (
        flat_rows_scenario(params, cfg, devices,
                           speculative=form == "speculative")
        for devices in (1, 4))
    assert_flat_rows_serve_the_same(flat, by_heads)
