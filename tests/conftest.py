"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip behavior (tp/dp/pp/sp/ep shardings, collectives) is tested on
host CPU devices exactly as SURVEY.md §4 prescribes. Both variables are
set before jax is imported, which is all JAX needs.
"""

import functools
import os
import signal
import sys
import threading

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# What the suite spends is XLA:CPU compiling programs it runs once or
# twice at toy widths: two thirds of a cold case's CPU seconds were
# LLVM's (49.9 s -> 30.4 for forty cases of tests/test_decode_gather.py,
# a whole run 985 s -> 771, with a compile cache on both sides; CHANGES.md,
# PR 48). Level 0 is LLVM's, not HLO's: the programs' operations, fusions
# and numerics are what they were (every tolerance and every bit-for-bit
# comparison of the suite holds), the described v5e compiles of
# tests/test_tpu_compile.py are libtpu's and unmoved; what runs long on
# the CPU (the sampler's cell-size cases) runs longer. A level the caller
# sets stays.
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ---- no persistent compile cache of the suite's own ---------------------
# Measured for PR 48 (CHANGES.md): the cache on for every process of a run,
# in one directory its workers shared, took a whole run from 787 s to
# 610-771, and a worker died in five of its seven runs, the last four
# inside the cache's own ``executable.serialize()`` (put) or deserialize
# (get) on XLA:CPU; without it no worker died at this level of LLVM. So a
# process has the cache only from the case that reaches
# utils/platform.pin_platform on, as before, or where the caller sets
# ``JAX_COMPILATION_CACHE_DIR``; the processes of a jax.distributed slice
# must not inherit that (tests/test_multihost.py::_slice_env: they hang).


def tiny_gpt_oss_model(seed=60):
    """Tiny randomized HF gpt-oss (sinks randomized — HF init may leave
    them empty/zero, and all-zero sinks are invisible to sharding and
    parity tests alike). One definition shared by the numerics and
    sharding suites."""
    import torch
    import transformers
    cfg = transformers.GptOssConfig(
        vocab_size=128, hidden_size=32, intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, num_local_experts=4, num_experts_per_tok=2,
        sliding_window=4, layer_types=["sliding_attention",
                                       "full_attention"],
        max_position_embeddings=64, rope_scaling=None,
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(seed)
    model = transformers.GptOssForCausalLM(cfg).eval()
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.self_attn.sinks.normal_(0.0, 1.0)
    return model


def tiny_glm45_moe_model(seed=58):
    """Tiny randomized HF GLM-4.5 MoE (q/k norms and the router
    correction bias perturbed away from their invariant inits)."""
    import torch
    import transformers
    cfg = transformers.Glm4MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        partial_rotary_factor=0.5, use_qk_norm=True,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        n_group=2, topk_group=1, routed_scaling_factor=1.5,
        norm_topk_prob=True, first_k_dense_replace=1,
        max_position_embeddings=64, tie_word_embeddings=False,
        pad_token_id=0)
    torch.manual_seed(seed)
    model = transformers.Glm4MoeForCausalLM(cfg).eval()
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.self_attn.q_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.q_norm.weight) + 0.5)
            lyr.self_attn.k_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.k_norm.weight) + 0.5)
            if hasattr(lyr.mlp, "gate"):
                lyr.mlp.gate.e_score_correction_bias.uniform_(0.0, 0.2)
    return model


# ---- the plain form of transformer.paged_prefill_tail ------------------
# As it stood before PR 38: the layer stack takes the pool's planes layer
# by layer, each layer writes its tail into its slice
# (write_block_run) and attends its prefix from the slice it wrote, and
# the slices come back re-stacked. The serving form gathers the prefix
# from the stacked pool by (layer, block) and writes once after the
# stack; tests hold the two equal bit for bit (tests/test_decode_gather.py,
# tests/test_trinity.py).

def paged_prefill_tail_per_layer_write(params, cfg, tokens, tail_len,
                                       tail_blocks, prefix_blocks,
                                       prefix_len, paged, lora_ids=None):
    import jax
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.models import transformer as tf
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache, paged_attend_prefix, write_block_run)
    b, t = tokens.shape
    if tail_blocks.ndim == 1:
        tail_blocks = tail_blocks[None]
    if tail_blocks.shape[0] != b:
        raise ValueError(
            f"tail_blocks batch {tail_blocks.shape[0]} != tokens batch {b}")
    q_pos = prefix_len[:, None] + jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32), (b, t))
    tail_valid = jnp.arange(t, dtype=jnp.int32)[None, :] < tail_len[:, None]
    x = tf.embed(params, cfg, tokens, q_pos)
    quantized = paged.quantized

    def make_body(seg_cfg):
        def body(x, layer_in):
            lp, ck, *rest = layer_in
            cv, scales = (rest[0], rest[1:]) if rest else (None, ())

            if seg_cfg.mla_latent_cache:
                def expand(rows):
                    return tf._mla_expand(
                        *tf._mla_split_rows(rows, seg_cfg), lp, seg_cfg)

                def mla_latent_attend(h, qp):
                    rows = tf._mla_latent_rows(h, lp, seg_cfg, qp)
                    with jax.named_scope("kv_write"):
                        nk = write_block_run(ck, rows, tail_blocks)
                    k, v = expand(rows)
                    attn = paged_attend_prefix(
                        tf._mla_q(h, lp, seg_cfg, qp), k, v, nk, None,
                        prefix_blocks, prefix_len, qp, tail_valid,
                        expand_rows=expand)
                    return attn, (nk,)
                return tf._block_body(
                    x, lp, seg_cfg, q_pos, None,
                    mla_latent_attend=mla_latent_attend, valid=tail_valid)

            def attend_write(q, k, v):
                if quantized:
                    # store int8 + scales; the tail attends its own fresh
                    # bf16 K/V plus the dequantized cached prefix
                    from distributed_llm_inferencing_tpu.ops.kvcache import (
                        quant_kv)
                    cks, cvs = scales
                    with jax.named_scope("kv_write"):
                        k8, ks = quant_kv(k)
                        v8, vs = quant_kv(v)
                        nk = write_block_run(ck, k8, tail_blocks)
                        nv = write_block_run(cv, v8, tail_blocks)
                        nks = write_block_run(cks, ks, tail_blocks)
                        nvs = write_block_run(cvs, vs, tail_blocks)
                    attn = paged_attend_prefix(
                        q, k, v, nk, nv, prefix_blocks, prefix_len, q_pos,
                        tail_valid,
                        sliding_window=tf._layer_window(seg_cfg, lp),
                        k_scale_layer=nks, v_scale_layer=nvs,
                        alibi=tf._alibi(seg_cfg),
                        softcap=seg_cfg.attn_softcap,
                        sinks=tf._sinks(seg_cfg, lp))
                    return attn, (nk, nv, nks, nvs)
                with jax.named_scope("kv_write"):
                    nk = write_block_run(ck, k, tail_blocks)
                    nv = write_block_run(cv, v, tail_blocks)
                win = tf._layer_window(seg_cfg, lp)
                attn = paged_attend_prefix(
                    q, k, v, nk, nv, prefix_blocks, prefix_len, q_pos,
                    tail_valid, sliding_window=win,
                    alibi=tf._alibi(seg_cfg), softcap=seg_cfg.attn_softcap,
                    sinks=tf._sinks(seg_cfg, lp),
                    kind=tf._layer_kind(cfg, win))
                return attn, (nk, nv)

            return tf._block_body(x, lp, seg_cfg, q_pos, attend_write,
                                  lora_ids=lora_ids, valid=tail_valid)
        return body

    x, cache_out = tf.scan_layer_stack(make_body, x, params, cfg,
                                       paged.planes())
    last_x = jnp.take_along_axis(
        x, jnp.maximum(tail_len - 1, 0)[:, None, None].astype(jnp.int32),
        axis=1)                                         # [B, 1, D]
    return tf.unembed(params, cfg, last_x)[:, 0], PagedKVCache(*cache_out)


# ---- a one-device pool's flat rows against the heads' own axis ----------
# ops/paged_kvcache.heads_in_rows: a one-device pool of fewer K/V heads
# than a tile has sublanes stores a position's heads side by side in one
# row; the same pool laid over a mesh keeps the heads as an axis. The
# scenario below runs the serving programs over either and hands back
# what they computed, the planes viewed by heads: tests hold the two
# the same (tests/test_paged.py, test_trinity.py, test_falcon_h1.py).

def flat_rows_scenario(params, cfg, devices, *, k=4, speculative=False):
    """A wave's write (two prompts and a padding row), then a second wave
    (a chunked prompt's next chunk over its own blocks and, where the
    model matches prefixes, a tail over the first prompt's blocks: a
    prefix hit), then a decode chunk of ``k`` passes (or a speculative
    one) over the three slots, on a pool made for ``devices`` devices.
    Returns (first wave's logits, second wave's, the chunk's outputs
    less the pool, the K and V planes by heads less the reserved
    block)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_llm_inferencing_tpu.models import transformer as tf
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        flat_pool, head_rows, init_paged_cache)
    bs, mb, r = 8, 8, 3
    paged = init_paged_cache(cfg, 40, bs, slots=r, devices=devices)
    assert flat_pool(cfg, paged) == (devices == 1)
    rng = np.random.default_rng(0)
    state = cfg.slot_cache    # per-slot state: a wave row names its slot

    def admit(tokens, tail_len, tail_blocks, prefix_blocks, prefix_len,
              slots, paged):
        fn = jax.jit(lambda *a: tf.paged_prefill_tail(
            params, cfg, *a[:-1], slots=a[-1] if state else None))
        return fn(*(jnp.asarray(x, jnp.int32) for x in (
            tokens, tail_len, tail_blocks, prefix_blocks, prefix_len)),
            paged, jnp.asarray(slots, jnp.int32))

    toks = rng.integers(3, cfg.vocab_size, (3, 16))
    logits1, paged = admit(toks, [16, 9, 1], [[1, 2], [3, 4], [0, 0]],
                           [[0, 0]] * 3, [0, 0, 0], [0, 1, r], paged)
    toks2 = rng.integers(3, cfg.vocab_size, (3, 8))
    # slot 0's next chunk; slot 2 over slot 0's two blocks (a state
    # model matches no prefix: a padding row in its place)
    hit = not state
    logits2, paged = admit(
        toks2, [5, 1, 8 if hit else 1], [[5], [0], [6 if hit else 0]],
        [[1, 2], [0, 0], [1, 2] if hit else [0, 0]],
        [16, 0, 16 if hit else 0], [0, r, 2 if hit else r], paged)
    tables = np.zeros((r, mb), np.int32)
    tables[0, :4], tables[1, :3] = [1, 2, 5, 7], [3, 4, 8]
    tables[2, :4] = [1, 2, 6, 9]
    context = np.asarray([21, 9, 24 if hit else 0], np.int32)
    budget = np.asarray([k, k, k if hit else 0], np.int32)
    z = np.zeros((r,), np.int32)
    last = np.asarray([5, 6, 7], np.int32)
    rows = (np.ones((r,), np.float32), z, np.ones((r,), np.float32),
            np.zeros((r,), bool))
    if speculative:
        hist = np.zeros((r, mb * bs + 1), np.int32)
        for i in range(r):
            hist[i, :context[i] + 1] = np.resize(
                rng.integers(3, cfg.vocab_size, 4), context[i] + 1)
        out = jax.jit(lambda pg: tf.paged_speculative_chunk(
            params, cfg, k, 2, hist[np.arange(r), context], hist, pg,
            tables, context, z, z, *rows, budget * 3, z - 1, 0))(paged)
    else:
        out = jax.jit(lambda pg: tf.decode_chunk_with_logits(
            params, cfg, k, last, pg, tables, context, z, z, *rows, budget,
            z - 1, 0))(paged)
        out = out[:-2] + out[-1:] + out[-2:-1]     # the pool last
    planes = out[-1].planes()[:2]
    if flat_pool(cfg, out[-1]):
        planes = [head_rows(p, cfg.num_kv_heads, cfg.head_dim)
                  for p in planes]
    return jax.device_get((logits1, logits2, out[:-1],
                           [p[:, 1:] for p in planes]))


def assert_flat_rows_serve_the_same(flat, by_heads, rtol=1e-4, atol=1e-5):
    """Two flat_rows_scenario results: tokens, emits and counts exactly,
    logits and planes to a float32's rounding. (The flat pool's tails
    and chunks attend rows of all the heads' columns under q
    zero-expanded to a row: the other heads' columns add exact zeros,
    but XLA sums a longer row in another order.)"""
    import jax
    import numpy as np
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(by_heads)):
        if np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)
        else:
            np.testing.assert_array_equal(a, b)


def served_as_under_auto(build, asked, env, monkeypatch):
    """``attn_backend`` / ``DLI_ATTENTION`` name the dense cache's flash
    kernels (ops/attention.resolve_backend); the batcher pins "xla" and
    reads neither. So ``build(asked)`` under ``env`` is the batcher
    ``build("auto")`` is: the same pinned config, the pool's read
    transformer._pool_kernel's choice. Returns it."""
    auto = build("auto")
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    b = build(asked)
    assert b.cfg.attn_backend == "xla" and b.cfg == auto.cfg
    assert b.pool_kernel == auto.pool_kernel
    return b


@functools.lru_cache(maxsize=None)
def jitted(fn, static_argnums=(1,)):
    """``fn(params, cfg, ...)`` of models/transformer.py behind one
    ``jax.jit`` a process, the configuration static as the serving
    programs have it. Called eagerly, such a function traces, lowers and
    compiles its ``lax.scan`` over the layers anew at every call (0.8 s a
    decode step at toy widths, compile cache or no): a loop of decode
    steps was a file's minutes. Not for a case that patches what the
    trace reads (``monkeypatch.setattr(transformer, ...)``): the first
    trace is the one every later call gets."""
    import jax
    return jax.jit(fn, static_argnums=static_argnums)


_PROGRAM_TABLES = {}


def share_programs(b):
    """Hand batcher ``b`` the admit and decode program tables of the
    process's first batcher that traces the same programs, so that a
    file's cases trace each once between them: the jitted closures of
    ``_admit_jit`` / ``_decode_jit`` / ``_spec_jit`` close over the
    pinned configuration, the block size, the reserved block and the
    mesh, take the weights as an argument, and are keyed by every shape
    (tail, prefix, wave; passes, slots, block-table columns). Returns
    ``b``. Not for a case that patches what a trace reads
    (``transformer._pool_ladder``, ``batcher.sample_batch``) or that
    reads whether a call compiled (tests/test_timeline.py,
    the adaptive controllers)."""
    key = (b.cfg, b.block_size, b._dummy, b.mesh_spec)
    b._prefill_fns, b._decode_fns = _PROGRAM_TABLES.setdefault(
        key, (b._prefill_fns, b._decode_fns))
    return b


def shared_batcher(*args, **kw):
    """``ContinuousBatcher(*args, **kw)`` over the process's shared
    program tables (share_programs)."""
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        ContinuousBatcher)
    return share_programs(ContinuousBatcher(*args, **kw))


def stop_worker(agent):
    """A WorkerAgent's teardown: its HTTP service shut, then every loaded
    model's batcher stopped and its loop joined. A scheduler thread still
    dispatching XLA work while the next case compiles, or while the
    interpreter is torn down, is a segmentation fault waiting for a
    loaded box."""
    agent.service.shutdown()
    for lm in list(agent.models.values()):
        if lm.batcher is not None:
            lm.batcher.stop()


# ---- lock-order watchdog gate (utils/locks.py) ------------------------
# When the suite runs with DLI_LOCK_CHECK=1 (scripts/check.sh arms it
# for the chaos suite), every runtime lock is instrumented and a
# dynamic lock-order inversion anywhere in the run must fail the build.
# The deliberate-inversion tests in tests/test_locks.py reset the
# watchdog behind themselves, so any report left at session end is real.

import pytest  # noqa: E402


# ---- a limit of its own for every test ---------------------------------
# A hang or a wait costs one case TEST_LIMIT_S, not the run its clock.
TEST_LIMIT_S = 120.0


@pytest.fixture(autouse=True)
def _limit_each_test(request):
    if (not hasattr(signal, "setitimer")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def expired(signum, frame):
        pytest.fail(f"{request.node.nodeid} passed its limit of "
                    f"{TEST_LIMIT_S:g} s (tests/conftest.py)",
                    pytrace=False)

    handler_was = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, handler_was)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: left out of tier-1 (-m 'not slow'); "
        "scripts/check.sh runs them in steps of their own")


def pytest_sessionfinish(session):
    """Name the threads that outlived their tests: one still inside
    XLA:CPU while the runtime is torn down is a segmentation fault at
    exit, and the next casualty should be tied to a name."""
    alive = sorted(t.name for t in threading.enumerate()
                   if t is not threading.main_thread() and t.is_alive())
    if alive:
        who = os.environ.get("PYTEST_XDIST_WORKER", "main")
        print(f"\n[{who}] threads alive at session end: {alive}",
              file=sys.stderr)


@pytest.fixture(scope="session", autouse=True)
def _lock_watchdog_gate():
    yield
    from distributed_llm_inferencing_tpu.utils import locks
    if locks.enabled():
        reports = locks.cycle_reports()
        assert not reports, (
            "lock-order watchdog detected potential deadlocks during "
            f"the run: {reports}")
