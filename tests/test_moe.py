"""The one expert dispatch (models/transformer.py _moe): sort the (token,
choice) pairs by expert, grouped matmuls over the experts' runs, unsort,
combine in float32. It must equal a per-token loop under any routing,
drop nothing, and be blind to pad rows; every MoE family the repo
registers must get from it the numbers the compute-every-expert
formulation it replaced gave (kept here as a test helper).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models import transformer
from distributed_llm_inferencing_tpu.models.params import init_params
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
from distributed_llm_inferencing_tpu.ops.quant import maybe_quantize
from distributed_llm_inferencing_tpu.parallel import sharding as shd
from distributed_llm_inferencing_tpu.parallel.mesh import (
    MeshSpec, create_mesh, validate_spec)

BASE = get_config("tiny-mixtral").replace(dtype="float32",
                                          attn_backend="xla")
PARAMS = None       # drawn by the first case that runs, not at import


@pytest.fixture(scope="module", autouse=True)
def _params():
    global PARAMS
    PARAMS = init_params(BASE, jax.random.PRNGKey(0), dtype=jnp.float32)


def _layer(cfg, seed=0, router=None, scale=0.1):
    """One MoE layer's float32 tree, leaves per the config's switches."""
    rng = np.random.default_rng(seed)
    E, D, I = cfg.num_experts, cfg.hidden_size, cfg.expert_intermediate_size

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    lp = {"router": {"w": w(D, E) if router is None else jnp.asarray(router)},
          "experts": {"gate": {"w": w(E, D, I)}, "up": {"w": w(E, D, I)},
                      "down": {"w": w(E, I, D)}}}
    if cfg.moe_router in ("deepseek_v3", "ernie", "topk_softmax"):
        lp["router"]["bias"] = w(E)
    if cfg.mlp_bias:    # gpt-oss per-expert biases
        for name, width in (("gate", I), ("up", I), ("down", D)):
            lp["experts"][name]["b"] = w(E, width)
    if cfg.moe_shared_experts:
        SI = I * cfg.moe_shared_experts
        lp.update(shared_gate={"w": w(D, SI)}, shared_up={"w": w(D, SI)},
                  shared_down={"w": w(SI, D)})
    return lp


def _expert_w(p):
    """[E, din, dout] float32 of an expert leaf, int8 levels rescaled."""
    if "w" in p:
        return np.asarray(p["w"], np.float32)
    return (np.asarray(p["q"], np.float32)
            * np.asarray(p["scale"], np.float32)[:, None, :])


def _every_expert(x, lp, cfg):
    """What `_moe_dense` computed: every expert for every token, weighted
    by the dense [N, E] gate (zero where not chosen), in float32."""
    idx, w = transformer._moe_route(x, lp, cfg)
    N, E = x.shape[0], cfg.num_experts
    gate = np.zeros((N, E), np.float32)
    np.put_along_axis(gate, np.asarray(idx), np.asarray(w), axis=1)
    ex, x32 = lp["experts"], np.asarray(x, np.float32)
    g = np.einsum("nd,edi->nei", x32, _expert_w(ex["gate"]))
    u = np.einsum("nd,edi->nei", x32, _expert_w(ex["up"]))
    if "b" in ex["gate"]:
        g, u = g + np.asarray(ex["gate"]["b"]), u + np.asarray(ex["up"]["b"])
    h = np.asarray(transformer._glu_h(jnp.asarray(g), jnp.asarray(u), cfg))
    out = np.einsum("nei,eid->ned", h, _expert_w(ex["down"]))
    if "b" in ex["down"]:
        out = out + np.asarray(ex["down"]["b"])
    out = np.einsum("ned,ne->nd", out, gate)
    if cfg.moe_shared_experts:
        sg, su, sd = (np.asarray(lp[k]["w"]) for k in
                      ("shared_gate", "shared_up", "shared_down"))
        out = out + (np.asarray(jax.nn.silu(x32 @ sg)) * (x32 @ su)) @ sd
    return out


def _token_loop(x, lp, cfg):
    """Token by token, choice by choice: the definition."""
    idx, w = (np.asarray(a) for a in transformer._moe_route(x, lp, cfg))
    ex, x32 = lp["experts"], np.asarray(x, np.float32)
    out = np.zeros_like(x32)
    for n in range(x32.shape[0]):
        for e, wt in zip(idx[n], w[n]):
            g = x32[n] @ _expert_w(ex["gate"])[e]
            u = x32[n] @ _expert_w(ex["up"])[e]
            h = np.asarray(jax.nn.silu(g)) * u
            out[n] += wt * (h @ _expert_w(ex["down"])[e])
    return out


GPT_OSS_LIKE = BASE.replace(moe_router="topk_softmax", mlp_bias=True,
                            moe_swiglu_limit=7.0, num_experts=8,
                            num_experts_per_tok=4)
FAMILIES = {
    "tiny-mixtral": BASE,
    "tiny-mixtral-int8": BASE.replace(quant="int8"),
    "tiny-mixtral-no-renorm": BASE.replace(moe_norm_topk=False),
    "tiny-deepseek": get_config("tiny-deepseek").replace(dtype="float32"),
    "tiny-kanana": get_config("tiny-kanana").replace(dtype="float32"),
    "ernie-router": get_config("tiny-kanana").replace(
        dtype="float32", moe_router="ernie", moe_routed_scale=1.0),
    "gpt-oss-biases-clamped-glu": GPT_OSS_LIKE,
}


@pytest.mark.parametrize("tokens", [1, 8, 64, 200])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_dispatch_gives_the_every_expert_numbers(family, tokens):
    """Every registered MoE family, under and over the old 32-token
    switch: the grouped dispatch equals compute-every-expert."""
    cfg = FAMILIES[family]
    lp = _layer(cfg, seed=tokens)
    if cfg.quant:
        lp = maybe_quantize({"layers": lp}, cfg)["layers"]
        assert "q" in lp["experts"]["up"]
    x = jnp.asarray(np.random.default_rng(tokens).standard_normal(
        (tokens, cfg.hidden_size)), jnp.float32)
    got, stats = transformer._moe(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(got), _every_expert(x, lp, cfg),
                               atol=2e-5, rtol=2e-4)
    stats = dict(zip(transformer.MOE_STATS, np.asarray(stats)))
    assert stats["rows"] == tokens * cfg.num_experts_per_tok
    assert stats["idle_rows"] == 0 and stats["layer_passes"] == 1


@pytest.mark.parametrize("routing", ["all-to-one-expert", "uniform"])
def test_dispatch_is_exact_under_any_imbalance(routing):
    """Every token to ONE expert (k=1; the old capacity form kept 2 of 8
    here and zeroed the rest) and a perfectly uniform routing: both
    equal the per-token loop, nothing dropped."""
    cfg = BASE.replace(num_experts_per_tok=1, num_experts=8)
    D, E, N = cfg.hidden_size, cfg.num_experts, 64
    rng = np.random.default_rng(2)
    router = np.zeros((D, E), np.float32)
    x = np.abs(rng.standard_normal((N, D))).astype(np.float32) + 0.1
    if routing == "all-to-one-expert":
        router[:, 5] = 1.0         # positive-sum tokens: expert 5 wins
    else:                          # token n -> expert n % E, by a marker
        x[:, :E] = 0.0
        x[np.arange(N), np.arange(N) % E] = 50.0
        router[np.arange(E), np.arange(E)] = 1.0
    lp = _layer(cfg, seed=3, router=router)
    got, stats = transformer._moe(jnp.asarray(x), lp, cfg)
    loop = _token_loop(jnp.asarray(x), lp, cfg)
    np.testing.assert_allclose(np.asarray(got), loop, atol=2e-5, rtol=2e-4)
    assert np.abs(loop).max(axis=1).min() > 1e-4     # every token has signal
    assert np.abs(np.asarray(got)).max(axis=1).min() > 1e-4   # none dropped
    stats = dict(zip(transformer.MOE_STATS, np.asarray(stats)))
    assert stats["experts_hit"] == (1 if routing != "uniform" else E)
    assert stats["max_load"] == (N if routing != "uniform" else N // E)


@pytest.mark.parametrize("family", ["tiny-mixtral", "tiny-kanana"])
@pytest.mark.parametrize("n_pad", [1, 17, 40])
def test_dispatch_is_blind_to_pad_rows(family, n_pad):
    """A real row's output is bit-identical whatever the pad rows hold
    and however many of the program's rows are pads."""
    cfg = FAMILIES[family]
    lp = _layer(cfg, seed=7)
    N = 48
    rng = np.random.default_rng(n_pad)
    x = rng.standard_normal((N, cfg.hidden_size)).astype(np.float32)
    alone, _ = transformer._moe(jnp.asarray(x), lp, cfg)
    run = jax.jit(lambda xx, v: transformer._moe(xx, lp, cfg, valid=v))
    valid = np.ones((N,), bool)
    valid[rng.choice(N, n_pad, replace=False)] = False
    outs = []
    for fill in (0.0, 1e4, -3.0):
        xp = x.copy()
        xp[~valid] = fill + rng.standard_normal((n_pad, cfg.hidden_size))
        out, stats = run(jnp.asarray(xp), jnp.asarray(valid))
        outs.append(np.asarray(out)[valid])
        stats = dict(zip(transformer.MOE_STATS, np.asarray(stats)))
        assert stats["idle_rows"] == n_pad * cfg.num_experts_per_tok
        assert stats["rows"] == (N - n_pad) * cfg.num_experts_per_tok
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
    # ... and the same numbers as with no pad in the program at all
    np.testing.assert_allclose(outs[0], np.asarray(alone)[valid],
                               atol=1e-6, rtol=1e-6)


def test_selection_takes_exactly_k_and_breaks_ties_by_index():
    """top-k of what the family ranks by: k experts even when scores tie
    (the thresholded form this replaced chose every tied expert)."""
    cfg = get_config("tiny-kanana").replace(dtype="float32")
    lp = _layer(cfg, seed=1)
    lp["router"]["w"] = jnp.zeros_like(lp["router"]["w"])   # all scores 0.5
    lp["router"]["bias"] = jnp.zeros_like(lp["router"]["bias"])
    x = jnp.ones((5, cfg.hidden_size), jnp.float32)
    idx, w = transformer._moe_route(x, lp, cfg)
    k = cfg.num_experts_per_tok
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.tile(np.arange(k), (5, 1)))
    np.testing.assert_allclose(np.asarray(w).sum(-1),
                               cfg.moe_routed_scale, rtol=1e-6)


def _prefill_logits(cfg, params, tokens, mesh=None, spec=None):
    B, S = tokens.shape
    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    lengths = jnp.full((B,), S, jnp.int32)
    if mesh is None:
        logits, _ = transformer.prefill(params, cfg, tokens, lengths, cache)
        return np.asarray(logits)
    with mesh:
        p = shd.shard_params(params, mesh, cfg, spec)
        cache = jax.device_put(cache,
                               shd.named(mesh, shd.cache_specs(cfg, spec)))
        logits, _ = jax.jit(
            lambda p, t, l, c: transformer.prefill(p, cfg, t, l, c)
        )(p, tokens, lengths, cache)
    return np.asarray(logits)


def test_ep_sharded_matches_unsharded():
    """Experts sharded over ep (and their widths over tp): GSPMD
    partitions the same formulation, no separate path."""
    rng = np.random.default_rng(0)
    spec = MeshSpec(ep=2, tp=2)
    validate_spec(spec, BASE)
    tokens = jnp.asarray(
        rng.integers(0, BASE.vocab_size, (2, 24)), jnp.int32)
    ref = _prefill_logits(BASE, PARAMS, tokens)
    got = _prefill_logits(BASE, PARAMS, tokens, create_mesh(spec), spec)
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)


def test_int8_experts_prefill_over_32_tokens():
    """An int8 MoE prompt over 32 tokens used to raise (the capacity
    form's scale broadcast); it runs, and close to the float weights."""
    rng = np.random.default_rng(0)
    cfg = BASE.replace(quant="int8")
    qparams = maybe_quantize(PARAMS, cfg)
    tokens = jnp.asarray(
        rng.integers(0, BASE.vocab_size, (1, 64)), jnp.int32)
    got = _prefill_logits(cfg, qparams, tokens)
    ref = _prefill_logits(BASE, PARAMS, tokens)
    assert np.isfinite(got).all()
    assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max()
