"""``ops.attention.attend`` against a plain float64 reference.

``attend`` keeps kv heads as an axis (query heads grouped by the kv head
they share) and takes the KV set in one or more segments; the reference
below does neither: it repeats K and V over the group, concatenates the
segments and works in float64 NumPy. Every model family's XLA attention
goes through this one function, so each of its options is a case here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inferencing_tpu.ops.attention import (
    alibi_slopes, attend)

B, H, HD = 2, 8, 16
CACHED = 12            # slots of the first (cache-like) segment
WINDOW = 5
FEATURES = {
    "plain": {},
    "window_static": {"sliding_window": WINDOW},
    "window_traced": {"sliding_window": jnp.asarray(WINDOW, jnp.int32)},
    "window_traced_off": {"sliding_window": jnp.asarray(-1, jnp.int32)},
    "alibi": {"alibi": alibi_slopes(H)},
    "softcap": {"softcap": 3.0},
    "sinks": {"sinks": jnp.linspace(-1.0, 2.0, H)},
    "scale": {"scale": 0.17},
}


def reference(q, ks, vs, q_pos, kv_poss, valids, sliding_window=None,
              alibi=None, softcap=None, sinks=None, scale=None):
    """Head-expanded, concatenated, float64."""
    f64 = lambda x: np.asarray(jnp.asarray(x, jnp.float32), np.float64)
    q, k, v = f64(q), np.concatenate([f64(k) for k in ks], 1), \
        np.concatenate([f64(v) for v in vs], 1)
    kv_pos = np.concatenate([np.asarray(p) for p in kv_poss], 1)
    valid = np.concatenate([np.asarray(m) for m in valids], 1)
    q_pos = np.asarray(q_pos)
    g = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) * (
        q.shape[-1] ** -0.5 if scale is None else scale)
    if softcap is not None:
        logits = np.tanh(logits / softcap) * softcap
    rel = (kv_pos[:, None, :] - q_pos[:, :, None])[:, None]    # [B,1,Sq,S]
    if alibi is not None:
        logits = logits + f64(alibi)[None, :, None, None] * rel
    mask = (rel <= 0) & valid[:, None, None, :]
    if sliding_window is not None and int(sliding_window) >= 0:
        mask = mask & (-rel < int(sliding_window))
    logits = np.where(mask, logits, -1e30)
    if sinks is not None:
        col = np.broadcast_to(f64(sinks)[None, :, None, None],
                              logits.shape[:-1] + (1,))
        logits = np.concatenate([logits, col], -1)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    if sinks is not None:
        probs = probs[..., :-1]
    return np.einsum("bhqk,bkhd->bqhd", probs, v)


def make_case(hkv, sq, segments, dtype, seed=0):
    """A cached stretch of per-row length plus ``sq`` fresh tokens, as
    one KV buffer or as (cache, fresh) segments."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    lengths = jnp.asarray([CACHED, CACHED - 5], jnp.int32)   # cached tokens
    q = jax.random.normal(ks[0], (B, sq, H, HD), dtype)
    k_cache = jax.random.normal(ks[1], (B, CACHED, hkv, HD), dtype)
    v_cache = jax.random.normal(ks[2], (B, CACHED, hkv, HD), dtype)
    k_new = jax.random.normal(ks[3], (B, sq, hkv, HD), dtype)
    v_new = jax.random.normal(ks[4], (B, sq, hkv, HD), dtype)
    cache_pos = jnp.broadcast_to(jnp.arange(CACHED, dtype=jnp.int32),
                                 (B, CACHED))
    cache_valid = cache_pos < lengths[:, None]
    q_pos = lengths[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]
    new_valid = jnp.ones((B, sq), bool)
    segs = ((k_cache, k_new), (v_cache, v_new), (cache_pos, q_pos),
            (cache_valid, new_valid))
    if segments == 2:
        return q, q_pos, segs
    return q, q_pos, tuple(jnp.concatenate(s, axis=1) for s in segs)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("feature", list(FEATURES))
@pytest.mark.parametrize("segments", [1, 2])
@pytest.mark.parametrize("sq", [1, 5])
@pytest.mark.parametrize("hkv", [H, 2, 1], ids=["G1", "G4", "GH"])
def test_attend_matches_float64_reference(hkv, sq, segments, feature, dtype):
    kw = FEATURES[feature]
    q, q_pos, (k, v, kv_pos, valid) = make_case(hkv, sq, segments,
                                                jnp.dtype(dtype))
    # array options go in as jit arguments, so a per-layer window is
    # traced here as it is in the layer stack
    static = {n: x for n, x in kw.items() if not hasattr(x, "shape")}
    traced = {n: x for n, x in kw.items() if hasattr(x, "shape")}
    got = jax.jit(lambda q, k, v, traced: attend(
        q, k, v, q_pos, kv_pos, valid, out_dtype=jnp.float32, **static,
        **traced))(q, k, v, traced)
    as_segs = (lambda x: x if segments == 2 else (x,))
    want = reference(q, as_segs(k), as_segs(v), q_pos, as_segs(kv_pos),
                     as_segs(valid), **kw)
    # the reference is fed the inputs' own (f32 or bf16) values, so both
    # dtypes meet it at f32 accuracy: nothing is rounded on the way
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=1e-5, atol=1e-5)


def test_attend_output_dtype_follows_q():
    q, q_pos, (k, v, kv_pos, valid) = make_case(2, 1, 2, jnp.bfloat16)
    out = attend(q, k, v, q_pos, kv_pos, valid)
    wide = attend(q, k, v, q_pos, kv_pos, valid, out_dtype=jnp.float32)
    assert out.dtype == jnp.bfloat16 and wide.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(wide.astype(jnp.bfloat16)))


def _dot_generals(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dot_generals(sub)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_no_f32_operand_at_default_precision(dtype):
    """The MXU rounds an f32 operand of a default-precision dot to bf16:
    the probabilities going into ``p @ V`` would lose 16 bits. Every
    dot_general of ``attend`` that sees an f32 operand asks for HIGHEST,
    and none of them takes K or V in another dtype than it is stored in."""
    q, q_pos, (k, v, kv_pos, valid) = make_case(2, 1, 2, jnp.dtype(dtype))
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: attend(q, k, v, q_pos, kv_pos, valid))(q, k, v)
    dots = list(_dot_generals(jaxpr.jaxpr))
    assert len(dots) == 4          # q.K and p.V, once per segment
    for eqn in dots:
        dtypes = [x.aval.dtype for x in eqn.invars]
        assert jnp.dtype(dtype) in dtypes, "K or V converted before the dot"
        assert eqn.params["preferred_element_type"] == jnp.float32
        if jnp.dtype("float32") in dtypes:
            prec = eqn.params["precision"]
            prec = prec if isinstance(prec, tuple) else (prec, prec)
            assert all(p == jax.lax.Precision.HIGHEST for p in prec), eqn
