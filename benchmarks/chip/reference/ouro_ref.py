"""Plain reference for the Ouro architecture (ByteDance Ouro-1.4B / 2.6B,
"LoopLM": a decoder whose whole layer stack runs `total_ut_steps` times a
token over ONE set of weights), as `OuroForCausalLM` describes it
(`modeling_ouro.py` beside the published checkpoint; the installed
`transformers` has no `ouro` to import). With L layers, T steps and x the
token embeddings:

    for u in 0..T-1:                        # the same L layers' weights
      for l in 0..L-1:
        a = Attention_l(RMSNorm(x; attn_norm_l))    # q, k, v, o, no bias;
                                            # rotary over the whole head;
                                            # causal softmax over the keys
                                            # and values THIS step wrote at
                                            # this layer (a cache would hold
                                            # them in plane u*L + l)
        x = x + RMSNorm(a; attn_post_norm_l)        # sandwich: the
                                            # sublayer's OUTPUT is normed
        n = RMSNorm(x; mlp_norm_l)
        x = x + RMSNorm(W_down(silu(W_gate n) * W_up n); mlp_post_norm_l)
      x = RMSNorm(x; final_norm)            # after EVERY pass: the next
                                            # pass's input, this pass's result
      lambda_u = sigmoid(w_exit . x + b_exit)       # the exit gate
    logits = x_exit W_head

The exit step is the first whose cumulative exit mass reaches
`early_exit_threshold`: step u exits with probability lambda_u of what
has not exited yet, the last step takes the rest, so the mass after the
last step is 1. At the published threshold 1 no earlier step can reach
it (a sigmoid is below 1) and every token runs all T steps: `exit_steps`
computes the rule and `forward` asserts the result is T - 1.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no cache, no batching, no
kernels; every position attends to the whole sequence under the causal
mask, one query head at a time. It reads the system's parameter tree (so
both see the same seeded weights, the bf16 values read as float32) and
imports nothing from the package: only the tree's names are shared
(the four norms of a layer are `attn_norm`, `attn_post_norm`, `mlp_norm`,
`mlp_post_norm`: the checkpoint's `input_layernorm`, `input_layernorm_2`,
`post_attention_layernorm`, `post_attention_layernorm_2`).
`benchmarks/chip/reference/` holds a copy, which is the benchmark's
yardstick.

Departures of the SYSTEM from the published order of operations, none of
them the reference's:
- the exit gate is loaded (`exit_gate`) and not evaluated when serving:
  at threshold 1 its value cannot change which step's result is used. A
  threshold below 1 is not served (ROADMAP R-M).
- the system keeps activations in the model's dtype (bf16 as published):
  the residual stream is rounded after every add, each norm's output and
  each projection's output once, K and V once where they are written to
  the pool (the side buffers and the pool are bf16), the softmax and the
  norms' statistics are float32. With T x L = 192 layer applications a
  token where a 48-layer model has 48, the roundings of the residual
  stream are four times as many; the final norm between passes brings
  the stream back to unit scale each time.
- the system runs the last pass's final norm inside `unembed` (norm,
  then head), the earlier passes' between the passes; the values are the
  published ones.

`forward` runs a whole sequence; `embed`, `layer`, `between` and `logits`
run it piece by piece (a layer at a time, the head over chosen
positions), which is how the benchmark's comparison computes it;
`keys_values` is a layer's K and V on their own, which that comparison
holds the pool's planes to, a (step, layer) pair at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def arch_of(cfg) -> dict:
    """The architecture's numbers, under the source config's names, from
    an object with the system's ModelConfig attributes."""
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "num_hidden_layers": cfg.num_layers,
        "total_ut_steps": cfg.loop_steps,
        "early_exit_threshold": 1.0,
        "vocab_size": cfg.vocab_size,
    }


def _w(p):
    return p["w"].astype(F32)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """x [T, H, d] rotated at `positions` [T]: full rotary, halves
    convention, x*cos + rotate_half(x)*sin."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]     # [T, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def keys_values(lp, arch, x, positions):
    """(k rotated, v), each [T, Hkv, hd], that a layer makes of its
    input x [T, D] (already normed): what a cache would hold in the
    (step, layer) pair's plane."""
    T, Hkv, hd = x.shape[0], arch["num_key_value_heads"], arch["head_dim"]
    k = rope((x @ _w(lp["k"])).reshape(T, Hkv, hd), positions,
             arch["rope_theta"])
    return k, (x @ _w(lp["v"])).reshape(T, Hkv, hd)


def attention(lp, arch, x, positions):
    """Causal attention over the whole sequence. x [T, D] (already
    normed) -> [T, D]."""
    T = x.shape[0]
    H, Hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch["head_dim"]
    q = rope((x @ _w(lp["q"])).reshape(T, H, hd), positions,
             arch["rope_theta"])
    k, v = keys_values(lp, arch, x, positions)
    mask = positions[:, None] >= positions[None, :]               # [q, k]

    def head(h):                          # one query head over every key
        kv = h // (H // Hkv)
        scores = (q[:, h] @ k[:, kv].T) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return probs @ v[:, kv]                                   # [T, hd]
    a = jnp.swapaxes(jax.lax.map(head, jnp.arange(H)), 0, 1)      # [T, H, hd]
    return a.reshape(T, H * hd) @ _w(lp["o"])


def layer_params(params, i: int):
    """Layer i's slice of the stacked layer tree."""
    return jax.tree.map(lambda a: a[i], params["layers"])


def layer(lp, arch, x, positions):
    """One layer, sandwich norms, given its own parameters. x [T, D]."""
    eps = arch["rms_norm_eps"]
    a = attention(lp, arch, rms_norm(x, lp["attn_norm"]["scale"], eps),
                  positions)
    x = x + rms_norm(a, lp["attn_post_norm"]["scale"], eps)
    n = rms_norm(x, lp["mlp_norm"]["scale"], eps)
    m = (jax.nn.silu(n @ _w(lp["gate"])) * (n @ _w(lp["up"]))) \
        @ _w(lp["down"])
    return x + rms_norm(m, lp["mlp_post_norm"]["scale"], eps)


def embed(params, arch, tokens):
    return params["embed"]["tokens"].astype(F32)[tokens]


def between(params, arch, x):
    """The final norm, after every pass: a pass's result and the next
    pass's input."""
    return rms_norm(x, params["final_norm"]["scale"], arch["rms_norm_eps"])


def exit_gate(params, arch, x):
    """lambda [T]: the gate on a pass's (normed) result."""
    g = params["exit_gate"]
    return jax.nn.sigmoid(x @ g["w"].astype(F32)
                          + g["b"].astype(F32))[:, 0]


def exit_steps(lambdas, threshold):
    """lambdas [steps, T] -> (exit step [T], cumulative exit mass
    [steps, T]). Step u exits lambda_u of what is still running, the
    last step all of it; a position exits at the first step whose
    cumulative mass reaches `threshold`."""
    steps = lambdas.shape[0]
    lam = jnp.concatenate([lambdas[:-1], jnp.ones_like(lambdas[-1:])])
    running = jnp.concatenate(
        [jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam[:-1], axis=0)])
    mass = jnp.cumsum(lam * running, axis=0)
    reached = mass >= threshold
    first = jnp.argmax(reached, axis=0)
    return jnp.where(reached.any(axis=0), first, steps - 1), mass


def logits(params, arch, x):
    """The head over a pass's (normed) result. x [T, D]."""
    return x @ _w(params["lm_head"])


def forward_steps(params, arch, tokens, steps):
    """(logits [T, V] of the exit step's result, exit step [T]) of a
    whole sequence run for `steps` passes, no cache."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = embed(params, arch, tokens)
    results, lambdas = [], []
    for _ in range(steps):
        for i in range(arch["num_hidden_layers"]):
            x = layer(layer_params(params, i), arch, x, positions)
        x = between(params, arch, x)
        results.append(x)
        lambdas.append(exit_gate(params, arch, x))
    step, _ = exit_steps(jnp.stack(lambdas), arch["early_exit_threshold"])
    x = jnp.take_along_axis(jnp.stack(results), step[None, :, None],
                            axis=0)[0]
    return logits(params, arch, x), step


def forward(params, arch, tokens, steps=None):
    """Logits [T, V] of a whole sequence, no cache. `steps` (a negative
    control's T - 1) overrides `total_ut_steps`. One jit a call: run op
    by op it dispatches every layer's every operation on every call."""
    steps = arch["total_ut_steps"] if steps is None else steps
    with jax.default_matmul_precision("highest"):
        out, step = jax.jit(
            lambda p, t: forward_steps(p, arch, t, steps))(params, tokens)
    if arch["early_exit_threshold"] >= 1.0:
        assert bool(jnp.all(step == steps - 1)), (
            "at threshold 1 every position runs every step")
    return out
