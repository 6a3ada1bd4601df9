"""Plain reference for the Falcon-H1 architecture (tiiuae Falcon-H1 0.5B to
34B, `model_type: falcon_h1`): every block runs a Mamba-2 state-space
mixer BESIDE its attention heads, as `FalconH1ForCausalLM` describes it
(transformers' `modeling_falcon_h1.py`, `torch_forward`). With D the
hidden size and u the block's normed input:

    x = embed(ids) * embedding_multiplier
    for every block:
      u = RMSNorm(x; input_layernorm)
      x = x + ssm_out_multiplier * Mixer(u)
            + attention_out_multiplier * Attn(attention_in_multiplier * u)
      x = x + MLP(RMSNorm(x; pre_ff_layernorm))
    logits = lm_head(RMSNorm(x; final_layernorm)) * lm_head_multiplier

    Attn: q, k, v, o without bias; k = k_proj(.) * key_multiplier before
      the rotation; full rotary (halves convention), causal GQA softmax,
      scale head_dim ** -0.5 (head_dim is the config's key, not D / heads).
    MLP:  down(up(n) * silu(gate(n) * mlp_multipliers[0]))
          * mlp_multipliers[1]
    Mixer (Mamba-2; H heads of P, state N, G groups, conv width K):
      [z | x | B | C | dt] = in_proj(u * ssm_in_multiplier) * mup_vector
          (mup_vector: ssm_multipliers[0..4] over the five parts)
      [x | B | C] = silu(causal depthwise conv1d_K([x | B | C]) + bias)
      dt = softplus(dt + dt_bias);  A = -exp(A_log), a number a head
      a head p of group g = p // (H / G) carries a state S [P, N]:
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t^g
          y_t = S_t C_t^g + D x_t
      y = y * silu(z); RMS over each of the G groups of H P / G; * scale
          (mamba_rms_norm true, mamba_norm_before_gate false)
      out_proj(y)

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: the mixer is the recurrence
token by token (a `lax.scan` over positions: no chunks, no cache, no
batching), attention one query head at a time over the whole sequence,
every multiplier applied at run time where the source applies it. It
reads the system's parameter tree (both see the same seeded weights, the
bf16 values read as float32) and imports nothing from the package: only
the tree's names are shared (`attn_norm` = input_layernorm, `mlp_norm` =
pre_ff_layernorm, `in_proj`, `conv` {w [K, C], b}, `dt_bias`, `A_log`,
`D`, `ssm_norm` = mamba.norm, `out_proj`). `benchmarks/chip/reference/`
holds a copy, which is the benchmark's yardstick.

Departures from `modeling_falcon_h1.py`, each the reference's own:
- the source computes prefill as the chunked SSD scan (`mamba_chunk_size`
  positions a chunk); this is the recurrence it equals, in float32.
- the source keeps activations in the model's dtype (bf16) and the
  recurrent state in the cache's dtype; here everything is float32.
- `time_step_limit` is (0, inf) as published, so dt is not clamped;
  `mamba_proj_bias`, `projectors_bias`, `attention_bias`, `mlp_bias` are
  false as published and have no leaves.
- no padding mask (`apply_mask_to_padding_states`): one sequence, no
  padding.

`forward` runs a whole sequence; `embed`, `layer` and `logits` run it
piece by piece (a layer at a time, the head over chosen positions),
which is how the benchmark's comparison computes it in blocks;
`final_states` returns each layer's state and conv window after the
last position: what a serving slot must hold then.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def arch_of(cfg) -> dict:
    """The architecture's numbers, under the source config's names, from
    an object with the system's ModelConfig attributes."""
    c = cfg.ssm
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "num_hidden_layers": cfg.num_layers,
        "vocab_size": cfg.vocab_size,
        "mamba_d_ssm": c.d_ssm, "mamba_n_heads": c.n_heads,
        "mamba_d_head": c.d_head, "mamba_d_state": c.d_state,
        "mamba_n_groups": c.n_groups, "mamba_d_conv": c.d_conv,
        "mamba_conv_bias": c.conv_bias,
        "embedding_multiplier": cfg.embed_scale,
        "lm_head_multiplier": cfg.logit_scale,
        "attention_in_multiplier": c.attn_in_multiplier,
        "attention_out_multiplier": c.attn_out_multiplier,
        "key_multiplier": c.key_multiplier,
        "mlp_multipliers": tuple(c.mlp_multipliers),
        "ssm_in_multiplier": c.in_multiplier,
        "ssm_out_multiplier": c.out_multiplier,
        "ssm_multipliers": tuple(c.multipliers),
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


def attention(lp, arch, u, positions):
    """Causal GQA over the whole sequence. u [T, D] (the block's normed
    input, before attention_in_multiplier) -> [T, D] before
    attention_out_multiplier."""
    T = u.shape[0]
    H, Hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch["head_dim"]
    a_in = u * arch["attention_in_multiplier"]
    q = rope((a_in @ _w(lp["q"])).reshape(T, H, hd), positions,
             arch["rope_theta"])
    k = rope(((a_in @ _w(lp["k"])) * arch["key_multiplier"])
             .reshape(T, Hkv, hd), positions, arch["rope_theta"])
    v = (a_in @ _w(lp["v"])).reshape(T, Hkv, hd)
    mask = positions[:, None] >= positions[None, :]               # [q, k]

    def head(h):                          # one query head over every key
        kv = h // (H // Hkv)
        scores = (q[:, h] @ k[:, kv].T) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return probs @ v[:, kv]                                   # [T, hd]
    a = jnp.swapaxes(jax.lax.map(head, jnp.arange(H)), 0, 1)      # [T, H, hd]
    return a.reshape(T, H * hd) @ _w(lp["o"])


def mixer(lp, arch, u, conv_bias=True, skip_d=False):
    """The Mamba-2 mixer over a whole sequence, token by token. u [T, D]
    -> (out [T, D] before ssm_out_multiplier, the state [H, P, N] and the
    conv window [K - 1, C] after the last position). `conv_bias` and
    `skip_d` are negative controls' (the bias, the D x term left out)."""
    T = u.shape[0]
    H, P, N = arch["mamba_n_heads"], arch["mamba_d_head"], arch["mamba_d_state"]
    G, K, ds = arch["mamba_n_groups"], arch["mamba_d_conv"], arch["mamba_d_ssm"]
    gn = G * N
    mup = jnp.concatenate([jnp.full((n,), m, F32) for n, m in zip(
        (ds, ds, gn, gn, H), arch["ssm_multipliers"])])
    zxbcdt = ((u * arch["ssm_in_multiplier"]) @ _w(lp["in_proj"])) * mup
    z, xbc, dt = (zxbcdt[:, :ds], zxbcdt[:, ds:2 * ds + 2 * gn],
                  zxbcdt[:, 2 * ds + 2 * gn:])
    # causal depthwise convolution: out_t = sum_j w_j in_{t-(K-1)+j}
    w = lp["conv"]["w"].astype(F32)                               # [K, C]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[j:j + T] * w[j] for j in range(K))
    if arch["mamba_conv_bias"] and conv_bias:
        conv = conv + lp["conv"]["b"].astype(F32)
    conv = jax.nn.silu(conv)
    x = conv[:, :ds].reshape(T, H, P)
    b = conv[:, ds:ds + gn].reshape(T, G, N)
    c = conv[:, ds + gn:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))          # [T, H]
    a = -jnp.exp(lp["A_log"].astype(F32))                         # [H]
    grp = jnp.arange(H) // (H // G)

    def step(s, inp):                     # s [H, P, N]
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[grp][:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t[grp])
    state, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, b, c, dt))
    if not skip_d:
        y = y + lp["D"].astype(F32)[None, :, None] * x
    y = y.reshape(T, ds) * jax.nn.silu(z)
    yg = y.reshape(T, G, ds // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                            + arch["rms_norm_eps"])
    y = yg.reshape(T, ds) * lp["ssm_norm"]["scale"].astype(F32)
    return y @ _w(lp["out_proj"]), state, padded[T:]


def layer_params(params, i: int):
    """Layer i's slice of the stacked layer tree."""
    return jax.tree.map(lambda a: a[i], params["layers"])


def layer(lp, arch, x, positions, **controls):
    """One block. x [T, D] -> (x [T, D], state, conv window)."""
    eps = arch["rms_norm_eps"]
    u = rms_norm(x, lp["attn_norm"]["scale"], eps)
    mixed, state, window = mixer(lp, arch, u, **controls)
    x = (x + arch["ssm_out_multiplier"] * mixed
         + arch["attention_out_multiplier"] * attention(lp, arch, u,
                                                        positions))
    n = rms_norm(x, lp["mlp_norm"]["scale"], eps)
    g0, g1 = arch["mlp_multipliers"]
    m = ((n @ _w(lp["up"])) * jax.nn.silu((n @ _w(lp["gate"])) * g0)) \
        @ _w(lp["down"]) * g1
    return x + m, state, window


def embed(params, arch, tokens):
    return params["embed"]["tokens"].astype(F32)[tokens] \
        * arch["embedding_multiplier"]


def logits(params, arch, x):
    """The final norm and the head. x [T, D]."""
    x = rms_norm(x, params["final_norm"]["scale"], arch["rms_norm_eps"])
    return (x @ _w(params["lm_head"])) * arch["lm_head_multiplier"]


def _run(params, arch, tokens, **controls):
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = embed(params, arch, tokens)
    states, windows = [], []
    for i in range(arch["num_hidden_layers"]):
        x, s, w = layer(layer_params(params, i), arch, x, positions,
                        **controls)
        states.append(s)
        windows.append(w)
    return logits(params, arch, x), jnp.stack(states), jnp.stack(windows)


def forward(params, arch, tokens, **controls):
    """Logits [T, V] of a whole sequence, no cache. One jit a call."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: _run(p, arch, t, **controls)[0])(
            params, tokens)


def final_states(params, arch, tokens, **controls):
    """(states [L, H, P, N], conv windows [L, K - 1, C]) after the
    sequence's last position: what a serving slot holds then."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: _run(p, arch, t, **controls)[1:])(
            params, tokens)
