"""Plain reference for the mimo_v2 architecture (XiaomiMiMo MiMo-V2-Flash /
MiMo-V2.5: windowed and full attention layers of different shapes in one
stack, q and k heads wider than the value heads, a partial rotation, a
scale on the values, a learned sink a query head in the windowed layers, a
leading dense layer and sigmoid-routed experts with no shared one), as its
`config.json` and model card describe it (`model_type: mimo_v2`).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no cache, no ring, no batching,
no kernels. Every position attends to the whole sequence under the layer's
full mask (causal, and `0 <= q - k < sliding_window` on a windowed layer),
one query head at a time; a windowed layer's sink is one more softmax
column that carries no value row; the value scale is applied at run time,
to the projected values; the router runs over all `n_routed_experts`
columns and the chosen experts are computed one at a time, each over every
position under a dense [T] column of the router's weights (zero where the
expert was not chosen).

Per layer (D hidden, H query heads of `head_dim`, value heads of
`v_head_dim`; `hybrid_layer_pattern[l]` 1 = windowed, 0 = full;
`moe_layer_freq[l]` 0 = dense MLP, 1 = experts):

    h = RMSNorm(x);  x = x + Attn_kind(h);  u = RMSNorm(x)
    x = x + (MLP(u) if dense else MoE(u))

    Attn_full: 4 K/V heads (`num_key_value_heads`), rotary base
      `rope_theta`, causal over the whole context, no sink.
    Attn_swa:  8 K/V heads (`swa_num_key_value_heads`), rotary base
      `swa_rope_theta`, the window, and `sinks[h]` appended to every
      softmax row of query head h and dropped after normalisation.
    both: v = attention_value_scale * Wv h; the first
      int(head_dim * partial_rotary_factor) columns of every q and k head
      are rotated (halves convention), the rest are not; scores
      q.k / sqrt(head_dim), softmax in float32.
    MoE: s = sigmoid(Wr u); chosen = top-k of s + e_score_correction_bias;
      w = s[chosen] / sum(s[chosen]) (norm_topk_prob), times
      routed_scaling_factor (null: 1); y = sum_i w_i down_i(silu(gate_i u)
      * up_i u).

`experts_held=(first, count)` is one chip's share under expert
parallelism: the tree's expert leaves then hold experts first ..
first + count - 1 alone, in order; the router still runs over all the
columns and the weights stay normalised over all the chosen experts, and
the absent experts' terms are left out of the sum (nothing stands in for
the other chips or their exchange: the partial sum goes on to the next
layer). `experts_held=None` computes the whole layer from leaves that
hold every expert.

It reads the system's parameter tree (so both see the same seeded weights,
the bf16 values read as float32) and imports nothing from the package:
only the tree's names are shared (`layers_dense` the leading dense layers,
`layers` the windowed MoE layers in order, `layers_full` the full MoE
layers in order). `benchmarks/chip/reference/` holds a copy, which is the
benchmark's yardstick.

Departures from the published description, and what is assumed where it
is silent:
- the 3 multi-token-prediction layers, the vision tower and the audio
  encoder are left out: the published config gives no size of any of
  them, and a text request passes through none.
- WHICH columns rotate is assumed: the first int(head_dim *
  partial_rotary_factor) of each head (as GPT-NeoX / Phi do), in the
  halves convention.
- no q / k norm is assumed (the config names none).
- `attention_chunk_size` and `attention_projection_layout: fused_qkv` are
  taken to change no arithmetic (a fused projection is the three side by
  side).
- the value scale is applied to the projected values; it commutes with
  the weighted sum, so where it is applied is no assumption. (The system
  does the same in bf16, and its caches hold the scaled rows.)
- the system routes under the name `deepseek_v3` with one group
  (`e_score_correction_bias` is its `router.bias`), sorts the (token,
  choice) pairs by expert and runs grouped matmuls; a choice that falls
  on an absent expert sorts into no expert's run.

`forward` runs a whole sequence; `embed`, `layer` and `logits` run it piece
by piece (a layer at a time, the head over chosen positions), which is how
it fits beside the model at published widths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def arch_of(cfg) -> dict:
    """The architecture's numbers, under the source config's names, from
    an object with the system's ModelConfig attributes."""
    nd = cfg.dense_prefix_layers
    return {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "swa_num_key_value_heads": cfg.swa.num_kv_heads,
        "head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim,
        "partial_rotary_factor": cfg.rope_pct,
        "rope_theta": cfg.rope_theta,
        "swa_rope_theta": cfg.swa.rope_theta,
        "sliding_window": cfg.sliding_window,
        "add_swa_attention_sink_bias": cfg.swa.sinks,
        "add_full_attention_sink_bias": cfg.attn_sinks,
        "attention_value_scale": cfg.attn_value_scale,
        "layernorm_epsilon": cfg.norm_eps,
        "num_hidden_layers": cfg.num_layers,
        "hybrid_layer_pattern": list(cfg.swa.pattern),
        "moe_layer_freq": [0] * nd + [1] * (cfg.num_layers - nd),
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.moe_norm_topk,
        "routed_scaling_factor": cfg.moe_routed_scale,
    }


def _w(p):
    return p["w"].astype(F32)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, positions, theta, rot):
    """x [T, H, d]: the first `rot` columns of every head rotated at
    `positions` [T] (halves convention over those columns, x*cos +
    rotate_half(x)*sin), the other d - rot left as they are."""
    xr, rest = x[..., :rot], x[..., rot:]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]   # [T, rot/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], axis=-1)


def attention(lp, arch, x, positions, windowed: bool, kv_pairs=False):
    """Grouped-query attention of one kind over the whole sequence.
    x [T, D] (already normed) -> [T, D]. `kv_pairs` is a control (a
    comparison must FAIL with it): the windowed layers' K/V heads read
    as half as many, every other one, the full layers' grouping."""
    T = x.shape[0]
    H, hd, vd = (arch["num_attention_heads"], arch["head_dim"],
                 arch["v_head_dim"])
    Hkv = arch["swa_num_key_value_heads" if windowed
               else "num_key_value_heads"]
    theta = arch["swa_rope_theta" if windowed else "rope_theta"]
    sink = arch["add_swa_attention_sink_bias" if windowed
                else "add_full_attention_sink_bias"]
    rot = int(hd * arch["partial_rotary_factor"])
    q = rope((x @ _w(lp["q"])).reshape(T, H, hd), positions, theta, rot)
    k = rope((x @ _w(lp["k"])).reshape(T, Hkv, hd), positions, theta, rot)
    v = (x @ _w(lp["v"])).reshape(T, Hkv, vd)
    if arch["attention_value_scale"] is not None:
        v = v * arch["attention_value_scale"]
    dist = positions[:, None] - positions[None, :]               # [q, k]
    mask = dist >= 0
    if windowed:
        mask = mask & (dist < arch["sliding_window"])
    sinks = (lp["sinks"].astype(F32) if sink
             else jnp.full((H,), -jnp.inf, F32))

    def head(h):                          # one query head over every key
        kv = h // (H // Hkv)
        if kv_pairs and windowed:
            kv = kv // 2 * 2
        scores = (q[:, h] @ k[:, kv].T) * hd ** -0.5
        scores = jnp.where(mask, scores, -jnp.inf)
        # the sink: one more column, dropped after normalisation
        col = jnp.broadcast_to(sinks[h], (T, 1))
        probs = jax.nn.softmax(jnp.concatenate([scores, col], -1), axis=-1)
        return probs[:, :T] @ v[:, kv]                           # [T, vd]
    a = jnp.swapaxes(jax.lax.map(head, jnp.arange(H)), 0, 1)     # [T, H, vd]
    return a.reshape(T, H * vd) @ _w(lp["o"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router_weights(lp, arch, x):
    """Dense [T, n_routed_experts] weights, zero where an expert was not
    chosen: sigmoid scores, selection by scores + correction bias, the
    unbiased scores normalised over the chosen, times the scaling factor."""
    scores = jax.nn.sigmoid(x @ lp["router"]["w"].astype(F32))   # [T, E]
    idx = jax.lax.top_k(scores + lp["router"]["bias"].astype(F32),
                        arch["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(scores, idx, axis=-1)        # unbiased scores
    if arch["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * (arch["routed_scaling_factor"] or 1.0)
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w)


def moe(lp, arch, x, experts_held=None):
    """The routed experts' sum, one expert at a time over every position
    under its column of the dense weights. With `experts_held` the sum
    runs over the held experts alone (the leaves hold just those)."""
    dense_w = router_weights(lp, arch, x)
    first, count = experts_held or (0, arch["n_routed_experts"])
    ex = lp["experts"]

    def one(j, acc):                       # leaf j is expert first + j
        def pick(p):
            return jax.lax.dynamic_index_in_dim(
                p["w"], j, keepdims=False).astype(F32)
        y = swiglu(x, pick(ex["gate"]), pick(ex["up"]), pick(ex["down"]))
        return acc + y * jax.lax.dynamic_slice_in_dim(
            dense_w, first + j, 1, axis=1)
    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(x))


def layer_params(params, arch, i):
    """Layer i's tree out of the system's stacks (`layers_dense` the
    leading dense layers, `layers` the windowed MoE layers, `layers_full`
    the full MoE layers; each [n, ...]-stacked or a list of layers)."""
    pattern, freq = arch["hybrid_layer_pattern"], arch["moe_layer_freq"]
    if not freq[i]:
        name, j = "layers_dense", sum(1 for f in freq[:i] if not f)
    else:
        name = "layers" if pattern[i] else "layers_full"
        j = sum(1 for p, f in zip(pattern[:i], freq[:i])
                if f and p == pattern[i])
    stack = params[name]
    if isinstance(stack, (list, tuple)):
        return stack[j]
    return jax.tree.map(lambda a: a[j], stack)


def embed(params, arch, tokens):
    return jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)


@jax.default_matmul_precision("highest")
def layer(params, arch, i, x, positions, experts_held=None, kv_pairs=False):
    """One decoder layer over the whole sequence. x [T, D] float32."""
    lp = layer_params(params, arch, i)
    eps = arch["layernorm_epsilon"]
    x = x + attention(lp, arch, rms_norm(x, lp["attn_norm"]["scale"], eps),
                      positions, bool(arch["hybrid_layer_pattern"][i]),
                      kv_pairs)
    u = rms_norm(x, lp["mlp_norm"]["scale"], eps)
    if arch["moe_layer_freq"][i]:
        return x + moe(lp, arch, u, experts_held)
    return x + swiglu(u, _w(lp["gate"]), _w(lp["up"]), _w(lp["down"]))


@jax.default_matmul_precision("highest")
def logits(params, arch, x):
    """Final norm and the untied head over the rows of x [n, D]."""
    return rms_norm(x, params["final_norm"]["scale"],
                    arch["layernorm_epsilon"]) @ _w(params["lm_head"])


def forward(params, arch, tokens, rows=None, experts_held=None,
            kv_pairs=False):
    """Logits [T or len(rows), V] of one sequence `tokens` [T]: a full
    forward pass with no cache. `rows` picks the positions whose logits
    are wanted (the head is the largest matrix)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = embed(params, arch, tokens)
    for i in range(arch["num_hidden_layers"]):
        x = layer(params, arch, i, x, positions, experts_held, kv_pairs)
    return logits(params, arch, x if rows is None else x[jnp.asarray(rows)])
