"""Plain reference for the afmoe architecture (Arcee Trinity: grouped-query
attention with a sigmoid gate on its output, q/k RMSNorm, three windowed
rotary layers to one full layer without rotation, four norms a layer,
sigmoid-routed experts + a shared expert behind leading dense layers,
a sqrt(hidden) multiplier on the embedding), as `AfmoeForCausalLM`
describes it (transformers `models/afmoe/modeling_afmoe.py`).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no cache, no batching, no
kernels, no bounded read. Every position attends to the whole sequence
under the layer's full mask (causal, and `0 <= q - k < sliding_window` on
a windowed layer), one query head at a time; the router is
`AfmoeTokenChoiceRouter` line by line; each expert is computed for every
position and weighted by a dense [T, E] mask of the router's weights
(zero where the expert was not chosen), one expert at a time.

It reads the system's parameter tree (so both see the same seeded weights,
the bf16 values read as float32) and imports nothing from the package:
only the tree's names are shared. `benchmarks/chip/reference/` holds a
copy, which is the benchmark's yardstick.

Departures of the SYSTEM from the published order of operations, none of
them the reference's:
- a full-attention layer does not rotate q and k (published: rotary
  embeddings are applied `if self.is_local_attention` only). The system
  computes the rotation on every layer and selects by the layer's
  `rope_on` leaf; the values are the published ones.
- the published gate multiplies in the model's dtype
  (`attn_output * torch.sigmoid(gate_states)`); the system takes the
  sigmoid and the product in float32 and rounds once.
- the published router takes `scores = sigmoid(gate(x))` in float32,
  selects the top-k of `scores + expert_bias`, gathers the unbiased
  scores, divides by their sum (`route_norm`, + 1e-20) and multiplies by
  `route_scale`. The system does the same under the name `deepseek_v3`
  with one group (`expert_bias` is its `router.bias`); it sorts the
  (token, choice) pairs by expert and runs grouped matmuls where this
  file runs every expert over every position.
- the system multiplies the embedding by `sqrt(hidden_size)` rounded to
  the compute dtype (bf16: 45.25 for 45.2548); the reference multiplies
  by the float32 value.

`forward` runs a whole sequence; `embed`, `layer` and `logits` run it piece
by piece (a layer at a time, the head over chosen positions), which is how
it fits beside the model at published widths and 8k positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def arch_of(cfg) -> dict:
    """The architecture's numbers, under the source config's names, from
    an object with the system's ModelConfig attributes."""
    windows = cfg.attn_windows or (cfg.sliding_window,) * cfg.num_layers
    return {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "num_hidden_layers": cfg.num_layers,
        "num_dense_layers": cfg.dense_prefix_layers,
        "layer_types": ["full_attention" if w is None
                        else "sliding_attention" for w in windows],
        "sliding_window": next((w for w in windows if w is not None), None),
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "route_norm": cfg.moe_norm_topk,
        "route_scale": cfg.moe_routed_scale,
        "num_shared_experts": cfg.moe_shared_experts,
        "mup_enabled": cfg.embed_scale is not None,
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


def attention(lp, arch, x, positions, sliding: bool):
    """Gated grouped-query attention over the whole sequence. x [T, D]
    (already normed) -> [T, D]."""
    T = x.shape[0]
    H, Hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd, eps = arch["head_dim"], arch["rms_norm_eps"]
    q = (x @ _w(lp["q"])).reshape(T, H, hd)
    k = (x @ _w(lp["k"])).reshape(T, Hkv, hd)
    v = (x @ _w(lp["v"])).reshape(T, Hkv, hd)
    g = x @ _w(lp["attn_gate"])                                   # [T, H*hd]
    q = rms_norm(q, lp["q_norm"]["scale"], eps)
    k = rms_norm(k, lp["k_norm"]["scale"], eps)
    dist = positions[:, None] - positions[None, :]                # [q, k]
    mask = dist >= 0
    if sliding:
        q = rope(q, positions, arch["rope_theta"])
        k = rope(k, positions, arch["rope_theta"])
        mask = mask & (dist < arch["sliding_window"])

    def head(h):                          # one query head over every key
        kv = h // (H // Hkv)
        scores = (q[:, h] @ k[:, kv].T) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return probs @ v[:, kv]                                   # [T, hd]
    a = jnp.swapaxes(jax.lax.map(head, jnp.arange(H)), 0, 1)      # [T, H, hd]
    return (a.reshape(T, H * hd) * jax.nn.sigmoid(g)) @ _w(lp["o"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router_weights(lp, arch, x):
    """AfmoeTokenChoiceRouter: dense [T, E] weights, zero where an expert
    was not chosen."""
    scores = jax.nn.sigmoid(x @ lp["router"]["w"].astype(F32))    # [T, E]
    idx = jax.lax.top_k(scores + lp["router"]["bias"].astype(F32),
                        arch["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(scores, idx, axis=-1)         # unbiased scores
    if arch["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * arch["route_scale"]
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w)


def moe(lp, arch, x):
    """Routed experts, one at a time over every position under the dense
    mask, plus the shared expert outside the routed sum."""
    dense_w = router_weights(lp, arch, x)
    ex = lp["experts"]

    def one(e, acc):
        def pick(p):
            return jax.lax.dynamic_index_in_dim(
                p["w"], e, keepdims=False).astype(F32)
        y = swiglu(x, pick(ex["gate"]), pick(ex["up"]), pick(ex["down"]))
        return acc + y * jax.lax.dynamic_slice_in_dim(dense_w, e, 1, axis=1)
    out = jax.lax.fori_loop(0, arch["num_experts"], one, jnp.zeros_like(x))
    if arch["num_shared_experts"]:
        out = out + swiglu(x, _w(lp["shared_gate"]), _w(lp["shared_up"]),
                           _w(lp["shared_down"]))
    return out


def layer_params(params, arch, i):
    """Layer i's tree out of the system's two segments (`layers_dense`
    ahead of `layers`), each [L, ...]-stacked or a list of layers."""
    nd = arch["num_dense_layers"] if "layers_dense" in params else 0
    stack, j = ((params["layers_dense"], i) if i < nd
                else (params["layers"], i - nd))
    if isinstance(stack, (list, tuple)):
        return stack[j]
    return jax.tree.map(lambda a: a[j], stack)


def embed(params, arch, tokens):
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)
    return x * arch["hidden_size"] ** 0.5 if arch["mup_enabled"] else x


@jax.default_matmul_precision("highest")
def layer(params, arch, i, x, positions):
    """One decoder layer over the whole sequence. x [T, D] float32."""
    lp = layer_params(params, arch, i)
    eps = arch["rms_norm_eps"]
    y = attention(lp, arch, rms_norm(x, lp["attn_norm"]["scale"], eps),
                  positions, arch["layer_types"][i] == "sliding_attention")
    x = x + rms_norm(y, lp["attn_post_norm"]["scale"], eps)
    h = rms_norm(x, lp["mlp_norm"]["scale"], eps)
    m = (moe(lp, arch, h) if "experts" in lp
         else swiglu(h, _w(lp["gate"]), _w(lp["up"]), _w(lp["down"])))
    return x + rms_norm(m, lp["mlp_post_norm"]["scale"], eps)


@jax.default_matmul_precision("highest")
def logits(params, arch, x):
    """Final norm and the untied head over the rows of x [n, D]."""
    return rms_norm(x, params["final_norm"]["scale"],
                    arch["rms_norm_eps"]) @ _w(params["lm_head"])


def forward(params, arch, tokens, rows=None):
    """Logits [T or len(rows), V] of one sequence `tokens` [T]: a full
    forward pass with no cache. `rows` picks the positions whose logits
    are wanted (the head is the largest matrix)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = embed(params, arch, tokens)
    for i in range(arch["num_hidden_layers"]):
        x = layer(params, arch, i, x, positions)
    return logits(params, arch, x if rows is None else x[jnp.asarray(rows)])
