"""Least bytes of a decode pass for an afmoe-shaped configuration (gated
grouped-query attention over K and V planes, windowed and full layers
mixed, routed experts + shared experts, leading dense layers), as
functions of the configuration file's shapes, under the source config's
names. The yardstick for `decode_hbm_share.win-moe`; a program cannot
change it. (`costs_mla_moe.py` counts a latent row a token and no gate;
`costs.py` one kind of layer and `num_local_experts` identical MLPs.)
"""

from __future__ import annotations

WEIGHT_BYTES = 2            # bf16 weights, as the configuration states
KV_DTYPE_BYTES = 2          # the pool is bf16


def attention_elems(c: dict) -> int:
    """One layer's attention projections: q, k, v, o and the output gate
    (as wide as q). The norms' scales are left out."""
    d, hd = c["hidden_size"], c["head_dim"]
    q = d * c["num_attention_heads"] * hd
    kv = d * c["num_key_value_heads"] * hd
    return 3 * q + 2 * kv


def dense_layer_elems(c: dict) -> int:
    return attention_elems(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def moe_layer_fixed_elems(c: dict) -> int:
    """An expert layer outside its routed experts: attention, the shared
    experts (one SwiGLU of width num_shared x moe_intermediate), router."""
    d = c["hidden_size"]
    shared = 3 * d * c["num_shared_experts"] * c["moe_intermediate_size"]
    return attention_elems(c) + shared + d * c["num_experts"]


def expert_elems(c: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def head_elems(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def layer_counts(c: dict) -> tuple:
    """(dense layers, expert layers) of the depth the file runs."""
    dense = min(c["num_dense_layers"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def attention_kinds(c: dict) -> tuple:
    """(windowed layers, full layers) of the depth the file runs."""
    kinds = c["layer_types"]
    assert len(kinds) == c["num_hidden_layers"]
    windowed = sum(k == "sliding_attention" for k in kinds)
    return windowed, len(kinds) - windowed


def weight_bytes(c: dict) -> int:
    """Everything the chip holds: embedding, head, every layer whole."""
    dense, moe = layer_counts(c)
    return WEIGHT_BYTES * (
        2 * head_elems(c) + dense * dense_layer_elems(c)
        + moe * (moe_layer_fixed_elems(c)
                 + c["num_experts"] * expert_elems(c)))


def kv_layer_bytes_per_token(c: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * KV_DTYPE_BYTES * c["num_key_value_heads"] * c["head_dim"]


def kv_bytes_per_token(c: dict) -> int:
    return c["num_hidden_layers"] * kv_layer_bytes_per_token(c)


def decode_pass_bytes(c: dict, experts_hit: float, context_tokens: float,
                      window_tokens: float) -> float:
    """Least bytes of one decode pass: the layers outside the routed
    experts once, the experts that were hit (mean a layer) once, the head
    once, K and V of the full layers over the live contexts
    (`context_tokens`: their sum over the pass's slots) and of the
    windowed layers over what their window holds (`window_tokens`: the
    sum of min(context, sliding_window)). The embedding rows of the
    pass's tokens (a few hundred KB) are left out."""
    dense, moe = layer_counts(c)
    windowed, full = attention_kinds(c)
    weights = (dense * dense_layer_elems(c)
               + moe * (moe_layer_fixed_elems(c)
                        + experts_hit * expert_elems(c))
               + head_elems(c))
    return (WEIGHT_BYTES * weights + kv_layer_bytes_per_token(c)
            * (full * context_tokens + windowed * window_tokens))
