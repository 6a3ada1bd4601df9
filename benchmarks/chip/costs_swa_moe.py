"""Least bytes of a decode pass for a mimo_v2-shaped configuration
(windowed and full attention layers of different K/V head counts, q and k
heads wider than the value heads, a leading dense layer, a held share of
sigmoid-routed experts with no shared one; the windowed layers' K and V
in a ring a serving slot, the full layers' in the block pool), as
functions of the configuration file's shapes, under the source config's
names. The yardstick for `decode_hbm_share.swa-moe`; a program cannot
change it. In such a file `n_routed_experts` counts the experts the chip
HOLDS and `router_columns` the router's width (the published
n_routed_experts). (`costs_win_moe.py` counts one head count, one head
width and every expert; `costs_mla_moe.py` a latent row a token.)
"""

from __future__ import annotations

WEIGHT_BYTES = 2            # bf16 weights, as the configuration states
KV_DTYPE_BYTES = 2          # pool and ring are bf16


def kv_heads(c: dict, windowed: bool) -> int:
    return c["swa_num_key_value_heads" if windowed else "num_key_value_heads"]


def attention_elems(c: dict, windowed: bool) -> int:
    """One layer's attention: q (heads x head_dim), k (its kind's K/V
    heads x head_dim), v (x v_head_dim), o (heads x v_head_dim back to
    hidden) and, in a windowed layer, a sink a query head. The norms'
    scales are left out."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    hd, vd, hkv = c["head_dim"], c["v_head_dim"], kv_heads(c, windowed)
    sink = h if windowed and c["add_swa_attention_sink_bias"] else 0
    return d * h * hd + d * hkv * hd + d * hkv * vd + h * vd * d + sink


def expert_elems(c: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_elems(c: dict) -> int:
    """The router's matrix and its correction bias, at its whole width."""
    return (c["hidden_size"] + 1) * c["router_columns"]


def layer_elems(c: dict, i: int, experts: float | None = None) -> float:
    """Layer i by its kinds (hybrid_layer_pattern: windowed or full;
    moe_layer_freq: dense MLP or experts), with `experts` of its routed
    experts (None: all the chip holds)."""
    attn = attention_elems(c, bool(c["hybrid_layer_pattern"][i]))
    if not c["moe_layer_freq"][i]:
        return attn + 3 * c["hidden_size"] * c["intermediate_size"]
    held = c["n_routed_experts"] if experts is None else experts
    return attn + router_elems(c) + held * expert_elems(c)


def head_elems(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def weight_elems(c: dict) -> int:
    """Everything the chip holds: embedding, head, every layer with the
    experts it holds."""
    return 2 * head_elems(c) + sum(
        layer_elems(c, i) for i in range(c["num_hidden_layers"]))


def weight_bytes(c: dict) -> int:
    return WEIGHT_BYTES * weight_elems(c)


def kv_layer_bytes(c: dict, windowed: bool) -> int:
    """K and V of one position in one layer of a kind."""
    return KV_DTYPE_BYTES * kv_heads(c, windowed) \
        * (c["head_dim"] + c["v_head_dim"])


def layer_kinds(c: dict) -> tuple:
    """(windowed layers, full layers) of the depth the file runs."""
    windowed = sum(c["hybrid_layer_pattern"])
    return windowed, c["num_hidden_layers"] - windowed


def kv_bytes_per_token(c: dict) -> int:
    """What a cached token takes of the block pool: the full layers'."""
    return layer_kinds(c)[1] * kv_layer_bytes(c, False)


def ring_positions(c: dict) -> int:
    """The window in whole blocks."""
    bs = c["batcher"]["block_size"]
    return -(-c["sliding_window"] // bs) * bs


def ring_bytes_per_slot(c: dict) -> int:
    """What a serving slot's ring holds: the windowed layers' K and V of
    ring_positions positions, whatever max_seq."""
    return layer_kinds(c)[0] * kv_layer_bytes(c, True) * ring_positions(c)


def decode_pass_bytes(c: dict, experts_hit: float, live_slots: float,
                      live_context_tokens: float) -> float:
    """Least bytes of one decode pass: the layers outside the routed
    experts once, the held experts that were hit (mean a layer) once,
    the head once, the full layers' K and V over the live contexts
    (`live_context_tokens`: their sum over the pass's slots) once, and
    the windowed layers' over min(context, sliding_window) a live slot
    (every context of the mix is past the window: live_slots x window).
    The embedding rows of the pass's tokens are left out."""
    windowed, full = layer_kinds(c)
    weights = head_elems(c) + sum(
        layer_elems(c, i, experts_hit)
        for i in range(c["num_hidden_layers"]))
    return (WEIGHT_BYTES * weights
            + full * kv_layer_bytes(c, False) * live_context_tokens
            + windowed * kv_layer_bytes(c, True)
            * live_slots * c["sliding_window"])
