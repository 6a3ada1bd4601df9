"""Least bytes a kernel or a pass has to move, as functions of a
configuration file's shapes. The yardstick for `*_hbm_share` and
`*_roofline` metrics; a program cannot change it.
"""

from __future__ import annotations

KV_DTYPE_BYTES = 2          # the paged pool is bf16
WEIGHT_BYTES = {"int8": 1, None: 2, "bf16": 2}


def _quant(config: dict):
    return config.get("overrides", {}).get("quant")


def kv_bytes_per_token(config: dict) -> int:
    """K and V of one token over all layers."""
    return (config["num_hidden_layers"] * 2 * config["num_key_value_heads"]
            * config["head_dim"] * KV_DTYPE_BYTES)


def kv_block_bytes(config: dict) -> int:
    return kv_bytes_per_token(config) * config["batcher"]["block_size"]


def layer_weight_elems(config: dict) -> int:
    """Elements of one layer's linear weights (norms and the router are
    a few thousand and left out)."""
    d, h = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * h
    kv = config["num_key_value_heads"] * h
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * config["intermediate_size"]
    return attn + mlp * config.get("num_local_experts", 1)


def decode_weight_bytes(config: dict) -> int:
    """Weights one decode pass must read: every layer once (all experts:
    16 tokens x top-2 hit all 8 with probability 0.99) and the output
    head once. Per-channel scales are 4 bytes per output column: left
    out, under 0.1 %."""
    per = WEIGHT_BYTES[_quant(config)]
    head = config["hidden_size"] * config["vocab_size"] * per
    return config["num_hidden_layers"] * layer_weight_elems(config) * per + head


def decode_pass_bytes(config: dict, live_context_tokens: float) -> float:
    """Least bytes of one decode pass: the weights once, plus K and V of
    the live context (all active slots' tokens) once."""
    return (decode_weight_bytes(config)
            + live_context_tokens * kv_bytes_per_token(config))
