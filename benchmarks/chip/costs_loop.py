"""Least bytes of a decode pass for a looped dense configuration (Ouro:
the whole layer stack runs `total_ut_steps` times a token over one set of
weights, and every (step, layer) pair keeps K and V planes of its own),
as functions of the configuration file's shapes, under the source
config's names. The yardstick for `decode_hbm_share.loop`; a program
cannot change it. (`costs.py` counts one traversal of the stack and a
plane a layer.)
"""

from __future__ import annotations

WEIGHT_BYTES = 2            # bf16 weights, as the configuration states
KV_DTYPE_BYTES = 2          # the pool is bf16


def layer_elems(c: dict) -> int:
    """One layer's linear weights: q, k, v, o and the SwiGLU's three (the
    four norms' scales, 8192 numbers, are left out)."""
    d, hd = c["hidden_size"], c["head_dim"]
    q = d * c["num_attention_heads"] * hd
    kv = d * c["num_key_value_heads"] * hd
    return 2 * q + 2 * kv + 3 * d * c["intermediate_size"]


def head_elems(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def weight_bytes(c: dict) -> int:
    """Everything the chip holds: embedding, head, every layer once."""
    return WEIGHT_BYTES * (2 * head_elems(c)
                           + c["num_hidden_layers"] * layer_elems(c))


def cache_planes(c: dict) -> int:
    """K and V planes a token leaves: one a (loop step, layer) pair."""
    return c["total_ut_steps"] * c["num_hidden_layers"]


def kv_bytes_per_token(c: dict) -> int:
    return (cache_planes(c) * 2 * c["num_key_value_heads"] * c["head_dim"]
            * KV_DTYPE_BYTES)


def decode_weight_bytes(c: dict) -> int:
    """Weights one decode pass must read: every layer once a loop step
    (4.9 GB of layers do not stay on the chip between steps: a v5e has
    no cache that holds them, so `total_ut_steps` reads are the least a
    pass can do) and the output head once."""
    return WEIGHT_BYTES * (c["total_ut_steps"] * c["num_hidden_layers"]
                           * layer_elems(c) + head_elems(c))


def decode_pass_bytes(c: dict, live_context_tokens: float) -> float:
    """Least bytes of one decode pass: the layers' weights once a loop
    step, the head once, and K and V of every (step, layer) plane over
    the live contexts (`live_context_tokens`: their sum over the pass's
    slots) once. The embedding rows of the pass's tokens are left out."""
    return (decode_weight_bytes(c)
            + live_context_tokens * kv_bytes_per_token(c))
