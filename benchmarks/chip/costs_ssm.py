"""Least bytes of a decode pass for a hybrid state-space configuration
(Falcon-H1: every block runs a Mamba-2 mixer beside its attention heads;
a serving slot keeps a recurrent state and a conv window beside its K
and V blocks), as functions of the configuration file's shapes, under
the source config's names. The yardstick for `decode_hbm_share.ssm`; a
program cannot change it. (`costs.py` counts attention and an MLP alone,
and no per-slot state.)
"""

from __future__ import annotations

WEIGHT_BYTES = 2            # bf16 weights, as the configuration states
KV_DTYPE_BYTES = 2          # the pool is bf16
STATE_BYTES = 4             # the recurrent state is float32
CONV_BYTES = 2              # the conv window is bf16


def conv_dim(c: dict) -> int:
    """Channels of the depthwise convolution: [x | B | C]."""
    return c["mamba_d_ssm"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def mixer_elems(c: dict) -> int:
    """The Mamba-2 mixer's parameters: in_proj D -> [z | x | B | C | dt],
    the depthwise filter and its bias, dt_bias, A_log and D a head, the
    gated norm's scale, out_proj."""
    d, ds, h = c["hidden_size"], c["mamba_d_ssm"], c["mamba_n_heads"]
    return (d * (ds + conv_dim(c) + h) + conv_dim(c) * c["mamba_d_conv"]
            + conv_dim(c) * bool(c["mamba_conv_bias"]) + 3 * h + ds + ds * d)


def attention_elems(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    return 2 * d * hd * (c["num_attention_heads"] + c["num_key_value_heads"])


def mlp_elems(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_elems(c: dict) -> int:
    """One block: attention, the mixer, the MLP and the two norms."""
    return (attention_elems(c) + mixer_elems(c) + mlp_elems(c)
            + 2 * c["hidden_size"])


def head_elems(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def weight_bytes(c: dict) -> int:
    """Everything the chip holds: embedding, head (untied), every layer."""
    return WEIGHT_BYTES * (2 * head_elems(c)
                           + c["num_hidden_layers"] * layer_elems(c))


def state_bytes_per_slot(c: dict) -> int:
    """What one serving slot holds beside its blocks, over all layers:
    the recurrent state [H, P, N] in float32 and the last d_conv - 1
    inputs of the convolution in bf16."""
    state = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
    window = (c["mamba_d_conv"] - 1) * conv_dim(c)
    return c["num_hidden_layers"] * (state * STATE_BYTES
                                     + window * CONV_BYTES)


def step_kernel_bytes(c: dict, slots: int) -> int:
    """Bytes one call of the state-step kernel (`ssm_state_step` in a
    trace, ops/pallas/ssm_step.py: one layer of one decode pass) has to
    move: every slot's state [H, P, N] in float32 read once and written
    once. Every slot, live or not: the kernel brings each row's tiles in
    (a dead row's come back as they were). The decay, dt x, B, C and y
    rows (0.3 % of it) are left out."""
    state = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
    return 2 * slots * state * STATE_BYTES


def kv_bytes_per_token(c: dict) -> int:
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * KV_DTYPE_BYTES)


def decode_weight_bytes(c: dict) -> int:
    """Weights one decode pass must read: every layer once and the
    output head once."""
    return WEIGHT_BYTES * (c["num_hidden_layers"] * layer_elems(c)
                           + head_elems(c))


def decode_pass_bytes(c: dict, live_slots: float,
                      live_context_tokens: float) -> float:
    """Least bytes of one decode pass: the layers once, the head once, K
    and V of the live contexts (`live_context_tokens`: their sum over
    the pass's slots) once, and the live slots' states and conv windows
    read once and written once. The embedding rows of the pass's tokens
    and the K and V rows it writes are left out."""
    return (decode_weight_bytes(c)
            + live_context_tokens * kv_bytes_per_token(c)
            + 2 * live_slots * state_bytes_per_slot(c))
