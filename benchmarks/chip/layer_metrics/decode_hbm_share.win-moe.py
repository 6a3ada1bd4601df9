"""Kernels: least bytes of one decode pass of a configuration that mixes
windowed and full attention layers over routed experts
(costs_win_moe.decode_pass_bytes: the layers outside the routed experts
once, the experts the counters say a pass hit once, the head once, K and
V of the full layers over the live contexts and of the windowed layers
over min(context, window)) over the chip's peak HBM bandwidth, over the
measured decode pass: the pass's share of its roofline. A request's
context is its mix's shared prefix, its own prompt part and half of what
it emitted. Memory is the bound that applies: 64 tokens a pass are 0.6
TFLOP at most against 8 GB of weights and 2 GB of K and V."""

import costs_win_moe
from readers import load_reader


def read(record):
    pass_ms = load_reader("layer_metrics", "decode_pass_ms")(record)
    batch = load_reader("layer_metrics", "decode_batch_mean")(record)
    c = record["counters"]
    passes = c.get("batcher_moe_layer_passes", 0)
    rows = [r for r in record["requests"] if r["tokens"]]
    config = record["config"]
    if not pass_ms or not batch or not passes or not rows \
            or not record.get("peaks") or "layer_types" not in config:
        return None
    hit = c.get("batcher_moe_experts_hit", 0) / passes
    shared = (record["traffic"].get("shared_prefix") or {}).get("tokens", 0)
    ctx = [shared + r["prompt_len"] + r["tokens"] / 2 for r in rows]
    window = config["sliding_window"]
    least_s = (costs_win_moe.decode_pass_bytes(
        config, hit, batch * sum(ctx) / len(ctx),
        batch * sum(min(x, window) for x in ctx) / len(ctx))
        / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (pass_ms * 1e-3)
