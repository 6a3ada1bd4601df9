"""Kernels: least bytes of one decode pass of a configuration with
windowed and full layers of different shapes over a held share of routed
experts (costs_swa_moe.decode_pass_bytes: the layers outside the routed
experts once, the held experts the counters say a pass hit once, the head
once, the full layers' K and V over the live contexts and the windowed
layers' over the window) over the chip's peak HBM bandwidth, over the
measured decode pass: the whole pass's share of its roofline. A request's
context is its prompt and half of what it emitted. Memory is the bound
that applies: 64 tokens a pass are 0.2 TFLOP at most against 6 GB of
weights and 0.8 GB of K and V."""

import costs_swa_moe
from readers import load_reader


def read(record):
    pass_ms = load_reader("layer_metrics", "decode_pass_ms")(record)
    batch = load_reader("layer_metrics", "decode_batch_mean")(record)
    c = record["counters"]
    passes = c.get("batcher_moe_layer_passes", 0)
    rows = [r for r in record["requests"] if r["tokens"]]
    config = record["config"]
    if not pass_ms or not batch or not passes or not rows \
            or not record.get("peaks") \
            or "hybrid_layer_pattern" not in config:
        return None
    hit = c.get("batcher_moe_experts_hit", 0) / passes
    ctx = sum(r["prompt_len"] + r["tokens"] / 2 for r in rows) / len(rows)
    least_s = (costs_swa_moe.decode_pass_bytes(config, hit, batch,
                                               batch * ctx)
               / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (pass_ms * 1e-3)
