"""Kernels: least bytes of one decode pass of a looped configuration
(costs_loop.decode_pass_bytes: the layers' weights once a loop step, the
head once, K and V of every (step, layer) plane over the live contexts
once) over the chip's peak HBM bandwidth, over the measured decode pass:
the pass's share of its roofline. A request's context is its prompt and
half of what it emitted. Memory is the bound that applies: 8 tokens a
pass are 0.16 TFLOP (0.8 ms at peak) against 20 GB of weight reads."""

import costs_loop
from readers import load_reader


def read(record):
    pass_ms = load_reader("layer_metrics", "decode_pass_ms")(record)
    batch = load_reader("layer_metrics", "decode_batch_mean")(record)
    rows = [r for r in record["requests"] if r["tokens"]]
    config = record["config"]
    if not pass_ms or not batch or not rows or not record.get("peaks") \
            or "total_ut_steps" not in config:
        return None
    ctx = sum(r["prompt_len"] + r["tokens"] / 2 for r in rows) / len(rows)
    least_s = (costs_loop.decode_pass_bytes(config, batch * ctx)
               / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (pass_ms * 1e-3)
