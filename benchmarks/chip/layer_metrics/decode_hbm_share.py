"""Kernels: least bytes of one decode pass (costs.decode_pass_bytes: the
weights and the output head once, K and V of the live context once) over
the chip's peak HBM bandwidth, over the measured decode pass. The bound
that applies is memory: 16 tokens a pass are ~0.23 TFLOP (1.2 ms at
peak) against at least 9 ms of weight reads."""

import costs
from readers import load_reader


def read(record):
    pass_ms = load_reader("layer_metrics", "decode_pass_ms")(record)
    batch = load_reader("layer_metrics", "decode_batch_mean")(record)
    rows = [r for r in record["requests"] if r["tokens"]]
    if not pass_ms or not batch or not rows or not record.get("peaks"):
        return None
    ctx = sum(r["prompt_len"] + r["tokens"] / 2 for r in rows) / len(rows)
    least_s = (costs.decode_pass_bytes(record["config"], batch * ctx)
               / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (pass_ms * 1e-3)
