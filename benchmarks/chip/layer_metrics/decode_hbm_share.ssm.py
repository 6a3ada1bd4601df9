"""Kernels: least bytes of one decode pass of a hybrid state-space
configuration (costs_ssm.decode_pass_bytes: the layers' weights and the
head once, K and V of the live contexts once, the live slots' recurrent
states and conv windows read once and written once) over the chip's peak
HBM bandwidth, over the measured decode pass: the pass's share of its
roofline. A request's context is its prompt and half of what it emitted.
Memory is the bound that applies: 64 tokens a pass are 0.7 TFLOP (3.6 ms
at peak) against 11 GB of reads and writes (13.9 ms)."""

import costs_ssm
from readers import load_reader


def read(record):
    pass_ms = load_reader("layer_metrics", "decode_pass_ms")(record)
    batch = load_reader("layer_metrics", "decode_batch_mean")(record)
    rows = [r for r in record["requests"] if r["tokens"]]
    config = record["config"]
    if not pass_ms or not batch or not rows or not record.get("peaks") \
            or "mamba_d_ssm" not in config:
        return None
    ctx = sum(r["prompt_len"] + r["tokens"] / 2 for r in rows) / len(rows)
    least_s = (costs_ssm.decode_pass_bytes(config, batch, batch * ctx)
               / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (pass_ms * 1e-3)
