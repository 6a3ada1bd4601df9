"""Kernels: the state-step kernel's share of its roofline: the bytes its
calls have to move (costs_ssm.step_kernel_bytes: every slot's float32
state read once and written once, a layer a call) over the chip's peak
HBM bandwidth, over the device time of the trace's `ssm_state_step`
operations (`record["trace"]["device_ops"]`, the traced interval's
largest operations by self time, found by the kernel's name). The calls
are the configuration's layers times the decode passes the trace holds
(the `jit_chunk` runs' device time over `decode_pass_ms`). Memory is the
bound: a call is 0.5 GB of traffic against 0.1 GFLOP. A program without
the kernel (the parent, a CPU rehearsal, a state the kernel does not
take) has no such operation and reports nothing."""

import costs_ssm
from readers import load_reader

KERNEL = "ssm_state_step"


def read(record):
    trace = record.get("trace") or {}
    op_s = sum(s for name, s in trace.get("device_ops", ())
               if name.startswith(KERNEL))
    runs = trace.get("modules", {}).get("jit_chunk")
    pass_ms = load_reader("layer_metrics", "decode_pass_ms")(record)
    config = record["config"]
    if not op_s or not runs or not pass_ms or not record.get("peaks") \
            or "mamba_d_ssm" not in config:
        return None
    passes = sum(du for _, du in runs) / (pass_ms * 1e-3)
    calls = passes * config["num_hidden_layers"]
    least_s = (calls * costs_ssm.step_kernel_bytes(
        config, config["batcher"]["slots"])
        / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / op_s
