"""Model step: what every start pays for its serving programs, compile
cache or not: seconds JAX spent tracing the admit, chunk and speculative
programs first used in set-up and lowering them to MLIR (each Pallas
kernel's own lowering lands here), summed over the programs."""

import setup_account


def read(record):
    return setup_account.rows_s(record, "trace_ms", "lower_ms")
