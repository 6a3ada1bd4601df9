"""Model step: what a token waits inside decode program calls: median,
over the window's finished requests of two tokens or more, of the cost
record's `decode_chunk_ms` (the scheduler's always-on `dispatch` +
`device_wait` (+ `spec_verify`) clocks, read at the request's first
token and at its finish) per token after the first. With
`tpot_admit_ms` and `tpot_host_ms` it splits `tpot_p50_ms`."""

import token_account


def read(record):
    return token_account.median_per_token(record, ("decode_chunk_ms",))
