"""Scheduler: what a token loses to the step's host work: median, over
the window's finished requests of two tokens or more, of the cost
record's `decode_emit_ms` (the `emit` clock) + `decode_host_ms`
(`host_prep`, `spec_draft`, `bookkeeping`, `other`, and `between`: from
one busy step's end to the next one's start) per token after the
first."""

import token_account


def read(record):
    return token_account.median_per_token(
        record, ("decode_emit_ms", "decode_host_ms"))
