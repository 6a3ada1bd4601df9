"""Scheduler: what a running token loses to others' admissions: median,
over the window's finished requests of two tokens or more, of the cost
record's `decode_admit_run_ms` (the `admit_run` clock: others' admit
programs) + `decode_admit_host_ms` (`admit` less `admit_run`: radix
match, packing, slot binding) per token after the first."""

import token_account


def read(record):
    return token_account.median_per_token(
        record, ("decode_admit_run_ms", "decode_admit_host_ms"))
