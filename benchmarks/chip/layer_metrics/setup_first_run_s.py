"""Device: seconds of the serving programs' first calls in set-up
outside trace, lowering and load: the arguments' way to the device, the
first run, the sync on its outputs (a program compiled ahead: its first
call's wall)."""

import setup_account


def read(record):
    return setup_account.rows_s(record, "run_ms")
