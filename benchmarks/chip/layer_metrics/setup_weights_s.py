"""Model step: the weights' share of a start: wall of the batcher's
`batcher.build.weights` (init or quantize, placement on the device,
unstacking, and the eager programs all of it runs one by one, each
traced, lowered and loaded): the host's wall; what the device still owes
of the weights lands in the first serving program's first run."""

import setup_account


def read(record):
    acct = setup_account.account(record)
    if acct is None or "weights" not in acct["build"]:
        return None
    return acct["build"]["weights"]["wall_ms"] / 1e3
