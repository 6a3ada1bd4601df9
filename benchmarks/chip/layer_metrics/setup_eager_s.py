"""Model step: seconds of trace, lowering and load of everything that
compiled in set-up outside the serving programs and outside the weights:
the pool's init, and the programs of threads with no label (op-by-op
dispatch of the constructor, the probes, the harness's own)."""

import setup_account


def read(record):
    acct = setup_account.account(record)
    if acct is None:
        return None
    _, pool, eager = setup_account.outside_rows(acct)
    return sum(part.get(f, 0.0) for part in (pool, eager)
               for f in ("trace_ms", "lower_ms", "load_ms")) / 1e3
