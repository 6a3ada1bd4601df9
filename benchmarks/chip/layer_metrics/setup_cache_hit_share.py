"""Device: the compile cache's hits over its hits and misses in set-up
(serving programs, the build, programs of no label): 100 on a warm
start, 0 on a cold one; None where the cache answered nothing (off, or
every program in memory already)."""

import setup_account


def read(record):
    acct = setup_account.account(record)
    if acct is None:
        return None
    parts = [r for r in acct["rows"] if not r["serving"]]
    parts += setup_account.outside_rows(acct)
    hits = sum(p.get("cache_hits", 0) for p in parts)
    misses = sum(p.get("cache_misses", 0) for p in parts)
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
