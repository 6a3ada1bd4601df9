"""95th percentile over requests of the time per output token after the
first (stats.tpots_ms): the requests that others' admissions stalled most."""

import stats


def read(record):
    return stats.percentile(stats.tpots_ms(record["requests"]), 95)
