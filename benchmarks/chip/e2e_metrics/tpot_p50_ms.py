"""Median over requests of the time per output token after the first
(stats.tpots_ms): what a streaming user sees between tokens. Steadier
than the 95th percentile beside it."""

import stats


def read(record):
    return stats.percentile(stats.tpots_ms(record["requests"]), 50)
