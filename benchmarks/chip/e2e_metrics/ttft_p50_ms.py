"""Median time from the instant a request was due to its first token in
the stream callback, over requests due in the window that were served."""

import stats


def read(record):
    return stats.percentile(stats.ttfts_ms(record["requests"]), 50)
