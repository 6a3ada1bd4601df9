"""95th percentile of due-to-first-token time (see ttft_p50_ms)."""

import stats


def read(record):
    return stats.percentile(stats.ttfts_ms(record["requests"]), 95)
