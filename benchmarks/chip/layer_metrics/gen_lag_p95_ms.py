"""Load generator: how late it submitted (actual submit - due time)."""

import stats


def read(record):
    return stats.percentile(
        [(r["submitted"] - r["due"]) * 1e3 for r in record["requests"]], 95)
