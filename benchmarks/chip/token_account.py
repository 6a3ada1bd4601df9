"""A token's time by where the scheduler spent it, from the batcher's
per-request cost record: the parts of `decode_ms` that its always-on
phase clocks give (`runtime/batcher.py: _decode_account`), for every
request of the whole window."""

import stats


def median_per_token(record, fields):
    """Median, over the requests `stats.tpots_ms` takes (no error, two
    tokens or more), of the sum of the cost record's `fields` per
    (`decode_tokens` - 1). None where a cost lacks a field (a batcher
    without the clocks), and the metric is left out."""
    values = []
    for r in record["requests"]:
        if r["error"] or len(r["times"]) < 2:
            continue
        cost = r["cost"]
        if any(f not in cost for f in fields):
            return None
        values.append(sum(cost[f] for f in fields)
                      / (cost["decode_tokens"] - 1))
    return stats.percentile(values, 50)
