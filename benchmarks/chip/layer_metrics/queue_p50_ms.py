"""Scheduler: median queue wait, from the batcher's own cost record
(submitted to the first admission wave's dispatch)."""

import stats


def read(record):
    return stats.percentile(
        [r["cost"]["queue_ms"] for r in record["requests"]
         if not r["error"] and "queue_ms" in r["cost"]], 50)
