"""Output tokens streamed to callers per second, counted between whole
emission events inside the window (stats.between_events_rate)."""

import stats


def read(record):
    t0, t1 = record["window"]
    times = [t for r in record["requests"] for t in r["times"]]
    return stats.between_events_rate(times, t0, t1)
