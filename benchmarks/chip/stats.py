"""Arithmetic on records: percentiles and the between-events rate.

Kept with the benchmark so that every PR computes the same number in
the same way.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence

# Callbacks of one emission event (one decode chunk's tokens, or one
# admission wave's first tokens) are microseconds apart: they come from
# one Python loop on the scheduler thread. Two device programs are at
# least one decode pass (>= 9 ms) apart. So a gap above this splits events.
EVENT_GAP_S = 0.002


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default). None for an empty sample."""
    if not values:
        return None
    s = sorted(values)
    if len(s) == 1:
        return float(s[0])
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))


def ttfts_ms(requests: Sequence[dict]) -> List[float]:
    """Due-to-first-token times of the requests that were served."""
    return [(r["times"][0] - r["due"]) * 1e3 for r in requests
            if not r["error"] and r["times"]]


def tpots_ms(requests: Sequence[dict]) -> List[float]:
    """Per request (last token time - first token time) / (tokens - 1).
    Tokens arrive in chunk-sized bursts, so a per-gap statistic would
    mostly read zero."""
    return [(r["times"][-1] - r["times"][0]) / (len(r["times"]) - 1) * 1e3
            for r in requests if not r["error"] and len(r["times"]) >= 2]


def events(times: Sequence[float], gap: float = EVENT_GAP_S) -> List[list]:
    """Group sorted callback times into emission events: [first, last, n]."""
    out: List[list] = []
    for t in sorted(times):
        if out and t - out[-1][1] <= gap:
            out[-1][1] = t
            out[-1][2] += 1
        else:
            out.append([t, t, 1])
    return out


def between_events_rate(times: Sequence[float], t0: float, t1: float,
                        gap: float = EVENT_GAP_S) -> Optional[float]:
    """Tokens per second over whole emission events inside [t0, t1]:
    tokens of every event after the first, over the time from the first
    event to the last. Tokens reach the host once per decode chunk, so a
    window edge then cannot cut a chunk in two. None with fewer than two
    events."""
    ev = events([t for t in times if t0 <= t <= t1], gap)
    if len(ev) < 2:
        return None
    span = ev[-1][1] - ev[0][1]
    if span <= 0:
        return None
    return sum(e[2] for e in ev[1:]) / span


def union_seconds(intervals: Sequence[Sequence[float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total
