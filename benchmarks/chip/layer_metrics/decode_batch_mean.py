"""Scheduler: tokens emitted per weight pass over the window (deltas of
the batcher's counters): how full the decode batch ran."""


def read(record):
    c = record["counters"]
    passes = c.get("batcher_weight_passes", 0)
    return c.get("batcher_tokens_emitted", 0) / passes if passes else None
