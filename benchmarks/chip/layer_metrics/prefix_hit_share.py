"""Scheduler: share of the admitted prompts' positions that were not
prefilled because an earlier admission had left them in the radix cache:
sum(prefix_positions) / sum(prefix_positions + tokens) over the traced
interval's `batcher.admit_wave` spans (`prefix_positions`: cached
positions the wave's rows attended, a chunked prompt's own earlier chunks
not counted; `tokens`: what the wave prefilled). A program whose spans
lack the attribute gives None."""

import spans


def read(record):
    waves = spans.admit_waves(record)
    if not waves or any("prefix_positions" not in s.attrs for s in waves):
        return None
    hit = sum(s.attrs["prefix_positions"] for s in waves)
    ran = sum(s.attrs["tokens"] for s in waves)
    return 100.0 * hit / (hit + ran) if hit + ran else None
