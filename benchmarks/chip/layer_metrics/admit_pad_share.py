"""Scheduler: share of the admit programs' token positions that held no
prompt token, 1 - sum(tokens) / sum(padded_tokens) over the traced
interval's `batcher.admit_wave` spans (`padded_tokens` is rows x tail
bucket: what the program's shape pays for)."""

import spans


def read(record):
    waves = spans.admit_waves(record)
    if not waves:
        return None
    padded = sum(s.attrs["padded_tokens"] for s in waves)
    real = sum(s.attrs["tokens"] for s in waves)
    return 100.0 * (1.0 - real / padded) if padded else None
