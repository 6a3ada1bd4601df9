"""Scheduler: what each running request loses per admission: mean wall
of the traced interval's `batcher.admit_wave` spans that ran while some
slot was already decoding (`active` > 0). Host and device part of the
program call, padding included."""

import spans


def read(record):
    waves = [s for s in spans.admit_waves(record) or []
             if s.attrs.get("active", 0) > 0]
    if not waves:
        return None
    return sum(s.end - s.start for s in waves) / len(waves) * 1e3
