"""Model step: device time of admission per thousand prompt tokens that
were really prefilled. The trace's `jit_admit` module runs are paired
with the traced interval's `batcher.admit_wave` spans by
`xplane.align_chunks` (the trace may hold a run more at either end), and
their device time is set against the spans' `tokens`: the real uncached
tail tokens, not the padded shape the program ran."""

import spans
import xplane

PROGRAM = "jit_admit"


def read(record):
    waves = spans.admit_waves(record)
    runs = (record.get("trace") or {}).get("modules", {}).get(PROGRAM)
    if not waves or not runs:
        return None
    got = xplane.align_chunks(
        [[s.start, s.end, int(s.attrs["tokens"])] for s in waves], runs)
    if not got or not got[1]:
        return None
    return got[0] * 1e3 / (got[1] / 1e3)
