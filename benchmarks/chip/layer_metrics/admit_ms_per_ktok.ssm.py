"""Model step: device time of admission per thousand prompt tokens that
were really prefilled, in a cell whose end-to-end metric is the time per
token: `admit_ms_per_ktok`'s pairing of the trace's `jit_admit` runs with
the traced interval's `batcher.admit_wave` spans, under a name that moves
`tpot_p50_ms`. In a hybrid state-space cell it is what the chunked scan
and the prompt's other layers cost (no prefix is reused, so every prompt
token is prefilled)."""

from readers import load_reader


def read(record):
    return load_reader("layer_metrics", "admit_ms_per_ktok")(record)
