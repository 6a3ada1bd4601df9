"""Model step: device time of admission per thousand prompt tokens that
were really prefilled, in a cell whose end-to-end metric is the time per
token: `admit_ms_per_ktok`'s pairing of the trace's `jit_admit` runs with
the traced interval's `batcher.admit_wave` spans, under a name that moves
`tpot_p50_ms`. In the hybrid-window cell it is what prompts of up to 2048
tokens cost through both kinds of layer (no prefix is reused, so every
prompt token is prefilled, and a windowed layer's tail attends itself
whole under its mask)."""

from readers import load_reader


def read(record):
    if "hybrid_layer_pattern" not in record["config"]:
        return None
    return load_reader("layer_metrics", "admit_ms_per_ktok")(record)
