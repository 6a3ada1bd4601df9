"""admit_ms_per_ktok below the knee: an admission's device time is time
the running requests stand still, which moves tpot_p95_ms there."""

from readers import load_reader

read = load_reader("layer_metrics", "admit_ms_per_ktok")
