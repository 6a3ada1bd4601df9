"""admit_pad_share below the knee (it moves tpot_p95_ms there: padding
lengthens the stall of the requests that are running)."""

from readers import load_reader

read = load_reader("layer_metrics", "admit_pad_share")
