"""decode_pass_ms below the knee (it moves tpot_p95_ms there)."""

from readers import load_reader

read = load_reader("layer_metrics", "decode_pass_ms")
