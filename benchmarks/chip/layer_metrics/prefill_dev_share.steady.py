"""prefill_dev_share below the knee: every admission stalls the decode of
the requests already running, which is what moves tpot_p95_ms there."""

from readers import load_reader

read = load_reader("layer_metrics", "prefill_dev_share")
