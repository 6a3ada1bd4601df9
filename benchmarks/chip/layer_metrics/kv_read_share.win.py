"""Model step: share of the pool's extent that a decode pass gathered and
read, over all layers: (windowed layers x the positions a windowed layer
read + full layers x the pool positions) / (layers x the pool positions),
a slot, from the deltas of the batcher's two counters over the window
(`batcher_decode_window_positions`, `batcher_decode_pool_positions`; both
are sums over passes). 100 % where every layer reads the whole extent; a
program without the first counter gives None."""


def read(record):
    c = record["counters"]
    pool = c.get("batcher_decode_pool_positions", 0)
    win = c.get("batcher_decode_window_positions", 0)
    kinds = record["config"].get("layer_types")
    if not pool or not win or not kinds:
        return None
    windowed = sum(k == "sliding_attention" for k in kinds)
    return 100.0 * (windowed * win + (len(kinds) - windowed) * pool) \
        / (len(kinds) * pool)
