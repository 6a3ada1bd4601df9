"""Model step: share of the pool's extent that a decode pass read, over
all layers, where the windowed layers read a ring a slot and not the pool:
(windowed layers x the ring positions a windowed layer read + full layers
x the pool positions) / (layers x the pool positions), a slot, from the
deltas of the batcher's two counters over the window
(`batcher_decode_ring_positions`, `batcher_decode_pool_positions`; both
are sums over passes). 100 % where every layer reads the whole extent; a
program without the first counter, or a model without a ring (the counter
stays 0), gives None."""


def read(record):
    c = record["counters"]
    pool = c.get("batcher_decode_pool_positions", 0)
    ring = c.get("batcher_decode_ring_positions", 0)
    kinds = record["config"].get("hybrid_layer_pattern")
    if not pool or not ring or not kinds:
        return None
    windowed = sum(kinds)
    return 100.0 * (windowed * ring + (len(kinds) - windowed) * pool) \
        / (len(kinds) * pool)
