"""Model step: moe_experts_hit_share where the program holds a share of
each layer's experts: distinct held experts hit, summed over passes and
expert layers, over those layer-passes times the experts HELD (deltas of
the batcher's counters `batcher_moe_experts_hit` and
`batcher_moe_experts_held` over the window). A program without the second
counter gives None."""


def read(record):
    c = record["counters"]
    held = c.get("batcher_moe_experts_held", 0)
    if not held or "experts_held" not in record["config"]:
        return None
    return 100.0 * c.get("batcher_moe_experts_hit", 0) / held
