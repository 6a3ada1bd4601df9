"""Model step: moe_experts_hit_share for a configuration file that names
its routed experts `num_experts` (afmoe), which that reader, written to
the deepseek_v3 key `n_routed_experts`, cannot find: distinct experts hit,
summed over passes and expert layers, over those layer-passes times the
experts a layer has (deltas of the batcher's counters over the window)."""


def read(record):
    c = record["counters"]
    passes = c.get("batcher_moe_layer_passes", 0)
    config = record["config"]
    experts = config.get("num_experts")
    if not passes or not experts or config.get("model_type") != "afmoe":
        return None
    return 100.0 * c.get("batcher_moe_experts_hit", 0) / (passes * experts)
