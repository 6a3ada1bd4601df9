"""Model step: traversals of the layer stack a decode pass makes (deltas
of the batcher's counters over the window: `batcher_stack_passes` over
`batcher_weight_passes`): a looped model's `total_ut_steps`, 1.0 for
every other. A program that lacks the counter reports nothing."""


def read(record):
    c = record["counters"]
    passes = c.get("batcher_weight_passes", 0)
    stack = c.get("batcher_stack_passes", 0)
    if not passes or not stack:
        return None
    return stack / passes
