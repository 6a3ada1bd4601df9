"""What the batcher's own spans say of the traced interval.

The readers run in the batcher's process, so they read the span ring
(`utils/trace.py`) directly. The ring also holds the warm-up's admission
waves and the waves of the rest of the window: only those between the
first traced decode chunk's start and the last one's end are read,
`record["traced"]["chunks"]` being those chunks' spans. A program without
the attributes (an older batcher) gives None, and the metric is left out.
"""


def admit_waves(record):
    """The traced interval's `batcher.admit_wave` spans that say what
    they cost (`tokens`, `padded_tokens`, `active`), in order; None if
    there is no traced interval or no such span."""
    chunks = (record.get("traced") or {}).get("chunks")
    if not chunks:
        return None
    from distributed_llm_inferencing_tpu.utils import trace
    t_a, t_b = chunks[0][0], chunks[-1][1]
    waves = [s for s in trace.get_tracer().spans()
             if s.name == "batcher.admit_wave" and "tokens" in s.attrs
             and s.start >= t_a and s.end <= t_b]
    return sorted(waves, key=lambda s: s.start) or None
