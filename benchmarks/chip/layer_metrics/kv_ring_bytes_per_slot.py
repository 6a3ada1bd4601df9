"""Kernels: bytes of windowed layers' K and V that one serving slot's
ring holds over all of them, as the batcher computes it from the ring
planes' own shapes (its gauge `batcher_kv_ring_bytes_per_slot`; a record
holds no gauges, so the same number is read from the attribute the traced
interval's `batcher.decode_chunk` spans carry, as
`kv_pool_bytes_per_token` reads its own). A program without a ring, or
without the attribute, reports nothing."""


def read(record):
    chunks = (record.get("traced") or {}).get("chunks")
    if not chunks:
        return None
    from distributed_llm_inferencing_tpu.utils import trace
    for span in reversed(trace.get_tracer().spans()):
        if span.name == "batcher.decode_chunk" \
                and span.attrs.get("kv_ring_bytes_per_slot"):
            return float(span.attrs["kv_ring_bytes_per_slot"])
    return None
