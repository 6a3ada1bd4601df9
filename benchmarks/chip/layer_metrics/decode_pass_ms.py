"""Model step: device time of one decode pass. Device durations of the
trace's `jit_chunk` module runs that lie wholly in the traced interval,
over the weight passes of those chunks (the batcher's decode_chunk spans
name each chunk's size)."""

import xplane

PROGRAM = "jit_chunk"


def read(record):
    trace, traced = record.get("trace"), record.get("traced")
    if not trace or not traced:
        return None
    got = xplane.align_chunks(traced["chunks"],
                              trace["modules"].get(PROGRAM, []))
    if not got or not got[1]:
        return None
    return got[0] / got[1] * 1e3
