"""Model step: device time in the admit (prefill) programs over device
time in admit + decode-chunk programs, from the trace's module runs."""


def read(record):
    mods = (record.get("trace") or {}).get("modules")
    if not mods:
        return None
    admit = sum(d for _, d in mods.get("jit_admit", []))
    chunk = sum(d for _, d in mods.get("jit_chunk", []))
    return 100.0 * admit / (admit + chunk) if admit + chunk else None
