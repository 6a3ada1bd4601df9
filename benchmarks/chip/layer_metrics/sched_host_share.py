"""Scheduler: share of the step loop's wall time outside `admit` and
`device_wait` (PhaseProfiler.summary(), enabled in the traced run): host
work the device waits for between programs."""


def read(record):
    ph = record.get("phases")
    if not ph or not ph.get("wall_s"):
        return None
    inside = sum(ph["phases"].get(k, {}).get("s", 0.0)
                 for k in ("admit", "device_wait"))
    return 100.0 * (1.0 - inside / ph["wall_s"])
