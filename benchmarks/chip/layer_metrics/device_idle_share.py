"""Device: share of the traced interval in which no operation ran."""


def read(record):
    t = record.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
