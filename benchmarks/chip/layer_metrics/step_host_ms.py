"""Scheduler: host time the device cannot hide, per busy step and in
absolute terms: the sampled steps' wall outside `device_wait` and the
nested `admit_run` (the two brackets in which the host waits for a
program), over the steps (`PhaseProfiler.summary()`, enabled in the
traced run). Unlike sched_host_share it does not shrink when the pass
does."""


def read(record):
    ph = record.get("phases")
    if not ph or not ph.get("steps_sampled") or "nested" not in ph:
        return None
    waited = (ph["phases"].get("device_wait", {}).get("s", 0.0)
              + ph["nested"].get("admit_run", {}).get("s", 0.0))
    return (ph["wall_s"] - waited) / ph["steps_sampled"] * 1e3
