"""Reduce a JAX profiler trace (`*.xplane.pb`) to what the per-layer
metrics read: per device, the XLA-module events (one per program run)
and the XLA-op events (what the device executed), in seconds from the
trace's first device event.

Read with `jax.profiler.ProfileData` and nothing else. On a TPU a device
is a plane `/device:TPU:<n>` with the lines `XLA Modules` and `XLA Ops`.
The CPU backend (rehearsal only) has no device plane: its ops are host
events carrying `hlo_module` and `run_id` stats, and a module run is
rebuilt as the span of one run_id's ops.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

from stats import union_seconds

MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


_OP = re.compile(r"^%?([^ ]+) = ([a-z0-9]+\[[0-9,]*\])?")


def op_name(name: str) -> str:
    """The TPU names an op by its whole HLO instruction; keep the
    instruction's name and its result's type: `broadcast.1 f32[16,8]`."""
    m = _OP.match(name)
    if not m:
        return name
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def base_name(name: str) -> str:
    """`jit_chunk(1234)` -> `jit_chunk`; op names keep their number."""
    return name.split("(", 1)[0].strip()


def read_events(path: str) -> dict:
    """{"devices": [{"name", "modules": [[name, start_s, dur_s]],
    "ops": [[name, start_s, dur_s]]}]}; times from the first device event."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [[op_name(ev.name), ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9] for ev in line.events]
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
    if not devices:
        devices = _cpu_devices(data)
    starts = [e[1] for d in devices for k in ("modules", "ops")
              for e in d[k]]
    t0 = min(starts) if starts else 0.0
    for d in devices:
        for k in ("modules", "ops"):
            d[k] = sorted([[n, s - t0, du] for n, s, du in d[k]],
                          key=lambda e: e[1])
    return {"devices": devices}


def _cpu_devices(data) -> List[dict]:
    ops, runs = [], {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                if "hlo_module" not in st:
                    continue
                s, du = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                ops.append([ev.name, s, du])
                r = runs.setdefault((st["hlo_module"], st.get("run_id")),
                                    [s, s + du])
                r[0], r[1] = min(r[0], s), max(r[1], s + du)
    if not ops:
        return []
    modules = [[name, s, e - s] for (name, _), (s, e) in runs.items()]
    return [{"name": "/host:CPU (rehearsal)", "modules": modules,
             "ops": ops}]


def self_times(ops: List[list]) -> Dict[str, float]:
    """Seconds per op name, a nested op's time taken out of its parent's
    (a `while` spans its body's ops on the same line)."""
    out: Dict[str, float] = {}
    stack: List[list] = []        # [name, end, self]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, _, self_s = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_s, 0.0)

    for name, s, du in ops:
        close(s)
        if stack:
            stack[-1][2] -= du
        stack.append([name, s + du, du])
    close(float("inf"))
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """What the metrics read: window and busy seconds (mean over the
    devices that ran anything), per-program module runs, the ops that took
    most time, and the device's idle gaps by the program that ended them."""
    devs = [d for d in events["devices"] if d["ops"] or d["modules"]]
    if not devs:
        return {}
    ends = [e[1] + e[2] for d in devs for k in ("modules", "ops")
            for e in d[k]]
    starts = [e[1] for d in devs for k in ("modules", "ops") for e in d[k]]
    window = max(ends) - min(starts)
    busy, op_s, gap_s = [], {}, {}
    modules: Dict[str, List[list]] = {}
    for d in devs:
        spans = [(s, s + du) for _, s, du in (d["ops"] or d["modules"])]
        busy.append(union_seconds(spans))
        for name, s_ in self_times(d["ops"]).items():
            op_s[name] = op_s.get(name, 0.0) + s_ / len(devs)
        for g0, g1 in _gaps(spans):
            nxt = next((base_name(n) for n, s, du in d["modules"]
                        if s + du > g1), "end_of_trace")
            key = "before:" + nxt
            gap_s[key] = gap_s.get(key, 0.0) + (g1 - g0) / len(devs)
    for name, s, du in devs[0]["modules"]:
        modules.setdefault(base_name(name), []).append([s, du])

    def rank(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": window, "busy_s": sum(busy) / len(busy),
            "devices": len(devs), "modules": modules,
            "device_ops": rank(op_s), "idle_gaps": rank(gap_s)}


def align_chunks(host_chunks, module_runs):
    """(device seconds, weight passes) of the decode chunks that ran
    wholly inside the traced interval. `host_chunks` are the batcher's
    own spans [start, end, k] of those chunks, in order; `module_runs`
    the trace's [start, duration] of the chunk program, in order, which
    may hold one more run at either end (cut by the trace's edges). The
    host's span is the device's run plus a few milliseconds, so the
    offset that makes the durations agree best pairs them."""
    m, n = len(host_chunks), len(module_runs)
    if not m or n < m:
        return None
    host = [e - s for s, e, _ in host_chunks]
    best = min(range(n - m + 1), key=lambda o: sum(
        abs(module_runs[o + i][1] - host[i]) for i in range(m)))
    dev = sum(module_runs[best + i][1] for i in range(m))
    return dev, sum(k for _, _, k in host_chunks)


def _gaps(spans):
    cur = None
    for s, e in sorted(spans):
        if cur is not None and s > cur:
            yield cur, s
        cur = e if cur is None else max(cur, e)
