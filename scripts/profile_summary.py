#!/usr/bin/env python3
"""Reduce a JAX profiler trace of a TPU worker to two tables, without
TensorBoard, and set the scheduler's own account of its time beside them:

    python scripts/profile_summary.py [<trace_dir>] [--account FILE] [--json]

1. The device's time by named scope: the self time of each `XLA Ops`
   event (a `while`'s time less its body's ops) under the innermost scope
   of the vocabulary in its op name (docs/observability.md), per program
   (`XLA Modules`: `jit_chunk`, `jit_admit`), and each program's largest
   operations with their scope.
2. The device's idle time by host phase: every gap between op events,
   split over the `dli.<phase>` annotations of the batcher's step loop
   that it overlaps (innermost bracket first; the profiler must have been
   on: `DLI_PROFILE=1` or `POST /api/profile`). The device plane's clock
   is a millisecond or two off the host plane's, so the offset is first
   estimated from the program calls themselves: no run starts before its
   `dli.dispatch` / `dli.admit_run` does, none ends after its
   `dli.device_wait` / `dli.admit_run` does.

3. With `--account FILE`: the batcher's always-on phase clocks
   (`utils/profiler.py`), i.e. the busy steps' wall by bracket, from a
   benchmark's result line (the window's `batcher_clock_<phase>_ms`
   counters) or from `GET /api/profile` (`summary()["clocks"]`, since the
   batcher was built); with a trace, each bracket's clock beside the
   device's idle time under it: the host's two views in one place. The
   clocks are all of the window or the process's life, the trace a few
   seconds of it: compare the shares, not the seconds. Where the file
   holds the program account too (`summary()["programs"]`: a `GET
   /api/profile` answer, a `/load_model` answer), what the start's
   seconds went to is printed after them: the build, each program's
   first use by trace, lowering, load and first run, and the programs
   of no label. A formatter of what the program returned, no reader of
   its own (docs/observability.md, "The program account").

`<trace_dir>` is what `POST /profile/start` or `jax.profiler.start_trace`
wrote (its newest `plugins/profile/*/*.xplane.pb`), or the file itself.
Events are read with `jax.profiler.ProfileData`. It does not expose op
names, so each instruction's scope is read from the programs' HloProto,
which the trace carries in its `/host:metadata` plane, in the protobuf
wire format directly; an instruction the compiler made (a fusion's root)
takes the scope of what it fuses. Names come from the compiled programs'
metadata: an executable read from a compilation cache that an older build
filled has none, and its time lands under `(no scope)`.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys

SCOPES = ("kv_gather", "attention", "attn_gate", "kv_write", "mlp",
          "moe_route", "moe_experts", "moe_combine", "mla_absorb",
          "lm_head", "sample",
          # a state-space mixer's parts (ops/ssm.py): the chunked scan in
          # admit programs, the one-step update in decode chunks
          "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_step",
          "ssm_gate_norm", "ssm_out_proj",
          # a windowed layer's rows into its slot's ring (mimo-v2)
          "ring_write")
# a layer's kind, named inside kv_gather / attention where a model mixes
# windowed and full layers: reported as `attention/win`
# (mimo-v2's kinds, of different shapes: `attention/attention_swa`)
KINDS = ("win", "full", "attention_swa", "attention_full")
NO_SCOPE = "(no scope)"
LOOP_STEP = "loop_step_"   # transformer.loop_layer_stack names each pass
# instructions a compiler pass makes without an op name, by what their
# own name starts with: `lax.ragged_dot` becomes `ragged-dot-none.N`
# custom calls (the grouped expert matmuls of admit programs); the
# decode chunks' are `expert_stream_matmul.N`, the Pallas kernel of
# ops/pallas/grouped_matmul.py by its own name
BY_NAME = {"ragged-dot": "moe_experts",
           "expert_stream_matmul": "moe_experts"}
TOP_OPS = 16    # operations listed for each program, largest first


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        sys.exit(f"error: no plugins/profile/*/*.xplane.pb under {path}")
    return found[-1]


# ---- scopes, from the wire format ------------------------------------

def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as int,
    a length-delimited field as a memoryview, fixed widths skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield key >> 3, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def _varints(buf):
    """The varints of a packed repeated field."""
    out, i = [], 0
    while i < len(buf):
        val, i = _varint(buf, i)
        out.append(val)
    return out


def scope_of(op_name: str) -> str:
    """`jit(chunk)/while/body/attention/dot_general` -> `attention`;
    `.../attention/win/dot_general` -> `attention/win`; under a looped
    model's pass (`.../loop_step_2/while/body/mlp/dot_general`) the
    step goes first: `loop_step_2:mlp`, `loop_step_2:(no scope)`."""
    parts = op_name.split("/")
    step = next((p + ":" for p in parts if p.startswith(LOOP_STEP)), "")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in SCOPES:
            kind = parts[i + 1] if i + 1 < len(parts) else None
            return step + (f"{parts[i]}/{kind}" if kind in KINDS
                           else parts[i])
    return step + NO_SCOPE


def program_scopes(hlo) -> dict:
    """{instruction name: scope} of one program, from the HloProto the
    trace carries: hlo_module 1 -> computations 3 {instructions 2, id 5}
    -> instruction {name 1, metadata 7 {op_name 2}, called computation
    ids 38}. An instruction the compiler made (a fusion's root, a copy)
    has no op name of its own: it takes the scope most of the
    instructions it calls have."""
    comps = {}
    for n, comp in _fields(dict(_fields(hlo))[1]):
        if n != 3:
            continue
        cid, instrs = 0, []
        for m, v in _fields(comp):
            if m == 5:
                cid = v
            elif m == 2:
                name, op_name, called = "", "", []
                for f, x in _fields(v):
                    if f == 1:
                        name = bytes(x).decode()
                    elif f == 7:
                        op_name = bytes(dict(_fields(x)).get(2, b"")).decode()
                    elif f == 38:
                        called = _varints(x)
                instrs.append((name, scope_of(op_name), called))
        comps[cid] = instrs
    scopes = {}
    for instrs in comps.values():
        for name, scope, called in instrs:
            if scope.endswith(NO_SCOPE) and called:
                votes = {}
                for cid in called:
                    for _, inner, _ in comps.get(cid, ()):
                        if not inner.endswith(NO_SCOPE):
                            votes[inner] = votes.get(inner, 0) + 1
                if votes:
                    scope = max(votes, key=votes.get)
            scopes[name] = scope
    return scopes


def all_program_scopes(path: str) -> dict:
    """{program as the trace names its runs: {instruction: scope}}, from
    the `/host:metadata` plane: XSpace.planes 1 -> XPlane{name 2,
    event_metadata 4 {name 2, stats 5 {bytes_value 6}}}."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    programs = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        parts = list(_fields(plane))
        if not any(n == 2 and bytes(v) == b"/host:metadata"
                   for n, v in parts):
            continue
        for n, entry in parts:
            if n != 4:
                continue
            name, hlo = "", None
            for m, v in _fields(dict(_fields(entry))[2]):
                if m == 2:
                    name = bytes(v).decode()
                elif m == 5:
                    hlo = dict(_fields(v)).get(6, hlo)
            if hlo is not None:
                programs[name] = program_scopes(hlo)
    return programs


# ---- events ------------------------------------------------------------

def read(path: str) -> dict:
    """{"devices": [{"name", "modules": [(program, start, end)], "ops":
    [(scope, start, end, instruction)]}], "host": [(name, start, end)]}:
    seconds, the
    devices on their plane's clock and `dli.*` annotations on the host's.
    An op's scope is looked up by its instruction's name in the program
    (`jit_chunk(<id>)`) whose run it falls in."""
    import jax
    programs = all_program_scopes(path)
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines or "XLA Modules" not in lines:
                continue
            runs = sorted((e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, e.name)
                          for e in lines["XLA Modules"].events)
            starts = [r[0] for r in runs]
            ops = []
            for e in lines["XLA Ops"].events:
                start = e.start_ns * 1e-9
                i = bisect.bisect_right(starts, start) - 1
                scopes = (programs.get(runs[i][2], {})
                          if i >= 0 and start < runs[i][1] else {})
                instruction = e.name.split(" = ", 1)[0].lstrip("%")
                scope = scopes.get(instruction, NO_SCOPE)
                if scope == NO_SCOPE:
                    scope = next((sc for prefix, sc in BY_NAME.items()
                                  if instruction.startswith(prefix)),
                                 NO_SCOPE)
                ops.append((scope, start,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            instruction))
            devices.append({
                "name": plane.name,
                "modules": [(name.split("(", 1)[0], a, z)
                            for a, z, name in runs],
                "ops": sorted(ops, key=lambda o: (o[1], -o[2]))})
        elif plane.name.startswith("/host:CPU"):
            host += [(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                     for line in plane.lines for e in line.events
                     if e.name.startswith("dli.")]
    return {"devices": devices, "host": sorted(host, key=lambda h: h[1])}


def self_times(spans):
    """[(span, self seconds)] of (key, start, end) spans sorted by start,
    outer first: a nested span's time is taken out of the one around it."""
    out, stack = [], []

    def close(upto):
        while stack and stack[-1][0][2] <= upto:
            span, self_s = stack.pop()
            out.append((span, max(self_s, 0.0)))
    for span in spans:
        close(span[1])
        if stack:
            stack[-1][1] -= span[2] - span[1]
        stack.append([span, span[2] - span[1]])
    close(float("inf"))
    return out


def by_scope(dev):
    """({program: {scope: seconds}}, {program: [(instruction, scope,
    seconds, events)]}: the TOP_OPS operations by self time) for one
    device."""
    starts = [m[1] for m in dev["modules"]]
    table, ops = {}, {}
    for (scope, start, _, name), self_s in self_times(dev["ops"]):
        i = bisect.bisect_right(starts, start) - 1
        program = (dev["modules"][i][0]
                   if i >= 0 and start < dev["modules"][i][2]
                   else "(no program)")
        row = table.setdefault(program, {})
        row[scope] = row.get(scope, 0.0) + self_s
        op = ops.setdefault(program, {}).setdefault((name, scope), [0.0, 0])
        op[0] += self_s
        op[1] += 1
    return table, {
        program: [(name, scope, s, n) for (name, scope), (s, n) in
                  sorted(row.items(), key=lambda kv: -kv[1][0])[:TOP_OPS]]
        for program, row in ops.items()}


def clock_offset(dev, host):
    """(seconds to add to the device's times, low, high): the host's
    program calls in order (`dli.dispatch` to the next `dli.device_wait`'s
    end, `dli.admit_run`) against the device's runs in order, a run or
    two shifted where the trace's edges cut one; None without both."""
    calls, open_ = [], None
    for name, start, end in host:
        if name == "dli.admit_run":
            calls.append(("jit_admit", start, end))
        elif name == "dli.dispatch":
            open_ = start
        elif name == "dli.device_wait" and open_ is not None:
            calls.append(("jit_chunk", open_, end))
            open_ = None
    runs = [m for m in dev["modules"] if m[0] in ("jit_admit", "jit_chunk")]
    best = None
    for shift in range(-2, 3):
        pairs = [(c, runs[j + shift]) for j, c in enumerate(calls)
                 if 0 <= j + shift < len(runs)]
        if len(pairs) < min(len(calls), len(runs)) - 2 or not pairs or \
                any(c[0] != r[0] for c, r in pairs):
            continue
        low = max(c[1] - r[1] for c, r in pairs)
        high = min(c[2] - r[2] for c, r in pairs)
        if low <= high and (best is None or
                            abs(low + high) < abs(best[1] + best[2])):
            best = ((low + high) / 2, low, high)
    return best


def idle_by_phase(dev, host, offset: float) -> dict:
    """{phase: seconds} of the device's idle time: every gap between op
    events, moved onto the host's clock, goes to the innermost `dli.*`
    bracket over each part of it; inside a step but outside its brackets
    is `other`, outside every step `(no step)`."""
    brackets = [(n[4:], s, e) for n, s, e in host]
    table = {}
    cur = None
    for _, start, end, *_ in dev["ops"]:      # (scope, start, end[, name])
        if cur is not None and start > cur:
            g0, g1 = cur + offset, start + offset
            inside = [(n, max(s, g0), min(e, g1)) for n, s, e in brackets
                      if min(e, g1) > max(s, g0)]
            covered = 0.0
            for (name, _, _), self_s in self_times(inside):
                name = "other" if name == "step" else name
                table[name] = table.get(name, 0.0) + self_s
                covered += self_s
            if g1 - g0 > covered:
                table["(no step)"] = (table.get("(no step)", 0.0)
                                      + g1 - g0 - covered)
        cur = end if cur is None else max(cur, end)
    return table


def summarize(path: str) -> dict:
    xplane = find_xplane(path)
    events = read(xplane)
    out = {"xplane": xplane, "devices": [], "annotations": {}}
    for dev in events["devices"]:
        ops = dev["ops"]
        offset = clock_offset(dev, events["host"])
        scopes, largest = by_scope(dev)
        out["devices"].append({
            "name": dev["name"],
            "window_s": max(o[2] for o in ops) - ops[0][1],
            "busy_s": sum(s for _, s in self_times(ops)),
            "by_scope": scopes,
            "by_op": largest,
            "clock_offset_ms": offset and [x * 1e3 for x in offset],
            "idle_by_phase": idle_by_phase(dev, events["host"],
                                           offset[0] if offset else 0.0)})
    for name, start, end in events["host"]:
        row = out["annotations"].setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += end - start
    return out


NESTED = ("admit_prep", "admit_run", "admit_post")


def _last_line(path: str) -> dict:
    with open(path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def read_programs(path: str):
    """The first program account (a `programs` that holds `rows`) in the
    JSON file's last line, at any depth; None if it holds none."""
    todo = [_last_line(path)]
    while todo:
        doc = todo.pop(0)
        found = doc.get("programs")
        if isinstance(found, dict) and "rows" in found:
            return found
        todo.extend(v for v in doc.values() if isinstance(v, dict))
    return None


def render_programs(acct: dict) -> str:
    """The program account as text: when the process had imported and
    built, the build by part, a line a program first used, the sums by
    `serving`, and the unlabelled programs' names by cost."""
    proc = acct["process"]
    lines = ["program account: serving code imported "
             f"{proc['imported_s']} s after the process began, batcher "
             f"built at {proc['built_s']} s"]
    build = acct["build"]
    lines.append(f"  build {build.get('wall_ms', 0) / 1e3:9.3f} s")
    head = (f"    {'':<22} {'wall':>9} {'trace':>9} {'lower':>9} "
            f"{'load':>9} {'run':>9}  ms")

    def cells(row):
        return " ".join(f"{row.get(k, 0):9.1f}" if k in row else " " * 9
                        for k in ("wall_ms", "trace_ms", "lower_ms",
                                  "load_ms", "run_ms"))
    lines.append(head)
    for part in ("weights", "pool", "eager"):
        row = build.get(part)
        if row:
            lines.append(f"    {part:<22} {cells(row)}  "
                         f"{row.get('programs', 0)} programs"
                         + (f", {row['bytes'] / 2 ** 30:.3f} GiB"
                            if "bytes" in row else ""))
    for row in acct["rows"]:
        key = row["key"]
        name = f"{row['kind']} " + ("x".join(map(str, key))
                                    if isinstance(key, list) else str(key))
        lines.append(
            f"    {name:<22} {cells(row)}  cache {row['cache']}"
            + (f", {row['pallas_call_sites']} kernels"
               if row["pallas_call_sites"] else "")
            + (", compiled ahead" if row.get("aot") else "")
            + (", SERVING" if row["serving"] else ""))
    for when, t in acct["totals"].items():
        lines.append(f"    {when + ' total':<22} {cells(t)}  "
                     f"{t['programs']} programs, cache "
                     f"{t['cache_hits']} hits {t['cache_misses']} misses")
    for when, e in acct["eager"].items():
        lines.append(f"    {'no label, ' + when:<22} {cells(e)}  "
                     f"{e['programs']} programs, cache "
                     f"{e['cache_hits']} hits {e['cache_misses']} misses")
        for name, ms in sorted(e["by_name"].items(),
                               key=lambda kv: -kv[1])[:8]:
            lines.append(f"      {name:<36} {ms:9.1f} ms")
    if acct.get("rows_dropped"):
        lines.append(f"    ({acct['rows_dropped']} rows past the bound)")
    return "\n".join(lines)


def read_account(path: str) -> dict:
    """{phase: seconds} of the scheduler's clocks from a JSON file (its
    last line, if it has several): a benchmark's result line, a worker's
    or master's `GET /api/profile` answer (the first profiler found), or
    a `summary()` itself."""
    doc = _last_line(path)
    prefix, suffix = "batcher_clock_", "_ms"
    if "counters" in doc:
        return {k[len(prefix):-len(suffix)]: v * 1e-3
                for k, v in doc["counters"].items()
                if k.startswith(prefix) and k.endswith(suffix)}
    while "clocks" not in doc:      # {"profilers": {model: {"summary": ..
        nested = [v for v in doc.values() if isinstance(v, dict)]
        if not nested:
            sys.exit(f"error: no clocks in {path}")
        doc = nested[0]
    return {**doc["clocks"]["phases"], **doc["clocks"]["nested"],
            "between": doc["clocks"]["between_s"]}


def render_account(clocks: dict, idle: dict = None) -> str:
    """The busy steps' wall by bracket (nested brackets indented, not
    summed again; `between` is the time from one busy step to the next,
    no step's wall), and beside each the device's idle time under it."""
    wall = sum(s for name, s in clocks.items()
               if name not in NESTED + ("between",))
    lines = [f"scheduler clocks: busy steps' wall {wall:.4f} s"
             + ("; device idle under each bracket from the trace"
                if idle is not None else "")]
    for name, s in sorted(clocks.items(), key=lambda kv: -kv[1]):
        label = ("  " if name in NESTED else "") + name
        line = (f"    {label:<14} {s:9.4f} s "
                f"{100 * s / wall if wall else 0:6.2f} %")
        if idle is not None:
            line += f"   idle {idle.get(name, 0.0) * 1e3:9.3f} ms"
        lines.append(line)
    return "\n".join(lines)


def render(summary: dict) -> str:
    lines = [f"trace: {summary['xplane']}"]
    for dev in summary["devices"]:
        idle = dev["window_s"] - dev["busy_s"]
        lines.append(f"\n{dev['name']}: busy {dev['busy_s']:.4f} s of "
                     f"{dev['window_s']:.4f} s, idle {idle * 1e3:.2f} ms")
        lines.append("device time by program and scope (self time, share "
                     "of the program):")
        for program, row in sorted(dev["by_scope"].items(),
                                   key=lambda kv: -sum(kv[1].values())):
            total = sum(row.values())
            named = total - row.get(NO_SCOPE, 0.0)
            lines.append(f"  {program}: {total:.4f} s, "
                         f"{100 * named / total:.1f} % under a scope")
            for scope, s in sorted(row.items(), key=lambda kv: -kv[1]):
                lines.append(f"    {scope:<12} {s:9.4f} s "
                             f"{100 * s / total:6.2f} %")
            for name, scope, s, n in dev["by_op"].get(program, ()):
                lines.append(f"      op {name:<40} {scope:<12} {s:9.4f} s "
                             f"{100 * s / total:6.2f} % in {n} events")
        off = dev["clock_offset_ms"]
        lines.append("idle time by host phase (device clock "
                     + (f"{off[0]:+.3f} ms, between {off[1]:+.3f} and "
                        f"{off[2]:+.3f}" if off else "not aligned: no "
                        "dli.dispatch / dli.device_wait pairs") + "):")
        for phase, s in sorted(dev["idle_by_phase"].items(),
                               key=lambda kv: -kv[1]):
            lines.append(f"    {phase:<12} {s * 1e3:9.3f} ms "
                         f"{100 * s / idle if idle else 0:6.2f} %")
    lines.append("\nhost annotations (count, seconds):")
    for name, (n, s) in sorted(summary["annotations"].items()):
        lines.append(f"    {name:<18} {n:6d} {s:9.4f} s")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir", nargs="?")
    ap.add_argument("--account", metavar="FILE",
                    help="a result line or a GET /api/profile answer: "
                    "print the scheduler's phase clocks too, and the "
                    "program account where the file holds one")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON object")
    args = ap.parse_args(argv)
    if not (args.trace_dir or args.account):
        ap.error("give a trace directory, --account, or both")
    summary = summarize(args.trace_dir) if args.trace_dir else {}
    if args.account:
        summary["clocks"] = read_account(args.account)
        summary["programs"] = read_programs(args.account)
    if args.json:
        print(json.dumps(summary))
        return 0
    if args.trace_dir:
        print(render(summary))
    if args.account:
        idle = (summary["devices"][0]["idle_by_phase"]
                if summary.get("devices") else None)
        print(render_account(summary["clocks"], idle))
        if summary["programs"]:
            print(render_programs(summary["programs"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
