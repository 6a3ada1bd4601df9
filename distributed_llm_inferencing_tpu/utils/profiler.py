"""Always-on phase clocks and a low-overhead sampling profiler for the
batcher's decode step loop.

The Chrome-trace spans from PR 1 answer "where did THIS request's time
go"; the XLA profiler (`/profile/start`) answers "what did the device
run". Neither answers the steady-state capacity question: across
thousands of scheduler steps, what fraction of wall time is host
argument prep vs program dispatch vs waiting on the device vs token
emission bookkeeping? That attribution decides whether the next speedup
comes from fusing kernels (device-bound) or from trimming the host path
(dispatch-bound) — and it has to be measurable on a production worker
without changing what is measured.

:class:`PhaseProfiler` is the answer: the step loop brackets its phases
with ``profiler.phase("dispatch")`` context managers and one
``step_begin()/step_end()`` pair per step. Whether or not it is enabled,
every bracket of a busy step adds its wall to a cumulative **clock** of
its name (seconds since construction; ``clocks()``, ``read()``), and the
step's wall outside every top-level bracket to ``other`` (the time from
a step that left slots running to the next one goes to ``between``):
about ten ``perf_counter`` pairs a step, no annotation, no sample. Each request
reads the clocks twice (first token, finish) and so accounts for its
decode time by bracket (``runtime/batcher.py: _cost_record``). When
enabled, besides:

- every bracket is also a ``jax.profiler.TraceAnnotation("dli.<phase>")``
  (keyword stats ride along: ``k``/``slots``/``chunk`` on ``dispatch``,
  ``rows``/``tail_bucket``/``prefix_bucket``/``tokens``/``wave`` on
  ``admit_run``) and the
  step a ``StepTraceAnnotation("dli.step", step_num=...)``, so ANY
  profiler trace (worker ``POST /profile/start``, the benchmark's
  ``Tracer``) holds the host phases in the ``/host:CPU`` plane on the
  same clock as the device's ``XLA Modules`` / ``XLA Ops``
  (``scripts/profile_summary.py`` reads them). An annotation costs well
  under a microsecond while no trace is running;
- each *sampled* step (every ``sample_every``-th) records its brackets as
  ordered ``(name, start, end, depth)`` tuples — epoch seconds, taken
  from ``perf_counter`` offsets against one wall-clock anchor per step,
  so they lay out truthfully beside the ``utils/trace.py`` spans — into
  a bounded ring. ``summary()`` derives the per-phase totals from them.

Top-level brackets used by the batcher (docs/observability.md); these
are the keys of ``summary()["phases"]``, each inclusive of what it
nests, and ``other`` conserves the step's total:

- ``admit``       — the admission wave, whole (nests the three below)
- ``host_prep``   — growth allocation + decode-chunk argument packing
- ``spec_draft``  — host-side drafting state prep (speculation)
- ``dispatch``    — the async jitted-program call (host->device args ride
                    along; returns before the device finishes)
- ``spec_verify`` — the fused draft+verify program incl. its sync
- ``device_wait`` — blocking ``device_get`` for the chunk's sampled
                    tokens (device compute the host couldn't hide)
- ``emit``        — token emission: per-request bookkeeping, stream
                    callbacks, eos/budget slot retirement
- ``bookkeeping`` — step-epilogue metrics/gauge refresh
- ``other``       — whatever the brackets above don't cover

Nested brackets (``summary()["nested"]``, never folded into ``phases``):

- ``admit_prep``  — radix match, block allocation, numpy packing
- ``admit_run``   — the admit program call and its one host sync
- ``admit_post``  — radix insert, slot bind, first-token emission

Export: ``summary()`` and ``chrome_events()`` (the sampled brackets at
their real timestamps, mergeable into the PR 1 ``/api/trace`` export).

Beside the clocks, what a stalled program call needs to say why it
stalled: ``call_readings()`` (the thread's and the process's CPU clocks, the
process's involuntary context switches and major faults, the seconds
spent in the cycle collector) taken at each call's start, and
``call_deltas()``, their deltas with the worst lateness of the process's
**heartbeat** over the call (one daemon thread a process, asleep 50 ms
at a time: if it woke late, no thread of this process ran).

And the **program account**: what each program's first use cost, in the
compiler's own words. One set of ``jax.monitoring`` listeners a process
(``programs()``, started like ``vitals()``) hears every trace, lowering
and backend compile (the cache's read on a hit) with its ``[start,
end]`` on ``time.time()``, on the thread that compiles and only when
something compiles. ``PhaseProfiler.program(kind, key)`` labels the
thread for one call; a labelled call that compiled leaves one row
(``trace_ms``, ``lower_ms``, ``load_ms``, ``run_ms``, ``cache``), the
constructor's ``build`` labels the weights' and the pool's share, and
what compiles on a thread with no label is summed under ``eager`` by
the program's name. ``summary()["programs"]`` holds the account, as
many rows as there are programs (docs/observability.md).
"""

from __future__ import annotations

import gc
import os
import resource
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from distributed_llm_inferencing_tpu.utils import clock

# canonical order of summary()'s phases (unknown names sort after these)
PHASE_ORDER = ("admit", "host_prep", "spec_draft", "dispatch",
               "spec_verify", "device_wait", "emit", "bookkeeping",
               "other")

_ORDER = {name: i for i, name in enumerate(PHASE_ORDER)}

DEFAULT_CAPACITY = 2048

_ANNOTATIONS = None


def _annotations():
    """(TraceAnnotation, StepTraceAnnotation), imported on first use so
    that a process that never arms a profiler never pays for it."""
    global _ANNOTATIONS
    if _ANNOTATIONS is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        _ANNOTATIONS = (TraceAnnotation, StepTraceAnnotation)
    return _ANNOTATIONS


class _Noop:
    """Shared do-nothing context manager: the disabled profiler's phase()
    return value. One global instance — no allocation on the hot path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Phase:
    """One bracket of a step: its wall goes to the clock of its name;
    under an enabled profiler it is a TraceAnnotation too, and in a
    sampled step one ``[name, start, end, depth]`` entry."""
    __slots__ = ("prof", "ann", "span")

    def __init__(self, prof: "PhaseProfiler", name: str, stats: dict):
        self.prof = prof
        self.ann = (_annotations()[0]("dli." + name, **stats)
                    if prof._annotate else None)
        self.span = [name, 0.0, 0.0, 0]

    def __enter__(self):
        prof = self.prof
        if self.ann is not None:
            self.ann.__enter__()
        self.span[3] = len(prof._open)
        prof._open.append(self.span)
        if prof._cur is not None:
            prof._cur.append(self.span)    # ordered by start
        self.span[1] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        span = self.span
        span[2] = time.perf_counter()
        prof = self.prof
        prof._open.pop()
        wall = span[2] - span[1]
        prof._step[span[0]] = prof._step.get(span[0], 0.0) + wall
        if span[3]:
            prof._nested.add(span[0])
        else:
            prof._step_top += wall
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


# ---- what the process was doing over a program call ------------------

HEARTBEAT_S = 0.050


class _Vitals:
    """One a process: the seconds spent in the cycle collector (a
    ``gc.callbacks`` timer, two clock reads a collection) and the
    heartbeat, a daemon thread that sleeps ``HEARTBEAT_S`` at a time
    (through the ``utils/clock.py`` seam) and keeps how late each
    wake-up came. A thread that wakes late was not scheduled, or could
    not take the interpreter: either way this process's Python stood
    still that long."""

    def __init__(self):
        self.gc_s = 0.0
        self._gc_t0 = None
        self._beats: deque = deque(maxlen=512)   # (woke, late s): 25 s
        self._asleep = time.perf_counter()       # the current nap's start
        self._lock = threading.Lock()
        gc.callbacks.append(self._on_gc)
        threading.Thread(target=self._beat, name="dli-heartbeat",
                         daemon=True).start()

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def _beat(self):
        while True:
            self._asleep = t = time.perf_counter()
            clock.sleep(HEARTBEAT_S)
            woke = time.perf_counter()
            with self._lock:
                self._beats.append((woke, woke - t - HEARTBEAT_S))

    def late_s(self, since: float) -> float:
        """The worst lateness of a wake-up since ``since`` (a
        ``perf_counter`` time), the nap still running included."""
        with self._lock:
            late = max((late for woke, late in self._beats
                        if woke >= since), default=0.0)
        return max(late, time.perf_counter() - self._asleep - HEARTBEAT_S,
                   0.0)


_VITALS: Optional[_Vitals] = None
_VITALS_LOCK = threading.Lock()


def vitals() -> _Vitals:
    """The process's one :class:`_Vitals`, started on first use (a
    batcher's construction), never at import."""
    global _VITALS
    if _VITALS is None:
        with _VITALS_LOCK:
            if _VITALS is None:
                _VITALS = _Vitals()
    return _VITALS


def call_readings() -> tuple:
    """``(perf_counter, thread CPU s, process CPU s, involuntary context
    switches, major faults, collector s)`` now: what a program call
    notes at its start (three clock reads and one ``getrusage``)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return (time.perf_counter(), time.thread_time(), time.process_time(),
            ru.ru_nivcsw, ru.ru_majflt, vitals().gc_s)


def call_deltas(before: tuple) -> dict:
    """What the thread and the process did since ``before``
    (:func:`call_readings`, taken on this thread)."""
    now = call_readings()
    return {
        "thread_cpu_ms": round((now[1] - before[1]) * 1e3, 1),
        "process_cpu_ms": round((now[2] - before[2]) * 1e3, 1),
        "invol_switches": now[3] - before[3],
        "major_faults": now[4] - before[4],
        "gc_ms": round((now[5] - before[5]) * 1e3, 1),
        "heartbeat_late_ms": round(vitals().late_s(before[0]) * 1e3, 1),
    }


# ---- what a program's first use cost ---------------------------------

# jax.monitoring's names (jax/_src/dispatch.py, compiler.py): the three
# time spans of one compilation, in the order they nest where they do
# (a kernel's own trace inside its lowering), and the cache's events
_SPAN_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": 0,
                "/jax/core/compile/jaxpr_to_mlir_module_duration": 1,
                "/jax/core/compile/backend_compile_duration": 2}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
# hits, misses (JAX counts a miss where it writes the entry: one that
# compiled faster than the cache's minimum is neither)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 1}
# the account is as large as the number of programs, and no larger
MAX_PROGRAM_ROWS = 512
MAX_EAGER_NAMES = 128

_THREAD = threading.local()     # .call: the labelled call in flight


def _union_s(*span_lists) -> float:
    """Seconds covered by the ``(start, end)`` spans of all the lists."""
    total, upto = 0.0, float("-inf")
    for start, end in sorted(sp for spans in span_lists for sp in spans):
        if end > upto:
            total += end - max(start, upto)
            upto = end
    return total


def _process_start() -> Optional[float]:
    """When the OS started this process, in epoch seconds (its start in
    clock ticks since boot against the uptime now); None off Linux."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - up + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


_IMPORTED_AT: Optional[float] = None


def mark_imported():
    """Note that the package's serving code is imported (the last line of
    ``runtime/batcher.py``, which pulls in JAX and the models): one clock
    read, the first time."""
    global _IMPORTED_AT
    if _IMPORTED_AT is None:
        _IMPORTED_AT = time.time()


def pallas_call_site():
    """Count one Pallas call site into the labelled call this thread is
    tracing, if any: the ``ops/pallas`` entry points call it as they are
    traced (nothing of it is in a program)."""
    call = getattr(_THREAD, "call", None)
    if call is not None:
        call.pallas += 1


def _compiled_ms(spans) -> Dict[str, float]:
    """``trace_ms``, ``lower_ms``, ``load_ms`` of one call's event spans
    (trace, lower, load): the union of each kind, so that an inner
    ``jit``'s trace is counted once, and what nests inside another kind
    (a kernel traced while it is lowered) counted for the outer one
    alone: the three add up to the time some event covered."""
    trace, lower, load = spans
    in_load = _union_s(load)
    in_lower = _union_s(lower, load)
    return {"trace_ms": round((_union_s(trace, lower, load) - in_lower)
                              * 1e3, 3),
            "lower_ms": round((in_lower - in_load) * 1e3, 3),
            "load_ms": round(in_load * 1e3, 3)}


class _Programs:
    """One a process: the ``jax.monitoring`` listeners. An event belongs
    to the labelled call of its thread (``_THREAD.call``); on a thread
    with none it is summed under ``eager`` by the program's name, apart
    for the time before and after a batcher's scheduler thread started
    (sums, not unions: an eager program's inner trace counts twice)."""

    def __init__(self):
        import jax.monitoring as monitoring
        self._lock = threading.Lock()
        self.started = _process_start()
        self.serving = 0      # batchers whose scheduler thread runs
        # [serving] -> {fun_name: [programs, trace s, lower s, load s]}
        self._eager = ({}, {})
        self._eager_cache = ([0, 0], [0, 0])
        monitoring.register_event_time_span_listener(self._on_span)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, fun_name="", **_):
        kind = _SPAN_EVENTS.get(event)
        if kind is None:
            return
        call = getattr(_THREAD, "call", None)
        if call is not None:
            if call.spans is None:
                call.spans = ([], [], [])
            call.spans[kind].append((start, end))
            if kind == 2 or not call.fun_name:
                call.fun_name = str(fun_name)
            return
        with self._lock:
            names = self._eager[bool(self.serving)]
            name = str(fun_name)
            if name not in names and len(names) >= MAX_EAGER_NAMES:
                name = "(others)"
            row = names.setdefault(name, [0, 0.0, 0.0, 0.0])
            row[0] += kind == 2
            row[1 + kind] += end - start

    def _on_duration(self, event, duration, **_):
        if event == _CACHE_READ:
            call = getattr(_THREAD, "call", None)
            if call is not None:
                call.cache_read_s += duration

    def _on_event(self, event, **_):
        kind = _CACHE_EVENTS.get(event)
        if kind is None:
            return
        call = getattr(_THREAD, "call", None)
        if call is not None:
            call.cache[kind] += 1
        else:
            with self._lock:
                self._eager_cache[bool(self.serving)][kind] += 1

    def eager(self) -> dict:
        """What compiled on threads with no label: ``setup`` (no
        scheduler thread ran) and ``serving``, each its programs, their
        seconds and the cache's answers, and ``by_name`` (milliseconds
        of trace + lower + load a program name)."""
        out = {}
        with self._lock:
            for key, names, cache in zip(("setup", "serving"), self._eager,
                                         self._eager_cache):
                sums = [sum(r[i] for r in names.values()) for i in range(4)]
                out[key] = {
                    "programs": sums[0],
                    "trace_ms": round(sums[1] * 1e3, 3),
                    "lower_ms": round(sums[2] * 1e3, 3),
                    "load_ms": round(sums[3] * 1e3, 3),
                    "cache_hits": cache[0], "cache_misses": cache[1],
                    "by_name": {n: round(sum(r[1:]) * 1e3, 3)
                                for n, r in sorted(names.items())}}
        return out


_PROGRAMS: Optional[_Programs] = None


def programs() -> _Programs:
    """The process's one :class:`_Programs`, started on first use (a
    batcher's construction), never at import."""
    global _PROGRAMS
    if _PROGRAMS is None:
        with _VITALS_LOCK:
            if _PROGRAMS is None:
                _PROGRAMS = _Programs()
    return _PROGRAMS


class _Call:
    """One labelled call: the thread's events are its own from enter to
    exit (an inner label takes over and hands back). Until an event
    comes it holds nothing, and a call that saw none records nothing."""
    __slots__ = ("prof", "kind", "key", "aot", "outer", "t0", "t1", "spans",
                 "cache", "cache_read_s", "fun_name", "pallas", "attrs")

    def __init__(self, prof: "PhaseProfiler", kind: str, key, aot: bool):
        self.prof, self.kind, self.key, self.aot = prof, kind, key, aot
        self.spans = None
        self.cache = [0, 0]
        self.cache_read_s = 0.0
        self.fun_name = ""
        self.pallas = 0
        self.attrs: dict = {}

    def __enter__(self):
        self.outer = getattr(_THREAD, "call", None)
        _THREAD.call = self
        self.t0 = time.time()
        return self

    def __exit__(self, *exc):
        self.t1 = time.time()
        _THREAD.call = self.outer
        if self.spans is not None and not (self.spans[1] or self.spans[2]):
            self.spans = None    # a trace found again: nothing compiled
        prof = self.prof
        if (self.spans is not None or self.kind == "build"
                or prof._await_run):
            prof._close_call(self)
        return False

    def account(self) -> dict:
        """The call's row: what its events covered, by kind, the cache's
        answer, and under ``run_ms`` the rest of its wall."""
        spans = self.spans or ((), (), ())
        ms = _compiled_ms(spans)
        hits, misses = self.cache
        wall_ms = (self.t1 - self.t0) * 1e3
        import jax
        cache = ("hit" if hits and hits >= len(spans[2]) else
                 "miss" if jax.config.jax_compilation_cache_dir else "off")
        key = list(self.key) if isinstance(self.key, tuple) else self.key
        return {"kind": self.kind, "key": key,
                "fun_name": self.fun_name, **ms,
                "cache": cache, "cache_hits": hits, "cache_misses": misses,
                "cache_read_ms": round(self.cache_read_s * 1e3, 3),
                "run_ms": round(max(0.0, wall_ms - sum(ms.values())), 3),
                "wall_ms": round(wall_ms, 3),
                "pallas_call_sites": self.pallas,
                "start": self.t0, "end": self.t1}


def step_phases(rec: dict) -> Dict[str, float]:
    """Seconds per top-level bracket of one recorded step (inclusive of
    what each nests), with the uncovered remainder under ``other`` so
    the values sum to the step's total."""
    phases: Dict[str, float] = {}
    for name, start, end, depth in rec["spans"]:
        if depth == 0:
            phases[name] = phases.get(name, 0.0) + (end - start)
    other = rec["total"] - sum(phases.values())
    if other > 0:
        phases["other"] = phases.get("other", 0.0) + other
    return phases


class PhaseProfiler:
    """Bounded ring of per-step phase timelines for one batcher.

    Thread model: ``step_begin``/``step_end``, the phase brackets and
    ``read()`` run on the scheduler thread only; ``configure``/readers
    may run on HTTP handler threads — the ring, the clocks and config
    flip under ``_lock``, and the in-flight step (``_in_step``, ``_cur``,
    ``_open``, ``_step``) is scheduler-thread-private.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 sample_every: int = 1, enabled: bool = False):
        self.enabled = bool(enabled)
        self.sample_every = max(1, int(sample_every))
        self._ring: deque = deque(maxlen=max(16, int(capacity)))
        self._lock = threading.Lock()
        self._in_step = False     # a step is open
        self._annotate = False    # ... of an enabled profiler
        self._cur: Optional[List[list]] = None   # its brackets, if sampled
        self._open: List[list] = []      # the brackets open now, outer first
        self._step_ann = None
        # the clocks: seconds per bracket name over the busy steps so
        # far, top-level and nested names side by side (``_nested`` says
        # which), and the open step's own share, folded in at its end
        self._clocks: Dict[str, float] = {}
        self._nested: set = set()
        self._busy = [0, 0.0]     # busy steps, their wall
        self._step: Dict[str, float] = {}
        self._step_top = 0.0      # ... its top-level brackets' wall
        self._t0 = time.perf_counter()     # the open (or last) step's start
        # when the last step ended, if it left slots running: the next
        # step follows at once, and the time between the two (the loop,
        # the step's own prologue and epilogue, other threads holding
        # the interpreter) is the scheduler's too: the clock `between`
        self._chain: Optional[float] = None
        self._shift = clock.now() - self._t0    # perf_counter -> epoch
        self.last_step: Dict[str, float] = {}   # the last busy step's clocks
        vitals()
        # the program account (module docstring): a row a program first
        # used, the constructor's build, and what the batcher has yet to
        # journal of them (``take_unreported``)
        self._account = programs()
        self._rows: List[dict] = []
        self._rows_dropped = 0
        self._build: Dict[str, dict] = {}
        # a batcher makes its profiler first: the build starts here
        self._build_began = time.time()
        self._eager_began = self._account.eager()["setup"]
        self._built_at: Optional[float] = None
        self._await_run: Dict[tuple, dict] = {}   # compiled, not yet run
        self._unreported: List[tuple] = []
        self.serving = False      # the scheduler thread was started
        self._step_n = 0          # steps seen while enabled (sampling clock)
        self._sampled = 0         # steps actually recorded

    @classmethod
    def from_env(cls) -> "PhaseProfiler":
        """DLI_PROFILE=1 arms the profiler at construction;
        DLI_PROFILE_SAMPLE=N records every Nth step (default 1);
        DLI_PROFILE_CAPACITY bounds the sample ring."""
        enabled = os.environ.get("DLI_PROFILE", "") .lower() in ("1", "true")
        try:
            sample = int(os.environ.get("DLI_PROFILE_SAMPLE", 1))
        except ValueError:
            sample = 1
        try:
            cap = int(os.environ.get("DLI_PROFILE_CAPACITY",
                                     DEFAULT_CAPACITY))
        except ValueError:
            cap = DEFAULT_CAPACITY
        return cls(capacity=cap, sample_every=sample, enabled=enabled)

    def configure(self, enabled: Optional[bool] = None,
                  sample_every: Optional[int] = None,
                  reset: bool = False) -> dict:
        """Runtime toggle (worker ``POST /api/profile``). Returns the
        resulting config so the caller can echo it."""
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if sample_every is not None:
                self.sample_every = max(1, int(sample_every))
            if reset:
                self._ring.clear()
                self._sampled = 0
                self._step_n = 0
        return {"enabled": self.enabled, "sample_every": self.sample_every,
                "capacity": self._ring.maxlen}

    # ---- hot path ----------------------------------------------------

    def step_begin(self) -> Optional[dict]:
        """Open one scheduler step: its start is read once, here, and
        anchors the step's times to the epoch (``epoch()``). Every step
        of an enabled profiler is a ``dli.step`` annotation and its
        brackets ``dli.<phase>`` annotations; returns the step's record,
        or None when it is not sampled (disabled, or skipped by the
        sampling stride)."""
        self._annotate = self.enabled
        if self._annotate:
            self._step_n += 1
            self._step_ann = _annotations()[1]("dli.step",
                                               step_num=self._step_n)
            self._step_ann.__enter__()
        self._t0 = t0 = time.perf_counter()
        self._shift = clock.now() - t0
        self._step = ({} if self._chain is None
                      else {"between": t0 - self._chain})
        self._step_top = 0.0
        del self._open[:]
        self._in_step = True
        if not self._annotate or (self._step_n - 1) % self.sample_every:
            return None
        self._cur = []
        return {"t": t0 + self._shift}

    def step_end(self, rec: Optional[dict], keep: bool = True,
                 active: int = 0, **meta) -> float:
        """Close the step and return its wall. ``keep=False`` discards
        its record and its share of the clocks (idle polls);
        ``active`` (slots still running: the next step follows at once)
        and ``meta`` ride a sampled step's record."""
        end = time.perf_counter()
        total = end - self._t0
        self._chain = end if keep and active else None
        self._in_step = False
        if self._step_ann is not None:
            self._step_ann.__exit__(None, None, None)
            self._step_ann = None
        spans, self._cur = self._cur, None
        if not keep:
            self._step = {}
            return total
        step = self.last_step = self._step
        step["other"] = max(0.0, total - self._step_top)
        shift = self._shift        # perf_counter -> epoch, one anchor
        if rec is not None:
            rec["total"] = total
            rec["spans"] = [(name, start + shift, end + shift, depth)
                            for name, start, end, depth in spans]
            rec["meta"] = {"active": active, **meta}
        with self._lock:
            for name, s in step.items():
                self._clocks[name] = self._clocks.get(name, 0.0) + s
            self._busy[0] += 1
            self._busy[1] += total
            if rec is not None:
                self._ring.append(rec)
                self._sampled += 1
        return total

    def phase(self, name: str, **stats):
        """Bracket of the current step; ``stats`` become the
        annotation's keyword stats under an enabled profiler. Outside a
        step the cost is one attribute check and a shared no-op."""
        if not self._in_step:
            return _NOOP
        return _Phase(self, name, stats)

    # ---- the program account -----------------------------------------

    def program(self, kind: str, key, aot: bool = False) -> _Call:
        """Label this thread for one program call (``kind``: admit,
        chunk, spec or build; ``key`` the program's own): what compiles inside
        is the call's. ``aot``: the call compiles and does not run (the
        first labelled call of the same key is then its first run)."""
        return _Call(self, kind, key, aot)

    def _close_call(self, call: _Call):
        where = (call.kind, call.key)
        if call.kind == "build":
            row = call.account()
            for name in ("kind", "key", "fun_name", "cache", "run_ms",
                         "pallas_call_sites"):
                del row[name]
            row.update(programs=len((call.spans or ((), (), ()))[2]),
                       **call.attrs)
            with self._lock:
                self._build[str(call.key)] = row
            return
        if call.spans is None:
            row = self._await_run.pop(where, None)
            if row is not None:     # a program compiled ahead: its first run
                run_ms = round((call.t1 - call.t0) * 1e3, 3)
                with self._lock:
                    row["run_ms"] = round(row["run_ms"] + run_ms, 3)
                self._unreported.append((row, run_ms, False))
            return
        row = call.account()
        row["serving"] = self.serving
        if call.aot:
            row["aot"] = True
            self._await_run[where] = row
        else:
            self._await_run.pop(where, None)
        with self._lock:
            if len(self._rows) < MAX_PROGRAM_ROWS:
                self._rows.append(row)
            else:
                self._rows_dropped += 1
        self._unreported.append((row, row["run_ms"], True))

    def awaits_run(self, kind: str, key) -> bool:
        """Whether ``program(kind, key)`` was compiled ahead and has not
        run yet."""
        return (kind, key) in self._await_run

    @property
    def first_use(self) -> bool:
        """Whether a labelled call since the last ``take_unreported``
        was a program's first use."""
        return bool(self._unreported)

    def take_unreported(self):
        """``(row, run ms to count, compiled)`` of the labelled calls
        since the last take that were a program's first use: one that
        compiled (its whole row), or the first run of one compiled ahead
        (``compiled`` false: its wall, added to the row it has)."""
        if not self._unreported:
            return ()
        out, self._unreported = self._unreported, []
        return out

    def set_serving(self, serving: bool):
        """The scheduler thread starts or stops: rows (and the process's
        unlabelled programs) from here on are ``serving``'s."""
        if serving != self.serving:
            self.serving = serving
            with self._account._lock:
                self._account.serving += 1 if serving else -1

    def built(self) -> dict:
        """Close the constructor's account: its wall since this profiler
        was made and what compiled on no label meanwhile. Returns
        ``programs()["build"]``."""
        self._built_at = time.time()
        start, was = self._build_began, self._eager_began
        now = self._account.eager()["setup"]
        eager = {k: round(now[k] - was[k], 3) for k in now if k != "by_name"}
        with self._lock:
            self._build = {"start": start, "end": self._built_at,
                           "wall_ms": round((self._built_at - start) * 1e3, 3),
                           **self._build, "eager": eager}
            return dict(self._build)

    def programs(self) -> dict:
        """The program account (JSON-safe, as large as the number of
        programs): ``process`` (seconds from the process's start, as the
        OS has it, to the serving code's import done, to this batcher's
        constructor begun (between the two: the backend's start, the
        caller's own work) and to the batcher built), ``build`` (the
        constructor: ``weights``, ``pool``, and ``eager``, what
        compiled outside both), ``eager`` (programs of
        threads with no label, the whole process's), ``rows`` (one a
        program first used) and their ``totals`` by ``serving``."""
        with self._lock:
            rows = [dict(r) for r in self._rows]
            build = dict(self._build)
            dropped = self._rows_dropped
        began = self._account.started

        def since_start(t):
            return round(t - began, 3) if began and t else None
        fields = ("trace_ms", "lower_ms", "load_ms", "run_ms",
                  "cache_read_ms", "cache_hits", "cache_misses",
                  "pallas_call_sites")
        totals = {key: dict.fromkeys(("programs",) + fields, 0)
                  for key in ("setup", "serving")}
        for r in rows:
            t = totals["serving" if r["serving"] else "setup"]
            t["programs"] += 1
            for f in fields:
                t[f] = round(t[f] + r[f], 3)
        return {
            "process": {"imported_s": since_start(_IMPORTED_AT),
                        "build_began_s": since_start(build.get("start")),
                        "built_s": since_start(self._built_at)},
            "build": build, "eager": self._account.eager(),
            "rows": rows, "rows_dropped": dropped, "totals": totals}

    def step_clocks(self) -> Dict[str, float]:
        """Seconds per bracket name of the open step's closed brackets
        (the scheduler thread's own view; not a copy)."""
        return self._step

    def elapsed(self) -> float:
        """Seconds since the open step began."""
        return time.perf_counter() - self._t0

    def epoch(self, t: float) -> float:
        """A ``perf_counter`` time of the open step as epoch seconds."""
        return t + self._shift

    def read(self, at: Optional[float] = None) -> Dict[str, float]:
        """The clocks at this moment, or at the epoch time ``at`` of a
        timestamp just taken in the open step, on the scheduler thread:
        the busy steps so far, and of the open step its closed brackets,
        the elapsed part of those still open, and under ``other`` its
        wall so far outside every top-level bracket. Two readings differ,
        over the top-level names, by the wall between them."""
        now = time.perf_counter() if at is None else at - self._shift
        out = dict(self._clocks)
        if not self._in_step:
            return out
        for name, s in self._step.items():
            out[name] = out.get(name, 0.0) + s
        top = self._step_top
        for name, start, _, depth in self._open:
            out[name] = out.get(name, 0.0) + max(0.0, now - start)
            if not depth:
                top += max(0.0, now - start)
        out["other"] = out.get("other", 0.0) + max(0.0, now - self._t0 - top)
        return out

    # ---- export ------------------------------------------------------

    def samples(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def clocks(self) -> dict:
        """The always-on account of the busy steps since construction:
        their count and wall, the seconds under each top-level bracket
        (``phases``: inclusive of what each nests, summing to ``wall_s``
        with ``other``) and each nested one (``nested``), and the
        seconds between a busy step and the one that followed it at
        once (``between_s``: no step's wall, but the scheduler's
        time)."""
        with self._lock:
            clocks = dict(self._clocks)
            steps, wall = self._busy
        between = clocks.pop("between", 0.0)
        top = sorted((k for k in clocks if k not in self._nested),
                     key=lambda k: _ORDER.get(k, len(_ORDER)))
        return {"steps": steps, "wall_s": round(wall, 6),
                "between_s": round(between, 6),
                "phases": {k: round(clocks[k], 6) for k in top},
                "nested": {k: round(clocks[k], 6)
                           for k in sorted(self._nested & set(clocks))}}

    def summary(self) -> dict:
        """Aggregate over the ring: seconds and fraction of the sampled
        steps' wall time per top-level bracket (``phases``: inclusive,
        conserved by ``other``) and per nested bracket (``nested``:
        reported beside, never folded in)."""
        samples = self.samples()
        totals: Dict[str, float] = {}
        nested: Dict[str, float] = {}
        wall = 0.0
        for s in samples:
            wall += s["total"]
            for k, v in step_phases(s).items():
                totals[k] = totals.get(k, 0.0) + v
            for name, start, end, depth in s["spans"]:
                if depth:
                    nested[name] = nested.get(name, 0.0) + (end - start)
        def table(items) -> dict:
            return {k: {"s": round(v, 6),
                        "frac": round(v / wall, 4) if wall else 0.0}
                    for k, v in items}
        return {
            "enabled": self.enabled,
            "sample_every": self.sample_every,
            "clocks": self.clocks(),
            "programs": self.programs(),
            "steps_sampled": len(samples),
            "steps_seen": self._step_n,
            "wall_s": round(wall, 6),
            "phases": table(sorted(
                totals.items(),
                key=lambda kv: _ORDER.get(kv[0], len(_ORDER)))),
            "nested": table(sorted(nested.items())),
        }

    def chrome_events(self, pid: int, tid: int = 0xD11) -> List[dict]:
        """Recent sampled steps as Chrome trace-event ``X`` spans, one per
        bracket at its measured start and duration (nested brackets
        nest). ``span_id`` args make a repeated merge (master scraping
        workers) deduplicate."""
        events: List[dict] = []
        for s in self.samples():
            for i, (name, start, end, depth) in enumerate(s["spans"]):
                events.append({
                    "name": f"profile.{name}", "cat": "profiler",
                    "ph": "X", "ts": start * 1e6,
                    "dur": (end - start) * 1e6,
                    "pid": pid, "tid": tid,
                    "args": {"span_id": f"prof-{int(s['t'] * 1e6)}-{i}",
                             "profile": True, "depth": depth},
                })
        return events
