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
