"""Low-overhead sampling profiler for the batcher's decode step loop.

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
``step_begin()/step_end()`` pair per step. When disabled (the default)
every call is a single attribute check returning a shared no-op — no
allocation, no timestamps, no annotation, zero samples. When enabled:

- every bracket is also a ``jax.profiler.TraceAnnotation("dli.<phase>")``
  (keyword stats ride along: ``k``/``slots`` on ``dispatch``, ``rows``/
  ``tail_bucket``/``prefix_bucket``/``tokens`` on ``admit_run``) and the
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
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

# canonical order of summary()'s phases (unknown names sort after these)
PHASE_ORDER = ("admit", "host_prep", "spec_draft", "dispatch",
               "spec_verify", "device_wait", "emit", "bookkeeping",
               "other")

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
    """One bracket of an enabled profiler's step: a TraceAnnotation, and
    in a sampled step one ``[name, start, end, depth]`` entry."""
    __slots__ = ("prof", "ann", "span")

    def __init__(self, prof: "PhaseProfiler", name: str, stats: dict):
        self.prof = prof
        self.ann = _annotations()[0]("dli." + name, **stats)
        self.span = [name, 0.0, 0.0, 0]

    def __enter__(self):
        prof = self.prof
        self.ann.__enter__()
        self.span[3] = prof._depth
        prof._depth += 1
        if prof._cur is not None:
            prof._cur.append(self.span)    # ordered by start
        self.span[1] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.span[2] = time.perf_counter()
        self.prof._depth -= 1
        self.ann.__exit__(*exc)
        return False


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

    Thread model: ``step_begin``/``step_end`` and the phase brackets run
    on the scheduler thread only; ``configure``/readers may run on HTTP
    handler threads — the ring and config flip under ``_lock``, and the
    in-flight step (``_in_step``, ``_cur``, ``_depth``) is
    scheduler-thread-private.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 sample_every: int = 1, enabled: bool = False):
        self.enabled = bool(enabled)
        self.sample_every = max(1, int(sample_every))
        self._ring: deque = deque(maxlen=max(16, int(capacity)))
        self._lock = threading.Lock()
        self._in_step = False     # a step of an enabled profiler is open
        self._cur: Optional[List[list]] = None   # its brackets, if sampled
        self._depth = 0
        self._step_ann = None
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
        """Open one scheduler step. Every step of an enabled profiler is
        a ``dli.step`` annotation and its brackets ``dli.<phase>``
        annotations; returns the step's record, or None when it is not
        sampled (disabled, or skipped by the sampling stride)."""
        if not self.enabled:
            return None
        self._step_n += 1
        self._step_ann = _annotations()[1]("dli.step",
                                           step_num=self._step_n)
        self._step_ann.__enter__()
        self._in_step = True
        self._depth = 0
        if (self._step_n - 1) % self.sample_every:
            return None
        self._cur = []
        return {"t": time.time(), "t0": time.perf_counter()}

    def step_end(self, rec: Optional[dict], keep: bool = True, **meta):
        """Close the step. ``keep=False`` discards its record (idle
        polls)."""
        if self._in_step:
            self._in_step = False
            self._step_ann.__exit__(None, None, None)
            self._step_ann = None
        if rec is None:
            return
        spans, self._cur = self._cur, None
        if not keep:
            return
        t0 = rec.pop("t0")
        rec["total"] = time.perf_counter() - t0
        shift = rec["t"] - t0      # perf_counter -> epoch, one anchor
        rec["spans"] = [(name, start + shift, end + shift, depth)
                        for name, start, end, depth in spans]
        if meta:
            rec["meta"] = meta
        with self._lock:
            self._ring.append(rec)
            self._sampled += 1

    def phase(self, name: str, **stats):
        """Bracket of the current step; ``stats`` become the
        annotation's keyword stats. Outside a step of an enabled
        profiler the cost is one attribute check and a shared no-op."""
        if not self._in_step:
            return _NOOP
        return _Phase(self, name, stats)

    # ---- export ------------------------------------------------------

    def samples(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

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
        order = {n: i for i, n in enumerate(PHASE_ORDER)}

        def table(items) -> dict:
            return {k: {"s": round(v, 6),
                        "frac": round(v / wall, 4) if wall else 0.0}
                    for k, v in items}
        return {
            "enabled": self.enabled,
            "sample_every": self.sample_every,
            "steps_sampled": len(samples),
            "steps_seen": self._step_n,
            "wall_s": round(wall, 6),
            "phases": table(sorted(
                totals.items(),
                key=lambda kv: order.get(kv[0], len(order)))),
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
