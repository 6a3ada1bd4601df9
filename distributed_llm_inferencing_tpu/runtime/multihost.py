"""Multi-host lockstep serving: one pjit program spanning TPU hosts.

The reference's "distributed" execution was per-hop HTTP between
independent single-device workers (SURVEY.md §2.6). On a multi-host TPU
slice the data plane is instead ONE SPMD program: every host joins a
``jax.distributed`` job, a ``Mesh`` spans all hosts' chips, and XLA
collectives ride ICI/DCN inside the jitted step. What the framework must
guarantee is the *control* invariant that SPMD imposes: **every process
launches the same programs in the same order**, or collectives deadlock.

This module provides that guarantee for the worker RPC surface:

- The **leader** (process 0) serves the public API. Every state-changing
  or compute op (load/unload/inference) is assigned a global sequence
  number, forwarded to every follower's ``/lockstep`` endpoint, and
  executed locally through the same sequence-ordered executor.
- **Followers** serve only ``/lockstep``: they enqueue forwarded ops and
  execute them strictly in sequence order, discarding results — their
  role is to co-execute the SPMD programs so the leader's collectives
  have partners. Direct calls to their mutating endpoints return 409.

Determinism notes (what makes co-execution bit-identical): the leader
resolves the sampling ``seed`` before forwarding (engine outputs are a
pure function of (params, prompt, seed)); random-init uses a fixed seed;
checkpoints/tokenizers load from the same paths on every host. Batched
serving (runtime/batcher.py) makes timing-dependent scheduling decisions,
so its REQUESTS are not mirrored; instead the leader's scheduler
broadcasts each *device program launch* (admission prefill / decode step)
with its full input set via ``batcher_program`` ops, and followers replay
them in sequence order — leader-decided schedule, SPMD-identical
execution (the round-2 leader-broadcast admission design).

Tested with multi-process CPU ``jax.distributed`` clusters
(tests/test_multihost.py) — the same code path as real multi-host TPU.
"""

from __future__ import annotations

import heapq
import threading
import time
from typing import Callable, Dict, List, Optional

import requests as http

from distributed_llm_inferencing_tpu.runtime import httpd
from distributed_llm_inferencing_tpu.utils import clock, locks
from distributed_llm_inferencing_tpu.utils.logging import setup_logging

log = setup_logging("multihost")

FORWARD_TIMEOUT = 30


class LockstepExecutor:
    """Executes submitted thunks strictly in sequence-number order."""

    def __init__(self):
        self._heap: list = []
        self._cv = locks.condition("multihost.exec")
        self._next = 0
        self._stopped = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="lockstep-exec")
        self._thread.start()

    def submit(self, seq: int, fn: Callable):
        box = {"done": threading.Event(), "result": None, "error": None}
        with self._cv:
            heapq.heappush(self._heap, (seq, id(box), fn, box))
            self._cv.notify_all()
        return box

    def run(self, seq: int, fn: Callable):
        box = self.submit(seq, fn)
        box["done"].wait()
        if box["error"] is not None:
            raise box["error"]
        return box["result"]

    def stop(self):
        with self._cv:
            self._stopped = True
            self._cv.notify_all()

    def _loop(self):
        while True:
            with self._cv:
                # drop stale entries (seq already executed) so a duplicate
                # can never wedge the queue
                while self._heap and self._heap[0][0] < self._next:
                    _, _, _, stale = heapq.heappop(self._heap)
                    stale["error"] = RuntimeError("stale sequence number")
                    stale["done"].set()
                while not (self._heap and self._heap[0][0] == self._next):
                    if self._stopped:
                        return
                    self._cv.wait(0.5)
                    while self._heap and self._heap[0][0] < self._next:
                        _, _, _, stale = heapq.heappop(self._heap)
                        stale["error"] = RuntimeError("stale sequence number")
                        stale["done"].set()
                seq, _, fn, box = heapq.heappop(self._heap)
                self._next += 1
            try:
                box["result"] = fn()
            except Exception as e:  # surfaced to the waiting handler
                box["error"] = e
            box["done"].set()


def _try(fn, *args):
    try:
        fn(*args)
        return None
    except Exception as e:
        return e


def _replace_route(service: httpd.JsonHTTPService, method: str,
                   pattern: str, fn: Callable):
    probe = httpd.Route(method, pattern, fn)
    for r in service.routes:
        if r.method == method and r.regex.pattern == probe.regex.pattern:
            r.fn = fn
            return
    service.routes.append(probe)


MIRRORED_OPS = ("load_model", "load_shard", "unload_model", "inference")


def _fresh_coordinator() -> str:
    """A new coordinator address on the original coordinator's host (the
    leader) — fresh port, so the dying job's service can never collide.
    A restarted LEADER has no prior address to derive from (127.0.0.1
    would be unreachable for remote followers) — the operator must pass
    one explicitly.

    Assumption (logged, not silently relied on): the free-port probe
    binds on THIS machine while the address reuses the old coordinator's
    host — correct when the leader hosts the coordinator (the deployment
    layout init_multihost sets up). If the coordinator lived elsewhere,
    or another process grabs the probed port before jax.distributed
    binds it (TOCTOU), the rejoin fails with a bind/connect error — in
    both cases pass an explicit {"coordinator": "host:port"} to
    /lockstep/recover instead of relying on this derivation."""
    import socket
    if not _DIST_STATE["coordinator"]:
        raise RuntimeError(
            "restarted leader has no prior coordinator address; pass "
            '{"coordinator": "host:port"} to /lockstep/recover')
    host = _DIST_STATE["coordinator"].rsplit(":", 1)[0]
    local = {"127.0.0.1", "localhost", socket.gethostname(),
             socket.getfqdn()}
    try:
        local.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass
    if host not in local:
        log.warning(
            "deriving a fresh coordinator on %r, but this process is %r "
            "— the free-port probe runs locally, so if %r is a different "
            "machine the port may be taken there; pass an explicit "
            '{"coordinator": "host:port"} to /lockstep/recover if the '
            "rejoin fails to bind/connect", host, socket.gethostname(),
            host)
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return f"{host}:{port}"


RECOVERY_POLL_S = 2.0   # degraded-leader probe cadence for follower return


class LockstepLeader:
    """Wraps a WorkerAgent's service as the slice leader.

    Elastic recovery: when a mirror forward fails the slice degrades
    (mirrored ops 503 fast), but a background probe keeps polling the
    followers; once every follower answers /health again the leader runs
    the epoch-bumped recovery protocol — reset each follower's lockstep
    state (/lockstep/reset), restart sequence numbering, and replay the
    model-establishing ops (load_model/load_shard bodies it remembered)
    through the normal mirrored path so every host reconstructs identical
    state. Serving then resumes without manual surgery. ``POST
    /lockstep/recover`` triggers the same protocol on demand.

    On a real TPU slice the restarted host must additionally rejoin
    ``jax.distributed`` before serving (data-plane collectives span hosts);
    the control protocol above is identical either way.
    """

    def __init__(self, agent, followers: List[str],
                 auth_key: Optional[str] = None):
        self.agent = agent
        self.followers = [f if f.startswith("http") else f"http://{f}"
                          for f in followers]
        self._auth = auth_key
        self.exec = LockstepExecutor()
        self._mirror_lock = locks.lock("multihost.mirror")
        self._seq = 0
        self._epoch = 0
        self._degraded: Optional[str] = None
        self._recovering = False
        self._recover_coordinator: Optional[str] = None
        self._loaded: Dict[str, dict] = {}   # model -> last load body
        self._recovery_thread: Optional[threading.Thread] = None
        self._handlers: Dict[str, Callable] = {}
        s = agent.service
        for op in MIRRORED_OPS:
            self._handlers[op] = self._make_handler(op)
            _replace_route(s, "POST", f"/{op}", self._handlers[op])
        _replace_route(s, "POST", "/inference_stream", self.inference_stream)
        _replace_route(s, "POST", "/lockstep/recover", self.recover_endpoint)
        _replace_route(s, "GET", "/lockstep/status", self.status)

    def _headers(self):
        return ({"Authorization": f"Bearer {self._auth}"}
                if self._auth else {})

    def _mirror(self, op: str, body: dict) -> int:
        """Assign a sequence number and forward to every follower.

        Forwards run concurrently (latency = max follower RTT, not sum).
        A failed forward means some hosts hold ops others don't — SPMD
        consistency is unrecoverable without a restart, so the slice is
        marked permanently degraded: the leader submits a local noop for
        the consumed seq (its own executor never wedges on the gap) and
        every later mirrored op is refused fast with 503.
        """
        from concurrent.futures import ThreadPoolExecutor
        with self._mirror_lock:
            if self._degraded:
                raise RuntimeError(self._degraded)
            seq = self._seq
            self._seq += 1

            def fwd(f):
                r = http.post(f"{f}/lockstep",
                              json={"seq": seq, "op": op, "body": body},
                              headers=self._headers(),
                              timeout=FORWARD_TIMEOUT)
                r.raise_for_status()

            if self.followers:
                with ThreadPoolExecutor(len(self.followers)) as pool:
                    errs = [e for e in pool.map(
                        lambda f: _try(fwd, f), self.followers)
                        if e is not None]
            else:
                errs = []
            if errs:
                self._degraded = (
                    f"lockstep forward of {op} failed ({errs[0]}); slice "
                    "degraded — auto-recovery engaged (or POST "
                    "/lockstep/recover once the followers are back)")
                log.error(self._degraded)
                self.exec.submit(seq, lambda: None)   # fill the gap locally
                self._start_recovery()
                raise RuntimeError(self._degraded)
            return seq

    def _prepare(self, op: str, body: dict) -> dict:
        body = dict(body)
        if op in ("inference", "inference_stream"):
            # identical RNG stream on every host
            body.setdefault("seed", time.time_ns() % (1 << 31))
        return body

    def _make_handler(self, op: str):
        local = getattr(self.agent, op)

        def handler(body):
            try:
                body = self._prepare(op, body)
            except ValueError as e:
                return 400, {"status": "error", "message": str(e)}
            if op == "inference" and self._is_batched(body):
                # batched serving: the REQUEST is leader-local scheduler
                # input, not an SPMD op — the batcher's device programs are
                # mirrored one by one via its program_hook instead
                return local(body)
            try:
                seq = self._mirror(op, body)
            except RuntimeError as e:
                return 503, {"status": "error", "message": str(e)}
            result = self.exec.run(seq, lambda: local(body))
            if op in ("load_model", "load_shard"):
                self._attach_batcher_hooks()
            # remember state-establishing ops so recovery can replay them
            status = result[0] if isinstance(result, tuple) else 200
            name = body.get("model_name")
            if status == 200 and name:
                if op in ("load_model", "load_shard"):
                    self._loaded[name] = {"op": op, "body": dict(body)}
                elif op == "unload_model":
                    self._loaded.pop(name, None)
            return result

        handler.__name__ = f"lockstep_{op}"
        return handler

    def _is_batched(self, body) -> bool:
        m = self.agent.models.get(body.get("model_name"))
        return m is not None and getattr(m, "batcher", None) is not None

    # ---- elastic recovery --------------------------------------------

    def status(self, body):
        with self._mirror_lock:
            return {"status": "ok", "role": "leader", "epoch": self._epoch,
                    "next_seq": self._seq, "degraded": self._degraded,
                    "loaded": sorted(self._loaded)}

    def _followers_healthy(self) -> bool:
        for f in self.followers:
            try:
                r = http.get(f"{f}/health", headers=self._headers(),
                             timeout=5)
                if r.status_code != 200:
                    return False
            except Exception:
                return False
        return True

    def _start_recovery(self):
        if (self._recovery_thread is None
                or not self._recovery_thread.is_alive()):
            self._recovery_thread = threading.Thread(
                target=self._recovery_loop, daemon=True,
                name="lockstep-recovery")
            self._recovery_thread.start()

    def _recovery_loop(self):
        while True:
            clock.sleep(RECOVERY_POLL_S)
            with self._mirror_lock:
                if not self._degraded:
                    return
            if not self._followers_healthy():
                continue
            try:
                self.recover({})
                return
            except Exception as e:
                log.warning("lockstep recovery attempt failed: %s", e)

    def recover_endpoint(self, body):
        try:
            return self.recover(body or {})
        except Exception as e:
            return 503, {"status": "error", "message": f"recovery failed: {e}"}

    def recover(self, body):
        """Epoch-bumped slice recovery: reset every follower's lockstep
        state, restart sequence numbering, replay model loads.

        On a slice with a jax.distributed job (init_multihost), recovery
        additionally RE-FORMS the distributed runtime: every model is
        dropped (its arrays belong to the dying job), every host rejoins
        a fresh coordinator (``/lockstep/reinit_dist`` on followers, then
        the leader's own blocking join — which doubles as the barrier
        that every host made it), and the replayed loads re-shard params
        onto the new job's devices. ``{"coordinator": "host:port"}``
        overrides the fresh coordinator address.

        ``{"force": true}`` runs the protocol even when the leader does
        not consider the slice degraded (operator escape hatch for states
        the leader cannot see). Epochs are adopted from the followers
        first, so a restarted leader (epoch back at 0) can still reset
        followers that lived through earlier epochs.
        """
        with self._mirror_lock:
            if body.get("coordinator") and (self._recovering
                                            or self._degraded
                                            or body.get("force")):
                # adopt the operator-supplied coordinator even when an
                # automatic attempt is mid-flight — a restarted leader's
                # auto-recovery NEEDS it (it has no prior address), and
                # dropping it with a 200 would strand the slice. On a
                # healthy slice (no force) nothing is adopted: a stashed
                # address would go stale before any future recovery.
                self._recover_coordinator = body["coordinator"]
            if self._recovering:
                return {"status": "success",
                        "message": "recovery already in progress"
                                   + ("; coordinator adopted for the next "
                                      "attempt" if body.get("coordinator")
                                      else "")}
            if not self._degraded and not body.get("force"):
                return {"status": "success",
                        "message": "slice not degraded; nothing to recover "
                                   "(pass {\"force\": true} to override)"}
            self._recovering = True
        try:
            return self._recover_inner(body)
        finally:
            self._recovering = False

    def _recover_inner(self, body):
        with self._mirror_lock:
            for f in self.followers:   # adopt the highest epoch out there
                try:
                    st = http.get(f"{f}/lockstep/status",
                                  headers=self._headers(), timeout=5).json()
                    self._epoch = max(self._epoch, int(st.get("epoch", 0)))
                except Exception as e:
                    # unreachable follower fails the reset below
                    log.debug("epoch probe of follower %s failed: %r", f, e)
            self._epoch += 1
            epoch = self._epoch
            for f in self.followers:
                r = http.post(f"{f}/lockstep/reset", json={"epoch": epoch},
                              headers=self._headers(),
                              timeout=FORWARD_TIMEOUT)
                r.raise_for_status()
            reloads = list(self._loaded.items())
            self._loaded = {}
            # mirrored ops keep failing fast while the (lockless) rejoin
            # below runs — holding the lock across a 120s blocking join
            # would hang /lockstep/status and turn fast 503s into client
            # timeouts
            self._degraded = self._degraded or "recovery in progress"
        try:
            if _DIST_STATE["num_processes"] > 0:
                # drop stale-job models BEFORE tearing down backends (the
                # followers' reset already dropped theirs)
                for name, _ in reloads:
                    try:
                        self.agent.unload_model({"model_name": name})
                    except Exception as e:
                        log.warning("pre-rejoin unload of %s: %s", name, e)
                if body.get("coordinator"):
                    new_coord = body["coordinator"]
                    with self._mirror_lock:
                        # this attempt consumes its own adoption; only a
                        # DIFFERENT concurrently adopted address survives
                        # for the next attempt
                        if self._recover_coordinator == new_coord:
                            self._recover_coordinator = None
                else:
                    with self._mirror_lock:   # consume exactly the value
                        # this attempt uses; a concurrently adopted one
                        # must survive for the next attempt
                        new_coord = self._recover_coordinator
                        self._recover_coordinator = None
                    new_coord = new_coord or _fresh_coordinator()
                log.info("re-forming jax.distributed at %s", new_coord)
                for f in self.followers:
                    r = http.post(f"{f}/lockstep/reinit_dist",
                                  json={"coordinator": new_coord},
                                  headers=self._headers(),
                                  timeout=FORWARD_TIMEOUT)
                    r.raise_for_status()
                # blocking join: returns only once every follower joined
                reinit_multihost(new_coord)
        except Exception as e:
            with self._mirror_lock:
                # restore the replay state — a retried recovery must not
                # "succeed" with the model loads silently dropped
                merged = dict(reloads)
                merged.update(self._loaded)
                self._loaded = merged
                self._degraded = f"distributed rejoin failed: {e}"
            self._start_recovery()
            raise
        with self._mirror_lock:
            self._seq = 0
            # fresh executor: its _next restarts at 0 alongside the seq
            # counter (the old one would treat replayed seq 0 as stale)
            self.exec.stop()
            self.exec = LockstepExecutor()
            self._degraded = None
        # Rebuild every model on every host through the normal mirrored
        # path: the leader drops its own copy first so leader and follower
        # reconstruct identical fresh state (engines are deterministic from
        # (checkpoint|seed); a batcher's radix/paged caches start empty on
        # all hosts, so no follower can be asked to read blocks it never
        # filled).
        errors = []
        for name, entry in reloads:
            try:
                self.agent.unload_model({"model_name": name})
                result = self._handlers[entry["op"]](entry["body"])
                status = result[0] if isinstance(result, tuple) else 200
                if status != 200:
                    errors.append(f"{name}: {result}")
            except Exception as e:
                errors.append(f"{name}: {e}")
        if errors:
            with self._mirror_lock:
                # keep un-replayed loads for the retry (successful ones
                # re-registered themselves through the mirrored handler)
                for name, entry in reloads:
                    self._loaded.setdefault(name, entry)
                self._degraded = f"recovery replay failed: {errors[0]}"
            self._start_recovery()
            raise RuntimeError(self._degraded)
        log.info("lockstep slice recovered (epoch %d, %d model(s) replayed)",
                 epoch, len(reloads))
        return {"status": "success", "epoch": epoch,
                "models_replayed": [n for n, _ in reloads]}

    def _attach_batcher_hooks(self):
        """Route every batched model's device programs through the mirror.

        Scheduling stays leader-local (admission, preemption, block
        allocation are host-side state only the leader holds); what crosses
        hosts is the resulting *program launches*, each with its full
        JSON-safe input set, which followers replay in sequence order —
        identical programs, identical order, identical cache evolution."""
        for name, m in self.agent.models.items():
            b = getattr(m, "batcher", None)
            if b is not None and b.program_hook is None:
                def hook(kind, args, run, _name=name):
                    seq = self._mirror("batcher_program",
                                       {"model_name": _name, "kind": kind,
                                        "args": args})
                    return self.exec.run(seq, run)
                b.program_hook = hook

    def inference_stream(self, body, _request=None):
        """Leader streams SSE to the client; followers co-execute the same
        generation as a plain inference (same seed/eos ⇒ same program
        sequence; only host-side sync timing differs).

        Model resolution happens INSIDE the sequence slot (via the
        worker's engine_stream_events), so the stream observes exactly
        the state the lockstep order establishes — e.g. an earlier
        mirrored unload fails it identically on every host instead of
        generating against a stale engine only the leader still holds.
        """
        try:
            body = self._prepare("inference_stream", body)
            # pre-validation only (proper 400s); the authoritative prep
            # re-runs inside the sequence slot against lockstep-ordered
            # state
            self.agent._prep_inference(body)
        except (KeyError, ValueError) as e:
            return 400, {"status": "error", "message": str(e)}
        if self._is_batched(body):
            # leader-local streaming; device programs mirror via the
            # batcher's program_hook (see _attach_batcher_hooks)
            return self.agent.inference_stream(body, _request=_request)
        try:
            seq = self._mirror("inference_stream", body)
        except RuntimeError as e:
            return 503, {"status": "error", "message": str(e)}
        ev = self.agent.engine_stream_events(
            body, lambda fn: self.exec.submit(seq, fn))
        return httpd.sse_stream(_request, ev)


class LockstepFollower:
    """Wraps a WorkerAgent's service as a follower: executes forwarded ops
    in order; rejects direct mutating calls."""

    def __init__(self, agent):
        self.agent = agent
        self.exec = LockstepExecutor()
        self._seen_lock = locks.lock("multihost.seen")
        self._seen: set = set()
        self._epoch = 0
        self._last_recv = -1   # forwards are serialized: seqs must arrive
        # consecutively, so any gap proves this follower missed ops (e.g.
        # it restarted between mirrors) and must refuse until reset
        if agent.service.auth_key is None:
            log.warning(
                "lockstep follower has NO auth key: /lockstep is slice "
                "control — bind to a trusted network or set "
                "DLI_AUTH_ENABLED + DLI_AUTH_KEY on every worker")
        self._ops: Dict[str, Callable] = {
            "load_model": agent.load_model,
            "load_shard": agent.load_shard,
            "unload_model": agent.unload_model,
            "inference": agent.inference,
            # co-execute the leader's stream as a plain generation: same
            # seed and eos give the identical jit/collective sequence
            "inference_stream": agent.inference,
            # replay one batched-scheduler device program (admission
            # prefill or decode step) with the leader's exact inputs
            "batcher_program": self._batcher_program,
            "noop": lambda body: {"status": "noop"},
        }
        self._dist_error: Optional[str] = None
        self._dist_thread: Optional[threading.Thread] = None
        s = agent.service
        s.add("POST", "/lockstep", self.lockstep)
        s.add("POST", "/lockstep/reset", self.reset)
        s.add("POST", "/lockstep/reinit_dist", self.reinit_dist)
        s.add("GET", "/lockstep/status", self.status)
        for op in MIRRORED_OPS + ("inference_stream",):
            _replace_route(s, "POST", f"/{op}", self._rejected(op))

    def status(self, body):
        return {"status": "ok", "role": "follower", "epoch": self._epoch,
                "next_seq": self.exec._next, "last_recv": self._last_recv,
                "loaded": sorted(self.agent.models),
                "dist": {**dist_status(), "error": self._dist_error}}

    def reinit_dist(self, body):
        """Leader-ordered distributed rejoin: join the fresh coordinator
        in a background thread (jax.distributed.initialize blocks until
        EVERY host connects — the leader joins last, so responding first
        is what lets the barrier complete). An in-flight join refuses a
        second order: two concurrent reinit_multihost calls would race on
        jax's global distributed state — the leader's recovery retries
        after the stale join times out."""
        coord = (body or {}).get("coordinator")
        if not coord:
            return 400, {"status": "error", "message": "coordinator required"}
        if _DIST_STATE["num_processes"] <= 0:
            return 409, {"status": "error",
                         "message": "host has no distributed identity"}
        if self._dist_thread is not None and self._dist_thread.is_alive():
            return 409, {"status": "error",
                         "message": "distributed rejoin already in flight"}

        def join():
            try:
                reinit_multihost(coord)
                self._dist_error = None
                log.info("rejoined jax.distributed at %s", coord)
            except Exception as e:
                self._dist_error = f"rejoin failed: {e}"
                log.error("distributed rejoin failed: %s", e)

        self._dist_error = "joining"
        self._dist_thread = threading.Thread(target=join, daemon=True,
                                             name="dist-rejoin")
        self._dist_thread.start()
        return {"status": "joining", "coordinator": coord}

    def reset(self, body):
        """Leader-ordered epoch reset: wipe lockstep ordering state and all
        models so the recovery replay rebuilds this host identically to the
        leader (runs before the leader re-opens mirroring, so no forwarded
        op can race the wipe)."""
        epoch = body.get("epoch")
        if not isinstance(epoch, int) or epoch <= self._epoch:
            return 409, {"status": "error",
                         "message": f"stale epoch {epoch!r} "
                                    f"(current {self._epoch})"}
        self._epoch = epoch
        self.exec.stop()
        self.exec = LockstepExecutor()
        with self._seen_lock:
            self._seen = set()
            self._last_recv = -1
        for name in list(self.agent.models):
            try:
                self.agent.unload_model({"model_name": name})
            except Exception as e:
                log.warning("reset: unload of %s failed: %s", name, e)
        log.info("lockstep follower reset to epoch %d", epoch)
        return {"status": "success", "epoch": epoch}

    def _batcher_program(self, body):
        m = self.agent.models.get(body.get("model_name"))
        if m is None or m.batcher is None:
            return 409, {"status": "error",
                         "message": "no such batched model on this host"}
        m.batcher.replay(body.get("kind"), body.get("args") or {})
        return {"status": "success"}

    def _rejected(self, op):
        def handler(body, _request=None):
            return 409, {"status": "error",
                         "message": f"this worker is a lockstep follower; "
                                    f"send {op} to the slice leader"}
        handler.__name__ = f"follower_reject_{op}"
        return handler

    def lockstep(self, body):
        seq = body.get("seq")
        op = body.get("op")
        if not isinstance(seq, int) or seq < 0 or op not in self._ops:
            return 400, {"status": "error", "message": "bad lockstep op"}
        with self._seen_lock:
            # duplicates/stale seqs would wedge or desync the ordered
            # executor — refuse them at the door
            if seq in self._seen or seq < self.exec._next:
                return 409, {"status": "error",
                             "message": f"sequence {seq} already received"}
            # the leader serializes forwards, so seqs arrive consecutively;
            # a gap means THIS follower missed ops (it restarted between
            # mirrors) — refusing makes the leader degrade and run
            # recovery instead of queueing an op that can never execute
            if seq != self._last_recv + 1:
                return 409, {"status": "error",
                             "message": f"lockstep gap: expected "
                                        f"{self._last_recv + 1}, got {seq} "
                                        "(follower needs reset)"}
            self._last_recv = seq
            self._seen.add(seq)
            if len(self._seen) > 4096:   # drop already-executed entries:
                # seq < _next is rejected above regardless of membership
                nxt = self.exec._next
                self._seen = {s for s in self._seen if s >= nxt}
        fn = self._ops[op]
        payload = body.get("body", {})

        def run():
            try:
                r = fn(payload)
                status = r[0] if isinstance(r, tuple) else 200
                if status != 200:
                    log.warning("lockstep %s (seq %d) returned %s: %s",
                                op, seq, status, r)
            except Exception as e:
                log.error("lockstep %s (seq %d) raised: %s", op, seq, e)

        self.exec.submit(int(seq), run)
        return {"status": "queued", "seq": seq}


# This host's distributed identity — what a fresh jax.distributed job
# needs to re-form after a host restart (reinit_multihost). coordinator
# is None when configured-but-not-joined (a restarted host whose old
# coordinator epoch is gone).
_DIST_STATE = {"coordinator": None, "num_processes": 0, "process_id": -1}


def init_multihost(coordinator: str, num_processes: int, process_id: int):
    """Join the slice's jax.distributed job (before any jax device use).

    Recoverability is enabled so a surviving host OUTLIVES a peer's death
    (jaxlib's default coordination client terminates the whole process
    when any task dies — which would turn one lost host into a lost
    slice, making elastic recovery impossible by construction)."""
    import jax
    jax.config.update("jax_enable_recoverability", True)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _DIST_STATE.update(coordinator=coordinator, num_processes=num_processes,
                       process_id=process_id)
    return jax.process_index(), jax.process_count()


def configure_multihost(num_processes: int, process_id: int):
    """Record this host's distributed identity WITHOUT joining a job — a
    restarted host whose old coordinator is gone starts this way and
    waits for the leader's recovery to order a fresh join
    (``/lockstep/reinit_dist`` -> reinit_multihost)."""
    _DIST_STATE.update(coordinator=None, num_processes=num_processes,
                       process_id=process_id)


def dist_status() -> dict:
    return {"configured": _DIST_STATE["num_processes"] > 0,
            "joined": _DIST_STATE["coordinator"] is not None,
            "process_id": _DIST_STATE["process_id"],
            "num_processes": _DIST_STATE["num_processes"]}


# Orphaned distributed runtimes from before a rejoin. Deliberately kept
# alive: a graceful shutdown of the old job cannot complete (its shutdown
# barrier waits for the very peer whose death triggered recovery), and
# letting the client/service destruct fires a ShutdownTask RPC whose
# failure path is process-FATAL in jaxlib (client.h). Leaked threads are
# the price of surviving; real deployments recycle hosts eventually.
_GRAVEYARD: list = []


def reinit_multihost(coordinator: str, timeout_s: float = 120.0):
    """Abandon this process's jax.distributed runtime (if any) and join a
    FRESH job at ``coordinator`` — the real-slice elastic-recovery step
    the control-plane epoch reset alone cannot provide.

    The old job is never shut down gracefully (see _GRAVEYARD) — its
    client/service objects are detached and kept referenced, then
    backends are cleared: live arrays from the old job (sharded params,
    caches) die with it, which is why recovery unloads every model
    BEFORE the rejoin and replays the loads after.
    """
    import gc

    import jax
    # Private surface, checked against the installed jax 0.9.0: detaching
    # a live job has no public API. A jax that moved any of it fails
    # here, by name, before anything is torn down.
    from jax._src import distributed as jdist
    from jax.extend import backend as jex_backend

    if _DIST_STATE["num_processes"] <= 0:
        raise RuntimeError("host has no distributed identity "
                           "(init_multihost/configure_multihost not called)")
    gs = jdist.global_state
    missing = [a for a in ("client", "service", "preemption_sync_manager",
                           "process_id") if not hasattr(gs, a)]
    if missing or not hasattr(jex_backend, "clear_backends"):
        raise RuntimeError(
            f"jax {jax.__version__} no longer has what elastic rejoin "
            f"detaches (jax._src.distributed.global_state{missing}, "
            "jax.extend.backend.clear_backends); reinit_multihost needs "
            "repair for this jax")
    if gs.client is not None or gs.service is not None:
        log.warning("abandoning the previous jax.distributed job "
                    "(graceful shutdown cannot complete with a dead peer)")
        _GRAVEYARD.append((gs.client, gs.service,
                           gs.preemption_sync_manager))
        gs.client = None
        gs.service = None
        gs.preemption_sync_manager = None
        gs.process_id = 0
        # joined=false until the fresh initialize below succeeds — a
        # failed rejoin must not report the abandoned job as live
        _DIST_STATE["coordinator"] = None
    gc.collect()
    jax.clear_caches()
    jex_backend.clear_backends()
    jax.config.update("jax_enable_recoverability", True)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=_DIST_STATE["num_processes"],
        process_id=_DIST_STATE["process_id"],
        initialization_timeout=int(timeout_s))
    _DIST_STATE["coordinator"] = coordinator
    return jax.process_index(), jax.process_count()
