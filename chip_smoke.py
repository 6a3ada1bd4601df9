#!/usr/bin/env python3
"""Prove that the main serving path starts and answers on the chip.

    python chip_smoke.py            # one TPU chip: worker + master
    python chip_smoke.py --chips 4  # four chips: the sharded path only

One chip: mistral-7b (models/registry.py), int8, all 32 layers, random
weights made from the batcher's seed, is loaded through the entry points
a user calls — ``python -m distributed_llm_inferencing_tpu worker`` (the
only process that touches the chip), ``... master`` (CPU pinned), ``POST
/api/nodes/add``, ``POST /api/models/load``, ``POST
/api/inference/submit`` — and answers a handful of requests of mixed
prompt lengths, some past one prefill chunk, four of them in flight
together. This process never imports JAX: it starts the children, talks
HTTP, and reads the device from the worker's ``/health``.

Every line on stdout is one JSON object. The last one is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only when every phase passed on a TPU; otherwise the
script exits non-zero without it. Children's logs go to
``chiprun_out/chip_smoke/``.

``--chips 4`` runs, one after the other (one process at a time holds the
chips): a child that prefills the same prompts under ``MeshSpec(tp=4)``
and on one device and compares the logits, then a worker that serves
them from a ``mesh: {"tp": 4}`` load and again from a one-device load.

``--rehearse`` (tests, CPU) runs the same control flow at ``--model``
size on whatever platform the worker has; off a TPU it still ends
non-zero and prints no ok line.
"""

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
PKG = "distributed_llm_inferencing_tpu"

MODEL = "mistral-7b"
# /load_model body. 7.2 GB of int8 weights leave ~8 GB of the chip's
# 16 GB: 1024 blocks x 16 tokens of bf16 KV for 32 layers x 8 kv heads
# x 128 is 2 GiB of pool; the admit program's transient adds as much
# again (compiled.memory_analysis(), described v5e). Widths are never cut.
LOAD = {"quantize": "int8", "serving": "batched", "allow_random_init": True,
        "slots": 8, "kv_blocks": 1024, "kv_block_size": 16, "max_seq": 2048}
# prompt lengths in characters == byte-level tokens (no tokenizer files:
# utils/tokenizer.ByteTokenizer). The prefill chunk is 32 blocks x 16 =
# 512 tokens, so 700 and 1100 are admitted in two and three chunks.
SIZES = {"load": LOAD, "first": 24, "long": 700,
         "wave": (40, 200, 1100, 90), "max_new": 32}
# --rehearse with a toy model (tiny-llama: 128 positions): the same flow,
# a 16-token prefill chunk so 40 and 60 are still admitted in chunks
TOY_SIZES = {"load": dict(LOAD, slots=4, kv_blocks=256, kv_block_size=4,
                          prefill_chunk=4, max_seq=None),
             "first": 10, "long": 40, "wave": (12, 24, 60, 18),
             "max_new": 8}
# tp=4 vs one device, last-position logits over the smoke's prompts, as
# a share of the largest |logit|. Rehearsed at full width and depth on
# four virtual CPU devices (XLA_FLAGS=--xla_force_host_platform_device_
# count=4, bf16): 0.0768 against a largest logit of 3.32, 2.3 %; the
# bound is twice that.
LOGIT_TOL_REL = 0.05


class SmokeFailure(Exception):
    pass


def say(**fields):
    print(json.dumps(fields), flush=True)


def http(method, url, body=None, timeout=30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw)
        except ValueError:
            return e.code, {"raw": raw[:500].decode(errors="replace")}


def free_port():
    # utils/platform.free_port, restated: this process imports nothing of
    # the repo (it must stay off JAX, and fail cleanly when alone)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wave_prompts(sizes, salt):
    return [prompt_of(n, salt + i) for i, n in enumerate(sizes["wave"])]


def prompt_of(n_chars, salt):
    """Deterministic text of exactly n_chars ASCII characters."""
    words = ("the", "chip", "serves", "tokens", "from", "a", "paged",
             "cache", "while", "requests", "arrive", "and", "leave")
    out, i = [], salt
    while sum(len(w) + 1 for w in out) < n_chars:
        out.append(words[i % len(words)])
        i += 1 + salt % 3
    return " ".join(out)[:n_chars].ljust(n_chars, ".")


class Children:
    """The processes this script starts; all of them die with it."""

    def __init__(self):
        self.procs = {}
        os.makedirs(LOG_DIR, exist_ok=True)

    def start(self, name, argv, env):
        with open(os.path.join(LOG_DIR, f"{name}.log"), "w") as log:
            self.procs[name] = subprocess.Popen(
                argv, env=env, cwd=HERE, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        return self.procs[name]

    def check_alive(self):
        for name, p in self.procs.items():
            if p.poll() is not None:
                raise SmokeFailure(
                    f"{name} died with code {p.returncode}:\n"
                    + self.tail(name))

    def tail(self, name, n=30):
        try:
            with open(os.path.join(LOG_DIR, f"{name}.log"),
                      errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def stop(self, name):
        p = self.procs.pop(name, None)
        if p is None or p.poll() is not None:
            return
        for sig, wait in ((signal.SIGTERM, 20), (signal.SIGKILL, 10)):
            try:
                os.killpg(p.pid, sig)
            except ProcessLookupError:
                return
            try:
                p.wait(timeout=wait)
                return
            except subprocess.TimeoutExpired:
                continue

    def stop_all(self):
        for name in list(self.procs):
            self.stop(name)


def child_env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def wait_http(children, url, timeout):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        children.check_alive()
        try:
            st, body = http("GET", url, timeout=5)
            if st == 200:
                return body
        except (OSError, ValueError):
            pass
        time.sleep(0.5)
    raise SmokeFailure(f"no answer from {url} within {timeout:.0f}s")


def start_worker(children, rehearse):
    """Start the worker child — the ONE process that may initialize the
    chip — and return (base url, /health body, device summary)."""
    port = free_port()
    t0 = time.monotonic()
    children.start("worker", [sys.executable, "-m", PKG, "worker",
                              "--host", "127.0.0.1", "--port", str(port)],
                   child_env())
    base = f"http://127.0.0.1:{port}"
    health = wait_http(children, base + "/health", 300)
    devs = health["resources"]["devices"]
    device = {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
              "count": len(devs)}
    say(phase="worker_up", seconds=round(time.monotonic() - t0, 2),
        device=device, compile_cache=health["compile_cache"])
    if device["platform"] != "tpu" and not rehearse:
        raise SmokeFailure(
            f"worker runs on {device['platform']!r}, not on a tpu")
    return base, health, device


def load_facts(health, model):
    """What /health says about the loaded model and the devices."""
    lm = next(m for m in health["loaded_models"] if m["name"] == model)
    sch = lm["scheduler"]
    return {"attn_backend": sch["attn_backend"],
            "interpreted_kernels": sch["interpreted_kernels"],
            "native_block_pool": sch["pool"]["native"],
            "native_build_s": health["native_build_s"],
            "mesh": sch["mesh"],
            "bytes_in_use": [d.get("bytes_in_use")
                             for d in health["resources"]["devices"]]}


def check_load(facts):
    if facts["interpreted_kernels"] or \
            facts["attn_backend"] == "pallas_interpret":
        raise SmokeFailure(
            f"pallas kernels interpreted: {facts['interpreted_kernels']}")
    if not facts["native_block_pool"]:
        raise SmokeFailure("the Python block pool is in use")


# ---------------------------------------------------------------------
# one chip: worker + master
# ---------------------------------------------------------------------

def submit_and_wait(children, master, model, prompts, label, timeout,
                    max_new):
    """Submit all prompts, then poll every one to a terminal state."""
    t0 = time.monotonic()
    ids = []
    for p in prompts:
        st, r = http("POST", master + "/api/inference/submit",
                     {"model_name": model, "prompt": p,
                      "max_new_tokens": max_new})
        if st != 200:
            raise SmokeFailure(f"submit refused ({st}): {r}")
        ids.append(r["request_id"])
    pending = dict(zip(ids, prompts))
    rows = {}
    while pending:
        if time.monotonic() - t0 > timeout:
            raise SmokeFailure(f"{label}: requests {sorted(pending)} not "
                               f"finished after {timeout:.0f}s")
        children.check_alive()
        for rid in list(pending):
            st, r = http("GET", f"{master}/api/inference/status/{rid}")
            row = r.get("request") or {}
            if row.get("status") in ("completed", "failed"):
                row["_seconds"] = round(time.monotonic() - t0, 2)
                rows[rid] = row
                del pending[rid]
        time.sleep(0.25)
    # in flight together: some request started before another finished
    spans = sorted((rows[i]["started_at"], rows[i]["completed_at"])
                   for i in ids)
    if len(ids) > 1 and not any(b[0] < a[1]
                                for a, b in zip(spans, spans[1:])):
        raise SmokeFailure(f"{label}: no two requests overlapped: {spans}")
    for rid in ids:
        row = rows[rid]
        st, c = http("GET", f"{master}/api/requests/{rid}/cost")
        tokens = ((c.get("cost") or {}).get("decode_tokens")
                  if st == 200 else None)
        say(phase="request", group=label, request_id=rid,
            prompt_chars=len(row["prompt"]), status=row["status"],
            tokens=tokens, seconds=row["_seconds"],
            attempts=row.get("attempts"),
            worker_seconds=row.get("execution_time"),
            error=row.get("error"))
        if row["status"] != "completed":
            raise SmokeFailure(f"request {rid} failed: {row.get('error')}")
        # the text may well be empty: random weights emit ids all over
        # the vocabulary and the byte-level tokenizer decodes 256 of them
        if not tokens:
            raise SmokeFailure(f"request {rid} returned no tokens")
    return time.monotonic() - t0


def run_one_chip(children, model, sizes, rehearse):
    t_start = time.monotonic()
    load, max_new = sizes["load"], sizes["max_new"]
    worker, health, device = start_worker(children, rehearse)
    cache0 = health["compile_cache"]

    mport = free_port()
    # the master is a control plane: it must never touch the chip
    children.start("master", [sys.executable, "-m", PKG, "master",
                              "--host", "127.0.0.1", "--port", str(mport),
                              "--db", ":memory:"],
                   child_env(JAX_PLATFORMS="cpu", DLI_PLATFORM="cpu"))
    master = f"http://127.0.0.1:{mport}"
    wait_http(children, master + "/health", 120)

    at = urllib.parse.urlsplit(worker)
    st, r = http("POST", master + "/api/nodes/add",
                 {"name": "chip0", "host": at.hostname, "port": at.port})
    if st != 200:
        raise SmokeFailure(f"/api/nodes/add refused ({st}): {r}")

    t0 = time.monotonic()
    st, r = http("POST", master + "/api/models/load",
                 dict(load, model_name=model), timeout=900)
    if st != 200:
        raise SmokeFailure(f"/api/models/load failed ({st}): {r}\n"
                           + children.tail("worker"))
    st, health = http("GET", worker + "/health")
    facts = load_facts(health, model)
    say(phase="load", model=model, quantize=load.get("quantize"),
        seconds=round(time.monotonic() - t0, 2),
        worker_load_time_s=round(r.get("load_time_s", 0.0), 2), **facts)
    check_load(facts)

    # compile included: the first request pays for its admit program and
    # its decode chunks; the long one for the chunked-prefill programs
    submit_and_wait(children, master, model,
                    [prompt_of(sizes["first"], 1)], "first", 600, max_new)
    say(phase="first_request_done",
        seconds_since_start=round(time.monotonic() - t_start, 2))
    submit_and_wait(children, master, model,
                    [prompt_of(sizes["long"], 2)], "long", 900, max_new)
    # four in flight together: the decode wave holds more than one slot
    submit_and_wait(children, master, model, wave_prompts(sizes, 3),
                    "wave", 900, max_new)
    # another such wave, everything compiled: a later request
    wave_s = submit_and_wait(children, master, model,
                             wave_prompts(sizes, 7), "wave_warm", 600,
                             max_new)

    st, health = http("GET", worker + "/health")
    facts = load_facts(health, model)
    sch = next(m for m in health["loaded_models"]
               if m["name"] == model)["scheduler"]
    say(phase="served", warm_wave_seconds=round(wave_s, 2),
        scheduler_steps=sch["steps"], tokens_out=sch["tokens_out"],
        decode_chunk_sizes=sch["chunk_sizes"],
        chunked_admissions=sch["chunked_admissions"],
        tokens_per_weight_pass=health["metrics"].get("gauges", {}).get(
            "decode_tokens_per_weight_pass"),
        bytes_in_use=facts["bytes_in_use"],
        compile_cache_before=cache0,
        compile_cache_after=health["compile_cache"])
    if sch["chunked_admissions"] < 1:
        raise SmokeFailure("no prompt was admitted in chunks")
    children.check_alive()
    return device


# ---------------------------------------------------------------------
# four chips: the sharded path and what it is compared with
# ---------------------------------------------------------------------

def tp_logits_child(model, sizes, tp, out_path):
    """Runs in a child of its own (it holds every chip): the same int8
    parameters prefill the smoke's prompts under MeshSpec(tp=N) and on
    one device; writes the logit difference and each prompt's top-two
    gap to ``out_path``."""
    sys.path.insert(0, HERE)
    from distributed_llm_inferencing_tpu.utils.platform import (
        ensure_backend)
    ensure_backend()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_llm_inferencing_tpu.models import transformer
    from distributed_llm_inferencing_tpu.models.params import init_params
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.kvcache import init_cache
    from distributed_llm_inferencing_tpu.parallel import sharding as shd
    from distributed_llm_inferencing_tpu.parallel.mesh import (
        MeshSpec, create_mesh)
    from distributed_llm_inferencing_tpu.utils.tokenizer import (
        load_tokenizer)

    base = get_config(model).replace(quant="int8")
    tok = load_tokenizer(None, base.vocab_size)
    rows = [tok.encode(p) for p in wave_prompts(sizes, 3)]
    seq = 16
    while seq < max(map(len, rows)):
        seq *= 2
    tokens = np.zeros((len(rows), seq), np.int32)
    for i, r in enumerate(rows):
        tokens[i, :len(r)] = r
    lengths = np.array([len(r) for r in rows], np.int32)
    # built once, on the default device; each mesh gets its own placement
    params = init_params(base, jax.random.PRNGKey(0))

    def last_logits(spec):
        # the engine's rule: resolve the backend against the PROGRAM's
        # mesh (one device on a TPU gets the flash prefill kernel)
        cfg = base.replace(
            attn_backend=transformer._cfg_backend(base, spec.num_devices),
            tp_row_sharded=spec.tp > 1)
        mesh = create_mesh(spec)
        with mesh:
            # one device: the parameters are already there (device 0)
            p = (shd.shard_params(params, mesh, cfg, spec)
                 if spec.num_devices > 1 else params)
            cache = jax.device_put(
                init_cache(cfg, len(rows), seq),
                shd.named(mesh, shd.cache_specs(cfg, spec)))
            fn = jax.jit(lambda p, t, n, c: transformer.prefill(
                p, cfg, t, n, c)[0][jnp.arange(len(rows)), n - 1])
            t0 = time.monotonic()
            logits = np.asarray(fn(p, jnp.asarray(tokens),
                                   jnp.asarray(lengths), cache), np.float32)
            dt = time.monotonic() - t0
        used = [(d.memory_stats() or {}).get("bytes_in_use")
                for d in mesh.devices.flat]
        del p, cache
        return logits, cfg.attn_backend, dt, used

    sharded, b_n, s_n, used_n = last_logits(MeshSpec(tp=tp))
    single, b_1, s_1, used_1 = last_logits(MeshSpec())
    top2 = np.sort(single, axis=-1)[:, -2:]
    result = {
        "phase": "tp_logits", "model": model, "tp": tp,
        "devices": [f"{d.platform}:{d.id}" for d in jax.devices()],
        "prompts": len(rows), "padded_len": seq,
        "finite": bool(np.isfinite(sharded).all()
                       and np.isfinite(single).all()),
        "max_abs_diff": float(np.abs(sharded - single).max()),
        "logit_abs_max": float(np.abs(single).max()),
        "logit_std": float(single.std()),
        "argmax_agree": int((sharded.argmax(-1) == single.argmax(-1)).sum()),
        "top2_gap": [float(g) for g in (top2[:, 1] - top2[:, 0])],
        "backend": {"sharded": b_n, "single": b_1},
        "seconds_with_compile": {"sharded": round(s_n, 2),
                                 "single": round(s_1, 2)},
        "bytes_in_use_sharded": used_n,
        "platform": jax.devices()[0].platform,
    }
    with open(out_path, "w") as f:
        json.dump(result, f)


def serve_direct(children, worker, model, mesh, sizes):
    """Load on the worker with ``mesh``, serve the wave greedily over
    HTTP (in flight together), unload; return tokens per prompt."""
    t0 = time.monotonic()
    st, r = http("POST", worker + "/load_model",
                 dict(sizes["load"], model_name=model, mesh=mesh),
                 timeout=900)
    if st != 200:
        raise SmokeFailure(f"/load_model mesh={mesh} failed ({st}): {r}\n"
                           + children.tail("worker"))
    st, health = http("GET", worker + "/health")
    facts = load_facts(health, model)
    say(phase="load", model=model, requested_mesh=mesh,
        seconds=round(time.monotonic() - t0, 2), **facts)
    check_load(facts)
    prompts = wave_prompts(sizes, 3)
    out = [None] * len(prompts)

    def one(i):
        out[i] = http("POST", worker + "/inference",
                      {"model_name": model, "prompt": prompts[i],
                       "max_new_tokens": sizes["max_new"],
                       "sampling": {"do_sample": False}}, timeout=900)

    t0 = time.monotonic()
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    children.check_alive()
    tokens = []
    for i, (st, r) in enumerate(out):
        if st != 200 or not r.get("tokens"):
            raise SmokeFailure(f"inference {i} on mesh={mesh} failed "
                               f"({st}): {str(r)[:300]}")
        tokens.append(r["tokens"])
    st, health = http("GET", worker + "/health")
    say(phase="served", requested_mesh=mesh,
        seconds=round(time.monotonic() - t0, 2),
        tokens=[len(t) for t in tokens],
        bytes_in_use=load_facts(health, model)["bytes_in_use"])
    st, r = http("POST", worker + "/unload_model", {"model_name": model},
                 timeout=120)
    if st != 200:
        raise SmokeFailure(f"/unload_model failed ({st}): {r}")
    return tokens, facts


def run_sharded(children, model, sizes, tp, rehearse):
    # (a) logits, in a child of their own
    out_path = os.path.join(LOG_DIR, "tp_logits.json")
    if os.path.exists(out_path):
        os.unlink(out_path)
    p = children.start("tp_logits", [
        sys.executable, os.path.abspath(__file__), "--tp-logits-child",
        out_path, "--model", model, "--chips", str(tp)], child_env())
    p.wait()
    children.procs.pop("tp_logits")
    if p.returncode != 0 or not os.path.exists(out_path):
        raise SmokeFailure(f"tp logits child failed ({p.returncode}):\n"
                           + children.tail("tp_logits"))
    with open(out_path) as f:
        logit = json.load(f)
    tol = LOGIT_TOL_REL * logit["logit_abs_max"]
    say(**logit, tolerance=tol)
    if not logit["finite"]:
        raise SmokeFailure("non-finite logits")
    if logit["max_abs_diff"] > tol:
        raise SmokeFailure(f"tp={tp} logits differ from one device by "
                           f"{logit['max_abs_diff']} > {tol}")
    if logit["platform"] != "tpu" and not rehearse:
        raise SmokeFailure("tp logits child ran off the tpu")

    # (b) the worker: tp=N load, then a one-device load
    worker, health, device = start_worker(children, rehearse)
    if device["count"] < tp:
        raise SmokeFailure(f"worker sees {device['count']} devices, "
                           f"needs {tp}")
    sharded, facts = serve_direct(children, worker, model, {"tp": tp},
                                  sizes)
    shares = facts["bytes_in_use"][:tp]
    if device["platform"] == "tpu":          # the cpu reports no bytes
        # every device holds its share: none far below the median (the
        # first may hold more — what the load left behind on device 0)
        if not all(shares) or min(shares) < 0.9 * sorted(shares)[tp // 2]:
            raise SmokeFailure(f"a device holds no full share of the "
                               f"weights and pool: {shares}")
        say(phase="note", device0_extra_bytes=shares[0] - min(shares))
    # create_mesh takes devices[:n] (parallel/mesh.py): a one-device
    # load always lands on device 0
    single, facts1 = serve_direct(children, worker, model, {}, sizes)
    say(phase="note", one_device_load_on="device 0 (create_mesh takes "
        "devices[:n])", bytes_in_use=facts1["bytes_in_use"])
    agree, first_diff = [], []
    for i, (a, b) in enumerate(zip(sharded, single)):
        n = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                 min(len(a), len(b)))
        agree.append(n)
        if a[0] != b[0]:
            first_diff.append(i)
    say(phase="greedy_agreement", tokens_agreeing_before_first_split=agree,
        of=[min(len(a), len(b)) for a, b in zip(sharded, single)],
        first_token_differs=first_diff,
        top2_gap=[logit["top2_gap"][i] for i in first_diff])
    for i in first_diff:
        # each of the two logits may move by the tolerance
        if logit["top2_gap"][i] > 2 * tol:
            raise SmokeFailure(
                f"prompt {i}: first greedy token differs although the "
                f"top-two logit gap is {logit['top2_gap'][i]}")
    return device


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--model", default=MODEL)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the control flow on any platform; off a "
                         "tpu the run still ends non-zero with no ok line")
    ap.add_argument("--tp-logits-child", metavar="OUT",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    sizes = SIZES if args.model == MODEL else TOY_SIZES
    if args.tp_logits_child:
        tp_logits_child(args.model, sizes, args.chips, args.tp_logits_child)
        return 0
    children = Children()
    try:
        if args.chips == 1:
            device = run_one_chip(children, args.model, sizes,
                                  args.rehearse)
        else:
            device = run_sharded(children, args.model, sizes, args.chips,
                                 args.rehearse)
    except SmokeFailure as e:
        print(f"chip_smoke failed: {e}", file=sys.stderr)
        return 1
    finally:
        children.stop_all()
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: phases passed on {device}, which is not "
              f"{args.chips} tpu chip(s); no result", file=sys.stderr)
        return 2
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
