"""CLI entry: python -m distributed_llm_inferencing_tpu <command>.

Replaces the reference's process entrypoints — ``manage.py runserver`` /
gunicorn for the master, ``app.py`` / gunicorn for the worker, and the
``manage.py shard_model`` CLI (reference: master/Dockerfile:44,
worker/Dockerfile:47, shard_model.py:11-14).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Mirrors ops/quant.py MODES — kept literal so jax-free subcommands
# (master, admin, --help) never import jax just to build the parser;
# tests/test_quant.py asserts the two stay in sync.
quant_modes = ("int8", "int4")


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="distributed_llm_inferencing_tpu",
        description="TPU-native distributed LLM inference framework")
    ap.add_argument("--platform", dest="global_platform", default=None,
                    help="force the jax platform for ANY subcommand "
                         "(tpu|cpu); also honored via DLI_PLATFORM. "
                         "Unset: worker/generate take JAX's default and "
                         "exit non-zero if that is the cpu; convert runs "
                         "on cpu (host-side weight transform needs no "
                         "chip)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    w = sub.add_parser("worker", help="run a worker agent (data plane)")
    w.add_argument("--host", default="0.0.0.0")
    w.add_argument("--port", type=int, default=8100)
    # Multi-host slice (runtime/multihost.py): every host joins one
    # jax.distributed job; process 0 is the lockstep leader serving the
    # public API, the rest co-execute forwarded ops in sequence order.
    w.add_argument("--coordinator", help="host:port of the jax.distributed "
                                         "coordinator (multi-host slices)")
    w.add_argument("--process_id", type=int, default=None)
    w.add_argument("--num_processes", type=int, default=None)
    w.add_argument("--followers",
                   help="leader only: comma-separated follower host:port "
                        "worker addresses (processes 1..N-1)")
    w.add_argument("--latejoin", action="store_true",
                   help="restarted host: record the distributed identity "
                        "(--num_processes/--process_id) WITHOUT joining — "
                        "the old coordinator died with the slice; the "
                        "leader's elastic recovery orders a fresh join "
                        "via /lockstep/reinit_dist")
    w.add_argument("--platform",
                   help="force the jax platform (tpu|cpu) before device "
                        "init — e.g. cpu for transport testing")

    m = sub.add_parser("master", help="run the master (control plane)")
    m.add_argument("--host", default="0.0.0.0")
    m.add_argument("--port", type=int, default=8000)
    m.add_argument("--db", default="master.sqlite3")

    p = sub.add_parser("plan", help="compute a placement plan "
                                    "(shard_model equivalent)")
    p.add_argument("--model_name", required=True)
    p.add_argument("--mesh", default=None,
                   help="e.g. 'tp=4,dp=2' or 'pp=4'; omit to let the "
                        "auto-parallelism planner search this host's "
                        "devices (docs/architecture.md)")
    p.add_argument("--max_seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=1)

    a = sub.add_parser("admin", help="operate on a running master "
                                     "(≙ reference Django admin, admin.py:4-19)")
    a.add_argument("--master", default="http://127.0.0.1:8000")
    a.add_argument("--auth_key", default=None)
    asub = a.add_subparsers(dest="admin_cmd", required=True)
    asub.add_parser("nodes", help="list nodes with live status")
    an = asub.add_parser("add-node", help="register a worker")
    an.add_argument("--name", required=True)
    an.add_argument("--node_host", required=True)
    an.add_argument("--node_port", type=int, default=8100)
    ar = asub.add_parser("remove-node", help="deregister a worker")
    ar.add_argument("--node_id", type=int, required=True)
    asub.add_parser("requests", help="recent inference requests + counts")
    asub.add_parser("plans", help="list placement plans")
    al = asub.add_parser("load-model", help="load a model on a worker")
    al.add_argument("--model_name", required=True)
    al.add_argument("--node_id", type=int)
    al.add_argument("--native_checkpoint")
    al.add_argument("--checkpoint_path")
    al.add_argument("--serving", choices=["batched"])
    al.add_argument("--allow_random_init", action="store_true")

    c = sub.add_parser("convert", help="HF checkpoint -> native sharded "
                                       "checkpoint (models/checkpoint.py)")
    c.add_argument("--checkpoint_path", help="local HF checkpoint dir")
    c.add_argument("--model_name", help="registry name (with "
                                        "--allow_random_init, for testing)")
    c.add_argument("--allow_random_init", action="store_true")
    c.add_argument("--out", required=True)
    c.add_argument("--dtype")
    c.add_argument("--quantize", choices=list(quant_modes),
                   help="store weight-only quantized weights (ops/quant.py)")
    c.add_argument("--embed_quantize", choices=["int8"], default=None,
                   help="per-row int8 token-embedding table "
                        "(halves the tied-head read and table footprint)")

    g = sub.add_parser("generate", help="one-shot local generation")
    g.add_argument("--model_name", default="gpt2")
    g.add_argument("--checkpoint_path")
    g.add_argument("--prompt", required=True)
    g.add_argument("--max_new_tokens", type=int, default=100)
    g.add_argument("--mesh", default="")
    g.add_argument("--allow_random_init", action="store_true")
    g.add_argument("--greedy", action="store_true")
    g.add_argument("--speculative", choices=["ngram"], default=None,
                   help="prompt-lookup speculative decoding "
                        "(ops/speculative.py; distribution-preserving)")
    g.add_argument("--spec_gamma", type=int, default=4)
    g.add_argument("--quantize", choices=list(quant_modes), default=None)
    g.add_argument("--embed_quantize", choices=["int8"], default=None)
    g.add_argument("--kv_quantize", choices=["int8"], default=None)

    args = ap.parse_args(argv)

    # Platform policy (utils/platform.py): an explicit request wins;
    # worker and generate otherwise take JAX's default and refuse to run
    # when that is the cpu (JAX's own fallback when it finds no chip).
    from distributed_llm_inferencing_tpu.utils.platform import (
        check_backend, force_platform, pin_platform)
    requested = (getattr(args, "platform", None) or args.global_platform
                 or os.environ.get("DLI_PLATFORM") or None)
    if args.cmd in ("worker", "generate"):
        asked = pin_platform(requested)
        # a multi-host worker joins jax.distributed first (backend init
        # must not precede it) and checks after the join
        if not (args.cmd == "worker"
                and (args.coordinator or args.latejoin)):
            check_backend(asked)
    elif args.cmd == "convert":
        force_platform(requested or "cpu")
    elif requested:
        force_platform(requested)

    if args.cmd == "worker":
        from distributed_llm_inferencing_tpu.runtime.worker import WorkerAgent
        if args.coordinator or args.latejoin:
            from distributed_llm_inferencing_tpu.runtime.multihost import (
                LockstepFollower, LockstepLeader, configure_multihost,
                init_multihost)
            if args.latejoin:
                if args.num_processes is None or args.process_id is None:
                    sys.exit("--latejoin needs --num_processes and "
                             "--process_id")
                configure_multihost(args.num_processes, args.process_id)
                pid, n = args.process_id, args.num_processes
            else:
                pid, n = init_multihost(args.coordinator,
                                        args.num_processes, args.process_id)
                check_backend(asked)
            agent = WorkerAgent()
            if pid == 0:
                followers = [f for f in (args.followers or "").split(",") if f]
                if n > 1 and len(followers) != n - 1:
                    sys.exit(f"leader needs --followers with {n - 1} "
                             "worker addresses")
                LockstepLeader(agent, followers,
                               auth_key=os.environ.get("DLI_AUTH_KEY"))
            else:
                LockstepFollower(agent)
            agent.serve(args.host, args.port)
        else:
            WorkerAgent().serve(args.host, args.port)
    elif args.cmd == "master":
        from distributed_llm_inferencing_tpu.runtime.master import Master
        Master(args.db).serve(args.host, args.port)
    elif args.cmd == "plan":
        if args.mesh:
            from distributed_llm_inferencing_tpu.parallel.plan import \
                make_plan
            mesh = dict(kv.split("=") for kv in args.mesh.split(",")
                        if kv)
            plan = make_plan(args.model_name, mesh, max_seq=args.max_seq,
                             batch=args.batch)
        else:
            # no explicit mesh: the auto-parallelism planner searches
            # this host's device inventory (one node class — the
            # fleet-wide search needs the master's measured views and
            # lives behind POST /api/plans/auto)
            import jax
            from distributed_llm_inferencing_tpu.parallel import planner
            devs = []
            for d in jax.devices():
                entry = {"kind": getattr(d, "device_kind", d.platform)}
                try:
                    ms = d.memory_stats()
                    if ms:
                        entry["memory_bytes"] = ms.get("bytes_limit")
                except Exception:
                    pass
                devs.append(entry)
            classes = planner.fit_node_classes(
                [{"id": 0, "devices": devs}])
            decision = planner.search(
                args.model_name, classes,
                max_seq=args.max_seq, batch=args.batch)
            if not decision.get("chosen"):
                print(json.dumps(decision), file=sys.stderr)
                sys.exit(1)
            plan = dict(decision["chosen"]["plan"],
                        planner={"mesh": decision["chosen"]["mesh"],
                                 "candidates": decision["candidates"],
                                 "scored": decision["scored"]})
        json.dump(plan, sys.stdout, indent=2)
        print()
    elif args.cmd == "admin":
        _admin(args)
    elif args.cmd == "convert":
        from distributed_llm_inferencing_tpu.models import checkpoint
        if args.checkpoint_path:
            cfg = checkpoint.convert_hf_to_native(
                args.checkpoint_path, args.out, dtype=args.dtype,
                quantize=args.quantize, embed_quantize=args.embed_quantize)
        elif args.allow_random_init and args.model_name:
            import jax
            from distributed_llm_inferencing_tpu.models.params import init_params
            from distributed_llm_inferencing_tpu.models.registry import get_config
            cfg = get_config(args.model_name)
            if args.dtype:
                cfg = cfg.replace(dtype=args.dtype)
            if args.quantize:
                cfg = cfg.replace(quant=args.quantize)
            if args.embed_quantize:
                cfg = cfg.replace(embed_quant=args.embed_quantize)
            checkpoint.save_checkpoint(
                args.out, cfg, init_params(cfg, jax.random.PRNGKey(0)))
        else:
            sys.exit("need --checkpoint_path, or --model_name with "
                     "--allow_random_init")
        print(f"saved native checkpoint for {cfg.name} -> {args.out}")
    elif args.cmd == "generate":
        _generate(args)


def _admin(args):
    """Thin HTTP client for the master's API — the CRUD surface the
    reference exposed only through Django admin (admin.py:4-19)."""
    import requests
    base = args.master.rstrip("/")
    headers = ({"Authorization": f"Bearer {args.auth_key}"}
               if args.auth_key else {})

    def show(resp):
        try:
            json.dump(resp.json(), sys.stdout, indent=2)
            print()
        except ValueError:
            print(resp.status_code, resp.text[:500])
        if resp.status_code != 200:
            sys.exit(1)

    if args.admin_cmd == "nodes":
        show(requests.get(f"{base}/api/nodes/status", headers=headers,
                          timeout=30))
    elif args.admin_cmd == "add-node":
        show(requests.post(f"{base}/api/nodes/add", headers=headers, json={
            "name": args.name, "host": args.node_host,
            "port": args.node_port}, timeout=30))
    elif args.admin_cmd == "remove-node":
        show(requests.post(f"{base}/api/nodes/remove/{args.node_id}",
                           headers=headers, json={}, timeout=30))
    elif args.admin_cmd == "requests":
        show(requests.get(f"{base}/api/inference/recent", headers=headers,
                          timeout=30))
    elif args.admin_cmd == "plans":
        show(requests.get(f"{base}/api/plans", headers=headers, timeout=30))
    elif args.admin_cmd == "load-model":
        body = {"model_name": args.model_name}
        for k in ("node_id", "native_checkpoint", "checkpoint_path",
                  "serving"):
            if getattr(args, k, None):
                body[k] = getattr(args, k)
        if args.allow_random_init:
            body["allow_random_init"] = True
        show(requests.post(f"{base}/api/models/load", headers=headers,
                           json=body, timeout=600))


def _generate(args):
    import jax.numpy as jnp
    from distributed_llm_inferencing_tpu.models.registry import get_config
    from distributed_llm_inferencing_tpu.ops.sampling import SamplingParams
    from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec
    from distributed_llm_inferencing_tpu.runtime.engine import InferenceEngine
    from distributed_llm_inferencing_tpu.utils.tokenizer import load_tokenizer

    if args.checkpoint_path and os.path.isdir(
            os.path.join(args.checkpoint_path, "params")):
        # native (Orbax) checkpoint dir, as produced by `convert` — no
        # torch/transformers on this path
        from distributed_llm_inferencing_tpu.models import checkpoint
        cfg, params = checkpoint.load_checkpoint(args.checkpoint_path)
    elif args.checkpoint_path:
        from distributed_llm_inferencing_tpu.models.convert import load_hf_model
        cfg, params = load_hf_model(args.checkpoint_path)
    elif args.allow_random_init:
        cfg, params = get_config(args.model_name), None
    else:
        sys.exit("need --checkpoint_path or --allow_random_init")
    if args.quantize:
        cfg = cfg.replace(quant=args.quantize)
    if args.embed_quantize:
        cfg = cfg.replace(embed_quant=args.embed_quantize)
    if args.kv_quantize:
        cfg = cfg.replace(kv_quant=args.kv_quantize)
    mesh = MeshSpec.from_dict(
        dict(kv.split("=") for kv in args.mesh.split(",") if kv))
    eng = InferenceEngine(cfg, params, mesh_spec=mesh)
    from distributed_llm_inferencing_tpu.utils.tokenizer import has_tokenizer
    tok = load_tokenizer(
        args.checkpoint_path if has_tokenizer(args.checkpoint_path) else None,
        cfg.vocab_size)   # weights-only dirs fall back to byte-level
    sp = SamplingParams.greedy() if args.greedy else SamplingParams()
    res = eng.generate([tok.encode(args.prompt)],
                       max_new_tokens=args.max_new_tokens, sampling=sp,
                       eos_token_id=tok.eos_token_id,
                       speculative=args.speculative,
                       spec_gamma=args.spec_gamma)
    print(tok.decode(res.tokens[0]))
    print(f"[prefill {res.prefill_ms:.0f}ms, "
          f"decode {res.decode_tokens_per_s:.1f} tok/s]", file=sys.stderr)


if __name__ == "__main__":
    main()
