"""Placement planning — the `shard_model` CLI capability, TPU-style.

The reference's ``manage.py shard_model`` (reference: shard_model.py:16-115)
materialized layer-range weight copies on disk plus a metadata.json. Here a
"plan" is pure metadata: the mesh spec, per-component partition specs, and
per-device memory math — checked against real shapes before anything runs.
The plan JSON is what the master stores/ships instead of shard files.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional

from distributed_llm_inferencing_tpu.models.config import ModelConfig
from distributed_llm_inferencing_tpu.models.registry import get_config
from distributed_llm_inferencing_tpu.parallel import sharding
from distributed_llm_inferencing_tpu.parallel.mesh import MeshSpec, validate_spec


def _leaf_entries(cfg: ModelConfig, specs, prefix=""):
    """Flatten spec pytree to {path: [axis names or None]}."""
    out = {}
    for k, v in specs.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_leaf_entries(cfg, v, path + "."))
        else:
            out[path] = list(v)
    return out


def _param_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    """(shape, itemsize) per param leaf without materializing arrays —
    itemsize is per-leaf since int8 quant mixes widths (ops/quant.py)."""
    import jax
    from distributed_llm_inferencing_tpu.models.params import init_params
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    flat = {}

    def walk(tree, prefix=""):
        for k, v in tree.items():
            if isinstance(v, dict):
                walk(v, f"{prefix}{k}.")
            else:
                flat[f"{prefix}{k}"] = (tuple(v.shape), v.dtype.itemsize)
    walk(shapes)
    return flat


def make_plan(model: str | ModelConfig, mesh: Dict[str, int] | MeshSpec,
              max_seq: int = 2048, batch: int = 1) -> Dict[str, Any]:
    cfg = model if isinstance(model, ModelConfig) else get_config(model)
    spec = mesh if isinstance(mesh, MeshSpec) else MeshSpec.from_dict(mesh)
    validate_spec(spec, cfg)

    pspecs = _leaf_entries(cfg, sharding.param_specs(cfg, spec))
    shapes = _param_shapes(cfg)
    axis_sizes = spec.axis_sizes()
    bytes_per_el = 2 if cfg.dtype == "bfloat16" else 4

    total = 0
    per_device = 0
    leaves = {}
    for path, (shape, itemsize) in shapes.items():
        n = 1
        for d in shape:
            n *= d
        shard_factor = 1
        for axis in pspecs.get(path, []):
            if axis is not None:
                shard_factor *= axis_sizes[axis]
        total += n * itemsize
        per_device += n * itemsize // shard_factor
        leaves[path] = {"shape": list(shape), "spec": pspecs.get(path)}

    # KV cache per device
    kv_elems = (cfg.cache_planes * batch * max_seq * cfg.num_kv_heads
                * cfg.qk_head_dim * 2)
    kv_shard = axis_sizes["dp"] * (axis_sizes["tp"] if spec.tp <= cfg.num_kv_heads else 1)
    kv_per_device = kv_elems * bytes_per_el // kv_shard

    return {
        "model": cfg.name,
        "mesh": spec.axis_sizes(),
        "num_devices": spec.num_devices,
        "param_bytes_total": total,
        "param_bytes_per_device": per_device,
        "kv_cache_bytes_per_device": kv_per_device,
        "hbm_per_device_estimate": per_device + kv_per_device,
        "max_seq": max_seq,
        "batch": batch,
        "partition_specs": leaves,
    }


def plan_to_json(plan: Dict[str, Any]) -> str:
    return json.dumps(plan, indent=2)


#: every key make_plan emits — plan_from_json refuses a payload missing
#: any of them, so a persisted planner decision either reloads to a
#: deployable plan or fails loudly at load time, not at /load_shard
PLAN_KEYS = frozenset((
    "model", "mesh", "num_devices", "param_bytes_total",
    "param_bytes_per_device", "kv_cache_bytes_per_device",
    "hbm_per_device_estimate", "max_seq", "batch", "partition_specs"))


def plan_from_json(text: str) -> Dict[str, Any]:
    """Inverse of :func:`plan_to_json`, schema-checked. Round-trips
    bitwise: ``plan_to_json(plan_from_json(plan_to_json(p))) ==
    plan_to_json(p)`` for every plan ``make_plan`` can produce (JSON
    objects preserve key order, and the values are plain ints/strings/
    lists — tests/test_planner.py proves it over the whole registry)."""
    plan = json.loads(text)
    if not isinstance(plan, dict):
        raise ValueError("plan JSON must be an object")
    missing = PLAN_KEYS - set(plan)
    if missing:
        raise ValueError(f"plan JSON missing keys: {sorted(missing)}")
    return plan
