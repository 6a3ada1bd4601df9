"""The ouro-2.6b cell's own yardsticks: costs_loop.py against the
arithmetic of PERF.md section 4, the two readers this configuration
brought on a recorded record, the reference's copy, and the comparison
script's and the harness's control flow at tiny-ouro."""

import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import costs_loop as costs
from readers import load_reader

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
CELL = "ouro-2.6b.cot-sat"
NEW = ["decode_hbm_share.loop", "loop_steps_per_pass"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def cfg():
    return json.loads((CHIP / "configs" / "ouro-2.6b.json").read_text())


def test_parameter_counts_nothing_cut():
    c = cfg()
    # q, k, v, o 2048 x 2048 each; gate, up, down 2048 x 5632 each
    assert costs.layer_elems(c) == 4 * 4_194_304 + 3 * 11_534_336 \
        == 51_380_224
    assert 48 * costs.layer_elems(c) == 2_466_250_752
    assert 2 * costs.head_elems(c) == 201_326_592
    assert abs(costs.weight_bytes(c) / 1e9 - 5.335) < 0.001
    assert costs.weight_bytes(c) / 16e9 > 0.25      # the cell's floor
    assert c["reduced"] == [] and c["source_config"] == {
        k: c[k] for k in c["source_config"]}


def test_pool_and_pass_bytes():
    c = cfg()
    assert costs.cache_planes(c) == 192
    assert costs.kv_bytes_per_token(c) == 1_572_864
    b = c["batcher"]
    assert b["slots"] * b["max_seq"] == b["num_blocks"] * b["block_size"] \
        == 5120
    pool = b["num_blocks"] * b["block_size"] * 1_572_864
    assert abs(pool / 1e9 - 8.05) < 0.005
    # a plane with the dummy block: 94 % of 2^31 elements
    plane = 192 * (b["num_blocks"] + 1) * 16 * 16 * 128
    assert 0.93 < plane / 2 ** 31 < 0.95
    t = json.loads((CHIP / "traffic" / "cot-sat.json").read_text())
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= b["max_seq"]
    # the layers 4 times and the head once: 19.9 GB; 8 slots at 400: 5 GB
    assert costs.decode_weight_bytes(c) == 2 * (
        4 * 2_466_250_752 + 100_663_296)
    least = costs.decode_pass_bytes(c, 8 * 400)
    assert least == costs.decode_weight_bytes(c) + 3200 * 1_572_864
    assert abs(least / 1e9 - 24.96) < 0.01
    assert 29 < least / 819e9 * 1e3 < 31          # ms at HBM speed


def record(counters, traced=True, config=None):
    now = time.time()
    return {
        "config": config or cfg(), "counters": counters, "traffic": {},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "requests": [{"prompt_len": 200, "tokens": 400}] * 4,
        "trace": {"modules": {"jit_chunk": [[0.0, 0.4]]}} if traced else {},
        "traced": ({"seconds": 8.0, "chunks": [[now, now + 0.5, 8]]}
                   if traced else None),
    }


COUNTERS = {"batcher_tokens_emitted": 800, "batcher_weight_passes": 100,
            "batcher_stack_passes": 400}


def test_readers_on_a_recorded_record():
    rec = record(COUNTERS)
    read = {m: load_reader("layer_metrics", m)(rec) for m in NEW}
    assert read["loop_steps_per_pass"] == 4.0
    # 50 ms a pass; 8 slots at 200 + 200
    least = costs.decode_pass_bytes(cfg(), 8 * 400)
    assert read["decode_hbm_share.loop"] == pytest.approx(
        100 * least / 819e9 / 0.050)
    assert 0 < read["decode_hbm_share.loop"] < 100


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_counters_gives_none(name):
    """A program that lacks what this configuration added (the parent: no
    `batcher_stack_passes`; another model's file: no `total_ut_steps`)
    leaves the metric out and does not raise."""
    read = load_reader("layer_metrics", name)
    other = record({"batcher_tokens_emitted": 10, "batcher_weight_passes": 1},
                   config={"hidden_size": 4096})
    assert read(other) is None
    assert read(record({}, traced=False)) is None


def test_the_manifest_lists_the_cell_where_it_reports():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {x["name"] for s in ("end_to_end", "per_layer") for x in m[s]
              if CELL in x.get("workloads", [])}
    assert listed == {"tpot_p50_ms", "decode_pass_ms.steady", "tpot_chunk_ms",
                      "tpot_admit_ms", "tpot_host_ms",
                      "kv_pool_bytes_per_token", *NEW}
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "cot-sat"
    conf = next(c for c in m["configs"] if c["name"] == "ouro-2.6b")
    assert conf["reduced"] == []
    t = json.loads((CHIP / "traffic" / "cot-sat.json").read_text())
    assert (t["loop"], t["callers"], t["size_pool"]) == ("closed", 12, 12)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.5, "min": 65, "max": 256}
    assert t["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.4, "min": 128, "max": 384}
    assert t["sampling"] == {"temperature": 0.7, "top_p": 0.9, "top_k": 0,
                             "do_sample": True}
    assert t["warm_shapes"] == {"tail_buckets": [128, 256],
                                "wave_buckets": [1, 2, 4, 8],
                                "decode_chunks": [8, 4, 2, 1]}
    c = cfg()
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Ouro-2.6B"' in line) if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() \
        else None
    if row is not None:
        assert c["source"] == row["source_url"]
        assert c["source_config"] == row["config"]


def test_the_reference_copy_is_the_packages_file():
    ours = (CHIP / "reference" / "ouro_ref.py").read_text()
    theirs = (ROOT / "distributed_llm_inferencing_tpu" / "models"
              / "reference" / "ouro_ref.py").read_text()
    assert ours == theirs
    assert "import distributed_llm" not in ours
    assert "from distributed_llm" not in ours


def test_compare_reference_loop_rehearses_on_the_cpu():
    """Control flow of the chip's comparison at tiny-ouro's widths: admit
    waves, decode chunks, the logits path through the same pool, the
    timed pool's planes against the reference's K and V by loop step;
    bf16 is under the limits, a step left out over one, the planes of the
    step before and the rolled block tables far off. (The limits are the
    chip's; that these toy widths fall on the same sides is not what
    they were set from.)"""
    p = subprocess.run(
        [sys.executable, str(CHIP / "compare_reference_loop.py"),
         "--config", str(CHIP / "tests" / "data" / "tiny-ouro.json"),
         "--steps", "16", "--wave", "2", "--min-prompt", "17",
         "--max-prompt", "40"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900)
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["tied"] and out["timed_programs_vs_logits_path"][
        "first_tokens_equal"] == 4
    assert out["contexts"] == [17, 56] and out["planes"] == 9
    for phase in ("prefill", "decode"):
        got = out["system_vs_reference"][phase]
        assert math.isfinite(got["p50"]) and got["p50"] < 0.03
        assert out["int8_vs_reference"][phase]["p50"] > got["p50"]
        assert out["system_vs_reference_one_step_short"][phase]["p50"] > 0.3
    assert out["last_block"]["ok"] and out["one_step_short_over_a_limit"]
    pool = out["timed_pool_vs_reference"]
    got = pool["rows_rel_diff_p50_by_step"]
    assert len(got) == 3 and out["timed_pool_under_limits"]
    assert all(math.isfinite(g) and 0 < g < 0.03 for g in got)
    assert all(q > g for q, g in zip(pool["control_int8_p50_by_step"], got))
    assert min(pool["control_step_before_p50_by_step"]) > 0.5
    assert out["tie_control_fails"] and out["timed_programs_vs_logits_path"][
        "control_rolled_tables_tokens_equal_share"] < 0.5


def test_the_harness_runs_a_looped_cell_on_the_cpu():
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--config",
         str(CHIP / "tests" / "data" / "tiny-ouro.json"), "--traffic",
         str(CHIP / "tests" / "data" / "rehearsal-loop.json"),
         "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", "1"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["programs_first_run_in_window"] == 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["loop_steps_per_pass"] == 3.0
    assert got["kv_pool_bytes_per_token"] == 9 * 2 * 4 * 16 * 2
    assert out["counters"]["batcher_stack_passes"] \
        == 3 * out["counters"]["batcher_weight_passes"]
