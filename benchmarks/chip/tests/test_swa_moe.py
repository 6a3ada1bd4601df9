"""The mimo-v2.5-l7 cell's own yardsticks: costs_swa_moe.py against hand
counts (ISSUE 45's numbers), the five readers this configuration brought
on a recorded record, the reference's copy, and the comparison script's
and the harness's control flow at tiny-mimo-v2."""

import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import costs_swa_moe as costs
from readers import load_reader

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(1, str(ROOT))       # the package, for the span ring
CELL = "mimo-v2.5-l7.longmix-sat"
NEW = ["decode_hbm_share.swa-moe", "kv_read_share.swa",
       "kv_ring_bytes_per_slot", "moe_experts_hit_share.held",
       "admit_ms_per_ktok.swa"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]


def cfg():
    return json.loads((CHIP / "configs" / "mimo-v2.5-l7.json").read_text())


def test_parameter_counts_of_the_cut():
    c = cfg()
    # q 4096 x 12288, k 4096 x 768, v 4096 x 512, o 8192 x 4096
    assert costs.attention_elems(c, False) == 50_331_648 + 3_145_728 \
        + 2_097_152 + 33_554_432 == 89_128_960
    # 8 K/V heads and a sink a query head
    assert costs.attention_elems(c, True) == 50_331_648 + 6_291_456 \
        + 4_194_304 + 33_554_432 + 64 == 94_371_904
    assert costs.expert_elems(c) == 25_165_824
    assert costs.layer_elems(c, 0) == 89_128_960 + 201_326_592 \
        == 290_455_552
    assert costs.layer_elems(c, 1) == 498_073_920    # windowed, 16 experts
    assert costs.layer_elems(c, 6) == 492_830_976    # full, 16 experts
    assert 2 * costs.head_elems(c) == 156_237_824
    assert costs.weight_elems(c) == 3_429_893_952
    assert abs(costs.weight_bytes(c) / 1e9 - 6.860) < 0.001
    assert costs.weight_bytes(c) / 16e9 > 0.25       # the cell's floor
    assert c["reduced"] == REDUCED
    assert c["published"] == {k: c["source_config"][k] for k in REDUCED}
    assert {k: c[k] for k in c["source_config"] if k not in REDUCED} == {
        k: v for k, v in c["source_config"].items() if k not in REDUCED}
    o = c["overrides"]
    assert (o["num_layers"], o["vocab_size"], o["experts_held"],
            o["swa"]["pattern"]) == (
        c["num_hidden_layers"], c["vocab_size"], [0, c["n_routed_experts"]],
        c["hybrid_layer_pattern"])
    assert c["router_columns"] == c["published"]["n_routed_experts"] == 256


def test_pool_ring_and_pass_bytes():
    c = cfg()
    assert costs.kv_bytes_per_token(c) == 2 * 4 * (192 + 128) * 2 == 5_120
    assert costs.ring_positions(c) == 128
    assert costs.ring_bytes_per_slot(c) == 5 * 8 * 320 * 2 * 128 \
        == 3_276_800
    b = c["batcher"]
    assert b["slots"] * b["max_seq"] == b["num_blocks"] * b["block_size"] \
        == 327_680
    assert abs(327_680 * 5_120 / 1e9 - 1.678) < 0.001
    assert abs(65 * 3_276_800 / 1e9 - 0.213) < 0.001
    # a uniform pool (every layer full-length) would not fit the chip
    uniform = 327_680 * (2 * 5_120 // 2 + 5 * 8 * 320 * 2)
    assert uniform / 1e9 > 10 and (uniform + costs.weight_bytes(c)) > 16e9
    t = json.loads((CHIP / "traffic" / "longmix-sat.json").read_text())
    assert t["prompt_len"]["max"] + t["output_len"]["max"] == b["max_seq"]
    assert t["prompt_len"]["max"] == b["prefill_chunk"] * b["block_size"]
    # ISSUE 45's pass: 13.9 of 16 experts hit, 64 slots at 1.8k
    least = costs.decode_pass_bytes(c, 13.9, 64, 64 * 1800)
    fixed = 2 * (290_455_552 + 5 * (94_371_904 + 1_048_832)
                 + 89_128_960 + 1_048_832 + 78_118_912)
    assert least == pytest.approx(
        fixed + 2 * 6 * 13.9 * 25_165_824 + 64 * 1800 * 5_120
        + 64 * 128 * 25_600)
    assert abs(least / 1e9 - 6.87) < 0.01
    assert 8.0 < least / 819e9 * 1e3 < 8.8          # ms at HBM speed


def record(counters, traced=True, config=None, attrs=None):
    """A record as run.py builds it, and the batcher's spans behind it."""
    from distributed_llm_inferencing_tpu.utils import trace
    now = time.time()
    if traced:
        tracer = trace.get_tracer()
        tracer.record("batcher.decode_chunk", now, now + 0.5,
                      attrs={"k": 8, "kv_bytes_per_token": 5120,
                             **(attrs or {})})
        tracer.record("batcher.admit_wave", now + 0.05, now + 0.25,
                      attrs={"tokens": 2000, "padded_tokens": 4096,
                             "active": 60, **(attrs or {})})
    return {
        "config": config or cfg(), "counters": counters, "traffic": {},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "requests": [{"prompt_len": 800, "tokens": 1000}] * 4,
        "trace": {"modules": {"jit_chunk": [[0.0, 0.16]],
                              "jit_admit": [[0.2, 0.1]]},
                  "device_ops": [["fusion.1 bf16[64,16384]", 0.01]]}
        if traced else {},
        "traced": ({"seconds": 8.0, "chunks": [[now, now + 0.5, 8]]}
                   if traced else None),
    }


COUNTERS = {"batcher_tokens_emitted": 5120, "batcher_weight_passes": 80,
            "batcher_moe_layer_passes": 480,
            "batcher_moe_experts_hit": 480 * 14,
            "batcher_moe_experts_held": 480 * 16,
            "batcher_decode_pool_positions": 80 * 5120,
            "batcher_decode_ring_positions": 80 * 128}


def test_readers_on_a_recorded_record():
    rec = record(COUNTERS, attrs={"kv_ring_bytes_per_slot": 3_276_800})
    read = {m: load_reader("layer_metrics", m)(rec) for m in NEW}
    assert read["kv_ring_bytes_per_slot"] == 3_276_800
    assert read["moe_experts_hit_share.held"] == pytest.approx(87.5)
    assert read["kv_read_share.swa"] == pytest.approx(
        100 * (5 * 128 + 2 * 5120) / (7 * 5120))
    # 20 ms a pass; 64 slots at 800 + 500, 14 experts hit a layer
    least = costs.decode_pass_bytes(cfg(), 14, 64, 64 * 1300)
    assert read["decode_hbm_share.swa-moe"] == pytest.approx(
        100 * least / 819e9 / 0.020)
    assert 0 < read["decode_hbm_share.swa-moe"] < 100
    # 100 ms of jit_admit for 2000 real tokens
    assert read["admit_ms_per_ktok.swa"] == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_ring_gives_none(name):
    """A program that lacks what this configuration added (the parent: no
    `kv_ring_bytes_per_slot` on its spans, no ring or held counters;
    another model's file: no `hybrid_layer_pattern`; a model without a
    ring: attribute and counters are 0) leaves the metric out and does
    not raise."""
    from distributed_llm_inferencing_tpu.utils import trace
    trace.get_tracer().clear()
    read = load_reader("layer_metrics", name)
    assert read(record({}, traced=False)) is None
    zeros = dict(COUNTERS, batcher_decode_ring_positions=0,
                 batcher_moe_experts_held=0)
    other = record(zeros, config={"hidden_size": 4096},
                   attrs={"kv_ring_bytes_per_slot": 0})
    assert read(other) is None
    if name not in ("admit_ms_per_ktok.swa", "decode_hbm_share.swa-moe"):
        parent = {k: v for k, v in COUNTERS.items()
                  if "ring" not in k and "held" not in k}
        assert read(record(parent)) is None


def test_the_manifest_lists_the_cell_where_it_reports():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {x["name"] for s in ("end_to_end", "per_layer") for x in m[s]
              if CELL in x.get("workloads", [])}
    assert listed == {"tpot_p50_ms", "decode_pass_ms.steady", "tpot_chunk_ms",
                      "tpot_admit_ms", "tpot_host_ms",
                      "kv_pool_bytes_per_token", *NEW}
    assert [x["name"] for x in m["per_layer"][-5:]] == NEW
    for x in m["per_layer"][-5:]:
        assert x["moves"] == "tpot_p50_ms" and x["workloads"] == [CELL]
    cell = m["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert cell["traffic"] == "longmix-sat" and len(cell["why"]) <= 200
    conf = m["configs"][-1]
    assert conf["name"] == "mimo-v2.5-l7" and conf["reduced"] == REDUCED
    t = json.loads((CHIP / "traffic" / "longmix-sat.json").read_text())
    assert (t["loop"], t["callers"], t["size_pool"], t["pairing_seed"]) \
        == ("closed", 96, 96, 1)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 768,
                               "sigma": 0.7, "min": 256, "max": 2048}
    assert t["output_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.5, "min": 256, "max": 3072}
    assert t["sampling"] == {"temperature": 0.7, "top_p": 0.9, "top_k": 0,
                             "do_sample": True}
    assert t["warm_shapes"] == {"tail_buckets": [256, 512, 1024, 2048],
                                "wave_buckets": [1, 2, 4, 8, 16],
                                "decode_chunks": [8, 4, 2, 1]}
    assert t["trace"] == {"start_frac": 0.3, "seconds": 8}
    assert t["at_window_end"] == "cancel"
    c = cfg()
    if CATALOG.exists():
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"name": "MiMo-V2.5"' in line)
        assert c["source"] == row["source_url"] == conf["source"]
        assert c["source_config"] == row["config"]


def test_the_reference_copy_is_the_packages_file():
    ours = (CHIP / "reference" / "mimo_v2_ref.py").read_text()
    theirs = (ROOT / "distributed_llm_inferencing_tpu" / "models"
              / "reference" / "mimo_v2_ref.py").read_text()
    assert ours == theirs
    assert "import distributed_llm" not in ours
    assert "from distributed_llm" not in ours


def test_compare_reference_mimo_rehearses_on_the_cpu():
    """Control flow of the chip's comparison at tiny-mimo-v2's widths, 8
    of 32 experts held: admit waves, decode chunks through ring and pool,
    a reused slot, a prompt in two chunks, the logits path through the
    same pool; the controls the toy widths can tell apart are far off.
    (The limits are the chip's; which side of them these toy widths fall
    on is not what they were set from.)"""
    p = subprocess.run(
        [sys.executable, str(CHIP / "compare_reference_mimo.py"),
         "--config", str(CHIP / "tests" / "data" / "tiny-mimo-v2.json"),
         "--steps", "16", "--reuse-steps", "8", "--slots", "4",
         "--min-prompt", "17", "--max-prompt", "60", "--chunked", "64"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900)
    out = json.loads(p.stdout.strip().splitlines()[-1])
    tie = out["timed_programs_vs_logits_path"]
    assert out["tied"] and tie["first_tokens_equal"] == 6 == tie["of_rows"]
    assert out["dead_slots_untouched"] is True
    assert out["prompt_lengths"][0] == 17 and out["prompt_lengths"][-1] == 60
    assert out["chunked_prompt"]["chunks"] == [32, 32]
    assert out["experts_held"] == [8, 8] and out["layers"] == 7
    for phase in ("prefill", "decode", "reused_slot", "chunked_prompt"):
        got = out["system_vs_reference"][phase]
        assert math.isfinite(got["p50"]) and got["p50"] < 0.03
    ctl = out["controls_vs_reference"]
    base = out["system_vs_reference"]["decode"]["p50"]
    assert ctl["int8"]["decode"]["p50"] > 1.5 * base
    for name in ("window_127", "window_129", "sink_left_out",
                 "value_scale_left_out", "kv_heads_as_pairs",
                 "absent_expert_added_back"):
        assert ctl[name]["decode"]["p50"] > 4 * base, name
        assert out["controls_fail"][name], name


def test_the_harness_runs_a_hybrid_window_cell_on_the_cpu():
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--config",
         str(CHIP / "tests" / "data" / "tiny-mimo-v2.json"), "--traffic",
         str(CHIP / "tests" / "data" / "rehearsal-swa.json"),
         "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", "1"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["programs_first_run_in_window"] == 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # 5 windowed layers x 8 positions x 4 heads x (24 + 16 -> whole
    # lanes: 128 + 128) x 2 B; 2 full layers x (128 + 128) x 2 B a token
    assert got["kv_ring_bytes_per_slot"] == 5 * 8 * 256 * 2
    assert got["kv_pool_bytes_per_token"] == 2 * 256 * 2
    assert got["kv_read_share.swa"] == pytest.approx(
        100 * (5 * 8 + 2 * 128) / (7 * 128))
    assert 0 < got["moe_experts_hit_share.held"] <= 100
    assert got["admit_ms_per_ktok.swa"] > 0
    c = out["counters"]
    assert c["batcher_moe_rows_away"] > c["batcher_moe_rows"] > 0
    assert c["batcher_moe_experts_held"] == 8 * c["batcher_moe_layer_passes"]
    assert "prefill_cached_tokens" not in c      # no prefix is reused
