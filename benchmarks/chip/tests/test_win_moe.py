"""The trinity-mini-l5 cell's own yardsticks: costs_win_moe.py against the
arithmetic of PERF.md section 4, the four readers this configuration
brought on a recorded record, the reference's copy, and the comparison
script's control flow."""

import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import costs_win_moe as costs
from readers import load_reader

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
CELL = "trinity-mini-l5.agent-sat"
NEW = ["decode_hbm_share.win-moe", "kv_read_share.win", "prefix_hit_share",
       "moe_experts_hit_share.afmoe"]


def cfg():
    return json.loads(
        (CHIP / "configs" / "trinity-mini-l5.json").read_text())


def test_parameter_counts_of_the_cut():
    c = cfg()
    # q, o and the gate 2048 x 4096 each; k and v 2048 x 512 each
    assert costs.attention_elems(c) == 3 * 8_388_608 + 2 * 1_048_576 \
        == 27_262_976
    assert costs.dense_layer_elems(c) == 27_262_976 + 3 * 2048 * 6144 \
        == 65_011_712
    # attention + shared 3 x 2048 x 1024 + router 2048 x 128
    assert costs.moe_layer_fixed_elems(c) == 27_262_976 + 6_291_456 \
        + 262_144 == 33_816_576
    assert costs.expert_elems(c) * 128 == 805_306_368
    assert 2 * costs.head_elems(c) == 819_986_432
    assert costs.layer_counts(c) == (1, 4)
    assert costs.attention_kinds(c) == (4, 1)
    # 1.640 + 0.130 + 4 x 1.678 GB
    assert abs(costs.weight_bytes(c) / 1e9 - 8.48) < 0.01
    assert costs.weight_bytes(c) / 16e9 > 0.25      # the cell's floor


def test_pool_and_pass_bytes():
    c = cfg()
    assert costs.kv_layer_bytes_per_token(c) == 2 * 2 * 4 * 128 == 2048
    assert costs.kv_bytes_per_token(c) == 10_240
    b = c["batcher"]
    pool = b["num_blocks"] * b["block_size"] * 10_240
    assert abs(pool / 1e9 - 2.01) < 0.005
    t = json.loads((CHIP / "traffic" / "agent-sat.json").read_text())
    shared = t["shared_prefix"]
    assert shared["tokens"] + t["prompt_len"]["max"] \
        + t["output_len"]["max"] <= b["max_seq"]
    # the mix's blocks: the shared prefixes once, a private part a slot
    private = -(-(t["prompt_len"]["max"] + t["output_len"]["max"])
                // b["block_size"])
    assert shared["groups"] * shared["tokens"] // b["block_size"] \
        + b["slots"] * private <= b["num_blocks"]
    # a pass that hits 126 experts a layer with 64 slots at 8,500: the
    # full layer reads 64 x 8500 positions, a windowed one 64 x 2048
    least = costs.decode_pass_bytes(c, 126, 64 * 8500, 64 * 2048)
    fixed = 2 * (65_011_712 + 4 * 33_816_576 + 409_993_216)
    assert least == fixed + 2 * 4 * 126 * 6_291_456 \
        + 2048 * (64 * 8500 + 4 * 64 * 2048)
    assert abs(least / 1e9 - 9.75) < 0.02
    # K and V are a quarter of it; read whole they would be over a third
    kv = 2048 * (64 * 8500 + 4 * 64 * 2048)
    assert 0.2 < kv / least < 0.25
    assert 2048 * 5 * 64 * 8500 / (least - kv + 2048 * 5 * 64 * 8500) > 0.4


def record(counters, traced=True, config=None):
    now = time.time()
    return {
        "config": config or cfg(), "counters": counters,
        "traffic": {"shared_prefix": {"tokens": 7680, "groups": 4}},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "requests": [{"prompt_len": 400, "tokens": 600}] * 4,
        "trace": {"modules": {"jit_chunk": [[0.0, 0.24]]}} if traced else {},
        "traced": ({"seconds": 8.0, "chunks": [[now, now + 0.3, 8]]}
                   if traced else None),
    }


COUNTERS = {"batcher_moe_layer_passes": 400, "batcher_moe_experts_hit": 50_400,
            "batcher_tokens_emitted": 6_400, "batcher_weight_passes": 100,
            "batcher_decode_pool_positions": 921_600,
            "batcher_decode_window_positions": 206_400}


def test_readers_on_a_recorded_record():
    from distributed_llm_inferencing_tpu.utils import trace
    rec = record(COUNTERS)
    now = rec["traced"]["chunks"][0][0]
    trace.get_tracer().record(
        "batcher.admit_wave", now + 0.01, now + 0.05,
        attrs={"members": 2, "rows": 2, "tail_bucket": 512,
               "prefix_bucket": 512, "tokens": 800, "padded_tokens": 1024,
               "active": 60, "prefix_positions": 15_360,
               "gathered_full": 16_384, "gathered_win": 4_128, "bounded": 1})
    read = {m: load_reader("layer_metrics", m)(rec) for m in NEW}
    assert read["moe_experts_hit_share.afmoe"] == pytest.approx(
        100 * 126 / 128)
    # (4 x 2064 + 9216) / (5 x 9216)
    assert read["kv_read_share.win"] == pytest.approx(37.9166666)
    assert read["prefix_hit_share"] == pytest.approx(100 * 15360 / 16160)
    # 30 ms a pass; 126 experts a layer, 64 slots at 7680 + 400 + 300
    least = costs.decode_pass_bytes(cfg(), 126, 64 * 8380, 64 * 2048)
    assert read["decode_hbm_share.win-moe"] == pytest.approx(
        100 * least / 819e9 / 0.030)
    assert 0 < read["decode_hbm_share.win-moe"] < 100


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_counters_gives_none(name):
    """A program that lacks what this configuration added (the parent, a
    dense model) leaves the metric out and does not raise."""
    read = load_reader("layer_metrics", name)
    dense = record({"batcher_tokens_emitted": 10, "batcher_weight_passes": 1,
                    "batcher_decode_pool_positions": 2048}, traced=False,
                   config={"hidden_size": 4096})
    assert read(dense) is None
    # this configuration under a batcher that counts no window positions
    # and whose spans carry no prefix positions (the parent)
    from distributed_llm_inferencing_tpu.utils import trace
    old = record({k: v for k, v in COUNTERS.items()
                  if k != "batcher_decode_window_positions"})
    now = old["traced"]["chunks"][0][0]
    trace.get_tracer().clear()
    trace.get_tracer().record(
        "batcher.admit_wave", now + 0.01, now + 0.05,
        attrs={"members": 1, "rows": 1, "tail_bucket": 512,
               "prefix_bucket": 512, "tokens": 400, "padded_tokens": 512,
               "active": 3})
    if name in ("kv_read_share.win", "prefix_hit_share"):
        assert read(old) is None


def test_the_manifest_lists_the_cell_where_it_reports():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {x["name"] for s in ("end_to_end", "per_layer") for x in m[s]
              if CELL in x.get("workloads", [])}
    assert listed == {"tpot_p50_ms", "decode_pass_ms.steady",
                      "kv_pool_bytes_per_token", *NEW}
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "agent-sat"
    conf = next(c for c in m["configs"] if c["name"] == "trinity-mini-l5")
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types"]
    t = json.loads((CHIP / "traffic" / "agent-sat.json").read_text())
    assert (t["callers"], t["size_pool"]) == (96, 96)
    assert t["shared_prefix"] == {"tokens": 7680, "groups": 4}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 0.15, "min": 272, "max": 496}
    assert t["output_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.5, "min": 128, "max": 1024}
    c = cfg()
    # depth alone is cut: every other number is the source's
    src = c["source_config"]
    assert {k for k in src if c[k] != src[k]} == set(c["reduced"])
    assert c["published"] == {k: src[k] for k in c["reduced"]}
    assert c["overrides"]["attn_windows"] == [
        c["sliding_window"] if kind == "sliding_attention" else None
        for kind in c["layer_types"]]
    # the wave bound leaves a 512 tail over 512 prefix blocks two rows
    from distributed_llm_inferencing_tpu.runtime.batcher import (
        WAVE_SCORE_BUDGET)
    widest = max(t["warm_shapes"]["wave_buckets"])
    tail, blocks = max(t["warm_shapes"]["tail_buckets"]), \
        max(t["warm_shapes"]["prefix_blocks"])
    per_row = tail * (blocks * c["batcher"]["block_size"] + tail)
    assert widest * per_row <= WAVE_SCORE_BUDGET < 2 * widest * per_row


def test_the_reference_copy_is_the_packages_file():
    ours = (CHIP / "reference" / "afmoe_ref.py").read_text()
    theirs = (ROOT / "distributed_llm_inferencing_tpu" / "models"
              / "reference" / "afmoe_ref.py").read_text()
    assert ours == theirs
    assert "import distributed_llm" not in ours
    assert "from distributed_llm" not in ours


def test_compare_reference_afmoe_rehearses_on_the_cpu():
    """Control flow of the chip's comparison at tiny-afmoe's widths: a
    prefix built a chunk at a time, tails over it, decode chunks; the
    timed programs tie to the logits path, bf16 is under the limits and
    int8 weights over one. (The limits are the chip's; that these toy
    widths fall on the same sides is not what they were set from.)"""
    p = subprocess.run(
        [sys.executable, str(CHIP / "compare_reference_afmoe.py"),
         "--config", str(CHIP / "tests" / "data" / "tiny-afmoe.json"),
         "--prefix", "64", "--groups", "2", "--steps", "16", "--wave", "2",
         "--checked", "2"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["tied"] and out["timed_programs_vs_logits_path"][
        "first_tokens_equal"] == 2
    assert out["contexts"] == [76, 92]
    for phase in ("prefill", "prefix", "decode"):
        got = out["system_vs_reference"][phase]
        assert math.isfinite(got["p50"]) and got["p50"] < 0.03
        assert out["int8_vs_reference"][phase]["p50"] > got["p50"]
