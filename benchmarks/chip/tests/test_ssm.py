"""The falcon-h1-34b-l6 cell's own yardsticks: costs_ssm.py against hand
counts, the three readers this configuration brought on a recorded
record, the reference's copy, and the comparison script's and the
harness's control flow at tiny-falcon-h1."""

import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import costs_ssm as costs
from readers import load_reader

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(1, str(ROOT))       # the package, for the span ring
CELL = "falcon-h1-34b-l6.chat-sat"
NEW = ["decode_hbm_share.ssm", "ssm_state_bytes_per_slot",
       "admit_ms_per_ktok.ssm", "ssm_state_step_roofline"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def cfg():
    return json.loads(
        (CHIP / "configs" / "falcon-h1-34b-l6.json").read_text())


def test_parameter_counts_depth_alone_is_cut():
    c = cfg()
    # q 5120 x 2560 and o 2560 x 5120; k, v 5120 x 512
    assert costs.attention_elems(c) == 2 * 13_107_200 + 2 * 2_621_440 \
        == 31_457_280
    # in_proj 5120 x 9248, the filter 5120 x 4 + 5120, dt_bias / A_log / D
    # 3 x 32, the gated norm 4096, out_proj 4096 x 5120
    assert costs.conv_dim(c) == 5120
    assert costs.mixer_elems(c) == 47_349_760 + 25_600 + 96 + 4096 \
        + 20_971_520 == 68_351_072
    assert costs.mlp_elems(c) == 3 * 110_100_480 == 330_301_440
    assert costs.layer_elems(c) == 430_120_032
    assert costs.head_elems(c) == 1_336_934_400
    assert abs(costs.weight_bytes(c) / 1e9 - 10.509) < 0.001
    assert costs.weight_bytes(c) / 16e9 > 0.25      # the cell's floor
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == 6 == c["overrides"]["num_layers"]
    assert {k: c[k] for k in c["source_config"]
            if k != "num_hidden_layers"} == {
        k: v for k, v in c["source_config"].items()
        if k != "num_hidden_layers"}
    assert c["source_config"]["num_hidden_layers"] == 72


def test_state_pool_and_pass_bytes():
    c = cfg()
    # float32 [32, 128, 256] and bf16 [3, 5120] a layer, 6 layers
    assert costs.state_bytes_per_slot(c) == 6 * (4_194_304 + 30_720) \
        == 25_350_144
    assert costs.kv_bytes_per_token(c) == 12_288
    b = c["batcher"]
    assert b["slots"] * b["max_seq"] == b["num_blocks"] * b["block_size"] \
        == 65_536
    assert abs(64 * costs.state_bytes_per_slot(c) / 1e9 - 1.622) < 0.001
    assert abs(65_536 * 12_288 / 1e9 - 0.805) < 0.001
    t = json.loads((CHIP / "traffic" / "chat-sat.json").read_text())
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= b["max_seq"]
    assert t["prompt_len"]["max"] <= b["prefill_chunk"] * b["block_size"]
    # layers 5.16 + head 2.67 + 64 states twice 3.24 + K and V 0.35
    assert costs.decode_weight_bytes(c) == 2 * (
        6 * 430_120_032 + 1_336_934_400)
    least = costs.decode_pass_bytes(c, 64, 64 * 450)
    assert least == costs.decode_weight_bytes(c) + 64 * 450 * 12_288 \
        + 2 * 64 * 25_350_144
    assert abs(least / 1e9 - 11.43) < 0.01
    assert 13.5 < least / 819e9 * 1e3 < 14.5          # ms at HBM speed
    # what the admit wave is bounded by, as the configuration file says
    assert "4,096" in c["assumed"]["admit_wave"]
    assert t["warm_shapes"]["wave_buckets"][-1] * 128 == 4096


def record(counters, traced=True, config=None, attrs=None,
           kernel=("ssm_state_step.9",)):
    """A record as run.py builds it, and the batcher's spans behind it."""
    from distributed_llm_inferencing_tpu.utils import trace
    now = time.time()
    if traced:
        tracer = trace.get_tracer()
        tracer.record("batcher.decode_chunk", now, now + 0.5,
                      attrs={"k": 8, "kv_bytes_per_token": 12288,
                             **(attrs or {})})
        tracer.record("batcher.admit_wave", now + 0.05, now + 0.25,
                      attrs={"tokens": 2000, "padded_tokens": 4096,
                             "active": 60, **(attrs or {})})
    return {
        "config": config or cfg(), "counters": counters, "traffic": {},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "requests": [{"prompt_len": 200, "tokens": 300}] * 4,
        "trace": {"modules": {"jit_chunk": [[0.0, 0.16]],
                              "jit_admit": [[0.2, 0.1]]},
                  "device_ops": [[name, 0.8 * 0.0394] for name in kernel]
                  + [["fusion.1 bf16[64,21504]", 0.01]]} if traced else {},
        "traced": ({"seconds": 8.0, "chunks": [[now, now + 0.5, 8]]}
                   if traced else None),
    }


COUNTERS = {"batcher_tokens_emitted": 5120, "batcher_weight_passes": 80}


def test_readers_on_a_recorded_record():
    rec = record(COUNTERS, attrs={"ssm_state_bytes_per_slot": 25_350_144})
    read = {m: load_reader("layer_metrics", m)(rec) for m in NEW}
    assert read["ssm_state_bytes_per_slot"] == 25_350_144
    # 20 ms a pass; 64 slots at 200 + 150
    least = costs.decode_pass_bytes(cfg(), 64, 64 * 350)
    assert read["decode_hbm_share.ssm"] == pytest.approx(
        100 * least / 819e9 / 0.020)
    assert 0 < read["decode_hbm_share.ssm"] < 100
    # 8 passes of 20 ms x 6 layers = 48 calls of 0.537 GB; the trace's
    # kernel operations took 0.8 x 39.4 ms: 48 x 0.656 ms / 31.5 ms
    assert costs.step_kernel_bytes(cfg(), 64) == 2 * 64 * 4_194_304
    assert read["ssm_state_step_roofline"] == pytest.approx(
        100 * 48 * 536_870_912 / 819e9 / (0.8 * 0.0394))
    assert 99 < read["ssm_state_step_roofline"] < 100
    # 100 ms of jit_admit for 2000 real tokens
    assert read["admit_ms_per_ktok.ssm"] == pytest.approx(50.0)
    assert read["admit_ms_per_ktok.ssm"] == load_reader(
        "layer_metrics", "admit_ms_per_ktok")(rec)


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_state_gives_none(name):
    """A program that lacks what this configuration added (the parent: no
    `ssm_state_bytes_per_slot` on its spans; another model's file: no
    `mamba_d_ssm`; a model without state layers: the attribute is 0)
    leaves the metric out and does not raise."""
    from distributed_llm_inferencing_tpu.utils import trace
    trace.get_tracer().clear()
    read = load_reader("layer_metrics", name)
    assert read(record({}, traced=False)) is None
    if name != "admit_ms_per_ktok.ssm":
        other = record(COUNTERS, config={"hidden_size": 4096},
                       attrs={"ssm_state_bytes_per_slot": 0})
        assert read(other) is None
    if name == "ssm_state_step_roofline":   # the jax.numpy form's trace
        assert read(record(COUNTERS, kernel=())) is None


def test_the_manifest_lists_the_cell_where_it_reports():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {x["name"] for s in ("end_to_end", "per_layer") for x in m[s]
              if CELL in x.get("workloads", [])}
    assert listed == {"tpot_p50_ms", "decode_pass_ms.steady", "tpot_chunk_ms",
                      "tpot_admit_ms", "tpot_host_ms",
                      "kv_pool_bytes_per_token", *NEW}
    for x in m["per_layer"]:
        if x["name"] in NEW:
            assert x["moves"] == "tpot_p50_ms" and x["workloads"] == [CELL]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == m["workloads"][-1] and cell["chips"] == 1
    assert cell["traffic"] == "chat-sat" and len(cell["why"]) <= 200
    conf = m["configs"][-1]
    assert conf["name"] == "falcon-h1-34b-l6"
    assert conf["reduced"] == ["num_hidden_layers"]
    t = json.loads((CHIP / "traffic" / "chat-sat.json").read_text())
    assert (t["loop"], t["callers"], t["size_pool"], t["pairing_seed"]) \
        == ("closed", 96, 96, 1)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 160,
                               "sigma": 0.7, "min": 65, "max": 512}
    assert t["output_len"] == {"dist": "lognormal", "median": 192,
                               "sigma": 0.5, "min": 64, "max": 512}
    assert t["sampling"] == {"temperature": 0.7, "top_p": 0.9, "top_k": 0,
                             "do_sample": True}
    assert t["warm_shapes"] == {"tail_buckets": [128, 256, 512],
                                "wave_buckets": [1, 2, 4, 8, 16, 32],
                                "decode_chunks": [8, 4, 2, 1]}
    assert t["trace"] == {"start_frac": 0.3, "seconds": 8}
    assert t["at_window_end"] == "cancel"
    c = cfg()
    if CATALOG.exists():
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"Falcon-H1-34B-Instruct"' in line)
        assert c["source"] == row["source_url"] == conf["source"]
        assert c["source_config"] == row["config"]
        assert {k: c[k] for k in row["config"]
                if k != "num_hidden_layers"} == {
            k: v for k, v in row["config"].items()
            if k != "num_hidden_layers"}


def test_the_reference_copy_is_the_packages_file():
    ours = (CHIP / "reference" / "falcon_h1_ref.py").read_text()
    theirs = (ROOT / "distributed_llm_inferencing_tpu" / "models"
              / "reference" / "falcon_h1_ref.py").read_text()
    assert ours == theirs
    assert "import distributed_llm" not in ours
    assert "from distributed_llm" not in ours


def test_compare_reference_ssm_rehearses_on_the_cpu():
    """Control flow of the chip's comparison at tiny-falcon-h1's widths:
    admit waves, decode chunks, a reused slot, the logits path through
    the same pool and planes, the timed state plane against the
    reference's final states; the controls the toy widths can tell apart
    are far off (a stale state, D or the conv bias left out, a
    multiplier set to 1) and the others read above the system. (The
    limits are the chip's; which side of them these toy widths fall on
    is not what they were set from.)"""
    p = subprocess.run(
        [sys.executable, str(CHIP / "compare_reference_ssm.py"),
         "--config", str(CHIP / "tests" / "data" / "tiny-falcon-h1.json"),
         "--steps", "16", "--reuse-steps", "8", "--slots", "4",
         "--min-prompt", "17", "--max-prompt", "60"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900)
    out = json.loads(p.stdout.strip().splitlines()[-1])
    tie = out["timed_programs_vs_logits_path"]
    assert out["tied"] and tie["first_tokens_equal"] == 5 == tie["of_rows"]
    assert out["dead_slots_untouched"] is True
    assert out["prompt_lengths"][0] == 17 and out["prompt_lengths"][-1] == 60
    assert out["reused_slot"]["slot"] == 3 and out["layers"] == 2
    for phase in ("prefill", "decode", "reused_slot"):
        got = out["system_vs_reference"][phase]
        assert math.isfinite(got["p50"]) and got["p50"] < 0.03
    ctl = out["controls_vs_reference"]
    assert ctl["int8"]["decode"]["p50"] \
        > out["system_vs_reference"]["decode"]["p50"]
    assert ctl["d_left_out"]["decode"]["p50"] > 0.3
    assert ctl["conv_bias_left_out"]["decode"]["p50"] > 0.3
    assert ctl["multiplier_one"]["prefill"]["p50"] > 0.05
    state = out["timed_state_vs_reference"]
    # (the toy's 16 x 16 states read 0.002, over the chip's limit of
    # 0.0016, which was set from 128 x 256 states)
    assert 0 < state["timed"]["p50"] < 0.012
    assert state["control_bf16_state"]["p50"] > state["timed"]["p50"]
    assert state["control_stale_state"]["p50"] > 0.1
    fails = out["controls_fail"]
    assert fails["stale_state"] and fails["d_left_out"] \
        and fails["conv_bias_left_out"] and fails["multiplier_one"]


def test_the_harness_runs_a_state_space_cell_on_the_cpu():
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--config",
         str(CHIP / "tests" / "data" / "tiny-falcon-h1.json"), "--traffic",
         str(CHIP / "tests" / "data" / "rehearsal-ssm.json"),
         "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", "1"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["programs_first_run_in_window"] == 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # 2 layers x (4 x 16 x 16 float32 + 3 x 128 bf16)
    assert got["ssm_state_bytes_per_slot"] == 2 * (4096 + 768)
    assert got["kv_pool_bytes_per_token"] == 2 * 2 * 2 * 24 * 2
    c = out["counters"]
    assert c["batcher_ssm_scan_positions"] == c["prefill_uncached_tokens"] > 0
    assert c["batcher_ssm_step_slot_passes"] >= c["batcher_tokens_emitted"] \
        - out["requests_sent"] > 0
    assert "prefill_cached_tokens" not in c      # no prefix is reused
