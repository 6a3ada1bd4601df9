"""The harness end to end on the CPU, on the rehearsal configuration."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run(*args):
    return subprocess.run(
        [sys.executable, str(CHIP / "run.py"), *args], cwd=ROOT, env=ENV,
        capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("mix", ["rehearsal-traffic", "rehearsal-closed"])
def test_rehearsal_ends_in_a_contract_shaped_line(mix):
    p = run("--config", "tiny-rehearsal", "--traffic",
            str(CHIP / "tests" / "data" / f"{mix}.json"),
            "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["metrics"]["setup_s"]["value"] > 0
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert out["checks"]["programs_first_run_in_window"] == 0


def test_a_cell_off_the_tpu_gives_no_result_line():
    p = run("--workload", "mistral-7b-int8.decode-sat", "--seed", "1",
            "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout
