"""CPU rehearsal of chip_smoke.py at tiny-llama size.

The script proves the serving path on a TPU; here only its control flow
is checked, and — the point of the contract — that off a TPU it exits
non-zero and never prints the ``{"ok": true, ...}`` line.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def run_smoke(*args, cwd=REPO, script=SCRIPT, **env):
    r = subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    assert not any("ok" in ln for ln in lines), r.stdout
    return r, lines


def by_phase(lines, phase):
    return [ln for ln in lines if ln.get("phase") == phase]


def test_rehearsal_drives_worker_and_master_but_gives_no_result():
    r, lines = run_smoke("--rehearse", "--model", "tiny-llama")
    assert r.returncode == 2, r.stderr[-2000:]
    assert "not 1 tpu chip" in r.stderr
    [up] = by_phase(lines, "worker_up")
    assert up["device"]["platform"] == "cpu"
    assert up["compile_cache"]["dir"]
    [load] = by_phase(lines, "load")
    assert load["native_block_pool"] is True
    assert load["interpreted_kernels"] == []
    assert load["attn_backend"] == "xla"
    reqs = by_phase(lines, "request")
    assert [q["group"] for q in reqs] == (
        ["first", "long"] + ["wave"] * 4 + ["wave_warm"] * 4)
    assert all(q["status"] == "completed" and q["tokens"] > 0
               for q in reqs)
    [served] = by_phase(lines, "served")
    assert served["chunked_admissions"] >= 1      # a prompt past one chunk
    assert (served["compile_cache_after"]["entries"]
            >= served["compile_cache_before"]["entries"])


def test_real_size_fails_fast_off_a_tpu():
    """Without --rehearse a worker on the cpu ends the run before the
    7B model is ever loaded."""
    r, lines = run_smoke()
    assert r.returncode == 1
    assert "not on a tpu" in r.stderr
    assert [ln["phase"] for ln in lines] == ["worker_up"]


def test_script_alone_in_a_directory_fails(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path)
    r, lines = run_smoke(cwd=tmp_path, script=alone, PYTHONPATH="")
    assert r.returncode == 1
    assert lines == []
    assert "worker died" in r.stderr


def test_four_chip_phase_rehearsed_on_virtual_devices():
    r, lines = run_smoke(
        "--chips", "4", "--rehearse", "--model", "tiny-llama",
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 2, r.stderr[-2000:]
    [logits] = by_phase(lines, "tp_logits")
    assert logits["finite"] and logits["tp"] == 4
    assert logits["max_abs_diff"] <= logits["tolerance"]
    assert len(logits["devices"]) == 4
    meshes = [ln["mesh"]["tp"] for ln in by_phase(lines, "load")]
    assert meshes == [4, 1]         # sharded load, then one device
    assert len(by_phase(lines, "served")) == 2
    [agree] = by_phase(lines, "greedy_agreement")
    assert len(agree["of"]) == 4
    # no phase other than the sharded path and its comparison ran
    assert not by_phase(lines, "request")
