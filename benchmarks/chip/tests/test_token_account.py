"""The readers of a token's time by where the scheduler spent it (the
cost record's `decode_*_ms` parts): a finite number from a traced
rehearsal run, computed from every finished request of the window; None
where the costs lack the fields (an older batcher under this
benchmark)."""

import json
import math

import pytest

from readers import load_reader
from test_rehearsal import CHIP, run

NEW = ["tpot_chunk_ms", "tpot_admit_ms", "tpot_host_ms"]
PARTS = {"decode_chunk_ms": 90.0, "decode_admit_run_ms": 6.0,
         "decode_admit_host_ms": 1.0, "decode_emit_ms": 0.5,
         "decode_host_ms": 1.5, "decode_stall_ms": 0.0}


def test_every_new_reader_reads_a_traced_rehearsal():
    p = run("--config", "tiny-rehearsal", "--traffic",
            str(CHIP / "tests" / "data" / "rehearsal-closed.json"),
            "--seed", str(2 ** 31 + 11), "--seconds", "4", "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    got = {}
    for name in NEW:
        got[name] = out["metrics"][name]["value"]
        assert math.isfinite(got[name]) and got[name] >= 0, (name, got)
    assert got["tpot_chunk_ms"] > 0 and got["tpot_host_ms"] > 0
    # the window's busy wall by bracket rides the line as counters
    clocks = {k for k in out["counters"] if k.startswith("batcher_clock_")}
    assert clocks >= {"batcher_clock_device_wait_ms",
                      "batcher_clock_admit_run_ms", "batcher_clock_emit_ms"}


def request(cost, tokens=11, error=None):
    return {"error": error, "times": [0.1 * i for i in range(tokens)],
            "cost": dict(cost, decode_tokens=tokens)}


def test_the_medians_are_per_token_after_the_first():
    short = {k: v / 2 for k, v in PARTS.items()}
    record = {"requests": [
        request(PARTS), request(short), request(short),
        request(PARTS, tokens=1),               # one token: no time per token
        request({}, error="cancelled")]}        # cut at the window's end
    want = {"tpot_chunk_ms": 4.5, "tpot_admit_ms": 0.35,
            "tpot_host_ms": 0.1}
    for name in NEW:
        assert load_reader("layer_metrics", name)(record) == \
            pytest.approx(want[name])


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_parts_gives_none(name):
    read = load_reader("layer_metrics", name)
    old = {"queue_ms": 1.0, "prefill_ms": 30.0, "decode_ms": 99.0}
    assert read({"requests": [request(old), request(old)]}) is None
    assert read({"requests": []}) is None       # nothing finished
