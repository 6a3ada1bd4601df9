"""The readers of the program account (`setup_account.py`, the six
`setup_*` metrics): a finite number each from a traced rehearsal run, by
the program's own account of its start, and None from a record without
one (an older batcher under this benchmark)."""

import json
import math

import pytest

import setup_account
from readers import load_reader
from test_rehearsal import CHIP, ROOT, run

NEW = ["setup_weights_s", "setup_trace_lower_s", "setup_program_load_s",
       "setup_first_run_s", "setup_eager_s", "setup_cache_hit_share"]


def test_every_new_reader_reads_a_traced_rehearsal():
    p = run("--config", "tiny-rehearsal", "--traffic",
            str(CHIP / "tests" / "data" / "rehearsal-traffic.json"),
            "--seed", str(2 ** 31 + 11), "--seconds", "6", "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    got = {name: out["metrics"][name]["value"] for name in NEW}
    for name, value in got.items():
        assert math.isfinite(value) and value >= 0, (name, value)
    # every serving program of the warm-up was traced, lowered and
    # loaded, and the parts stay inside the harness's own set-up
    assert got["setup_trace_lower_s"] > 0
    assert got["setup_program_load_s"] > 0
    assert 0 <= got["setup_cache_hit_share"] <= 100
    parts = sum(got[name] for name in NEW[:5])
    assert parts < sum(out["setup"][k] for k in
                       ("build_s", "warm_s", "probe_and_start_s"))
    assert out["metrics"]["sched_host_share"]["value"] > 0    # still read


def account():
    row = {"kind": "admit", "key": [32, 0, 2], "trace_ms": 300.0,
           "lower_ms": 200.0, "load_ms": 1000.0, "run_ms": 250.0,
           "cache_hits": 1, "cache_misses": 0, "serving": False}
    part = {"trace_ms": 10.0, "lower_ms": 20.0, "load_ms": 70.0,
            "cache_hits": 2, "cache_misses": 1}
    return {"process": {"imported_s": 3.0, "built_s": 20.0},
            "build": {"wall_ms": 17000.0,
                      "weights": dict(part, wall_ms=15000.0),
                      "pool": dict(part, wall_ms=500.0),
                      "eager": dict(part)},
            "eager": {"setup": dict(part, by_name={}),
                      "serving": dict(part, load_ms=9e9, by_name={})},
            "rows": [row, dict(row, kind="chunk", key=8),
                     # first used while serving: no part of set-up
                     dict(row, serving=True, load_ms=9e9, cache_misses=50)],
            "totals": {}}


def test_the_readers_take_set_up_alone():
    record = {"phases": {"programs": account()}}
    got = {n: load_reader("layer_metrics", n)(record) for n in NEW}
    assert got == pytest.approx({
        "setup_weights_s": 15.0, "setup_trace_lower_s": 1.0,
        "setup_program_load_s": 2.0, "setup_first_run_s": 0.5,
        "setup_eager_s": 0.2,       # the pool's and the unlabelled
        "setup_cache_hit_share": 100.0 * 8 / 11})
    cold = account()
    for part in cold["rows"] + setup_account.outside_rows(cold):
        part["cache_hits"], part["cache_misses"] = 0, 3
    assert load_reader("layer_metrics", "setup_cache_hit_share")(
        {"phases": {"programs": cold}}) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_account_gives_none(name):
    read = load_reader("layer_metrics", name)
    assert read({"phases": None, "trace": {}, "traced": None}) is None
    # traced, by a batcher whose profiler keeps no program account
    old = {"phases": {"wall_s": 1.0, "steps_sampled": 2, "clocks": {},
                      "phases": {"device_wait": {"s": 0.5, "frac": 0.5}}},
           "trace": {}, "traced": {"seconds": 8.0, "chunks": []}}
    assert read(old) is None


def test_the_manifest_names_the_six_in_every_cell():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in m["workloads"]]
    mine = {x["name"]: x for x in m["per_layer"] if x["name"] in NEW}
    assert sorted(mine) == sorted(NEW)
    for x in mine.values():
        assert x["moves"] == "setup_s" and x["workloads"] == cells
        assert x["unit"] in ("s", "%")
        assert x["layer"] in ("model step", "device")
