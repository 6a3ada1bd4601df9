import json
import re
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_finds_its_files():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        body = json.loads((ROOT / c["file"]).read_text())
        assert not body.get("rehearsal"), "a rehearsal is never a cell"
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
    for w in m["workloads"]:
        assert w["config"] in configs
        assert (CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    used = {w["config"] for w in m["workloads"]}
    assert used == set(configs)


def test_every_metric_has_a_reader_and_a_sound_name():
    m = manifest()
    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for section, folder in (("end_to_end", "e2e_metrics"),
                            ("per_layer", "layer_metrics")):
        for x in m[section]:
            assert NAME.match(x["name"]), x["name"]
            assert UNIT.match(x["unit"]), x["unit"]
            assert x["better"] in ("lower", "higher")
            assert (CHIP / folder / f"{x['name']}.py").is_file(), x["name"]
            assert set(x.get("workloads", cells)) <= cells
    for x in m["per_layer"]:
        assert x["moves"] in e2e
        moved = next(y for y in m["end_to_end"] if y["name"] == x["moves"])
        assert set(x.get("workloads", cells)) <= \
            set(moved.get("workloads", cells))
    for w in m["workloads"] + m["configs"]:
        assert NAME.match(w["name"])
    for w in cells:        # set-up, one more end-to-end, one per-layer
        assert sum(w in x.get("workloads", cells)
                   for x in m["end_to_end"]) >= 2
        assert any(w in x.get("workloads", cells) for x in m["per_layer"])


def test_traffic_files_declare_their_shapes():
    for path in (CHIP / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        assert t["loop"] in ("open", "closed")
        assert t["at_window_end"] in ("cancel", "drain")
        shapes = t["warm_shapes"]
        assert shapes["tail_buckets"] and shapes["wave_buckets"]
        assert shapes["decode_chunks"]
        assert t["prompt_len"]["max"] <= max(shapes["tail_buckets"])


def test_peaks_name_their_source():
    peaks = json.loads((CHIP / "peaks.json").read_text())
    for kind, row in peaks.items():
        assert row["hbm_bytes_per_s"] > 0 and row["source"]
