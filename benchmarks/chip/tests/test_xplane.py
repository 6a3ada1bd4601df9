"""The trace reducer on a trace recorded on a TPU v5e (PR 23, cell
mistral-7b-int8.decode-sat before the decode-chunk cap: one admit program
between two 64-pass chunks), trimmed to the device's module line and the
ops from 30 ms before the admit program to 120 ms after it."""

from pathlib import Path

import pytest

import xplane

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def events():
    return xplane.read_events(str(DATA / "v5e_decode_sat_trimmed.xplane.pb"))


def test_reads_the_device_plane(events):
    [dev] = events["devices"]
    assert dev["name"] == "/device:TPU:0"
    assert [xplane.base_name(m[0]) for m in dev["modules"]] == \
        ["jit_chunk", "jit_admit", "jit_chunk"]
    assert len(dev["ops"]) == 6458
    assert dev["ops"] == sorted(dev["ops"], key=lambda e: e[1])
    # op names are cut to the instruction's name and its result's type
    assert all(" = " not in o[0] and len(o[0]) < 80 for o in dev["ops"])


def test_reduces_to_modules_busy_time_and_gaps(events):
    r = xplane.reduce(events)
    assert r["devices"] == 1
    assert r["modules"]["jit_admit"] == [[pytest.approx(9.426033725),
                                         pytest.approx(0.101542092)]]
    assert len(r["modules"]["jit_chunk"]) == 2
    assert r["window_s"] == pytest.approx(12.001750848)
    # only 0.25 s of ops were kept, and the device ran nearly all of it
    assert 0.23 < r["busy_s"] < 0.25
    gaps = dict(r["idle_gaps"])
    assert gaps["before:jit_admit"] == pytest.approx(0.010831, abs=1e-5)
    assert gaps["before:jit_chunk"] == pytest.approx(0.004028, abs=1e-5)
    top = [name for name, _ in r["device_ops"]]
    assert len(top) == 10
    assert any(n.startswith("broadcast") and "f32[16,2112,8,4,128]" in n
               for n in top[:2])


def test_op_name():
    assert xplane.op_name(
        "%broadcast.1719 = f32[16,2112,8,4,128]{4,3,2,1,0:T(4,128)} "
        "broadcast(f32[16,2112,8,128]{3,2,1,0} %x), dimensions={0,1,2,4}") \
        == "broadcast.1719 f32[16,2112,8,4,128]"
    assert xplane.op_name("%while.39 = (s32[]{:T(128)}, bf16[16]) while()") \
        == "while.39"
    assert xplane.op_name("dot_general.1") == "dot_general.1"


def test_self_times_take_children_out_of_the_parent():
    ops = [["while", 0.0, 10.0], ["a", 1.0, 2.0], ["b", 4.0, 3.0],
           ["a", 5.0, 1.0], ["c", 12.0, 1.0]]
    assert xplane.self_times(ops) == {"while": 5.0, "a": 3.0, "b": 2.0,
                                      "c": 1.0}


def test_align_chunks_pairs_host_spans_with_device_runs():
    host = [[100.0, 101.13, 8], [101.2, 102.33, 8], [102.4, 102.98, 4]]
    # the trace also caught the tail of a chunk cut by its start, and
    # the head of one cut by its end
    runs = [[0.0, 0.4], [0.5, 1.12], [1.7, 1.12], [2.9, 0.57], [3.5, 0.2]]
    dev, passes = xplane.align_chunks(host, runs)
    assert passes == 20 and dev == pytest.approx(1.12 + 1.12 + 0.57)
    assert xplane.align_chunks(host, runs[:2]) is None
    assert xplane.align_chunks([], runs) is None
