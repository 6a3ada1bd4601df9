"""The readers of the batcher's admission spans and nested phases: a
finite number from a traced rehearsal run, None where the program has no
such span or bracket (an older batcher under this benchmark)."""

import json
import math
import time

import pytest

from readers import load_reader
from test_rehearsal import CHIP, run

NEW = ["admit_ms_per_ktok", "admit_ms_per_ktok.steady", "admit_pad_share",
       "admit_pad_share.steady", "admit_stall_ms.steady", "step_host_ms"]


def test_every_new_reader_reads_a_traced_rehearsal():
    p = run("--config", "tiny-rehearsal", "--traffic",
            str(CHIP / "tests" / "data" / "rehearsal-traffic.json"),
            "--seed", str(2 ** 31 + 7), "--seconds", "6", "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    for name in NEW:
        value = out["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    assert out["metrics"]["admit_pad_share"]["value"] < 100
    assert out["metrics"]["sched_host_share"]["value"] > 0    # still read


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_spans_gives_none(name):
    from distributed_llm_inferencing_tpu.utils import trace
    read = load_reader("layer_metrics", name)
    # not traced at all
    assert read({"phases": None, "trace": {}, "traced": None}) is None
    # traced, by a batcher that knows neither the attributes nor the
    # nested brackets: its waves carry rows and buckets only
    now = time.time()
    trace.get_tracer().record(
        "batcher.admit_wave", now + 1, now + 2,
        attrs={"members": 1, "rows": 1, "tail_bucket": 32,
               "prefix_bucket": 1})
    old = {"phases": {"wall_s": 1.0, "steps_sampled": 2,
                      "phases": {"device_wait": {"s": 0.5, "frac": 0.5}}},
           "trace": {"modules": {"jit_admit": [[0.1, 0.2]]}},
           "traced": {"seconds": 8.0, "chunks": [[now, now + 4, 8]]}}
    assert read(old) is None
