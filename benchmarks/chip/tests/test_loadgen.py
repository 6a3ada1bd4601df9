import json
from pathlib import Path

import loadgen

CHIP = Path(__file__).resolve().parents[1]


def mix(name):
    return json.loads((CHIP / "traffic" / f"{name}.json").read_text())


def test_same_seed_same_schedule():
    t = mix("chat-steady")
    a, b = (loadgen.open_loop(t, 2 ** 31 + 9, 45.0) for _ in range(2))
    assert [(s.due, s.prompt_len, s.out_len, s.token_seed, s.sample_seed)
            for s in a] == \
        [(s.due, s.prompt_len, s.out_len, s.token_seed, s.sample_seed)
         for s in b]
    assert a[0].prompt(32000) == b[0].prompt(32000)


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    t = mix("chat-steady")
    a, b = loadgen.open_loop(t, 1, 45.0), loadgen.open_loop(t, 2, 45.0)
    assert len(a) == len(b) == round(t["rate_rps"] * 45)
    gaps = lambda xs: sorted(round(y.due - x.due, 9) for x, y in zip(xs, xs[1:]))
    first = lambda xs: round(xs[0].due, 9)
    assert sorted(gaps(a) + [first(a)]) == sorted(gaps(b) + [first(b)])
    assert sorted((s.prompt_len, s.out_len) for s in a) == \
        sorted((s.prompt_len, s.out_len) for s in b) == \
        sorted(loadgen.size_pool(t, len(a)))
    assert [s.prompt_len for s in a] != [s.prompt_len for s in b]
    assert 0 < a[0].due and a[-1].due < 45.0


def test_clips_and_medians():
    for name in ("chat-steady", "decode-sat"):
        t = mix(name)
        pool = loadgen.size_pool(t, 64)
        assert len(pool) == 64
        for p, o in pool:
            assert t["prompt_len"]["min"] <= p <= t["prompt_len"]["max"]
            assert t["output_len"]["min"] <= o <= t["output_len"]["max"]
        ps = sorted(p for p, _ in pool)
        assert abs(ps[len(ps) // 2] - t["prompt_len"]["median"]) <= 8
        # the clip is the shape budget: no prompt reaches chunked prefill
        # (cap 512) and every tail lands in a declared bucket
        assert max(ps) <= max(t["warm_shapes"]["tail_buckets"])
        assert min(ps) > 64


def test_closed_loop_cycles_the_pool_with_fresh_prompts():
    t = mix("decode-sat")
    it = loadgen.closed_loop(t, 7)
    n = t["size_pool"]
    specs = [next(it) for _ in range(2 * n)]
    assert sorted((s.prompt_len, s.out_len) for s in specs[:n]) == \
        sorted((s.prompt_len, s.out_len) for s in specs[n:])
    assert len({s.token_seed for s in specs}) == 2 * n
    assert all(s.due is None and 0 <= s.sample_seed < 2 ** 31 for s in specs)
    assert all(3 <= x < 32000 for x in specs[0].prompt(32000))
