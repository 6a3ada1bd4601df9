import json
from pathlib import Path

import costs

CHIP = Path(__file__).resolve().parents[1]


def cfg(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def test_mistral_block_and_weights():
    c = cfg("mistral-7b-int8")
    assert costs.kv_block_bytes(c) == 2 * 2 ** 20          # 2 MiB a block
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert costs.layer_weight_elems(c) == layer == 218_103_808
    # int8: one byte a weight; 32 layers and the 4096 x 32000 head
    assert costs.decode_weight_bytes(c) == 32 * layer + 131_072_000
    # 16 slots at 250 tokens of context add 0.5 GB of K and V
    assert costs.decode_pass_bytes(c, 4000) == \
        costs.decode_weight_bytes(c) + 4000 * 131_072


def test_mixtral_depth_cut_counts_every_expert():
    # the depth-cut mixtral-8x7b of PERF.md's Open questions: its file is
    # not in the tree yet, the arithmetic for it is
    c = dict(cfg("mistral-7b-int8"), num_hidden_layers=6,
             num_local_experts=8)
    assert costs.kv_block_bytes(c) == 384 * 2 ** 10        # 384 KiB a block
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 8 * 3 * 4096 * 14336
    assert costs.layer_weight_elems(c) == layer
    assert abs(layer - 1.45e9) < 0.01e9
    assert costs.decode_weight_bytes(c) == 6 * layer + 131_072_000
