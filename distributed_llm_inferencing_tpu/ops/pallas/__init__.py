"""Hand-written TPU Pallas kernels for the hot ops.

The reference's native compute layer was vendored torch/CUDA kernels behind
HF ``model.generate()`` (reference: worker/app.py:297-305, SURVEY.md §2.5).
This package is the TPU-native equivalent: Mosaic-compiled kernels for the
two attention regimes —

- ``flash_attention``: tiled online-softmax causal attention for prefill
  (compute-bound, MXU-saturating)
- ``flash_decode``: single-token cached attention streaming the KV cache
  from HBM (bandwidth-bound)

(the single-stream engine's dense cache: ``attn_backend`` /
``DLI_ATTENTION``, ops/attention.py's backend dispatch), plus

- ``quant_matmul.q4_matmul``: nibble-packed int4 dequant-GEMV that
  never materializes unpacked weights in HBM

and the three kernels the benchmark's cells run, each chosen by the code
from shapes it can see in a one-device TPU program's decode chunks
(PERF.md section 3):

- ``grouped_matmul.grouped_matmul`` (kanana, trinity): the experts'
  grouped matmul where an expert holds a handful of rows, each hit
  expert's weights streamed once (``lax.ragged_dot`` keeps every other
  size; the batcher pins ``cfg.expert_matmul``,
  models/transformer.py:_expert_stream decides)
- ``paged_attention.paged_attend`` (mistral-7b, Ouro-2.6B, kanana,
  falcon-h1-34b, mimo-v2.5's full layers): a pass's attention over the
  paged pool, the pages
  read where they lie by (plane, block-table entry), each slot as far
  as its own context, the chunk's side rows in the same softmax; K and
  V planes whose heads of whole lanes fill a tile's 8 sublanes or
  divide them (16, 8, 4, 2, 1), a latent pool's one plane of shared
  rows taken as K and V at once, or flat rows (a model with layer
  kinds: a position's heads side by side in one row of each plane, V's
  narrower than K's) (the in-loop gather as far as
  _pool_ladder's rung keeps every other pool; the batcher pins
  ``cfg.pool_kernel``, models/transformer.py:_pool_kernel decides:
  the one place a decode chunk's read of the pool is chosen)
- ``ssm_step.ssm_step`` (falcon-h1-34b): a Mamba-2 mixer's one-step
  update of the per-slot state plane, a (slot, group) tile read and
  written once through an aliased output (the jax.numpy form, which
  reads a state twice, keeps every other shape; the same pin,
  models/transformer.py:_ssm_kernel decides)

All run in interpreter mode on CPU for tests (tests/test_pallas_attention.py,
tests/test_pallas_parity.py, tests/test_grouped_matmul.py — the
differential suites against the XLA oracles) and compiled on TPU via
ops/attention.py's backend dispatch (the attention kernels) or the
batcher's pins (the grouped matmul, the pool kernel);
tests/test_tpu_compile.py compiles them for a described v5e.
"""

from distributed_llm_inferencing_tpu.ops.pallas.flash_attention import (  # noqa: F401
    flash_attention,
    flash_decode,
)
