"""distributed_llm_inferencing_tpu — a TPU-native distributed LLM inference framework.

A from-scratch re-design of the capabilities of
MihirPanpatil/Distributed-LLM-Inferencing (a Django-master / Flask-worker
HTTP-sharded HF-inference platform — see SURVEY.md) built TPU-first:

- compute path: pure-JAX causal LMs, jitted prefill/decode with a static-shape
  KV cache, XLA-compiled sampling, Pallas kernels for the hot ops
- parallelism: ``jax.sharding.Mesh`` + ``NamedSharding`` (tensor / data /
  pipeline / sequence / expert axes) with XLA collectives over ICI — replacing
  the reference's file-level shard copies and per-hop HTTP
  (reference: master/dashboard/management/commands/shard_model.py,
  worker/app.py:332-372)
- control plane: a dependency-free master service (node registry, request
  queue, dashboard) + per-host worker agents speaking the same lifecycle RPC
  surface as the reference worker (worker/app.py:49-413)
"""

__version__ = "0.1.0"

import os as _os

if _os.environ.get("DLI_PLATFORM"):
    # Select the JAX backend per process (e.g. DLI_PLATFORM=cpu for a
    # control-plane process that must not claim a TPU).
    import jax as _jax

    _jax.config.update("jax_platforms", _os.environ["DLI_PLATFORM"])

from distributed_llm_inferencing_tpu.models.config import ModelConfig  # noqa: F401
from distributed_llm_inferencing_tpu.models.registry import get_config, list_models  # noqa: F401
