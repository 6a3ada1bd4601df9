"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip behavior (tp/dp/pp/sp/ep shardings, collectives) is tested on
host CPU devices exactly as SURVEY.md §4 prescribes. Both variables are
set before jax is imported, which is all JAX needs.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tiny_gpt_oss_model(seed=60):
    """Tiny randomized HF gpt-oss (sinks randomized — HF init may leave
    them empty/zero, and all-zero sinks are invisible to sharding and
    parity tests alike). One definition shared by the numerics and
    sharding suites."""
    import torch
    import transformers
    cfg = transformers.GptOssConfig(
        vocab_size=128, hidden_size=32, intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, num_local_experts=4, num_experts_per_tok=2,
        sliding_window=4, layer_types=["sliding_attention",
                                       "full_attention"],
        max_position_embeddings=64, rope_scaling=None,
        tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(seed)
    model = transformers.GptOssForCausalLM(cfg).eval()
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.self_attn.sinks.normal_(0.0, 1.0)
    return model


def tiny_glm45_moe_model(seed=58):
    """Tiny randomized HF GLM-4.5 MoE (q/k norms and the router
    correction bias perturbed away from their invariant inits)."""
    import torch
    import transformers
    cfg = transformers.Glm4MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        partial_rotary_factor=0.5, use_qk_norm=True,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        n_group=2, topk_group=1, routed_scaling_factor=1.5,
        norm_topk_prob=True, first_k_dense_replace=1,
        max_position_embeddings=64, tie_word_embeddings=False,
        pad_token_id=0)
    torch.manual_seed(seed)
    model = transformers.Glm4MoeForCausalLM(cfg).eval()
    with torch.no_grad():
        for lyr in model.model.layers:
            lyr.self_attn.q_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.q_norm.weight) + 0.5)
            lyr.self_attn.k_norm.weight.mul_(
                torch.rand_like(lyr.self_attn.k_norm.weight) + 0.5)
            if hasattr(lyr.mlp, "gate"):
                lyr.mlp.gate.e_score_correction_bias.uniform_(0.0, 0.2)
    return model


# ---- lock-order watchdog gate (utils/locks.py) ------------------------
# When the suite runs with DLI_LOCK_CHECK=1 (scripts/check.sh arms it
# for the chaos suite), every runtime lock is instrumented and a
# dynamic lock-order inversion anywhere in the run must fail the build.
# The deliberate-inversion tests in tests/test_locks.py reset the
# watchdog behind themselves, so any report left at session end is real.

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lock_watchdog_gate():
    yield
    from distributed_llm_inferencing_tpu.utils import locks
    if locks.enabled():
        reports = locks.cycle_reports()
        assert not reports, (
            "lock-order watchdog detected potential deadlocks during "
            f"the run: {reports}")
