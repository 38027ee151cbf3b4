"""deepseek-v2-lite [mla_moe] — 27L d_model=2048 16H MLA (kv_lora 512,
qk 128+64, v 128), YaRN x40, layer 0 dense (10944), 26 MoE layers of 64
routed experts (1408) top-6 + 2 shared, vocab=102400.
[hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434]

``full()`` is the published architecture, holding every expert.  A
device's share of a deployment is cut from it with
``replace(n_layers=..., vocab_size=..., expert_range=(lo, hi))``; the
smoke configuration holds a quarter of its experts, so that the
held-expert path is what the tests exercise.
"""

from repro.configs.base import ModelConfig, register


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite", family="mla_moe",
        n_layers=27, first_k_dense=1, d_model=2048, n_heads=16, n_kv_heads=16,
        d_ff=10944, vocab_size=102400, norm_eps=1e-6,
        n_experts=64, experts_per_token=6, moe_d_ff=1408,
        n_shared_experts=2, norm_topk_prob=False,
        router_aux_coef=0.001,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=10_000.0,
        yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
        init_std=0.006, dtype="bfloat16", param_dtype="float32",
        remat=True, remat_policy="full", attn_chunk_threshold=1024 * 1024,
    )


def smoke() -> ModelConfig:
    return full().replace(
        name="deepseek-v2-lite-smoke", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=96, vocab_size=256, n_experts=16,
        experts_per_token=3, moe_d_ff=32, expert_range=(0, 4),
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, yarn_original_max_pos=64, init_std=0.05,
        dtype="float32", remat=False, attn_chunk_threshold=4096 * 4096,
    )


register("deepseek-v2-lite", full, smoke)
