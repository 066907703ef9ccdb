"""Qwen2-57B-A14B [hf:Qwen/Qwen2-57B-A14B] — GQA MoE: 64 routed experts,
top-8 without renormalisation, plus a sigmoid-gated shared expert; QKV
bias, untied head.  All 64 experts held (one chip's share is set by
``experts_held`` / ``expert_offset``)."""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen2-57b-a14b",
    family="moe",
    n_layers=28,
    d_model=3584,
    n_heads=28,
    n_kv_heads=4,
    d_ff=2560,              # moe_intermediate_size, per routed expert
    vocab=151936,
    qkv_bias=True,
    rope_theta=1e6,
    norm_eps=1e-6,
    n_experts=64,
    top_k=8,
    shared_expert_ff=20480,
    norm_topk_prob=False,
)
