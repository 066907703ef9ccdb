"""Architecture registry: full configs + reduced (smoke-test) variants."""

from __future__ import annotations

import dataclasses
from typing import Dict

from repro.models.config import ModelConfig

from repro.configs.olmoe_1b_7b import CONFIG as _olmoe
from repro.configs.granite_moe_1b_a400m import CONFIG as _granite
from repro.configs.qwen1_5_110b import CONFIG as _qwen110b
from repro.configs.minicpm3_4b import CONFIG as _minicpm3
from repro.configs.qwen2_1_5b import CONFIG as _qwen2_15b
from repro.configs.qwen1_5_32b import CONFIG as _qwen32b
from repro.configs.whisper_small import CONFIG as _whisper
from repro.configs.rwkv6_7b import CONFIG as _rwkv6
from repro.configs.internvl2_1b import CONFIG as _internvl2
from repro.configs.zamba2_2_7b import CONFIG as _zamba2
from repro.configs.qwen2_57b_a14b import CONFIG as _qwen2_57b

ARCHS: Dict[str, ModelConfig] = {
    c.name: c
    for c in (_olmoe, _granite, _qwen110b, _minicpm3, _qwen2_15b, _qwen32b,
              _whisper, _rwkv6, _internvl2, _zamba2, _qwen2_57b)
}


def list_archs():
    return sorted(ARCHS)


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {list_archs()}")
    return ARCHS[name]


def reduced_config(name: str) -> ModelConfig:
    """Same family/shape *structure*, laptop-scale dims — for smoke tests
    and CPU examples.  Ratios (GQA grouping, MoE top-k, MLA ranks, hybrid
    interleave) are preserved so the code paths match the full config."""
    c = get_config(name)
    changes = dict(
        n_layers=min(c.n_layers, 4 if c.family != "hybrid"
                     else 2 * c.hybrid_attn_every),
        d_model=256,
        vocab=512,
        d_ff=512 if c.family != "moe" else 128,
        max_seq=256,
        d_head=None,
    )
    if c.family == "hybrid":
        changes["hybrid_attn_every"] = c.hybrid_attn_every
    if c.n_heads:
        group = max(c.n_heads // max(c.n_kv_heads, 1), 1)
        n_heads = 4
        changes["n_heads"] = n_heads
        changes["n_kv_heads"] = max(n_heads // group, 1)
    if c.family == "moe":
        changes["n_experts"] = 8
        changes["top_k"] = min(c.top_k, 4)
        if c.shared_expert_ff:
            # keep the shared expert, and hold a strict subset of the
            # routed experts, so smoke tests run a chip's share of a layer
            changes.update(shared_expert_ff=256, experts_held=4,
                           expert_offset=2)
    if c.attn_type == "mla":
        changes.update(q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=32,
                       qk_rope_head_dim=16, v_head_dim=32)
    if c.family == "ssm":
        changes["rwkv_head_size"] = 32
    if c.family == "hybrid":
        changes.update(ssm_state=16, ssm_head_dim=32)
    if c.n_encoder_layers:
        changes["n_encoder_layers"] = 2
    if c.n_frontend_tokens:
        changes["n_frontend_tokens"] = 16
    return dataclasses.replace(c, **changes)
