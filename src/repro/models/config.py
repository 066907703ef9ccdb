"""Unified model configuration covering all assigned architecture families."""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: Optional[int] = None   # default d_model // n_heads

    # attention options
    attn_type: str = "gqa"         # gqa | mla | none
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # MLA (MiniCPM3 / DeepSeek-V2 style)
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64

    # MoE
    n_experts: int = 0             # the router's width (published count)
    top_k: int = 0
    # experts this chip holds: [expert_offset, expert_offset +
    # experts_held); 0 holds all n_experts (expert parallelism gives each
    # chip its share of every layer's experts)
    expert_offset: int = 0
    experts_held: int = 0
    shared_expert_ff: int = 0      # >0: a gated shared SwiGLU expert
    norm_topk_prob: bool = True    # renormalise the top-k router weights
    capacity_factor: float = 1.25  # training's dispatch; serving drops none

    # SSM / recurrent
    rwkv_head_size: int = 64
    ssm_state: int = 64
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4

    # hybrid (Zamba2): one shared attention block applied every k mamba layers
    hybrid_attn_every: int = 6

    # encoder-decoder (Whisper): n_layers is the decoder depth
    n_encoder_layers: int = 0
    n_frontend_tokens: int = 0     # stubbed modality tokens (audio frames /
                                   # vision patches), prepended or cross-attended
    frontend: Optional[str] = None  # 'audio' | 'vision' | None

    # misc
    act: str = "swiglu"            # swiglu | gelu
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    max_seq: int = 32768

    # ---- performance knobs (EXPERIMENTS.md §Perf; defaults = baseline) ----
    pv_bf16: bool = False          # cast softmax probs to bf16 for the PV
                                   # einsum (halves the dominant score-tensor
                                   # traffic; max/sum stay fp32)
    moe_group_size: int = 0        # >0: dispatch in token groups of this
                                   # size (GShard grouping — makes the
                                   # dispatch tensor linear instead of
                                   # quadratic in sequence length)
    pad_vocab_to: int = 0          # >0: pad embed/head rows to a multiple
                                   # (restores vocab-TP for odd vocabs;
                                   # loss masks the padding)

    def __post_init__(self):
        if self.d_head is None:
            object.__setattr__(self, "d_head", self.d_model // max(self.n_heads, 1))

    @property
    def n_held(self) -> int:
        """Routed experts this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def vocab_padded(self) -> int:
        if self.pad_vocab_to <= 0:
            return self.vocab
        m = self.pad_vocab_to
        return ((self.vocab + m - 1) // m) * m

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode state: SSM and hybrid run long_500k."""
        return self.family in ("ssm", "hybrid")

    @property
    def param_count(self) -> int:
        """Approximate parameter count (for 6ND model-FLOPs accounting)."""
        d, L, V = self.d_model, self.n_layers, self.vocab
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.family == "moe":
            attn = L * (d * self.n_heads * self.d_head
                        + 2 * d * self.n_kv_heads * self.d_head
                        + self.n_heads * self.d_head * d)
            ffn = L * (self.n_held * 3 * d * self.d_ff + d * self.n_experts
                       + self._shared_params)
            return emb + attn + ffn
        if self.family == "ssm":  # rwkv6
            tm = L * d * d * 5          # r,k,v,g,o projections
            cm = L * (d * self.d_ff + self.d_ff * d)
            return emb + tm + cm
        if self.family == "hybrid":
            d_in = self.ssm_expand * d
            mamba = L * (d * 2 * d_in + d_in * d + d_in * 2)
            n_shared = max(1, self.n_layers // self.hybrid_attn_every)
            attn = (d * self.n_heads * self.d_head * 2
                    + 2 * d * self.n_kv_heads * self.d_head) + 3 * d * self.d_ff
            return emb + mamba + attn + L * 3 * d * self.d_ff // self.hybrid_attn_every
        attn = L * (d * self.n_heads * self.d_head
                    + 2 * d * self.n_kv_heads * self.d_head
                    + self.n_heads * self.d_head * d)
        n_ff = 3 if self.act == "swiglu" else 2
        ffn = L * n_ff * d * self.d_ff
        enc = self.n_encoder_layers * (attn // max(L, 1) + ffn // max(L, 1))
        return emb + attn + ffn + enc

    @property
    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.family != "moe":
            return self.param_count
        d, L = self.d_model, self.n_layers
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        attn = L * (d * self.n_heads * self.d_head
                    + 2 * d * self.n_kv_heads * self.d_head
                    + self.n_heads * self.d_head * d)
        ffn = L * (self.top_k * 3 * d * self.d_ff + self._shared_params)
        return emb + attn + ffn

    @property
    def _shared_params(self) -> int:
        """The shared expert and its gate, per layer."""
        fs = self.shared_expert_ff
        return 3 * self.d_model * fs + self.d_model if fs else 0


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    """One assigned (input-shape) cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES = (
    ShapeCell("train_4k", 4096, 256, "train"),
    ShapeCell("prefill_32k", 32768, 32, "prefill"),
    ShapeCell("decode_32k", 32768, 128, "decode"),
    ShapeCell("long_500k", 524288, 1, "decode"),
)


def get_shape(name: str) -> ShapeCell:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)
