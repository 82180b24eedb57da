"""Architecture config schema + shape cells (assigned benchmark grid)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ..models.common import QuantConfig
from ..models.rope import YarnScaling


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0                # 0 => d_model // n_heads
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    n_held_experts: int = 0        # experts held here (0 => all n_experts)
    expert_offset: int = 0         # first held expert's id
    norm_topk_prob: bool = True    # renormalize the top-k gate values
    first_dense_layers: int = 0    # leading layers with a dense MLP
    dense_d_ff: int = 0            # their MLP width
    # multi-head latent attention (MLA): kv_lora_rank > 0 selects it
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    yarn: Optional[YarnScaling] = None   # yarn-scaled RoPE (MLA layers)
    # SSM / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    hybrid_attn_every: int = 0     # zamba2: shared attn block period
    # attention flavor
    mlp_kind: str = "swiglu"       # swiglu | gelu | relu
    qkv_bias: bool = False
    rope_theta: float = 1e4
    sliding_window: int = 0        # gemma2 local layers
    alt_local_global: bool = False
    attn_softcap: float = 0.0
    logit_softcap: float = 0.0
    mrope: bool = False            # qwen2-vl
    use_post_norms: bool = False   # gemma2
    tie_embeddings: bool = True
    # enc-dec
    is_encdec: bool = False
    enc_layers: int = 0
    conformer_encoder: bool = False
    kv_cache_bits: int = 16        # 16 = bf16 cache; 8 = int8-quantized
    kv_cache_scale: float = 0.25   # static dequant scale for int8 caches
    ssm_chunk: int = 128           # SSD chunk length
    rwkv_chunk: int = 32           # WKV chunk length (overflow-bounded)
    # quantization (BWQ-A)
    quant: QuantConfig = QuantConfig()
    # training details
    remat: bool = True
    scan_layers: bool = True
    dtype: str = "float32"         # activation/compute dtype
    # vlm stub
    vision_tokens: int = 0         # prefix patch-embedding slots

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def held_experts(self) -> int:
        return self.n_held_experts or self.n_experts

    def with_quant(self, qc: QuantConfig) -> "ModelConfig":
        return dataclasses.replace(self, quant=qc)

    def tiny(self, **over) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        def shrink_vocab(v):
            return min(v, 512)
        base = dict(
            n_layers=min(self.n_layers, 2 if not self.hybrid_attn_every else 4),
            d_model=128, n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads
            else 4,
            d_ff=256, vocab=shrink_vocab(self.vocab), d_head=32,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_headdim=32 if self.ssm_state else 64,
            sliding_window=min(self.sliding_window, 16)
            if self.sliding_window else 0,
            hybrid_attn_every=2 if self.hybrid_attn_every else 0,
            enc_layers=min(self.enc_layers, 2) if self.enc_layers else 0,
            vision_tokens=min(self.vision_tokens, 16)
            if self.vision_tokens else 0,
            remat=False,
        )
        if self.kv_lora_rank:
            base.update(kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
                        v_head_dim=32, d_head=48)
        if self.first_dense_layers:
            base.update(n_layers=3, dense_d_ff=384)
        if self.n_held_experts:
            base.update(n_held_experts=max(
                1, base["n_experts"] * self.n_held_experts // self.n_experts))
        base.update(over)
        return dataclasses.replace(self, **base)


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str                      # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


LM_SHAPES: Tuple[ShapeCell, ...] = (
    ShapeCell("train_4k", 4096, 256, "train"),
    ShapeCell("prefill_32k", 32768, 32, "prefill"),
    ShapeCell("decode_32k", 32768, 128, "decode"),
    ShapeCell("long_500k", 524288, 1, "decode"),
)

# archs allowed to run long_500k (sub-quadratic state path); the rest skip
# it per the assignment (see DESIGN.md §5).
LONG_CONTEXT_OK = ("rwkv6-1.6b", "zamba2-1.2b")


def cells_for(cfg: ModelConfig):
    for cell in LM_SHAPES:
        if cell.name == "long_500k" and cfg.name not in LONG_CONTEXT_OK:
            continue
        yield cell
