"""deepseek-v2-lite [moe] — 27L d=2048, multi-head latent attention (16
heads, kv_lora_rank 512, qk 128+64, v 128, yarn RoPE x40), layer 0 dense
(SwiGLU 10944), layers 1-26 MoE: 64 routed experts of 1408, top-6 softmax
gates not renormalized, 2 shared experts; vocab 102400, untied head
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json].

Held here: routed experts 0-15 of every MoE layer, one chip's share of a
4-way expert-parallel deployment (attention, shared experts, layer 0 and
the head replicated)."""
from .base import ModelConfig
from ..models.common import QuantConfig
from ..models.rope import YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_head=192,
    d_ff=1408, vocab=102400, n_experts=64, top_k=6, n_shared_experts=2,
    n_held_experts=16, expert_offset=0, norm_topk_prob=False,
    first_dense_layers=1, dense_d_ff=10944,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    yarn=YarnScaling(factor=40.0, original_max=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    rope_theta=1e4, tie_embeddings=False, dtype="bfloat16",
    quant=QuantConfig(mode="fake", n_bits=8, act_bits=8, wb_rows=8,
                      wb_cols=128, quantize_embeddings=True),
)
