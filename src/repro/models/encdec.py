"""Encoder-decoder model (seamless-m4t-style): conformer-ish speech encoder
(stub frontend supplies precomputed frame embeddings) + causal text decoder
with cross-attention.  Same stacked-scan layout as the decoder-only LM."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..dist.sharding import constraint, shard_params_tree
from .attention import attn_forward
from .common import (embed_init, make_weight, prepare_params, qmatmul,
                     rms_norm)
from .transformer import scan_or_loop
from .ffn import mlp_forward


def _enc_block_init(key, cfg: ModelConfig, stack: int) -> Dict:
    d, dh = cfg.d_model, cfg.head_dim
    qc = cfg.quant
    ks = jax.random.split(key, 8)
    p = {
        "ln_attn": jnp.zeros((stack, d), jnp.float32),
        "ln_mlp": jnp.zeros((stack, d), jnp.float32),
        "attn": {
            "wq": make_weight(ks[0], (stack, d, cfg.n_heads * dh), qc),
            "wk": make_weight(ks[1], (stack, d, cfg.n_kv_heads * dh), qc),
            "wv": make_weight(ks[2], (stack, d, cfg.n_kv_heads * dh), qc),
            "wo": make_weight(ks[3], (stack, cfg.n_heads * dh, d), qc),
        },
        "mlp": {
            "w_in": make_weight(ks[4], (stack, d, cfg.d_ff), qc),
            "w_out": make_weight(ks[5], (stack, cfg.d_ff, d), qc),
        },
    }
    if cfg.conformer_encoder:
        p["ln_conv"] = jnp.zeros((stack, d), jnp.float32)
        p["conv_pw1"] = make_weight(ks[6], (stack, d, 2 * d), qc)
        p["conv_dw"] = jax.random.normal(
            jax.random.fold_in(ks[6], 1), (stack, 15, d), jnp.float32) * 0.1
        p["conv_pw2"] = make_weight(ks[7], (stack, d, d), qc)
    return p


def _dec_block_init(key, cfg: ModelConfig, stack: int) -> Dict:
    d, dh = cfg.d_model, cfg.head_dim
    qc = cfg.quant
    ks = jax.random.split(key, 10)
    return {
        "ln_self": jnp.zeros((stack, d), jnp.float32),
        "ln_cross": jnp.zeros((stack, d), jnp.float32),
        "ln_mlp": jnp.zeros((stack, d), jnp.float32),
        "self_attn": {
            "wq": make_weight(ks[0], (stack, d, cfg.n_heads * dh), qc),
            "wk": make_weight(ks[1], (stack, d, cfg.n_kv_heads * dh), qc),
            "wv": make_weight(ks[2], (stack, d, cfg.n_kv_heads * dh), qc),
            "wo": make_weight(ks[3], (stack, cfg.n_heads * dh, d), qc),
        },
        "cross_attn": {
            "wq": make_weight(ks[4], (stack, d, cfg.n_heads * dh), qc),
            "wk": make_weight(ks[5], (stack, d, cfg.n_kv_heads * dh), qc),
            "wv": make_weight(ks[6], (stack, d, cfg.n_kv_heads * dh), qc),
            "wo": make_weight(ks[7], (stack, cfg.n_heads * dh, d), qc),
        },
        "mlp": {
            "w_in": make_weight(ks[8], (stack, d, cfg.d_ff), qc),
            "w_out": make_weight(ks[9], (stack, cfg.d_ff, d), qc),
        },
    }


def init_encdec(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 4)
    return {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model),
        "enc_layers": _enc_block_init(ks[1], cfg, cfg.enc_layers),
        "dec_layers": _dec_block_init(ks[2], cfg, cfg.n_layers),
        "enc_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }


def _conformer_conv(lp, x):
    """Pointwise-GLU -> depthwise conv -> pointwise (simplified Conformer)."""
    h = qmatmul(x, lp["conv_pw1"])
    a, b = jnp.split(h, 2, axis=-1)
    h = a * jax.nn.sigmoid(b)                     # GLU
    w = lp["conv_dw"]                             # (K, d)
    k, d = w.shape
    h = jax.lax.conv_general_dilated(
        h, w[:, None, :].astype(h.dtype), (1,), [(k // 2, k - 1 - k // 2)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=d)
    return qmatmul(jax.nn.silu(h), lp["conv_pw2"])


def encode(mp, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """frames: (B, S_enc, d_model) precomputed frontend embeddings."""
    h = frames.astype(jnp.dtype(cfg.dtype))
    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(carry, lp):
        h = carry
        x = rms_norm(h, lp["ln_attn"])
        out, _ = attn_forward(lp["attn"], x, pos, n_heads=cfg.n_heads,
                              n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
                              rope_theta=cfg.rope_theta, causal=False)
        h = h + out
        if cfg.conformer_encoder:
            h = h + _conformer_conv(lp, rms_norm(h, lp["ln_conv"]))
        h = h + mlp_forward(lp["mlp"], rms_norm(h, lp["ln_mlp"]), "gelu")
        return constraint(h, "batch", None, None), None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = scan_or_loop(body, h, mp["enc_layers"], cfg.scan_layers,
                        cfg.enc_layers)
    return rms_norm(h, mp["enc_norm"])


def decode(mp, cfg: ModelConfig, tokens, enc_out, cache=None, index=None):
    from .transformer import decode_positions

    h = jnp.take(mp["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    b, s, _ = h.shape
    if index is None:
        pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    else:
        pos = decode_positions(index, b, s)
    enc_pos = jnp.broadcast_to(
        jnp.arange(enc_out.shape[1])[None, :], (b, enc_out.shape[1]))

    def body(carry, lp):
        h, cache_c, li = carry
        out, cache_c = attn_forward(
            lp["self_attn"], rms_norm(h, lp["ln_self"]), pos,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
            rope_theta=cfg.rope_theta, causal=True,
            cache=cache_c, cache_index=index, layer=li)
        h = h + out
        out, _ = attn_forward(
            lp["cross_attn"], rms_norm(h, lp["ln_cross"]), pos,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
            rope_theta=cfg.rope_theta, x_kv=enc_out, kv_positions=enc_pos)
        h = h + out
        h = h + mlp_forward(lp["mlp"], rms_norm(h, lp["ln_mlp"]), "gelu")
        return (constraint(h, "batch", None, None), cache_c, li + 1), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (h, new_cache, _), _ = scan_or_loop(
        body, (h, cache, jnp.asarray(0, jnp.int32)), mp["dec_layers"],
        cfg.scan_layers, cfg.n_layers)
    h = rms_norm(h, mp["final_norm"])
    logits = qmatmul(h, mp["embed"].T).astype(jnp.float32)
    return constraint(logits, "batch", None, "vocab"), new_cache


def encdec_forward(params, cfg: ModelConfig, frames, tokens,
                   cache=None, index=None):
    mp = shard_params_tree(prepare_params(params, jnp.dtype(cfg.dtype)))
    enc_out = encode(mp, cfg, frames)
    logits, new_cache = decode(mp, cfg, tokens, enc_out, cache, index)
    return logits, new_cache, enc_out


def encdec_loss(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    logits, _, _ = encdec_forward(params, cfg, batch["frames"],
                                  batch["tokens"])
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - ll)
    return ce, dict(ce=ce, aux=jnp.asarray(0.0))


def encdec_init_cache(cfg: ModelConfig, batch: int, max_len: int):
    from .transformer import make_kv_cache
    return make_kv_cache(cfg, cfg.n_layers, batch, max_len)


def encdec_decode_tokens(params, cfg: ModelConfig, tokens, cache, index,
                         enc_out):
    """Decoder-only forward over a (B, S) token block starting at cache
    position ``index`` (encoder output precomputed) — full (B, S, V)
    logits.  S=1 is the decode step; S>1 is a chunked-prefill insertion."""
    mp = shard_params_tree(prepare_params(params, jnp.dtype(cfg.dtype)))
    return decode(mp, cfg, tokens, enc_out, cache, index)


def encdec_decode_step(params, cfg: ModelConfig, tokens, cache, index,
                       enc_out):
    """One decoder token; encoder output precomputed at prefill time.
    ``index`` may be a scalar or a per-slot (B,) vector."""
    logits, new_cache = encdec_decode_tokens(params, cfg, tokens, cache,
                                             index, enc_out)
    return logits[:, -1], new_cache
