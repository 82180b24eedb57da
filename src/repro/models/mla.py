"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434) over
a latent KV cache.

Per token, ``q = x W_q`` gives each head ``[q_nope | q_pe]``;
``[c_kv | k_pe] = x W_kva``, ``c_kv`` then passes its own RMSNorm, and
``k_pe`` is one rope key shared by every head.  RoPE (yarn-scaled where
the config says) turns ``q_pe`` and ``k_pe`` only, in the repo's
half-split form: a fixed permutation of the published interleaved pairs'
columns.  ``W_kvb`` expands a latent into every head's ``[k_nope | v]``.

The cache holds, per token and layer, only ``c_kv`` (after its norm) and
``k_pe`` (after RoPE): leaves ``ckv`` (…, r) and ``kpe`` (…, dr), plus a
per-token scale each (``ckv_scale``, ``kpe_scale``) when they are stored
as int8, quantized apart.

* Prefill, or any step of several tokens, takes the expanded form: the
  cached latents are expanded through ``W_kvb`` to per-head K and V and
  attended with ``k = [k_nope | k_pe]``.
* A decode token takes the absorbed form: ``q_lat = q_nope W_UK^T`` per
  head, scores ``q_lat . c_kv + q_pe . k_pe``, ``o_lat = softmax . c_kv``
  and ``o = o_lat W_UV`` per head, so the cache is read as stored, once
  (``kernels.latent_attention`` under the ``fused`` backend, its gather
  oracle otherwise).  ``W_kvb`` is dequantized for the two absorbed
  products, which stay in the ``attn_in`` / ``attn_out`` scopes.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..kernels.kv_pages import read_pages
from ..kernels.latent_attention import latent_attention
from ..kernels.ref import latent_attention_ref
from .attention import (_cache_write, attention_core,
                        current_paged_attn_backend, identity_pool,
                        quantize_kv, write_pages)
from .common import make_weight, qdense, qmatmul, rms_norm
from .rope import apply_rope, rope_angles, yarn_softmax_scale


def init_mla(key, cfg, stack: int, dtype=jnp.float32) -> Dict:
    """``stack`` layers' MLA projections (``W_q``, ``W_kva``, ``W_kvb``,
    ``W_o``) and the latent's norm scale."""
    qc = cfg.quant
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": make_weight(ks[0], (stack, d, h * (cfg.qk_nope_dim
                                                 + cfg.qk_rope_dim)),
                          qc, dtype=dtype),
        "wkv_a": make_weight(ks[1], (stack, d, r + cfg.qk_rope_dim), qc,
                             dtype=dtype),
        "kv_norm": jnp.zeros((stack, r), dtype),
        "wkv_b": make_weight(ks[2], (stack, r, h * (cfg.qk_nope_dim
                                                    + cfg.v_head_dim)),
                             qc, dtype=dtype),
        "wo": make_weight(ks[3], (stack, h * cfg.v_head_dim, d), qc,
                          dtype=dtype),
    }


def make_latent_cache(cfg, stack: int, batch: int, max_len: int,
                      dtype) -> Dict:
    """Zero stacked latent cache at ``cfg.kv_cache_bits`` (8, or a float
    cache at ``dtype``)."""
    shape = (stack, batch, max_len)
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    if cfg.kv_cache_bits == 8:
        return {"ckv": jnp.zeros(shape + (r,), jnp.int8),
                "ckv_scale": jnp.zeros(shape, jnp.float32),
                "kpe": jnp.zeros(shape + (dr,), jnp.int8),
                "kpe_scale": jnp.zeros(shape, jnp.float32)}
    if cfg.kv_cache_bits < 16:
        raise ValueError(f"the latent cache stores int8 or a float dtype, "
                         f"not {cfg.kv_cache_bits}-bit entries")
    return {"ckv": jnp.zeros(shape + (r,), dtype),
            "kpe": jnp.zeros(shape + (dr,), dtype)}


def _write(cache: Dict, c, k_pe, idx, layer) -> Dict:
    """The stacked cache with layer ``layer``'s latents written at fill
    level ``idx`` (int8 rounds each once, with a scale per token and
    part); a paged cache scatters them into their pages in place."""
    store = cache.get("pages", cache)
    if "ckv_scale" in store:
        qc, sc = quantize_kv(c[:, :, None, :], 8)
        qp, sp = quantize_kv(k_pe[:, :, None, :], 8)
        new = {"ckv": qc[:, :, 0], "ckv_scale": sc[:, :, 0],
               "kpe": qp[:, :, 0], "kpe_scale": sp[:, :, 0]}
    else:
        dt = store["ckv"].dtype
        new = {"ckv": c.astype(dt), "kpe": k_pe.astype(dt)}
    if "table" in cache:
        return write_pages(cache, new, idx, c.shape[1], layer)
    return dict(cache, **{n: _cache_write(cache[n], u, idx, layer)
                          for n, u in new.items()})


def _read(cache: Dict, dtype, layer):
    """Each slot's whole (B, T, r) latent and (B, T, dr) rope keys of
    layer ``layer``, dequantized (a paged cache's pages gathered in token
    order)."""
    if "table" in cache:
        pool, table = cache["pages"], cache["table"][layer]
        store = {n: read_pages(leaf, pool.fmt(n), layer, table)
                 for n, leaf in pool.items()}
    else:
        with jax.named_scope("kv_slice"):
            store = {n: jax.lax.dynamic_index_in_dim(leaf, layer, 0,
                                                     keepdims=False)
                     for n, leaf in cache.items()}
    out = []
    for n in ("ckv", "kpe"):
        x = store[n].astype(jnp.float32)
        if f"{n}_scale" in store:
            x = x * store[f"{n}_scale"].reshape(x.shape[:-1])[..., None]
        out.append(x.astype(dtype))
    return out


def _decode_read(q_lat, q_pe, cache: Dict, kv_len, sm_scale: float,
                 backend: str, layer):
    """o_lat (B, H, r) of one decode token, read straight from layer
    ``layer``'s stored latents; a contiguous cache is read as an
    identity-table pool of its layer, as in ``attention._decode_read``."""
    if "table" in cache:
        store, table, trash = cache["pages"], cache["table"][layer], True
    else:
        store, table = identity_pool(cache, layer)
        layer, trash = 0, False
    args = (q_lat, q_pe, store["ckv"], store.get("ckv_scale"),
            store["kpe"], store.get("kpe_scale"), table, kv_len)
    kw = dict(page=store.page, sm_scale=sm_scale, layer=layer)
    if backend == "fused":
        return latent_attention(*args, trash_page=trash, **kw)
    return latent_attention_ref(*args, **kw)


def mla_forward(p: Dict, x: jnp.ndarray, positions: jnp.ndarray, cfg,
                cache: Optional[Dict] = None,
                cache_index: Optional[jnp.ndarray] = None, layer=0):
    """MLA sub-layer on normed, quantized activations x (B, S, D): the
    projections, the write and read of layer ``layer`` of the stacked
    cache, attention and ``W_o``.  Returns (out (B, S, D), new stacked
    cache or None)."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    y = cfg.yarn
    if y is not None and y.mscale != y.mscale_all_dim:
        raise NotImplementedError("yarn with mscale != mscale_all_dim "
                                  "scales cos/sin; not modelled")
    sm_scale = yarn_softmax_scale(y, dn + dr)
    decode = cache is not None and s == 1
    with jax.named_scope("attn_in"):
        q = qmatmul(x, p["wq"]).reshape(b, s, h, dn + dr)
        kva = qmatmul(x, p["wkv_a"])
        c = rms_norm(kva[..., :r], p["kv_norm"])
        ang = rope_angles(positions, dr, cfg.rope_theta, y)
        q_nope, q_pe = q[..., :dn], apply_rope(q[..., dn:], ang)
        k_pe = apply_rope(kva[..., None, r:], ang)[:, :, 0]
        if decode:
            w_kvb = qdense(p["wkv_b"], x.dtype).reshape(r, h, dn + dv)
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0],
                               w_kvb[..., :dn],
                               preferred_element_type=jnp.float32)
    new_cache = None
    if cache is not None:
        with jax.named_scope("kv_write"):
            new_cache = _write(cache, c, k_pe, cache_index, layer)
    with jax.named_scope("attn_core"):
        if decode:
            kv_len = jnp.broadcast_to(
                jnp.asarray(cache_index, jnp.int32) + 1, (b,))
            with jax.named_scope("latent_attn"):
                o_lat = _decode_read(q_lat, q_pe[:, 0], new_cache, kv_len,
                                     sm_scale,
                                     current_paged_attn_backend(), layer)
        else:
            kv_len = None
            if cache is None:
                c_all, pe_all, kv_pos = c, k_pe, positions
            else:
                c_all, pe_all = _read(new_cache, x.dtype, layer)
                kv_pos = jnp.broadcast_to(
                    jnp.arange(c_all.shape[1])[None, :],
                    (b, c_all.shape[1]))
                kv_len = jnp.broadcast_to(
                    jnp.asarray(cache_index, jnp.int32) + s, (b,))
            t = c_all.shape[1]
            kv = qmatmul(c_all, p["wkv_b"]).reshape(b, t, h, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    pe_all[:, :, None, :].astype(kv.dtype), (b, t, h, dr))],
                axis=-1)
            o = attention_core(jnp.concatenate([q_nope, q_pe], -1), k,
                               kv[..., dn:], positions, kv_pos,
                               causal=True, kv_len=kv_len,
                               sm_scale=sm_scale)
    with jax.named_scope("attn_out"):
        if decode:
            o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype),
                           w_kvb[..., dn:])[:, None]
        return qmatmul(o.reshape(b, s, h * dv), p["wo"]), new_cache
