"""Grouped-query attention with RoPE/M-RoPE, sliding windows, softcaps,
and a KV-cache decode path.  Pure functions over plain arrays; sharding is
annotated with logical axes (heads on the 'model' mesh axis)."""
from __future__ import annotations

import contextlib
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.quantize import pack_int4, unpack_int4
from ..dist.sharding import (constrain_pool, constraint, data_size,
                              get_mesh, model_size, run_kernel)
from ..kernels.kv_pages import (PageFormat, PagePool, read_pages, to_rows,
                                write_tokens)
from ..kernels.paged_attention import paged_attention
from ..kernels.pallas_utils import fit_block
from ..kernels.ref import paged_attention_ref
from .common import qmatmul
from .common import softcap as _softcap
from .rope import apply_rope, mrope_angles, rope_angles

NEG_INF = -2.0e38

# Blockwise-attention dispatch knobs.  The dry-run calibration pass lowers
# with min_elems=inf (dense) so XLA cost analysis sees un-scanned bodies.
ATTN_OPTS = {"min_elems": 4096 * 2048, "q_block": 512, "kv_block": 1024}


def init_attn(key, d_model: int, n_heads: int, n_kv: int, d_head: int,
              qc, make_weight, qkv_bias: bool = False,
              d_model_in: Optional[int] = None, dtype=jnp.float32) -> Dict:
    din = d_model_in or d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": make_weight(ks[0], (din, n_heads * d_head), qc, dtype=dtype),
        "wk": make_weight(ks[1], (din, n_kv * d_head), qc, dtype=dtype),
        "wv": make_weight(ks[2], (din, n_kv * d_head), qc, dtype=dtype),
        "wo": make_weight(ks[3], (n_heads * d_head, d_model), qc, dtype=dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), dtype)
        p["bk"] = jnp.zeros((n_kv * d_head,), dtype)
        p["bv"] = jnp.zeros((n_kv * d_head,), dtype)
    return p


def _split_heads(x, n, d):
    return x.reshape(*x.shape[:-1], n, d)


# ---------------------------------------------------------------------------
# quantized-at-rest KV cache
# ---------------------------------------------------------------------------
#
# K/V are quantized ONCE when written (per-token/per-head dynamic scales,
# KIVI-style) and dequantized in-graph per attention call, so repeated
# decode steps never re-round already-stored entries.  int8 stores one
# value per byte; int4 nibble-packs pairs along the head dim.

def is_kv_node(node) -> bool:
    """True for one layer-stack's attention cache: per-head K/V leaves
    (``k``, ``v`` [, scales]) or the latent leaves of multi-head latent
    attention (``ckv``, ``kpe`` [, scales]; ``models.mla``)."""
    return isinstance(node, dict) and (
        ("k" in node and "v" in node) or "ckv" in node)


def cache_bits(cache) -> int:
    """Storage precision of a KV cache dict: 32 (float), 8, or 4.

    Accepts either a contiguous cache ``{"k", "v", ...}`` or a paged one
    ``{"pages": {"k", ...}, "table": ...}``."""
    if "pages" in cache:
        cache = cache["pages"]
    dt = cache["k"].dtype
    if dt == jnp.int8:
        return 8
    if dt == jnp.uint8:
        return 4
    return 32


def quantize_kv(x: jnp.ndarray, bits: int):
    """(B, S, KV, dh) float -> (quantized, scale(B, S, KV)).

    int8: one int8 per element; int4: two's-complement nibbles packed in
    uint8 pairs along dh (dh must be even)."""
    lim = 127.0 if bits == 8 else 7.0
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / lim
    s = jnp.maximum(s, 1e-6)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -lim, lim)
    if bits == 8:
        return q.astype(jnp.int8), s
    return pack_int4(q, axis=-1), s


def dequantize_kv(qx: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv` (int8 or packed-int4 -> ``dtype``)."""
    if qx.dtype == jnp.int8:
        return qx.astype(dtype) * scale[..., None].astype(dtype)
    w = unpack_int4(qx, axis=-1)
    return w.astype(dtype) * scale[..., None].astype(dtype)


def _cache_write(buf, update, idx, layer):
    """Write ``update`` (B, S, ...) into layer ``layer`` of a stacked
    contiguous leaf (stack, B, T, ...) at offset ``idx`` along T.

    Scalar ``idx`` writes every batch row at the same offset (legacy
    whole-batch decode); a (B,) vector writes each row at its own offset
    (continuous batching).  Offsets clamp so the update fits, as
    ``dynamic_update_slice`` does."""
    update = update.astype(buf.dtype)
    zeros = (0,) * (buf.ndim - 3)
    if jnp.ndim(idx) == 1:
        return jax.vmap(
            lambda b, u, i: jax.lax.dynamic_update_slice(
                b, u[None], (layer, i) + zeros),
            in_axes=(1, 0, 0), out_axes=1)(buf, update, idx)
    return jax.lax.dynamic_update_slice(buf, update[None],
                                        (layer, 0, idx) + zeros)


# ---------------------------------------------------------------------------
# paged cache (block-granular pool + per-slot block tables)
# ---------------------------------------------------------------------------
#
# A paged cache is ``{"pages": PagePool, "table"}`` for the whole layer
# stack: pool leaves hold every layer's pages in the lane-dense storage of
# ``kernels.kv_pages`` — (stack, blocks, rows, lanes), a token's values
# flattened into its slot of a row — and ``table`` (stack, B, nb) maps
# each slot's block b to the pool page holding its tokens [b*page,
# (b+1)*page).  Readers and writers take the stack and a layer index and
# touch only that layer's pages: no layer is sliced out of the stack or
# written back.  Page 0 is reserved as the trash page: parked slots and
# unallocated table entries point at it, and everything routed there
# stays masked by the per-slot fill level.  The values (int8 /
# nibble-packed int4 + per-token scales) are the contiguous cache's —
# paging changes residency and layout, not representation.

def page_coords(table, idx, seq: int, page: int):
    """Slot-relative write positions -> (pool page ids, in-page offsets).

    ``table``: (B, nb) block table; ``idx``: scalar or (B,) fill level.
    Returns two (B, seq) int32 arrays for positions idx .. idx+seq-1.
    Positions past the table end clamp into the last block (jnp gather
    semantics); callers only ever send masked scratch writes there."""
    b = table.shape[0]
    idx = jnp.asarray(idx, jnp.int32)
    base = idx[:, None] if jnp.ndim(idx) == 1 else idx
    pos = jnp.broadcast_to(base + jnp.arange(seq, dtype=jnp.int32), (b, seq))
    pids = jnp.take_along_axis(table, pos // page, axis=1)
    return pids, pos % page


# ---------------------------------------------------------------------------
# decode-attention dispatch (mirrors models.common.matmul_backend)
# ---------------------------------------------------------------------------
#
# ``gather`` is the legacy read side (``_gather_read``: each slot's pages
# gathered in token order + in-graph dequant + attention_core); ``fused``
# walks the block table inside the Pallas kernel so neither the contiguous
# (B, T, ...) KV view nor the f32 KV tree is ever materialized; ``ref`` is
# the kernel's pure-jnp oracle.  Both non-gather backends are decode-only
# (one query token, causal self-attention) — every other shape falls back
# to gather in-trace, which the graph lint flags under a fused engine.

PAGED_ATTN_BACKENDS = ("gather", "fused", "ref")
_PA_BACKEND_STACK = ["gather"]


@contextlib.contextmanager
def paged_attn_backend(name: str):
    """Ambient decode-attention backend for :func:`paged_attn`
    (trace-time, like :func:`repro.models.common.matmul_backend`)."""
    if name not in PAGED_ATTN_BACKENDS:
        raise ValueError(f"unknown paged-attention backend {name!r}; "
                         f"choose from {PAGED_ATTN_BACKENDS}")
    _PA_BACKEND_STACK.append(name)
    try:
        yield
    finally:
        _PA_BACKEND_STACK.pop()


def current_paged_attn_backend() -> str:
    return _PA_BACKEND_STACK[-1]


def paged_attn(q, store: PagePool, table, kv_len, *, layer=0, window=0,
               attn_softcap: float = 0.0, trash_page: bool = True,
               backend: Optional[str] = None) -> jnp.ndarray:
    """One decode step of attention straight over a (quantized) page pool.

    q: (B, 1, H, dh) roped queries; ``store``: the stacked pool
    ``{"k", "v"[, "k_scale", "v_scale"]}`` and ``layer`` its layer to
    read; ``table``: (B, nb); ``kv_len``: (B,) fill levels *including*
    the token just written.  Returns (B, 1, H, dh) in q's dtype.  The
    contiguous cache is served through the same entry by viewing each
    slot's (T, ...) row as pages with ``trash_page=False``: its page 0
    holds data, so the fused kernel bounds a row by ``kv_len`` alone
    (see ``_decode_read``)."""
    backend = backend or current_paged_attn_backend()
    b, s, h, dh = q.shape
    kv = store.tails["k"][0]
    qg = q.reshape(b, kv, h // kv, dh)
    win = None if isinstance(window, int) and window == 0 else \
        jnp.asarray(window, jnp.int32)
    args = (qg, store["k"], store["v"], store.get("k_scale"),
            store.get("v_scale"), table, kv_len)
    kw = dict(page=store.page, layer=layer, window=win, softcap=attn_softcap)
    if backend == "fused":
        out = _fused(args, kw, trash_page,
                     store.head_split("k", model_size()))
    elif backend == "ref":
        out = paged_attention_ref(*args, **kw)
    else:
        raise ValueError(f"paged_attn executes 'fused' or 'ref', "
                         f"got {backend!r}")
    return out.reshape(b, s, h, dh).astype(q.dtype)


def _fused(args, kw, trash_page, split: bool):
    """The paged-attention kernel under the active mesh.  With ``split``
    (the pool's K/V lanes sharded on 'model', ``PagePool.head_split``)
    each device takes its share of the KV heads: its queries, its lanes
    of the pool and, from the whole scale leaves, its heads' scales.
    Otherwise every device takes every head.

    Meshes with data axes wider than 1 are refused: they shard the page
    pool, and block tables hold global page ids, so no device could read
    its slots' pages alone."""
    mesh = get_mesh()
    if data_size(mesh) > 1:
        raise NotImplementedError(
            f"fused paged attention runs on meshes with data "
            f"size 1 only (this mesh: {dict(mesh.shape)}); the page pool "
            f"is sharded over the data axes and block tables hold global "
            f"page ids.  Use attn_backend='gather' on this mesh.")
    ops = [a for a in args if a is not None] + [kw["layer"]] + \
        ([] if kw["window"] is None else [kw["window"]])
    quant = args[3] is not None
    kv = args[0].shape[1]
    heads = "model" if split else None
    specs = [P(None, heads)] + [P(None, None, None, heads)] * 2 \
        + [P()] * (len(ops) - 3)

    def kern(q, k, v, *rest):
        ks, vs, rest = (rest[0], rest[1], rest[2:]) if quant \
            else (None, None, rest)
        table, kv_len, layer = rest[:3]
        head0 = jax.lax.axis_index("model") * q.shape[1] if split else 0
        return paged_attention(q, k, v, ks, vs, table, kv_len,
                               page=kw["page"], layer=layer,
                               window=rest[3] if len(rest) > 3 else None,
                               softcap=kw["softcap"], trash_page=trash_page,
                               head0=head0, scale_heads=kv)
    return run_kernel(kern, *ops, in_specs=specs,
                      out_specs=P(None, heads))


def identity_pool(cache: Dict, layer):
    """Layer ``layer`` of a contiguous (stack, B, T, ...) cache as a
    one-layer page pool read through an identity block table: each
    slot's (T, ...) row becomes T // page pages (no trash page).  The
    re-layout is a copy of the layer, so it runs under ``kv_slice``."""
    with jax.named_scope("kv_slice"):
        b, t = next(iter(cache.values())).shape[1:3]
        page = fit_block(min(128, t), t, 1)
        tails = {n: leaf.shape[3:] for n, leaf in cache.items()}
        leaves = {}
        for n, tail in tails.items():
            x = jax.lax.dynamic_index_in_dim(cache[n], layer, 0,
                                             keepdims=False)
            w = math.prod(tail)
            leaves[n] = to_rows(x.reshape(1, b * (t // page), page, w),
                                PageFormat(page, w))
        ident = jnp.arange(b * (t // page), dtype=jnp.int32).reshape(
            b, t // page)
        return PagePool(leaves, page, b * (t // page), tails), ident


def _mask_for(q_pos, kv_pos, causal, window, kv_len):
    """(B, S, T) boolean mask from position arrays (window may be traced)."""
    mask = jnp.ones((q_pos.shape[0], q_pos.shape[-1], kv_pos.shape[-1]), bool)
    if causal:
        mask &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if isinstance(window, (int, float)):
        if window > 0:
            mask &= kv_pos[:, None, :] > q_pos[:, :, None] - window
    else:
        wm = kv_pos[:, None, :] > q_pos[:, :, None] - window
        mask &= jnp.where(window > 0, wm, True)
    if kv_len is not None:
        mask &= kv_pos[:, None, :] < kv_len[:, None, None]
    return mask


def blockwise_attention_core(q, k, v, q_pos, kv_pos, *, causal=True,
                             window=0, attn_softcap=0.0, kv_len=None,
                             q_block: int = 512, kv_block: int = 1024,
                             sm_scale: Optional[float] = None
                             ) -> jnp.ndarray:
    """Flash-style memory-efficient attention: never materializes (S, T).

    Outer scan over query blocks, inner scan over KV blocks with running
    (max, denom, acc) — O(q_block * kv_block) live scores.  Differentiable
    (autodiff through the scans; layer-level remat bounds residuals).
    """
    b, s, h, dh = q.shape
    t, kv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kv
    q_block = min(q_block, s)
    kv_block = min(kv_block, t)
    assert s % q_block == 0 and t % kv_block == 0, (s, t, q_block, kv_block)
    nq, nk = s // q_block, t // kv_block

    qg = q.reshape(b, nq, q_block, kv, g, dh)
    qp = q_pos.reshape(b, nq, q_block)
    kb = k.reshape(b, nk, kv_block, kv, dh)
    vb = v.reshape(b, nk, kv_block, kv, dv)
    kp = kv_pos.reshape(b, nk, kv_block)
    scale = 1.0 / jnp.sqrt(dh) if sm_scale is None else sm_scale

    def q_step(_, q_in):
        qi, qpi = q_in                       # (b,qb,kv,g,dh), (b,qb)

        def kv_step(carry, kv_in):
            m, l, acc = carry
            ki, vi, kpi = kv_in              # (b,kb,kv,dh), ..., (b,kb)
            sc = jnp.einsum("bqkgd,btkd->bkgqt", qi, ki,
                            preferred_element_type=jnp.float32) * scale
            if attn_softcap:
                sc = _softcap(sc, attn_softcap)
            msk = _mask_for(qpi, kpi, causal, window, kv_len)
            sc = jnp.where(msk[:, None, None, :, :], sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(vi.dtype), vi)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, kv, g, q_block, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step),
            (m0, l0, a0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
             jnp.moveaxis(kp, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = jnp.moveaxis(out, 3, 1)        # (b, qb, kv, g, dh)
        return None, out

    _, outs = jax.lax.scan(
        q_step, None,
        (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(qp, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1)           # (b, nq, qb, kv, g, dv)
    return out.reshape(b, s, h, dv).astype(q.dtype)


def attention_core(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   q_pos: jnp.ndarray, kv_pos: jnp.ndarray,
                   *, causal: bool = True, window: int = 0,
                   attn_softcap: float = 0.0,
                   kv_len: Optional[jnp.ndarray] = None,
                   sm_scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B,S,H,dh), k: (B,T,KV,dh), v: (B,T,KV,dv), positions:
    (B,S)/(B,T) -> (B,S,H,dv).

    ``window > 0`` restricts attention to the last ``window`` positions
    (Gemma-2 local layers); ``kv_len`` masks cache tails during decode;
    ``sm_scale`` replaces the softmax scale ``dh**-0.5``.  Large (S x T)
    problems dispatch to the blockwise flash-style core.
    """
    qb, kb = ATTN_OPTS["q_block"], ATTN_OPTS["kv_block"]
    if q.shape[1] * k.shape[1] > ATTN_OPTS["min_elems"] and \
            q.shape[1] % qb == 0 and k.shape[1] % kb == 0:
        return blockwise_attention_core(
            q, k, v, q_pos, kv_pos, causal=causal, window=window,
            attn_softcap=attn_softcap, kv_len=kv_len,
            q_block=qb, kv_block=kb, sm_scale=sm_scale)
    b, s, h, dh = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    qg = q.reshape(b, s, kv, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    if sm_scale is None:
        scores = scores / jnp.sqrt(dh).astype(scores.dtype)
    else:
        scores = scores * sm_scale
    if attn_softcap:
        scores = _softcap(scores, attn_softcap)
    mask = _mask_for(q_pos, kv_pos, causal, window, kv_len)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(b, s, h, v.shape[-1])


def attn_forward(p: Dict, x: jnp.ndarray, positions: jnp.ndarray, *,
                 n_heads: int, n_kv: int, d_head: int, rope_theta: float,
                 causal: bool = True, window: int = 0,
                 attn_softcap: float = 0.0, mrope: bool = False,
                 x_kv: Optional[jnp.ndarray] = None,
                 kv_positions: Optional[jnp.ndarray] = None,
                 cache: Optional[Dict] = None,
                 cache_index: Optional[jnp.ndarray] = None,
                 layer=0) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full attention sub-layer: projections + rope + core + output proj.

    With ``cache`` (a stacked cache; ``layer`` its layer for this
    sub-layer) given, appends K/V at ``cache_index`` and attends over
    the cache (decode / incremental prefill); the stacked cache comes
    back with only this layer's new tokens written.  ``x_kv`` enables
    cross-attention.
    """
    with jax.named_scope("attn_in"):
        q, k, v, q_pos, kv_pos = _project(
            p, x, x_kv, positions, kv_positions, n_heads=n_heads, n_kv=n_kv,
            d_head=d_head, rope_theta=rope_theta, mrope=mrope)

    new_cache, kv_len, out = None, None, None
    if cache is not None:
        with jax.named_scope("kv_write"):
            new_cache = _write_kv(cache, k, v, cache_index,
                                  cache_bits(cache), layer)
    with jax.named_scope("attn_core"):
        if cache is not None:
            kv_len = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32)
                                      + x.shape[1], (x.shape[0],))
            # fused / ref decode attention reads the cache *in its stored
            # representation* (one query token, causal self-attention
            # only); every other shape keeps the gather read side.
            pa = current_paged_attn_backend()
            if pa != "gather" and x.shape[1] == 1 and x_kv is None \
                    and causal:
                out = _decode_read(q, new_cache, kv_len, window,
                                   attn_softcap, pa, layer)
            else:
                k, v = _gather_read(new_cache, q.dtype, layer)
                kv_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None, :],
                                          (x.shape[0], k.shape[1]))
        if out is None:
            out = attention_core(q, k, v, q_pos, kv_pos,
                                 causal=causal and x_kv is None,
                                 window=window, attn_softcap=attn_softcap,
                                 kv_len=kv_len)
        out = out.reshape(*x.shape[:-1], n_heads * d_head)
    with jax.named_scope("attn_out"):
        return qmatmul(out, p["wo"]), new_cache


def _project(p: Dict, x, x_kv, positions, kv_positions, *, n_heads: int,
             n_kv: int, d_head: int, rope_theta: float, mrope: bool):
    """q/k/v projections and rope; returns (q, k, v, q_pos, kv_pos)."""
    xk_src = x_kv if x_kv is not None else x
    q = qmatmul(x, p["wq"])
    k = qmatmul(xk_src, p["wk"])
    v = qmatmul(xk_src, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = _split_heads(q, n_heads, d_head)
    k = _split_heads(k, n_kv, d_head)
    v = _split_heads(v, n_kv, d_head)
    q = constraint(q, "batch", None, "heads", None)
    k = constraint(k, "batch", None, "kv_heads", None)

    if kv_positions is None:
        kv_positions = positions
    if x_kv is not None:  # rope only for self-attention
        return q, k, v, positions, kv_positions
    if mrope:
        ang_q = mrope_angles(positions, d_head, rope_theta)
        ang_k = mrope_angles(kv_positions, d_head, rope_theta)
        q_pos = positions[..., 0]
        kv_pos = kv_positions[..., 0]
    else:
        ang_q = rope_angles(positions, d_head, rope_theta)
        ang_k = rope_angles(kv_positions, d_head, rope_theta)
        q_pos, kv_pos = positions, kv_positions
    return apply_rope(q, ang_q), apply_rope(k, ang_k), v, q_pos, kv_pos


def _write_kv(cache: Dict, k, v, idx, bits: int, layer) -> Dict:
    """The stacked cache with layer ``layer``'s new K/V written at fill
    level ``idx``.

    A quantized-at-rest cache (int8 / packed int4 with per-token/head
    dynamic scales) rounds each written position exactly once; reads
    dequantize in-graph, so HBM traffic drops 2x/4x at ~3% metadata
    overhead without compounding rounding error.  A paged cache
    scatters the new tokens into their slots' pool pages of that layer
    (``kv_pages.write_tokens``), in place under donation."""
    kq, vq = k, v
    scales = {}
    if bits < 32:
        kq, scales["k_scale"] = quantize_kv(k, bits)
        vq, scales["v_scale"] = quantize_kv(v, bits)
    new = {"k": kq, "v": vq, **scales}
    if "table" in cache:
        return write_pages(cache, new, idx, k.shape[1], layer)
    return dict(cache, **{n: _cache_write(cache[n], u, idx, layer)
                          for n, u in new.items()})


def write_pages(cache: Dict, new: Dict, idx, seq: int, layer) -> Dict:
    """The paged cache with ``new`` (leaf name -> (B, ``seq``, ...)
    values) written at fill level ``idx`` of layer ``layer``.  The pool
    is held to its placement (``constrain_pool``) before and after the
    write, so a sharded program's layer loop carries it as placed."""
    pool = constrain_pool(cache["pages"])
    pids, offs = page_coords(cache["table"][layer], idx, seq, pool.page)
    return dict(cache, pages=constrain_pool(pool.replace(**{
        n: write_tokens(pool[n], pool.fmt(n), layer, pids, offs, u)
        for n, u in new.items()})))


def _decode_read(q, cache: Dict, kv_len, window, attn_softcap: float,
                 backend: str, layer=0):
    """One decode token's attention straight over layer ``layer`` of the
    stored cache.  The contiguous cache goes through the same kernel as
    an identity-table pool of its layer (:func:`identity_pool`)."""
    if "table" in cache:
        return paged_attn(q, cache["pages"], cache["table"][layer], kv_len,
                          layer=layer, window=window,
                          attn_softcap=attn_softcap, backend=backend)
    pool, ident = identity_pool(cache, layer)
    return paged_attn(q, pool, ident, kv_len, window=window,
                      attn_softcap=attn_softcap, trash_page=False,
                      backend=backend)


def _gather_read(cache: Dict, dtype, layer=0):
    """Each slot's contiguous (B, T, KV, dh) K and V of layer ``layer``
    in ``dtype``; a paged cache gathers the slot's pages in token order
    (one gather a leaf, ``kv_pages.read_pages``), so attention (and
    therefore decoding) is bit-identical to the contiguous cache."""
    if "table" in cache:
        pool, table = cache["pages"], cache["table"][layer]
        store = {n: read_pages(leaf, pool.fmt(n), layer, table).reshape(
                     table.shape[0], -1, *pool.tails[n])
                 for n, leaf in pool.items()}
    else:
        with jax.named_scope("kv_slice"):
            store = {n: jax.lax.dynamic_index_in_dim(leaf, layer, 0,
                                                     keepdims=False)
                     for n, leaf in cache.items()}
    if "k_scale" in store:
        return (dequantize_kv(store["k"], store["k_scale"], dtype),
                dequantize_kv(store["v"], store["v_scale"], dtype))
    return store["k"], store["v"]
