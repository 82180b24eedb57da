"""Pallas TPU kernel: fused paged decode attention over a quantized page
pool (the serving hot path).

The gather fallback in ``models.attention`` pays O(max_len) per decode
step twice: ``paged_gather`` materializes a contiguous (B, T, ...) int
view of every slot's pages, then ``dequantize_kv`` materializes the f32
K/V tree — before a single score is computed.  This kernel walks each
slot's block table *inside the grid* instead: scalar-prefetched table
entries drive the page index maps, so exactly one pool page per grid
step lands in VMEM, is dequantized there (int8 / nibble-packed int4 with
per-token scales), and feeds the flash-attention running (max, denom,
acc) accumulation.  Neither the contiguous KV view nor the f32 KV tree
ever exists; HBM traffic per step is the *quantized* bytes of the pages
a slot actually fills.

Grid: (B, KV // block_kv, nb).  The last axis iterates a slot's blocks
in order, revisiting the output block with running rescaling; positions
at or past the slot's fill level are masked, which is also what keeps
the reserved trash page (page 0 — where unallocated table entries point)
inert.  GQA queries arrive pre-grouped as (B, KV, G, dh).  ``block_kv``
(KV heads per grid cell) defaults to all heads.

``interpret=None`` auto-selects interpret mode off-TPU (pallas_utils),
so CPU tests and CI exercise the same program.  Compiled for the TPU
(tests/test_tpu_compile.py, phi3 widths: 32 KV heads of 96, int8 and
int4 pages of 16 tokens), each grid cell takes all KV heads, because the
(page, KV) scale tile's minor dim must be the whole head axis; a head
dim that is not a multiple of 128 (96, or 48 packed) is fine as the full
minor dim of the page tile.  The wrapper never pads the pool leaves (a
pad would be the per-step O(pool) copy this kernel exists to delete).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_utils import fit_block, resolve_interpret

NEG_INF = -2.0e38


def _kv_parts(raw, scale_ref, bits: int):
    """(page, bkv, dh_s) stored page -> f32 head-dim parts, in VMEM.

    int8 / f32 pages are one part (page, bkv, dh).  A nibble-packed int4
    page holds even head positions in its low nibbles and odd ones in its
    high nibbles (``core.quantize.pack_int4``); it unpacks to those two
    (page, bkv, dh//2) parts, not to the interleaved head dim, which the
    TPU cannot re-lay cheaply.  The queries and the output are split the
    same way outside the kernel."""
    if bits == 4:
        x = raw.astype(jnp.int32)            # no 8-bit shifts in Mosaic
        parts = [(x << 28) >> 28, (x << 24) >> 28]   # sign-extended nibbles
    else:
        parts = [raw]
    parts = [x.astype(jnp.float32) for x in parts]
    if scale_ref is not None:
        sc = scale_ref[0].astype(jnp.float32)[..., None]
        parts = [x * sc for x in parts]
    return parts


def _decode_kernel(table_ref, len_ref, win_ref,      # scalar prefetch
                   q_ref, *rest, bits: int, page: int, softcap: float,
                   sm_scale: float):
    quantized = bits < 32
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref = rest
    else:
        k_ref, v_ref, o_ref, m_ref, l_ref = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    ks = _kv_parts(k_ref[0], ks_ref, bits)           # [(page, bkv, dhp)]
    s = 0.0
    for i, k in enumerate(ks):
        # batched over the kv-head tile: (bkv, g, dhp) x (page, bkv, dhp)
        s = s + jax.lax.dot_general(
            q_ref[0, i].astype(jnp.float32), k,
            dimension_numbers=(((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)      # (bkv, g, page)
    s = s * sm_scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    ln = len_ref[b]
    w = win_ref[0]
    first = jnp.where(w > 0, ln - w, 0)              # sliding-window start
    s = jnp.where((pos < ln) & (pos >= first), s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]          # (bkv, g)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])                # (bkv, g, page)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
    for i, v in enumerate(_kv_parts(v_ref[0], vs_ref, bits)):
        pv = jax.lax.dot_general(
            p, v, dimension_numbers=(((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)      # (bkv, g, dhp)
        o_ref[0, i] = o_ref[0, i] * corr[..., None] + pv

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-30)[..., None]


@functools.partial(jax.jit, static_argnames=("softcap", "block_kv",
                                             "interpret"))
def paged_attention(q, k_pages, v_pages, k_scale, v_scale, table, kv_len,
                    *, window=None, softcap: float = 0.0,
                    block_kv: int | None = None,
                    interpret: bool | None = None):
    """Decode attention straight over a (quantized) page pool.

    q:        (B, KV, G, dh) grouped queries (one decode token per slot).
    k/v:      (P, page, KV, dh) int8 or f32, or (P, page, KV, dh//2)
              uint8 nibble pairs (``core.quantize.pack_int4`` layout).
    k/v_scale: (P, page, KV) f32 per-token/head scales (None when f32).
    table:    (B, nb) int32 block table; page 0 is the reserved trash
              page, live blocks are a contiguous per-row prefix (PA2).
    kv_len:   (B,) int32 fill levels; position ``kv_len - 1`` is the
              decode token itself, so causality == the fill mask.
    window:   optional ()-shaped int (or Python int): > 0 restricts
              attention to the last ``window`` positions.

    Returns (B, KV, G, dh) f32.  ``block_kv`` tiles KV heads per grid
    cell (largest divisor of KV <= block_kv is used; None = all heads).
    Compiled for the TPU, the scale tile (page, block_kv) must be legal
    under the (8, 128) tiling rule: ``page`` a multiple of 8 and
    ``block_kv`` all KV heads.
    """
    interpret = resolve_interpret(interpret)
    b, kv, g, dh = q.shape
    p_pages, page = k_pages.shape[0], k_pages.shape[1]
    nb = table.shape[1]
    bits = {jnp.dtype(jnp.int8): 8, jnp.dtype(jnp.uint8): 4}.get(
        jnp.dtype(k_pages.dtype), 32)
    dh_s = k_pages.shape[-1]
    if bits == 4 and dh_s * 2 != dh:
        raise ValueError(f"int4 pool head dim {dh_s}*2 != query dh {dh}")
    if bits != 4 and dh_s != dh:
        raise ValueError(f"pool head dim {dh_s} != query dh {dh}")
    quantized = bits < 32

    q = q.astype(jnp.float32)
    table = jnp.asarray(table, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(b)
    win = jnp.asarray(0 if window is None else window,
                      jnp.int32).reshape(1)
    block_kv = kv if block_kv is None else fit_block(min(block_kv, kv), kv, 1)
    grid = (b, kv // block_kv, nb)
    # head-dim parts: int4 pages unpack to (even, odd) halves, so split q
    # the same way — (B, parts, KV, G, dh // parts)
    parts = 2 if bits == 4 else 1
    q = jnp.moveaxis(q.reshape(b, kv, g, dh // parts, parts), -1, 1)

    def at_qo(bi, hi, ji, tab, ln, wn):
        return (bi, 0, hi, 0, 0)

    def at_page(bi, hi, ji, tab, ln, wn):
        return (tab[bi, ji], 0, hi, 0)

    def at_scale(bi, hi, ji, tab, ln, wn):
        return (tab[bi, ji], 0, hi)

    qo_spec = pl.BlockSpec((1, parts, block_kv, g, dh // parts), at_qo)
    kv_spec = pl.BlockSpec((1, page, block_kv, dh_s), at_page)
    sc_spec = pl.BlockSpec((1, page, block_kv), at_scale)
    in_specs = [qo_spec, kv_spec] + ([sc_spec] if quantized else []) \
        + [kv_spec] + ([sc_spec] if quantized else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=qo_spec,
        scratch_shapes=[pltpu.VMEM((block_kv, g), jnp.float32),
                        pltpu.VMEM((block_kv, g), jnp.float32)])
    kern = functools.partial(_decode_kernel, bits=bits, page=page,
                             softcap=float(softcap),
                             sm_scale=1.0 / math.sqrt(dh))
    operands = (table, kv_len, win, q, k_pages) \
        + ((k_scale,) if quantized else ()) + (v_pages,) \
        + ((v_scale,) if quantized else ())
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret, name="paged_attention")(*operands)
    return jnp.moveaxis(out, 1, -1).reshape(b, kv, g, dh)
