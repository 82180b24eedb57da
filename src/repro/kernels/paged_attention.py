"""Pallas TPU kernel: fused paged decode attention over a quantized page
pool (the serving hot path).

The gather fallback in ``models.attention`` pays O(max_len) per decode
step twice: ``paged_gather`` materializes a contiguous (B, T, ...) int
view of every slot's pages, then ``dequantize_kv`` materializes the f32
K/V tree — before a single score is computed.  This kernel walks each
slot's block table itself: scalar-prefetched table entries drive the
page index maps, so only the pages a row has filled land in VMEM, are
dequantized there (int8 / nibble-packed int4 with per-token f32 scales)
and feed the flash-attention running (max, denom, acc) accumulation.
Neither the contiguous KV view nor the f32 KV tree ever exists.

The walk: a row's work scales with the pages it has filled, not with
``max_len``.

* Per-row bound.  The wrapper computes each row's end: its fill level
  ``kv_len``, cut, for a real paged pool, to the live prefix of its
  table (page 0 is the reserved trash page and live blocks are a
  contiguous prefix, contract PA2/PA3).  The contiguous cache's identity
  view (``trash_page=False``) holds data in page 0 and is bounded by
  ``kv_len`` alone.  Positions at or past the end, or before a sliding
  window's start, are masked.
* Pages per step.  A grid step covers a block of ``ppb`` pages, about
  128 tokens (8 pages of 16; 1 of the contiguous view's pages of 128):
  each of the ``ppb`` pages is its own operand (the pool passed ``ppb``
  times), so the Pallas pipeline copies the next step's pages while the
  current ones are computed.  Table entries past the table's end (``ppb``
  need not divide ``nb``) are clamped to the last entry; their positions
  are masked.  Mosaic refuses a hand-issued copy out of a pool whose
  minor dim is not a multiple of 128 (phi3's head dim of 96, and the
  (page, KV) scales), so the pipeline issues the copies.
* Only live steps.  The grid is (KV head tiles, steps), the number of
  steps a traced value: the sum of the rows' block counts.  Steps run
  row by row, blocks in order within a row, each step's (row, block)
  read from scalar-prefetched lists, so no step lies past a row's end
  and a parked row (an all-trash table) has none; its output is zeros.
* Compute form, chosen by the group size ``g`` (query heads per KV
  head).  ``g == 1``: each token's score is a VPU multiply-and-reduce
  over the head dim of its own (KV, dh) tile, so no page is re-laid
  out; the scores of 16 tokens gather into one (KV, 16) tile, on which
  the scales and the softmax act, and each token's weight then scales
  its value tile.  A per-token scale multiplies the token's score and
  weight: the dequantization, moved past the linear head-dim sum.
  ``g > 1``: batched MXU dots over the KV heads, a grouped query filling
  the matrix unit's rows.

The queries, the scores and the accumulation are f32.

``interpret=None`` auto-selects interpret mode off-TPU (pallas_utils),
so CPU tests and CI exercise the same program.  Compiled for the TPU
(tests/test_tpu_compile.py, phi3 widths: 32 KV heads of 96, int8 and
int4 pages of 16 tokens), each step takes all KV heads, because the
(page, KV) scale tile's minor dim must be the whole head axis; a head
dim that is not a multiple of 128 (96, or 48 packed) is fine as the full
minor dim of a page.  The wrapper never pads the pool leaves (a pad
would be the per-step O(pool) copy this kernel exists to delete).
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
BLOCK_TOKENS = 128          # tokens a grid step covers (whole pages)
CHUNK_TOKENS = 16           # tokens one softmax update takes


def _kv_parts(raw, scale, bits: int):
    """(..., bkv, dh_s) stored tokens -> f32 head-dim parts, each
    scaled by its (..., bkv) per-token scale when one is given.

    int8 / f32 tokens are one part (..., bkv, dh).  A nibble-packed int4
    token holds even head positions in its low nibbles and odd ones in
    its high nibbles (``core.quantize.pack_int4``); it unpacks to those
    two (..., bkv, dh//2) parts, not to the interleaved head dim, which the
    TPU cannot re-lay cheaply.  The queries and the output are split the
    same way outside the kernel."""
    if bits == 4:
        x = raw.astype(jnp.int32)            # no 8-bit shifts in Mosaic
        parts = [(x << 28) >> 28, (x << 24) >> 28]   # sign-extended nibbles
    else:
        parts = [raw]
    parts = [x.astype(jnp.float32) for x in parts]
    if scale is not None:
        sc = scale.astype(jnp.float32)[..., None]
        parts = [x * sc for x in parts]
    return parts


def _softmax_step(s, valid, m, l, sm_scale: float, softcap: float):
    """Online-softmax update over the last (token) axis of raw scores:
    (weights, new max, new denominator, rescale of the old terms)."""
    s = s * sm_scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    return p, m_new, l * corr + jnp.sum(p, axis=-1, keepdims=True), corr


def _vpu_chunk(q, k, ksc, v, vsc, valid, m, l, acc, bits, sm_scale,
               softcap):
    """``t`` tokens for g == 1, on the VPU.  q parts (bkv, dhp); k/v the
    stored tokens (t, bkv, dh_s); their scales (t, bkv) or None; valid
    (1, t); running m, l (bkv, 1) and acc parts (bkv, dhp).

    Each token's score is a multiply-and-reduce over the head dim of its
    own (bkv, dh) tile; the scores gather into one (bkv, t) tile, heads
    by tokens, where the K scales (transposed once), the softmax and the
    V scales act on a few vregs.  The V scales fold into the weights,
    which then scale each token's tile (the dequantization, moved past
    the linear head-dim sum and token sum)."""
    t = k.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q[0].shape[0], t), 1)
    s = jnp.zeros(lane.shape, jnp.float32)
    for i in range(t):
        col = sum(jnp.sum(kp * qp, axis=-1, keepdims=True)
                  for kp, qp in zip(_kv_parts(k[i], None, bits), q))
        s = jnp.where(lane == i, col, s)
    if ksc is not None:
        s = s * ksc.T
    p, m, l, corr = _softmax_step(s, valid, m, l, sm_scale, softcap)
    if vsc is not None:
        p = p * vsc.T
    acc = [a * corr for a in acc]
    for i in range(t):
        w = jnp.sum(jnp.where(lane == i, p, 0.0), axis=-1, keepdims=True)
        acc = [a + w * vp for a, vp in zip(acc, _kv_parts(v[i], None, bits))]
    return m, l, acc


def _mxu_chunk(q, k, ksc, v, vsc, valid, m, l, acc, bits, sm_scale,
               softcap):
    """``t`` tokens for g > 1, on the MXU.  q parts (bkv, g, dhp); k/v
    (t, bkv, dh_s) and scales as for ``_vpu_chunk``; valid (1, 1, t);
    running m, l (bkv, g, 1) and acc parts (bkv, g, dhp)."""
    s = sum(jax.lax.dot_general(
        qp, kp, dimension_numbers=(((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
        for kp, qp in zip(_kv_parts(k, ksc, bits), q))    # (bkv, g, t)
    p, m, l, corr = _softmax_step(s, valid, m, l, sm_scale, softcap)
    acc = [a * corr + jax.lax.dot_general(
        p, vp, dimension_numbers=(((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
        for a, vp in zip(acc, _kv_parts(v, vsc, bits))]
    return m, l, acc


def _decode_kernel(rows_ref, blks_ref, nblk_ref, end_ref, len_ref,
                   win_ref, table_ref,                     # scalar prefetch
                   q_ref, *rest, bits: int, page: int, ppb: int,
                   softcap: float, sm_scale: float):
    n_leaves = 4 if bits < 32 else 2
    pages = [rest[i * ppb:(i + 1) * ppb] for i in range(n_leaves)]
    k_refs, v_refs = pages[0], pages[n_leaves // 2]
    ks_refs, vs_refs = (pages[1], pages[3]) if bits < 32 else \
        ([None] * ppb, [None] * ppb)
    o_ref, m_ref, l_ref = rest[n_leaves * ppb:]
    parts = q_ref.shape[1]
    vpu = q_ref.ndim == 4                    # g == 1: (B, parts, KV, dhp)
    chunk = _vpu_chunk if vpu else _mxu_chunk
    tc = math.gcd(page, CHUNK_TOKENS)
    step = pl.program_id(1)
    b, blk = rows_ref[step], blks_ref[step]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    end = end_ref[b]
    w = win_ref[0]
    lo = jnp.where(w > 0, len_ref[b] - w, 0)         # sliding-window start
    q = [q_ref[0, p] for p in range(parts)]
    m, l = m_ref[...], l_ref[...]
    acc = [o_ref[0, p] for p in range(parts)]
    shape = (1, tc) if vpu else (1, 1, tc)
    for j in range(ppb):
        for c0 in range(0, page, tc):
            tok = pl.ds(c0, tc)
            sc = [None if r is None else r[0, tok]
                  for r in (ks_refs[j], vs_refs[j])]
            pos = (blk * ppb + j) * page + c0 + jax.lax.broadcasted_iota(
                jnp.int32, shape, len(shape) - 1)
            m, l, acc = chunk(q, k_refs[j][0, tok], sc[0], v_refs[j][0, tok],
                              sc[1], (pos < end) & (pos >= lo), m, l, acc,
                              bits, sm_scale, softcap)
    m_ref[...] = m
    l_ref[...] = l
    for p in range(parts):
        o_ref[0, p] = acc[p]

    @pl.when(blk == nblk_ref[b] - 1)
    def _finish():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-30)


def _schedule(table, kv_len, page: int, ppb: int, trash_page: bool):
    """The walk: every row's end position and block count, and the grid
    steps as (row, block) pairs, rows in order, blocks in order within a
    row.  Returns (steps, rows, blks, nblk, end); ``rows``/``blks`` are
    padded to the most steps any call can take."""
    b, nb = table.shape
    end = kv_len
    if trash_page:
        live = jnp.sum(jnp.cumprod(table != 0, axis=1), axis=1)
        end = jnp.minimum(end, live.astype(jnp.int32) * page)
    nblk = (-(-end // (ppb * page))).astype(jnp.int32)
    stop = jnp.cumsum(nblk)
    step = jnp.arange(b * -(-nb // ppb), dtype=jnp.int32)
    rows = jnp.minimum(jnp.sum(step[:, None] >= stop[None, :], axis=1),
                       b - 1).astype(jnp.int32)
    blks = step - (stop - nblk)[rows]
    return stop[-1], rows, blks, nblk, end


@functools.partial(jax.jit, static_argnames=("softcap", "block_kv",
                                             "trash_page", "interpret"))
def paged_attention(q, k_pages, v_pages, k_scale, v_scale, table, kv_len,
                    *, window=None, softcap: float = 0.0,
                    block_kv: int | None = None, trash_page: bool = True,
                    interpret: bool | None = None):
    """Decode attention straight over a (quantized) page pool.

    q:        (B, KV, G, dh) grouped queries (one decode token per slot).
    k/v:      (P, page, KV, dh) int8 or f32, or (P, page, KV, dh//2)
              uint8 nibble pairs (``core.quantize.pack_int4`` layout).
    k/v_scale: (P, page, KV) f32 per-token/head scales (None when f32).
    table:    (B, nb) int32 block table.  With ``trash_page`` page 0 is
              the reserved trash page and live blocks are a contiguous
              per-row prefix (PA2/PA3); a row attends only its live
              prefix (an all-trash row returns zeros).  Without it every
              entry is real (the contiguous cache's identity view).
    kv_len:   (B,) int32 fill levels; position ``kv_len - 1`` is the
              decode token itself, so causality == the fill mask.
    window:   optional ()-shaped int (or Python int): > 0 restricts
              attention to the last ``window`` positions.

    Returns (B, KV, G, dh) f32.  ``block_kv`` tiles KV heads per grid
    step (largest divisor of KV <= block_kv is used; None = all heads).
    Compiled for the TPU, the scale tile (page, block_kv) must be legal
    under the (8, 128) tiling rule: ``page`` a multiple of 8 and
    ``block_kv`` all KV heads.
    """
    interpret = resolve_interpret(interpret)
    b, kv, g, dh = q.shape
    page = k_pages.shape[1]
    nb = table.shape[1]
    bits = {jnp.dtype(jnp.int8): 8, jnp.dtype(jnp.uint8): 4}.get(
        jnp.dtype(k_pages.dtype), 32)
    dh_s = k_pages.shape[-1]
    if bits == 4 and dh_s * 2 != dh:
        raise ValueError(f"int4 pool head dim {dh_s}*2 != query dh {dh}")
    if bits != 4 and dh_s != dh:
        raise ValueError(f"pool head dim {dh_s} != query dh {dh}")
    quantized = bits < 32

    table = jnp.asarray(table, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(b)
    win = jnp.asarray(0 if window is None else window,
                      jnp.int32).reshape(1)
    block_kv = kv if block_kv is None else fit_block(min(block_kv, kv), kv, 1)
    ppb = max(1, min(nb, BLOCK_TOKENS // page))
    steps, rows, blks, nblk, end = _schedule(table, kv_len, page, ppb,
                                             trash_page)
    # head-dim parts: int4 pages unpack to (even, odd) halves, so split q
    # the same way — (B, parts, KV, G, dh // parts); g == 1 drops G
    parts = 2 if bits == 4 else 1
    dhp = dh // parts
    q = jnp.moveaxis(q.astype(jnp.float32).reshape(b, kv, g, dhp, parts),
                     -1, 1)
    if g == 1:
        q = q.reshape(b, parts, kv, dhp)
    tail = (0,) * (q.ndim - 3)

    def at_qo(hi, si, row, *_):
        return (row[si], 0, hi) + tail

    def page_at(j, *minor):
        def at(hi, si, row, blk, n, end, ln, wn, tab):
            return (tab[row[si], jnp.minimum(blk[si] * ppb + j, nb - 1)],
                    0, hi) + minor
        return at

    qo_spec = pl.BlockSpec((1, parts, block_kv) + q.shape[3:], at_qo)
    kv_specs = [pl.BlockSpec((1, page, block_kv, dh_s), page_at(j, 0))
                for j in range(ppb)]
    sc_specs = [pl.BlockSpec((1, page, block_kv), page_at(j))
                for j in range(ppb)]
    leaves = ([k_pages, k_scale, v_pages, v_scale] if quantized
              else [k_pages, v_pages])
    specs = ([kv_specs, sc_specs] * 2) if quantized else [kv_specs] * 2
    stat = (block_kv, 1) if g == 1 else (block_kv, g, 1)    # running m, l
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(kv // block_kv, steps),
        in_specs=[qo_spec] + [s for ss in specs for s in ss],
        out_specs=qo_spec,
        scratch_shapes=[pltpu.VMEM(stat, jnp.float32),
                        pltpu.VMEM(stat, jnp.float32)])
    kern = functools.partial(_decode_kernel, bits=bits, page=page, ppb=ppb,
                             softcap=float(softcap),
                             sm_scale=1.0 / math.sqrt(dh))
    operands = (rows, blks, nblk, end, kv_len, win, table, q) \
        + tuple(x for x in leaves for _ in range(ppb))
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret, name="paged_attention")(*operands)
    # a row with no blocks is never visited: its output is zeros
    out = jnp.where((nblk > 0).reshape((b,) + (1,) * (q.ndim - 1)),
                    out, 0.0)
    out = out.reshape(b, parts, kv, g, dhp)
    return jnp.moveaxis(out, 1, -1).reshape(b, kv, g, dh)
