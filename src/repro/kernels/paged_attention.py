"""Pallas TPU kernel: fused paged decode attention over a quantized page
pool (the serving hot path).

The gather fallback in ``models.attention`` pays O(max_len) per decode
step twice: it materializes a contiguous (B, T, ...) int view of every
slot's pages, then the f32 K/V tree — before a single score is computed.
This kernel walks each slot's block table itself: scalar-prefetched
table entries drive the page index maps, so only the pages a row has
filled land in VMEM, are dequantized there (int8 / nibble-packed int4
with per-token f32 scales) and feed the flash-attention running (max,
denom, acc) accumulation.

The pool is read where it lies.  Its leaves are the whole layer stack in
the lane-dense storage of ``kernels.kv_pages`` — (stack, blocks, rows,
lanes), each token's KV x dh values flattened head-major into its slot —
and the layer index is one more scalar-prefetched operand, so a page's
``BlockSpec`` index is (layer, page block, 0, 0) and no layer is ever
sliced out of the stack.  On a tensor-parallel mesh each device holds
its KV heads' lanes of every row (``PagePool.head_split``) and runs the
kernel on them, picking its heads' scales from the whole scale leaves
by the first head's index, prefetched with the layer's.

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
  128 tokens (8 pages of 16): each page is its own operand (the pool
  passed ``ppb`` times), so the Pallas pipeline copies the next step's
  pages while the current ones are computed.  Table entries past the
  table's end are clamped to the last entry; their positions are masked.
* Only live steps.  The grid is (KV head tiles, steps), the number of
  steps a traced value: the sum of the rows' block counts.  Steps run
  row by row, blocks in order within a row, each step's (row, block)
  read from scalar-prefetched lists; a parked row (an all-trash table)
  has none, and its output is zeros.
* Compute, on the page's lane-dense tile.  A token's row holds every KV
  head's values side by side, so the head sums are MXU dots against a
  block-diagonal query: row (h, g) of ``qb`` holds query head (h, g) at
  the lanes of KV head h and zeros elsewhere, and ``qb . K^T`` gives
  every (head, token) score at once.  Values come back the same way,
  ``p . V``, each query head's output at its KV head's lanes (the
  wrapper keeps those).  The per-token scales, stored token-major too,
  are moved to (head, token) order by two 0/1 selection dots.  Where a
  row holds several tokens (narrow configs), a selection dot first
  moves the step's tokens to one a row, each in its own slot of lanes
  (``token_slots``), and the query is tiled over the slots.

The scores and the accumulation are f32, every dot at full f32
precision, but for bf16 queries against an int8 / int4 pool, whose
products one bf16 MXU pass already gives exactly.

``interpret=None`` auto-selects interpret mode off-TPU (pallas_utils),
so CPU tests and CI exercise the same program.  Compiled for the TPU
(tests/test_tpu_compile.py, phi3 widths: 32 KV heads of 96, int8 and
int4 pages of 16 tokens).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_pages import PageFormat
from .pallas_utils import fit_block, resolve_interpret

NEG_INF = -2.0e38
BLOCK_TOKENS = 128          # tokens a grid step covers (whole pages)
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, precision=_HI):
    """(M, K) x (K, N) -> (M, N) in f32, at full precision by default."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b, precision=_HI):
    """(M, K) x (N, K) -> (M, N) in f32, at full precision by default."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _kv_parts(raw, bits: int):
    """Stored values -> f32 parts.  A nibble-packed int4 byte holds an
    even head position in its low nibble and the odd one above it
    (``core.quantize.pack_int4``); it unpacks to those two parts, the
    queries and the output split the same way outside the kernel."""
    if bits == 4:
        x = raw.astype(jnp.int32)            # no 8-bit shifts in Mosaic
        parts = [(x << 28) >> 28, (x << 24) >> 28]   # sign-extended nibbles
    else:
        parts = [raw]
    return [x.astype(jnp.float32) for x in parts]


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


def token_slots(blocks, fmt: PageFormat, first):
    """Each token's slot of a lane-dense leaf, one token a row.

    ``blocks``: the (n_blocks * rows, lanes) f32 rows of consecutive
    fetched blocks; token i (of ``n = n_blocks * page``) lies in block
    ``i // page`` at token ``i % page + first[i // page]`` of the block,
    ``first`` being the page's offset in its block, ``(pid % group) *
    page``.  Returns (n, lanes): row i is the stored row holding token
    i (a 0/1 selection dot, exact at full precision), zero outside the
    token's slot."""
    n_rows = blocks.shape[0]
    n = len(first) * fmt.page
    tok = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    u = tok % fmt.page
    for j, f in enumerate(first):
        u = jnp.where(tok // fmt.page == j, u + f, u)
    row = (tok // fmt.page) * fmt.rows + u // fmt.per_row
    pick = jax.lax.broadcasted_iota(jnp.int32, (n, n_rows), 1) == row
    y = _dot(pick.astype(jnp.float32), blocks)
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    lo = (u % fmt.per_row) * fmt.slot
    return jnp.where((lane >= lo) & (lane < lo + fmt.slot), y, 0.0)


def token_values(blocks, fmt: PageFormat, first, sel_t, precision=_HI):
    """(R, n) = ``sel_t`` (R, lanes) dotted with each token's slot
    (:func:`token_slots`): with ``sel_t`` a 0/1 lane selection, the
    tokens' stored values in (row, token) order; with a query tiled over
    the slots of a row, its scores (at ``precision``)."""
    return _dot_nt(sel_t, token_slots(blocks, fmt, first), precision)


def _decode_kernel(rows_ref, blks_ref, nblk_ref, end_ref, len_ref,
                   win_ref, table_ref, layer_ref,          # scalar prefetch
                   q_ref, *rest, bits: int, kv_fmt: PageFormat,
                   sc_fmt, ppb: int, nb: int, g: int, softcap: float,
                   sm_scale: float):
    quant = sc_fmt is not None
    n_leaves = 4 if quant else 2
    pages = [rest[i * ppb:(i + 1) * ppb] for i in range(n_leaves)]
    k_refs, v_refs = pages[0], pages[n_leaves // 2]
    o_ref, m_ref, l_ref = rest[n_leaves * ppb:]
    parts, bhg = q_ref.shape[1], q_ref.shape[2]
    page, n = kv_fmt.page, ppb * kv_fmt.page
    step = pl.program_id(1)
    b, blk = rows_ref[step], blks_ref[step]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    pids = [table_ref[b, jnp.minimum(blk * ppb + j, nb - 1)]
            for j in range(ppb)]

    def tokens(refs, fmt):
        """The step's tokens, one a row (n, lanes) per part."""
        rows = _kv_parts(jnp.concatenate([r[0, 0] for r in refs], axis=0),
                         bits)
        if fmt.per_row == 1 and fmt.group == 1:
            return rows
        first = [(pid % fmt.group) * page for pid in pids]
        return [token_slots(x, fmt, first) for x in rows]

    def scales(refs):
        """(heads, n) per-token scales: row (h, g) takes KV head h's."""
        head = jax.lax.broadcasted_iota(jnp.int32, (bhg, sc_fmt.lanes), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (bhg, sc_fmt.lanes), 1)
        h0 = layer_ref[1] + pl.program_id(0) * (bhg // g)
        sel_t = (lane % sc_fmt.slot == h0 + head // g).astype(jnp.float32)
        blocks = jnp.concatenate([r[0, 0] for r in refs], axis=0)
        first = [(pid % sc_fmt.group) * page for pid in pids]
        return token_values(blocks, sc_fmt, first, sel_t)

    k, v = tokens(k_refs, kv_fmt), tokens(v_refs, kv_fmt)
    if q_ref.dtype == jnp.bfloat16:
        # bf16 queries times integer K values: every product is exact in
        # one bf16 MXU pass, summed in f32
        s = sum(jax.lax.dot_general(
            q_ref[0, p], k[p].astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for p in range(parts))
    else:
        s = sum(_dot_nt(q_ref[0, p], k[p]) for p in range(parts))
    if quant:
        s = s * scales(pages[1])
    i = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    pos = blk * n + i
    w = win_ref[0]
    lo = jnp.where(w > 0, len_ref[b] - w, 0)         # sliding-window start
    p_, m, l, corr = _softmax_step(s, (pos < end_ref[b]) & (pos >= lo),
                                   m_ref[...], l_ref[...], sm_scale,
                                   softcap)
    if quant:
        p_ = p_ * scales(pages[3])
    m_ref[...] = m
    l_ref[...] = l
    for p in range(parts):
        o_ref[0, p] = o_ref[0, p] * corr + _dot(p_, v[p])

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


def block_diag_queries(q, fmt: PageFormat, parts: int):
    """(B, KV, G, dh) queries -> (B, parts, KV*G, lanes): row (h, g)
    holds query head (h, g)'s head-dim part at KV head h's lanes of every
    token slot of a stored row, zeros elsewhere."""
    b, kv, g, dh = q.shape
    dhp = dh // parts
    qp = jnp.moveaxis(q.reshape(b, kv, g, dhp, parts),
                      -1, 1)                        # (B, parts, KV, G, dhp)
    eye = jnp.eye(kv, dtype=q.dtype)
    qb = qp[:, :, :, :, None, :] * eye[None, None, :, None, :, None]
    qb = qb.reshape(b, parts, kv * g, kv * dhp)
    qb = jnp.pad(qb, ((0, 0),) * 3 + ((0, fmt.slot - kv * dhp),))
    return jnp.concatenate([qb] * fmt.per_row, axis=-1)


def head_outputs(out, fmt: PageFormat, kv: int, g: int, dh: int):
    """Kernel output (B, parts, KV*G, lanes) -> (B, KV, G, dh): each
    query head's values at its own KV head's lanes, the token slots of a
    row summed."""
    b, parts = out.shape[:2]
    dhp = dh // parts
    x = out.reshape(b, parts, kv * g, fmt.per_row, fmt.slot).sum(3)
    x = x[..., :kv * dhp].reshape(b, parts, kv, g, kv, dhp)
    x = jnp.einsum("bpkgkd->bpkgd", x)
    return jnp.moveaxis(x, 1, -1).reshape(b, kv, g, dh)


@functools.partial(jax.jit, static_argnames=("page", "softcap", "block_kv",
                                             "trash_page", "scale_heads",
                                             "interpret"))
def paged_attention(q, k_pages, v_pages, k_scale, v_scale, table, kv_len,
                    *, page: int, layer=0, window=None, softcap: float = 0.0,
                    block_kv: int | None = None, trash_page: bool = True,
                    head0=0, scale_heads: int | None = None,
                    interpret: bool | None = None):
    """Decode attention straight over a (quantized) stacked page pool.

    q:        (B, KV, G, dh) grouped queries (one decode token per slot).
    k/v:      (stack, blocks, rows, lanes) lane-dense pool leaves
              (``kv_pages``; a token's KV x dh values, int8 or f32, or
              KV x dh//2 uint8 nibble pairs, ``core.quantize.pack_int4``).
    k/v_scale: the same storage of the (KV,) per-token/head f32 scales
              (None when f32).
    page:     tokens a page; ``layer``: ()-shaped int, the stack index.
    table:    (B, nb) int32 block table.  With ``trash_page`` page 0 is
              the reserved trash page and live blocks are a contiguous
              per-row prefix (PA2/PA3); a row attends only its live
              prefix (an all-trash row returns zeros).  Without it every
              entry is real (the contiguous cache's identity view).
    kv_len:   (B,) int32 fill levels; position ``kv_len - 1`` is the
              decode token itself, so causality == the fill mask.
    window:   optional ()-shaped int (or Python int): > 0 restricts
              attention to the last ``window`` positions.
    head0, scale_heads: the scale leaves hold ``scale_heads`` KV heads
              (default: q's), of which q's are those from ``head0`` (a
              ()-shaped int) on: a device of a tensor-parallel mesh reads
              its own heads' lanes of K and V and picks its heads' scales
              from the whole scale leaves.

    Returns (B, KV, G, dh) f32.  ``block_kv`` tiles KV heads per grid
    step (largest divisor of KV <= block_kv is used; None = all heads);
    each tile reads whole pages.
    """
    interpret = resolve_interpret(interpret)
    b, kv, g, dh = q.shape
    nb = table.shape[1]
    bits = {jnp.dtype(jnp.int8): 8, jnp.dtype(jnp.uint8): 4}.get(
        jnp.dtype(k_pages.dtype), 32)
    parts = 2 if bits == 4 else 1
    kv_fmt = PageFormat(page, kv * dh // parts)
    if k_pages.shape[2:] != (kv_fmt.rows, kv_fmt.lanes):
        raise ValueError(f"pool leaf {k_pages.shape} is not the "
                         f"lane-dense storage of {kv} heads of {dh} "
                         f"({bits}-bit) in pages of {page}: "
                         f"(..., {kv_fmt.rows}, {kv_fmt.lanes})")
    quantized = bits < 32
    sc_fmt = PageFormat(page, scale_heads or kv) if quantized else None

    table = jnp.asarray(table, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(b)
    win = jnp.asarray(0 if window is None else window,
                      jnp.int32).reshape(1)
    layer = jnp.stack([jnp.asarray(layer, jnp.int32).reshape(()),
                       jnp.asarray(head0, jnp.int32).reshape(())])
    block_kv = kv if block_kv is None else fit_block(min(block_kv, kv), kv, 1)
    ppb = max(1, min(nb, BLOCK_TOKENS // page))
    steps, rows, blks, nblk, end = _schedule(table, kv_len, page, ppb,
                                             trash_page)
    # bf16 queries stay bf16 against an integer pool (exact products)
    exact_bf16 = q.dtype == jnp.bfloat16 and quantized
    qb = block_diag_queries(q if exact_bf16 else q.astype(jnp.float32),
                            kv_fmt, parts)

    def at_qo(hi, si, row, *_):
        return (row[si], 0, hi, 0)

    def page_at(j, group):
        def at(hi, si, row, blk, n, end, ln, wn, tab, li):
            pid = tab[row[si], jnp.minimum(blk[si] * ppb + j, nb - 1)]
            return (li[0], pid // group, 0, 0)
        return at

    def specs(fmt):
        return [pl.BlockSpec((1, 1, fmt.rows, fmt.lanes),
                             page_at(j, fmt.group)) for j in range(ppb)]

    leaves = ([k_pages, k_scale, v_pages, v_scale] if quantized
              else [k_pages, v_pages])
    page_specs = ([specs(kv_fmt), specs(sc_fmt)] * 2) if quantized \
        else [specs(kv_fmt)] * 2
    bhg = block_kv * g
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(kv // block_kv, steps),
        in_specs=[pl.BlockSpec((1, parts, bhg, kv_fmt.lanes), at_qo)]
        + [s for ss in page_specs for s in ss],
        out_specs=pl.BlockSpec((1, parts, bhg, kv_fmt.lanes), at_qo),
        scratch_shapes=[pltpu.VMEM((bhg, 1), jnp.float32),
                        pltpu.VMEM((bhg, 1), jnp.float32)])
    kern = functools.partial(_decode_kernel, bits=bits, kv_fmt=kv_fmt,
                             sc_fmt=sc_fmt, ppb=ppb, nb=nb, g=g,
                             softcap=float(softcap),
                             sm_scale=1.0 / math.sqrt(dh))
    operands = (rows, blks, nblk, end, kv_len, win, table, layer, qb) \
        + tuple(x for x in leaves for _ in range(ppb))
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, parts, kv * g, kv_fmt.lanes),
                                       jnp.float32),
        interpret=interpret, name="paged_attention")(*operands)
    # a row with no blocks is never visited: its output is zeros
    out = jnp.where((nblk > 0)[:, None, None, None], out, 0.0)
    return head_outputs(out, kv_fmt, kv, g, dh)
