"""Pallas TPU kernel: decode attention over a paged *latent* cache (MLA).

Multi-head latent attention (DeepSeek-V2) caches one row per token: the
normed latent ``c_kv`` (rank ``r``) and the roped key part ``k_pe``
(``dr``), each int8 with its own per-token scale.  A decode token's
heads attend in the absorbed form: with ``q_lat = q_nope W_UK^T`` per
head, the score of position t is ``q_lat . c_t + q_pe . k_pe_t`` and the
output ``o_lat = sum_t p_t c_t`` (``models.mla`` maps ``o_lat`` back
through ``W_UV``).  So the latent is read once per token for the scores
and the sum alike, and no per-head K or V exists.

The pool is read where it lies: the leaves are the whole layer stack in
the lane-dense storage of ``kernels.kv_pages`` — ``ckv`` one token a
row of ``r`` lanes, ``kpe`` several tokens a row of 128, the scales 8
pages of 16 a row — and the layer index is scalar-prefetched, so a
page's ``BlockSpec`` index is (layer, page block, 0, 0).

The walk is ``paged_attention``'s (its ``_schedule``): the grid visits
only each row's live blocks of ``ppb`` pages (about 128 tokens), rows in
order, each step's (row, block) scalar-prefetched, and a row with no
live block (parked) returns zeros.  Each page is its own operand, so the
Pallas pipeline copies the next step's pages while these compute.  Per
step, on the MXU: scores (H, tokens) = ``q_lat c^T`` and ``q_pe k_pe^T``,
each times its per-token scale row (the dequantization, moved past the
sums); the online softmax; ``acc += (p * c_scale) c``.  A leaf holding
several tokens a row is moved to one token a row by 0/1 selection dots
(``paged_attention.token_slots``), a query tiled over a row's token
slots taking each token's scores, so the scales and ``k_pe`` cost a few
small dots a step.

Queries, scores and the accumulation are f32.  The score and value
dots against the latents and rope keys run at the dot's default
precision; the 0/1 selection dots that move stored values run at full
precision, so they move them exactly.  ``interpret=None`` auto-selects
interpret mode off-TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_pages import PageFormat
from .paged_attention import (BLOCK_TOKENS, NEG_INF, _dot, _dot_nt,
                              _schedule, _softmax_step, token_slots,
                              token_values)
from .pallas_utils import resolve_interpret

_DEFAULT = None      # the dot's default precision


def _tile_slots(x, fmt: PageFormat):
    """(..., width) -> (..., lanes): ``x`` at every token slot of a row."""
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, fmt.slot - x.shape[-1])])
    return jnp.concatenate([x] * fmt.per_row, axis=-1)


def _kernel(rows_ref, blks_ref, nblk_ref, end_ref, table_ref,   # prefetch
            layer_ref, ql_ref, qp_ref, *rest, c_fmt: PageFormat,
            pe_fmt: PageFormat, s_fmt, ppb: int, nb: int, sm_scale: float):
    n_leaves = 4 if s_fmt is not None else 2
    refs = [rest[i * ppb:(i + 1) * ppb] for i in range(n_leaves)]
    c_refs, p_refs = refs[0], refs[n_leaves // 2]
    o_ref, m_ref, l_ref = rest[n_leaves * ppb:]
    page = c_fmt.page
    step = pl.program_id(0)
    b, blk = rows_ref[step], blks_ref[step]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    pids = [table_ref[b, jnp.minimum(blk * ppb + j, nb - 1)]
            for j in range(ppb)]

    def rows(refs):
        return jnp.concatenate([r[0, 0] for r in refs],
                               axis=0).astype(jnp.float32)

    def first(fmt):
        return [(pid % fmt.group) * page for pid in pids]

    ql, qp = ql_ref[0], qp_ref[0]                  # (H, lanes) each
    if c_fmt.per_row == 1 and c_fmt.group == 1:
        c = rows(c_refs)                           # (n, r), token order
    else:
        c = token_slots(rows(c_refs), c_fmt, first(c_fmt))
    s_c = _dot_nt(ql, c, _DEFAULT)                # (H, n)
    s_pe = token_values(rows(p_refs), pe_fmt, first(pe_fmt), qp,
                        _DEFAULT)
    p_scale = None
    if s_fmt is not None:
        ones = jnp.ones((1, s_fmt.lanes), jnp.float32)
        c_scale = token_values(rows(refs[1]), s_fmt, first(s_fmt), ones)
        p_scale = token_values(rows(refs[3]), s_fmt, first(s_fmt), ones)
        s_c, s_pe = s_c * c_scale, s_pe * p_scale
    n = ppb * page
    pos = blk * n + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    p, m, l, corr = _softmax_step(s_c + s_pe, pos < end_ref[b],
                                  m_ref[...], l_ref[...], sm_scale, 0.0)
    if s_fmt is not None:
        p = p * c_scale
    m_ref[...] = m
    l_ref[...] = l
    o_ref[0] = o_ref[0] * corr + _dot(p, c, _DEFAULT)

    @pl.when(blk == nblk_ref[b] - 1)
    def _finish():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("page", "sm_scale",
                                             "trash_page", "interpret"))
def latent_attention(q_lat, q_pe, ckv, ckv_scale, kpe, kpe_scale, table,
                     kv_len, *, page: int, sm_scale: float, layer=0,
                     trash_page: bool = True, interpret: bool | None = None):
    """Absorbed-form MLA decode attention straight over a stacked latent
    pool.

    q_lat:     (B, H, r) queries in latent space (``q_nope W_UK^T``).
    q_pe:      (B, H, dr) roped query parts.
    ckv, kpe:  (stack, blocks, rows, lanes) lane-dense pool leaves
               (``kernels.kv_pages``) of the (r,) latents and (dr,) rope
               keys, int8 or a float dtype.
    ckv_scale, kpe_scale: the same storage of the per-token f32 scales,
               or None for a float pool.
    page:      tokens a page; ``layer``: ()-shaped int, the stack index.
    table:     (B, nb) int32 block table (page 0 the trash page when
               ``trash_page``; see ``paged_attention``).
    kv_len:    (B,) fill levels, the decode token included.

    Returns o_lat (B, H, r) f32: the softmax-weighted sum of the
    dequantized latents.
    """
    interpret = resolve_interpret(interpret)
    b, h, r = q_lat.shape
    dr = q_pe.shape[-1]
    nb = table.shape[1]
    c_fmt, pe_fmt = PageFormat(page, r), PageFormat(page, dr)
    s_fmt = None if ckv_scale is None else PageFormat(page, 1)
    table = jnp.asarray(table, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(b)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    ppb = max(1, min(nb, BLOCK_TOKENS // page))
    steps, rows, blks, nblk, end = _schedule(table, kv_len, page, ppb,
                                             trash_page)

    def at_row(si, row, *_):
        return row[si], 0, 0

    def page_at(j, group):
        def at(si, row, blk, n, end, tab, li):
            pid = tab[row[si], jnp.minimum(blk[si] * ppb + j, nb - 1)]
            return (li[0], pid // group, 0, 0)
        return at

    def pages(fmt):
        return [pl.BlockSpec((1, 1, fmt.rows, fmt.lanes),
                             page_at(j, fmt.group)) for j in range(ppb)]

    leaves = [(ckv, c_fmt), (kpe, pe_fmt)]
    if s_fmt is not None:
        leaves = [(ckv, c_fmt), (ckv_scale, s_fmt), (kpe, pe_fmt),
                  (kpe_scale, s_fmt)]
    ql = _tile_slots(q_lat.astype(jnp.float32), c_fmt)
    qp = _tile_slots(q_pe.astype(jnp.float32), pe_fmt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, h, c_fmt.lanes), at_row),
                  pl.BlockSpec((1, h, pe_fmt.lanes), at_row)]
        + [s for _, fmt in leaves for s in pages(fmt)],
        out_specs=pl.BlockSpec((1, h, c_fmt.lanes), at_row),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32)])
    kern = functools.partial(_kernel, c_fmt=c_fmt, pe_fmt=pe_fmt,
                             s_fmt=s_fmt, ppb=ppb, nb=nb,
                             sm_scale=float(sm_scale))
    operands = (rows, blks, nblk, end, table, layer, ql, qp) \
        + tuple(x for x, _ in leaves for _ in range(ppb))
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c_fmt.lanes), jnp.float32),
        interpret=interpret, name="latent_attention")(*operands)
    # a row with no blocks is never visited: its output is zeros
    out = jnp.where((nblk > 0)[:, None, None], out, 0.0)
    # a row of several tokens: each token slot's sum, added
    out = out.reshape(b, h, c_fmt.per_row, c_fmt.slot).sum(2)
    return out[..., :r]
