"""Pallas TPU kernel: decode attention over a paged *latent* cache (MLA).

Multi-head latent attention (DeepSeek-V2) caches one row per token: the
normed latent ``c_kv`` (rank ``r``) and the roped key part ``k_pe``
(``dr``), each int8 with its own per-token scale.  A decode token's
heads attend in the absorbed form: with ``q_lat = q_nope W_UK^T`` per
head, the score of position t is ``q_lat . c_t + q_pe . k_pe_t`` and the
output ``o_lat = sum_t p_t c_t`` (``models.mla`` maps ``o_lat`` back
through ``W_UV``).  So the latent is read once per token for the scores
and the sum alike, and no per-head K or V exists.

The walk is ``paged_attention``'s (its ``_schedule``): the grid visits
only each row's live blocks of ``ppb`` pages (about 128 tokens), rows in
order, each step's (row, block) scalar-prefetched, and a row with no
live block (parked) returns zeros.  Each page is its own operand, so the
Pallas pipeline copies the next step's pages while these compute.  Per
page, on the MXU: scores (H, page) = ``q_lat c^T`` and ``q_pe k_pe^T``,
each times its per-token scale row (the dequantization, moved past the
sums); the online softmax; ``acc += (p * c_scale) c``.

The per-token scales are (P, page) pool leaves.  A (1, page) block
breaks the TPU's (8, 128) tiling rule, so a step reads the (8, page)
block holding the page's row and picks the row out: 8 pages' scales
(512 bytes) beside a page's 9 KB of latent.  A pool whose page count is
not a multiple of 8 is padded per call to one that is.

Queries, scores and the accumulation are f32.  ``interpret=None``
auto-selects interpret mode off-TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import BLOCK_TOKENS, NEG_INF, _schedule, _softmax_step
from .pallas_utils import resolve_interpret

SCALE_ROWS = 8          # pages per scale block: the tiling's sublane count


def _dot_nt(a, b):
    """(M, K) x (N, K) -> (M, N) in f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scale_row(ref, pid):
    """The (1, page) scale row of pool page ``pid`` from its (8, page)
    block."""
    blk = ref[...]
    sel = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) \
        == pid % SCALE_ROWS
    return jnp.sum(jnp.where(sel, blk, 0.0), axis=0, keepdims=True)


def _kernel(rows_ref, blks_ref, nblk_ref, end_ref, table_ref,   # prefetch
            ql_ref, qp_ref, *rest, page: int, ppb: int, nb: int,
            sm_scale: float):
    c_refs, cs_refs, p_refs, ps_refs = (rest[i * ppb:(i + 1) * ppb]
                                        for i in range(4))
    o_ref, m_ref, l_ref = rest[4 * ppb:]
    step = pl.program_id(0)
    b, blk = rows_ref[step], blks_ref[step]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    end = end_ref[b]
    ql, qp = ql_ref[0], qp_ref[0]                  # (H, r), (H, dr)
    m, l, acc = m_ref[...], l_ref[...], o_ref[0]
    for j in range(ppb):
        pid = table_ref[b, jnp.minimum(blk * ppb + j, nb - 1)]
        cs = _scale_row(cs_refs[j], pid)            # (1, page)
        ps = _scale_row(ps_refs[j], pid)
        c = c_refs[j][0].astype(jnp.float32)       # (page, r)
        pe = p_refs[j][0].astype(jnp.float32)      # (page, dr)
        s = _dot_nt(ql, c) * cs + _dot_nt(qp, pe) * ps
        pos = (blk * ppb + j) * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)
        p, m, l, corr = _softmax_step(s, pos < end, m, l, sm_scale, 0.0)
        acc = acc * corr + jax.lax.dot_general(
            p * cs, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_ref[...] = m
    l_ref[...] = l
    o_ref[0] = acc

    @pl.when(blk == nblk_ref[b] - 1)
    def _finish():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("sm_scale", "trash_page",
                                             "interpret"))
def latent_attention(q_lat, q_pe, ckv, ckv_scale, kpe, kpe_scale, table,
                     kv_len, *, sm_scale: float, trash_page: bool = True,
                     interpret: bool | None = None):
    """Absorbed-form MLA decode attention straight over a latent pool.

    q_lat:     (B, H, r) queries in latent space (``q_nope W_UK^T``).
    q_pe:      (B, H, dr) roped query parts.
    ckv, kpe:  (P, page, r) and (P, page, dr) pool leaves (int8, or a
               float dtype with unit scales).
    ckv_scale, kpe_scale: (P, page) f32 per-token scales.
    table:     (B, nb) int32 block table (page 0 the trash page when
               ``trash_page``; see ``paged_attention``).
    kv_len:    (B,) fill levels, the decode token included.

    Returns o_lat (B, H, r) f32: the softmax-weighted sum of the
    dequantized latents.
    """
    interpret = resolve_interpret(interpret)
    b, h, r = q_lat.shape
    dr = q_pe.shape[-1]
    n_pages, page = ckv.shape[:2]
    nb = table.shape[1]
    pad = -n_pages % SCALE_ROWS
    if pad:
        ckv_scale, kpe_scale = (jnp.pad(s, ((0, pad), (0, 0)))
                                for s in (ckv_scale, kpe_scale))
    table = jnp.asarray(table, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(b)
    ppb = max(1, min(nb, BLOCK_TOKENS // page))
    steps, rows, blks, nblk, end = _schedule(table, kv_len, page, ppb,
                                             trash_page)

    def at_row(si, row, *_):
        return row[si], 0, 0

    def page_at(j, scale: bool):
        def at(si, row, blk, n, end, tab):
            pid = tab[row[si], jnp.minimum(blk[si] * ppb + j, nb - 1)]
            return (pid // SCALE_ROWS, 0) if scale else (pid, 0, 0)
        return at

    def pages(width):
        return [pl.BlockSpec((1, page, width), page_at(j, False))
                for j in range(ppb)]

    scales = [pl.BlockSpec((SCALE_ROWS, page), page_at(j, True))
              for j in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, h, r), at_row),
                  pl.BlockSpec((1, h, dr), at_row)]
        + pages(r) + scales + pages(dr) + scales,
        out_specs=pl.BlockSpec((1, h, r), at_row),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32)])
    kern = functools.partial(_kernel, page=page, ppb=ppb, nb=nb,
                             sm_scale=float(sm_scale))
    operands = (rows, blks, nblk, end, table,
                q_lat.astype(jnp.float32), q_pe.astype(jnp.float32)) \
        + tuple(x for x in (ckv, ckv_scale, kpe, kpe_scale)
                for _ in range(ppb))
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        interpret=interpret, name="latent_attention")(*operands)
    # a row with no blocks is never visited: its output is zeros
    return jnp.where((nblk > 0)[:, None, None], out, 0.0)
