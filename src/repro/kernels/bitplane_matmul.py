"""Pallas TPU kernel: bit-plane-sliced mixed-precision matmul (BWQ core).

The digital analogue of BWQ-H's precision-aware OU mapping (paper Fig. 5c):
weights live in HBM as 1-bit planes (packed 8 rows/byte) plus a packed sign
plane and the per-WB (bit, block) mask LUT.  Each grid step streams the
packed tiles HBM->VMEM ((n_bits+1)/8 bytes per weight instead of 2-4),
decodes them in-register, composes the masked magnitude, and issues ONE MXU
matmul per (m, n, k) tile.  Masked planes contribute zero exactly as the
memory controller skips their OUs.

Tiling: wb_rows | block_k and wb_cols | block_n so mask expansion is a
sublane/lane-aligned broadcast (TPU-native WB geometry 8x128; the paper's
9x8 geometry stays on the pure-jnp path — DESIGN.md §2).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .pallas_utils import (expand_lut, fit_block, lut_by_col_block, pad_dim,
                           resolve_interpret, round_up, tile_block)


def _kernel(x_ref, planes_ref, sign_ref, mask_ref, scale_ref, o_ref, *,
            n_bits: int, wbr: int, wbc: int, block_k: int, per_block: bool):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)              # (bm, bk)
    bn = o_ref.shape[1]

    def unpack(packed):                             # (bk//8, bn) -> (bk, bn)
        packed = packed.astype(jnp.int32)           # shifts on 32-bit lanes
        parts = [((packed >> r) & 1) for r in range(8)]
        st = jnp.stack(parts, axis=1)               # (bk//8, 8, bn)
        return st.reshape(block_k, bn)

    # compose magnitude = sum_b 2^b * plane_b * mask_b   (masked planes skip)
    mag = jnp.zeros((block_k, bn), jnp.float32)
    for b in range(n_bits):
        plane = unpack(planes_ref[b]).astype(jnp.float32)
        m = expand_lut(mask_ref[0, b], wbr, wbc)    # (bk, bn) from the tile
        mag = mag + (2.0 ** b) * plane * m

    sign = 1.0 - 2.0 * unpack(sign_ref[...]).astype(jnp.float32)
    if per_block:
        # per-WB effective scale (serving layout): /(2^n - 1) and each
        # block's power-of-two rescale factor are pre-folded into the LUT
        w = sign * mag * expand_lut(scale_ref[0], wbr, wbc)
    else:
        w = sign * mag * (scale_ref[0] / (2.0 ** n_bits - 1.0))
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_bits", "wbr", "wbc",
                                             "block_m", "block_n", "block_k",
                                             "interpret"))
def bitplane_matmul(x, planes_packed, sign_packed, mask, scale, *,
                    n_bits: int = 8, wbr: int = 8, wbc: int = 128,
                    block_m: int = 128, block_n: int = 256,
                    block_k: int = 512, interpret: bool | None = None):
    """y[M,N] = x[M,K] @ compose(planes, sign, mask, scale).

    planes_packed: (n_bits, K//8, N) uint8; sign_packed: (K//8, N) uint8;
    mask: (n_bits, K//wbr, N//wbc); scale: (1,) f32 per-layer, divided by
    ``2^n - 1`` in-kernel, OR (K//wbr, N//wbc) f32 per-WB *effective*
    scale (the serving layout: /(2^n - 1) and per-block rescale factors
    pre-folded — this is what carries BWQ's mixed per-block precision to
    the MXU).  M/K/N that do not divide the tile sizes are zero-padded up
    to tile multiples and the output trimmed back; ``planes_packed`` may
    carry extra zero byte-pad rows beyond K//wbr WB rows (odd block-padded
    K under e.g. the 9x8 paper geometry packs up to the byte boundary).
    ``interpret=None`` auto-selects interpret mode off-TPU.
    """
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    n = planes_packed.shape[-1]
    per_block = scale.ndim == 2
    unit_k = math.lcm(8, wbr)          # bit-packing rows AND WB rows align
    kp = round_up(k, unit_k)
    kp = max(kp, round_up(planes_packed.shape[1] * 8, unit_k))
    np_ = round_up(n, wbc)
    mp = round_up(m, 8)
    x = pad_dim(pad_dim(x, 1, kp), 0, mp)
    planes_packed = pad_dim(pad_dim(planes_packed, 1, kp // 8), 2, np_)
    sign_packed = pad_dim(pad_dim(sign_packed, 0, kp // 8), 1, np_)
    mask = pad_dim(pad_dim(mask, 1, kp // wbr), 2, np_ // wbc)
    if per_block:
        scale = pad_dim(pad_dim(scale, 0, kp // wbr), 1, np_ // wbc)

    block_m = fit_block(min(block_m, mp), mp, 8)
    block_n = tile_block(block_n, np_, math.lcm(wbc, 128))
    # K tiles: x's lane dim (128), the packed planes' sublane dim (8 byte
    # rows = 64 weight rows) and the LUT's sublane dim (8 WB rows)
    block_k = tile_block(block_k, kp, math.lcm(unit_k, 128, 64, 8 * wbr))
    grid = (mp // block_m, np_ // block_n, kp // block_k)
    cb = block_n // wbc

    kern = functools.partial(_kernel, n_bits=n_bits, wbr=wbr, wbc=wbc,
                             block_k=block_k, per_block=per_block)
    mask = lut_by_col_block(mask, cb)               # (nN, n_bits, GR, cb)
    if per_block:
        scale = lut_by_col_block(scale, cb)         # (nN, GR, cb)
        scale_spec = pl.BlockSpec((1, block_k // wbr, cb),
                                  lambda i, j, kk: (j, kk, 0))
    else:
        scale_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((n_bits, block_k // 8, block_n),
                         lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((block_k // 8, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, n_bits, block_k // wbr, cb),
                         lambda i, j, kk: (j, 0, kk, 0)),
            scale_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="bitplane_matmul",
    )(x, planes_packed, sign_packed, mask, scale)
    return y[:m, :n]
