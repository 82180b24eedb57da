"""Pallas TPU kernel: packed-integer dequant matmul (BWQ deployment path).

After training, BWQ weights are packed to int8 (or int4 nibble pairs) with
a per-WB scale — this is what serving reads from HBM.  The kernel streams
the packed tile, dequantizes in VMEM (nibble unpack + per-block scale
broadcast) and performs a single MXU matmul.  HBM weight traffic drops 2x
(int8) / 4x (int4) vs bf16 — the roofline lever for decode shapes.

Geometry is defined by the per-WB scale grid: K = scale.shape[0] * wbr and
N = scale.shape[1] * wbc.  Operands that do not divide the tile sizes are
zero-padded up to tile multiples and the output is trimmed back — this
covers decode-shaped M in 1..16, ragged K/N, and the int4 odd-block-padded
K case (an extra zero WB row absorbs the unpaired nibble).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_utils import (expand_lut, fit_block, lut_by_col_block, pad_dim,
                           resolve_interpret, round_up, tile_block)


def _kernel8(x_ref, w_ref, s_ref, o_ref, *, wbr, wbc):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    s = expand_lut(s_ref[0], wbr, wbc)
    o_ref[...] += jnp.dot(x, w * s, preferred_element_type=jnp.float32)


def _kernel4(x_ref, w_ref, s_ref, o_ref, *, wbr, wbc, block_k):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    # nibbles as sign-extended int32 (two's complement in [-8, 7]);
    # shifting on 32-bit lanes, since Mosaic has no 8-bit shifts
    packed = w_ref[...].astype(jnp.int32)                # (bk//2, bn)
    lo = (packed << 28) >> 28
    hi = (packed << 24) >> 28
    w = jnp.stack([lo, hi], axis=1).reshape(block_k, packed.shape[1])
    s = expand_lut(s_ref[0], wbr, wbc)
    o_ref[...] += jnp.dot(x, w.astype(jnp.float32) * s,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "wbr", "wbc", "block_m",
                                             "block_n", "block_k",
                                             "interpret"))
def packed_matmul(x, w_int, scale, *, bits: int = 8, wbr: int = 8,
                  wbc: int = 128, block_m: int = 128, block_n: int = 256,
                  block_k: int = 512, interpret: bool | None = None):
    """y[M, N] = x[M, K] @ (dequant(w_int) * per-WB scale).

    int8: w_int (K, N) int8.  int4: w_int (ceil(K/2), N) uint8 (row 2j in
    the low nibble; an odd K carries one zero pad row in the last byte).
    scale: (K//wbr, N//wbc) f32.  x may have fewer than K columns (the
    unpadded true fan-in); the missing columns multiply zero-padded weight
    rows and are zero-filled here.  ``interpret=None`` auto-selects
    interpret mode off-TPU.
    """
    interpret = resolve_interpret(interpret)
    m, k_x = x.shape
    gr, gc = scale.shape
    k, n = gr * wbr, gc * wbc
    if k_x > k or w_int.shape[-1] != n:
        raise ValueError(f"operand geometry mismatch: x K={k_x}, "
                         f"scale grid K={k} N={n}, w N={w_int.shape[-1]}")

    # pad K up to a tile unit that is both a WB-row multiple and (for int4)
    # an even row count, so nibble unpacking never straddles a tile edge
    unit_k = wbr if (bits == 8 or wbr % 2 == 0) else 2 * wbr
    kp = round_up(k, unit_k)
    mp = round_up(m, 8)            # decode-shaped M (1..16) pads to one tile
    x = pad_dim(pad_dim(x, 1, kp), 0, mp)
    scale = pad_dim(scale, 0, kp // wbr)
    if bits == 8:
        w_int = pad_dim(w_int, 0, kp)
    elif bits == 4:
        w_int = pad_dim(w_int, 0, kp // 2)
    else:
        raise ValueError(bits)

    block_m = fit_block(min(block_m, mp), mp, 8)
    block_n = tile_block(block_n, n, math.lcm(wbc, 128))
    # K tiles: x's lane dim (128), the LUT's sublane dim (8 WB rows) and,
    # for int4, the nibble-pair rows must all stay tile-aligned
    block_k = tile_block(block_k, kp, math.lcm(unit_k, 128, 8 * wbr))
    grid = (mp // block_m, n // block_n, kp // block_k)
    common = dict(
        grid=grid,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        interpret=interpret,
    )
    scale = lut_by_col_block(scale, block_n // wbc)
    s_spec = pl.BlockSpec((1, block_k // wbr, block_n // wbc),
                          lambda i, j, kk: (j, kk, 0))
    x_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    if bits == 8:
        kern = functools.partial(_kernel8, wbr=wbr, wbc=wbc)
        w_spec = pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j))
    else:
        kern = functools.partial(_kernel4, wbr=wbr, wbc=wbc, block_k=block_k)
        w_spec = pl.BlockSpec((block_k // 2, block_n),
                              lambda i, j, kk: (kk, j))
    y = pl.pallas_call(kern, in_specs=[x_spec, w_spec, s_spec],
                       name="packed_matmul", **common)(x, w_int, scale)
    return y[:m]
