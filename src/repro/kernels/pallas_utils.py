"""Shared helpers for the Pallas kernels: platform-aware interpret default,
pad-and-trim tiling geometry, and the per-WB LUT block layout.

Every kernel entry point takes ``interpret=None`` and resolves it here, so
the same call site runs compiled on TPU and interpreted everywhere else
(CPU CI, tests, notebooks) without the caller threading a platform flag.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def default_interpret() -> bool:
    """Interpret Pallas kernels everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def fit_block(pref: int, total: int, multiple: int) -> int:
    """Largest block <= pref that divides total and is a multiple-multiple.

    ``total`` must itself be a multiple of ``multiple`` (the pad-and-trim
    wrappers guarantee this), so a valid block always exists.
    """
    best = multiple
    d = multiple
    while d <= min(pref, total):
        if total % d == 0:
            best = d
        d += multiple
    return best


def pad_dim(a: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    """Zero-pad ``a`` along ``axis`` up to length ``target`` (no-op if
    already there)."""
    cur = a.shape[axis]
    if cur == target:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, target - cur)
    return jnp.pad(a, pad)


def tile_block(pref: int, total: int, unit: int) -> int:
    """Block length along an axis whose blocks must be ``unit``-aligned
    for the TPU's (8, 128) tiling rule: the largest ``unit`` multiple
    <= ``pref`` dividing ``total`` when ``total`` is ``unit``-aligned,
    otherwise the whole axis (a full-extent block is always legal)."""
    if total % unit:
        return total
    return fit_block(max(min(pref, total), unit), total, unit)


def lut_by_col_block(lut: jnp.ndarray, cb: int) -> jnp.ndarray:
    """(..., GR, GC) per-WB LUT -> (GC // cb, ..., GR, cb).

    A kernel tile of ``cb`` WB columns reads a (GR-slice, cb) LUT tile.
    Cut straight from the (GR, GC) grid its minor dim would be ``cb``
    (e.g. 2), which the TPU's tiling rule refuses; with the column-block
    index leading, the tile spans the whole minor dim and is legal.  The
    LUT is 1/(wbr*wbc) of the weight, so the re-layout is cheap."""
    *lead, gr, gc = lut.shape
    x = lut.reshape(*lead, gr, gc // cb, cb)
    return jnp.moveaxis(x, -2, 0)


def expand_lut(s: jnp.ndarray, wbr: int, wbc: int) -> jnp.ndarray:
    """In-kernel (r, c) LUT tile -> (r*wbr, c*wbc) per-element map."""
    return jnp.repeat(jnp.repeat(s, wbr, axis=0), wbc, axis=1)
