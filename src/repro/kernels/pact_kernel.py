"""Pallas TPU kernel: fused symmetric-PACT clip + uniform quantize.

Elementwise VPU kernel; fusing clip+round+rescale keeps the activation
quantization a single HBM round-trip in front of each quantized matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_utils import fit_block, pad_dim, resolve_interpret, round_up


def _kernel(x_ref, beta_ref, o_ref, *, act_bits: int):
    x = x_ref[...]
    b = jnp.maximum(beta_ref[0], 1e-6).astype(x.dtype)
    levels = jnp.asarray(2 ** (act_bits - 1) - 1, x.dtype)
    y = jnp.clip(x, -b, b)
    o_ref[...] = jnp.round(y / b * levels) * (b / levels)


@functools.partial(jax.jit, static_argnames=("act_bits", "block_rows",
                                             "interpret"))
def pact_quant_pallas(x, beta, *, act_bits: int = 8, block_rows: int = 256,
                      interpret: bool | None = None):
    """x: (R, C) any float dtype; beta: (1,) clip level.

    Rows that do not divide ``block_rows`` are padded and trimmed back;
    ``interpret=None`` auto-selects interpret mode off-TPU."""
    interpret = resolve_interpret(interpret)
    r, c = x.shape
    rp = round_up(r, 8)
    block_rows = fit_block(min(block_rows, rp), rp, 8)
    x = pad_dim(x, 0, rp)
    y = pl.pallas_call(
        functools.partial(_kernel, act_bits=act_bits),
        grid=(rp // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, c), x.dtype),
        interpret=interpret,
        name="pact",
    )(x, beta)
    return y[:r]
