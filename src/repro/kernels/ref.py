"""Pure-jnp oracles for every kernel (the allclose targets).

Layouts (TPU-native WB geometry — see DESIGN.md §2; wb_rows=8, wb_cols=128
by default so block boundaries align with sublanes/lanes):

* bit-plane: ``planes_packed`` (n, K//8, N) uint8, bit r of byte j = plane
  value at row 8j+r; ``sign_packed`` (K//8, N) uint8 (1 = negative);
  ``mask`` (n, K//wbr, N//wbc) {0,1}; ``scale`` () per-layer.
* packed-int: ``w_int`` int8 (K, N) signed magnitudes (int8 mode) or
  (K//2, N) uint8 two nibbles (int4 mode, row 2j in low nibble);
  ``scale`` (K//wbr, N//wbc) per-WB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def unpack_bits(packed: jnp.ndarray) -> jnp.ndarray:
    """(..., K//8, N) uint8 -> (..., K, N) {0,1} float32 (row-major bits)."""
    bits = [(packed >> r) & 1 for r in range(8)]
    x = jnp.stack(bits, axis=-2)                   # (..., K//8, 8, N)
    shape = x.shape[:-3] + (x.shape[-3] * 8, x.shape[-1])
    return x.reshape(shape).astype(jnp.float32)


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., K, N) {0,1} -> (..., K//8, N) uint8."""
    k = bits.shape[-2]
    x = bits.reshape(*bits.shape[:-2], k // 8, 8, bits.shape[-1])
    x = x.astype(jnp.uint8)
    out = jnp.zeros(x.shape[:-2] + (x.shape[-1],), jnp.uint8)
    for r in range(8):
        out = out | (x[..., r, :] << r)
    return out


def expand_mask(mask: jnp.ndarray, wbr: int, wbc: int) -> jnp.ndarray:
    m = jnp.repeat(mask, wbr, axis=-2)
    return jnp.repeat(m, wbc, axis=-1)


def bitplane_matmul_ref(x, planes_packed, sign_packed, mask, scale,
                        wbr: int = 8, wbc: int = 128,
                        out_dtype=jnp.float32) -> jnp.ndarray:
    """y = x @ W, W = (1-2*sign) * scale/(2^n -1) * sum_b 2^b plane_b*mask_b.

    ``scale``: scalar per-layer (divided by ``2^n - 1`` here) or a 2-D
    (K//wbr, N//wbc) per-WB *effective* scale LUT (serving layout, applied
    as-is — the /(2^n-1) and per-block rescale factors are pre-folded).
    ``planes_packed`` may pack beyond the K//wbr WB rows up to a byte
    boundary (odd block-padded K); the surplus rows are trimmed, and ``x``
    with fewer than K columns is zero-filled like the packed oracle."""
    n = planes_packed.shape[0]
    planes = unpack_bits(planes_packed)            # (n, K8, N)
    sign = 1.0 - 2.0 * unpack_bits(sign_packed)    # (K8, N) in {+1,-1}
    m = jax.vmap(lambda mm: expand_mask(mm, wbr, wbc))(mask)
    kp = m.shape[-2]
    if planes.shape[-2] > kp:      # byte-pad rows beyond the WB grid
        planes = planes[..., :kp, :]
        sign = sign[:kp, :]
    weights = (2.0 ** jnp.arange(n, dtype=jnp.float32))
    mag = jnp.tensordot(weights, planes * m, axes=(0, 0))
    if jnp.ndim(scale) == 2:
        w = sign * mag * expand_mask(scale, wbr, wbc)
    else:
        w = sign * mag * (scale / (2.0 ** n - 1.0))
    if x.shape[-1] < w.shape[0]:
        x = jnp.pad(x, ((0, 0), (0, w.shape[0] - x.shape[-1])))
    return (x.astype(jnp.float32) @ w).astype(out_dtype)


def packed_matmul_ref(x, w_int, scale, bits: int = 8,
                      wbr: int = 8, wbc: int = 128,
                      out_dtype=jnp.float32) -> jnp.ndarray:
    """y = x @ (dequant(w_int) * per-block scale).

    Shares the kernel's geometry contract: K/N come from the scale grid;
    an int4 odd block-padded K carries one zero pad row in the last byte
    (trimmed here), and x may have fewer than K columns (zero-filled)."""
    if bits == 8:
        w = w_int.astype(jnp.float32)
    elif bits == 4:
        lo = (w_int & 0xF).astype(jnp.int8)
        hi = ((w_int >> 4) & 0xF).astype(jnp.int8)
        # two's-complement nibbles in [-8, 7]
        lo = jnp.where(lo >= 8, lo - 16, lo).astype(jnp.float32)
        hi = jnp.where(hi >= 8, hi - 16, hi).astype(jnp.float32)
        k2, n_ = w_int.shape
        w = jnp.stack([lo, hi], axis=1).reshape(2 * k2, n_)
    else:
        raise ValueError(bits)
    s = expand_mask(scale, wbr, wbc)
    w = w[:s.shape[0]]
    if x.shape[-1] < s.shape[0]:
        x = jnp.pad(x, ((0, 0), (0, s.shape[0] - x.shape[-1])))
    return (x.astype(jnp.float32) @ (w * s)).astype(out_dtype)


def _unpack_nibbles(u: jnp.ndarray) -> jnp.ndarray:
    """(..., dh//2) uint8 nibble pairs -> (..., dh) int32 in [-8, 7]
    (``core.quantize.pack_int4`` layout: even positions in the low
    nibble)."""
    lo = (u & 0xF).astype(jnp.int32)
    hi = ((u >> 4) & 0xF).astype(jnp.int32)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(*u.shape[:-1],
                                                u.shape[-1] * 2)


def paged_attention_ref(q, k_pages, v_pages, k_scale, v_scale, table,
                        kv_len, *, page: int, layer=0, window=None,
                        softcap: float = 0.0) -> jnp.ndarray:
    """Gather-then-softmax oracle for ``kernels.paged_attention``.

    Same contract as the kernel (q (B, KV, G, dh); stacked lane-dense
    pool leaves, ``kernels.kv_pages``; per-token scales or None; (B, nb)
    table; (B,) fill levels) — but it materializes the contiguous
    (B, T, ...) view and the f32 KV tree the kernel exists to avoid, so
    it is the allclose target, never the hot path."""
    from .kv_pages import PageFormat, read_pages
    b, kv, g, dh = q.shape
    neg_inf = -2.0e38
    dh_s = dh // 2 if k_pages.dtype == jnp.uint8 else dh

    def gather(leaf, tail):
        x = read_pages(leaf, PageFormat(page, kv * tail), layer, table)
        return x.reshape(b, -1, kv, tail) if tail > 1 else \
            x.reshape(b, -1, kv)

    k, v = gather(k_pages, dh_s), gather(v_pages, dh_s)
    if k.dtype == jnp.uint8:                         # nibble-packed int4
        k, v = _unpack_nibbles(k), _unpack_nibbles(v)
    if k_scale is not None:
        k = k.astype(jnp.float32) * gather(k_scale, 1)[..., None]
        v = v.astype(jnp.float32) * gather(v_scale, 1)[..., None]
    else:
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    t = k.shape[1]
    s = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.float32), k,
                   preferred_element_type=jnp.float32) / jnp.sqrt(float(dh))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(t, dtype=jnp.int32)[None, :]
    ln = jnp.asarray(kv_len, jnp.int32)[:, None]
    valid = pos < ln
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        valid &= jnp.where(w > 0, pos >= ln - w, True)
    s = jnp.where(valid[:, None, None, :], s, neg_inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", p, v)


def latent_attention_ref(q_lat, q_pe, ckv, ckv_scale, kpe, kpe_scale,
                         table, kv_len, *, page: int, sm_scale: float,
                         layer=0) -> jnp.ndarray:
    """Gather-then-softmax oracle for ``kernels.latent_attention``: the
    same contract, materializing each row's dequantized (B, T, r) latent
    and (B, T, dr) rope keys."""
    from .kv_pages import PageFormat, read_pages
    r, dr = q_lat.shape[-1], q_pe.shape[-1]

    def gather(leaf, width):
        return read_pages(leaf, PageFormat(page, width), layer,
                          table).astype(jnp.float32)

    c, pe = gather(ckv, r), gather(kpe, dr)
    if ckv_scale is not None:
        c = c * gather(ckv_scale, 1)
        pe = pe * gather(kpe_scale, 1)
    s = (jnp.einsum("bhr,btr->bht", q_lat.astype(jnp.float32), c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,btd->bht", q_pe.astype(jnp.float32), pe,
                      preferred_element_type=jnp.float32)) * sm_scale
    pos = jnp.arange(c.shape[1], dtype=jnp.int32)[None, None, :]
    s = jnp.where(pos < jnp.asarray(kv_len, jnp.int32)[:, None, None], s,
                  -2.0e38)
    return jnp.einsum("bht,btr->bhr", jax.nn.softmax(s, axis=-1), c)


def pact_quant_ref(x, beta, act_bits: int) -> jnp.ndarray:
    """Symmetric PACT clip + uniform quantization (forward only)."""
    levels = float(2 ** (act_bits - 1) - 1)
    b = jnp.maximum(beta, 1e-6)
    y = jnp.clip(x, -b, b)
    return (jnp.round(y / b * levels) * (b / levels)).astype(x.dtype)
