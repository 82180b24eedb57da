"""High-level jitted wrappers: QuantizedTensor -> kernel-ready layouts.

``to_bitplane_layout`` / ``to_packed_layout`` convert a trained
QuantizedTensor (after requantization) into the deployment tensors the
Pallas kernels consume; ``bwq_dense_*`` are drop-in y = x @ W ops.

PackedLayout / ServingWeight contract
-------------------------------------
:class:`PackedLayout` is the kernel-facing view of one packed matrix and
:class:`repro.serve.deploy.ServingWeight` is the same wire format carried
inside a model param tree (plus the true, unpadded ``shape``).  Both obey:

* geometry comes from the per-WB ``scale`` grid — Kp = GR * wbr rows and
  Np = GC * wbc cols of *block-padded* weight; a ServingWeight's true
  (K, N) = ``shape[-2:]`` satisfies K <= Kp, N <= Np and the padded tail
  is exact zeros;
* ``w_int`` stores int8 rows directly, or int4 two's-complement nibble
  pairs packed along K (row 2j in the low nibble).  An odd Kp packs one
  trailing zero row so ``w_int`` has ceil(Kp/2) byte rows;
* ``scale`` is the per-WB *effective* scale: blocks whose live bit-width
  exceeds the container are power-of-two rescaled at pack time with the
  factor folded into their scale entry, so ``dequant = w_int * scale``
  reproduces every block at its own effective bit-width exactly (BWQ's
  mixed precision stays visible to the kernel — nothing is flattened to
  uniform int8);
* dequantization is therefore always ``expand_block_map(scale) * w_int``
  followed by trimming to the true (K, N).

``serve.deploy.serving_to_packed_layout`` adapts a ServingWeight leaf to a
PackedLayout with no copy; ``models.common.qmatmul`` is the call site that
routes model matmuls here.  The plane-sliced serving wire format
(``serve.deploy.BitplaneServingWeight`` -> :class:`BitplaneLayout` via
``serving_to_bitplane_layout``) obeys the same scale-grid geometry, with
a per-WB *effective* scale LUT instead of the per-layer scalar and K
byte-padded up to a multiple of 8 for the 1-bit packing.

Contract appendix — the statically checkable rules
--------------------------------------------------
``repro.analysis.contracts.validate_serving_tree`` enforces the above
declaratively at engine construction and deploy time; each rule id below
is what its path-qualified findings cite (see README "Static analysis &
lint"):

* ``SW1`` — ``scale`` is (..., GR, GC): the per-WB grid IS the geometry.
* ``SW2`` — the grid is the *minimal* block cover of the true shape:
  K <= GR*wbr < K + wbr and N <= GC*wbc < N + wbc.
* ``SW3`` — layer-stack dims LEAD every tensor (scan-sliceable; the QAT
  ``QuantizedTensor`` whose bit axis leads is NOT a serving layout).
* ``SW4`` — payload dtype/shape per precision: bits=8 -> int8
  (..., Kp, Np); bits=4 -> uint8 (..., ceil(Kp/2), Np) nibble pairs,
  and an odd Kp's high pad nibble is exact zeros.
* ``BP1`` — ``planes`` (..., bits, Kp8//8, Np) and ``sign``
  (..., Kp8//8, Np) uint8 with Kp8 = ceil(Kp/8)*8; byte-pad rows are
  zeros (the byte-boundary mirror of SW4's nibble rule).
* ``BP2`` — ``mask`` is (..., bits, GR, GC) f32, binary, and
  prefix-monotone along the bit axis: block occupancy is its
  min(bw, bits) LOW planes — exactly the OU occupancy
  ``weight_stream_bytes`` bills.
* ``BP3`` — ``scale`` LUT is f32 and finite (it pre-folds /(2^n - 1)
  and each block's power-of-two container rescale, so a NaN/inf here
  silently poisons every dequant).
* ``PC1``-``PC3`` — paged decode caches: the pool is a
  ``kv_pages.PagePool`` whose leaves have the lane-dense page storage of
  their per-token shapes, block tables are integer
  (stack, n_slots, nb) with every id inside the pool (``PC2`` flags
  orphaned ids and un-refcounted page sharing), and quantized pools
  carry their per-token scale leaves.
* ``PA1``-``PA3`` — fused paged-attention invariants: k/v pools agree
  on dtype/shape and carry float32 scales, one per token and KV head
  (``PA1``); the pool holds the
  reserved trash page 0 plus >= 1 allocatable page and >= 1 block per
  slot row (``PA2``); a slot's live pages are a contiguous prefix of
  its table row — the kernel walks blocks in order and the fill level
  masks only the trash tail (``PA3``).
* ``PX1``-``PX3`` — live-scheduler ledger invariants
  (``analysis.contracts.validate_scheduler``): prefix-cache refcounts
  equal the live slots aliasing each shared page and the allocator's
  in-use count closes against slot + cache ownership (``PX1``, so a
  parked snapshot holds no pool pages); every slot's write frontier
  sits at or past its shared-prefix region — shared pages are
  read-only (``PX2``); free/parked block-table rows are all zeros and
  live rows mirror the host ledger exactly (``PX3``).
* ``AT1`` — an autotuned assignment respects its byte budget exactly:
  ``weight_stream_bytes(tree) <= budget`` under the same occupancy
  accounting the allocator optimized against (no double bookkeeping).
* ``AT2`` — a speculative draft tree is a pure mask-truncation view of
  the deployed tree: payload tensors (planes/sign/scale) are shared,
  and each block's draft mask keeps exactly its ``min(k, occ)`` HIGHEST
  live planes — a contiguous top run of the deployed prefix, so the
  draft reads a strict subset of the bytes the verify pass streams.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from ..core.bitrep import QuantizedTensor, compose_int, _levels
from .bitplane_matmul import bitplane_matmul
from .packed_matmul import packed_matmul
from .ref import pack_bits


class BitplaneLayout(NamedTuple):
    planes_packed: jnp.ndarray   # (n, K//8, N) uint8
    sign_packed: jnp.ndarray     # (K//8, N) uint8
    mask: jnp.ndarray            # (n, K//wbr, N//wbc) f32
    scale: jnp.ndarray           # (1,) per-layer OR (K//wbr, N//wbc) per-WB
    n_bits: int
    wbr: int
    wbc: int


class PackedLayout(NamedTuple):
    w_int: jnp.ndarray           # int8 (K,N) or uint8 (K//2, N) nibbles
    scale: jnp.ndarray           # (K//wbr, N//wbc)
    bits: int
    wbr: int
    wbc: int


def to_bitplane_layout(qt: QuantizedTensor) -> BitplaneLayout:
    """Requires a TPU-aligned spec (wb_rows multiple-of-8-compatible: K%8==0)."""
    assert qt.planes.ndim == 3, "single matrix expected"
    q = jnp.clip(jnp.round(compose_int(qt)), 0, _levels(qt.n_bits))
    q = q.astype(jnp.int32)
    planes = jnp.stack([((q >> b) & 1).astype(jnp.uint8)
                        for b in range(qt.n_bits)])
    planes_packed = pack_bits(planes)
    sign_bits = (qt.sign < 0).astype(jnp.uint8)
    sign_packed = pack_bits(sign_bits[None])[0]
    scale = jnp.reshape(qt.scale.astype(jnp.float32), (1,))
    return BitplaneLayout(planes_packed, sign_packed,
                          qt.mask.astype(jnp.float32), scale, qt.n_bits,
                          qt.spec.wb_rows, qt.spec.wb_cols)


def to_packed_layout(qt: QuantizedTensor, bits: int = 8) -> PackedLayout:
    """Per-WB scale folded so each block uses its own bitwidth ceiling.

    A WB with bitwidth bw stores magnitudes in [0, 2^bw-1]; rescaling by
    2^(n-bw) maps them onto the shared int grid without precision loss when
    bw <= bits-1 (sign takes one bit in two's complement).
    """
    q = jnp.clip(jnp.round(compose_int(qt)), 0, _levels(qt.n_bits))
    signed = qt.sign * q                                  # (K, N)
    spec = qt.spec
    gscale = qt.scale.astype(jnp.float32) / _levels(qt.n_bits)
    gr, gc = qt.mask.shape[-2], qt.mask.shape[-1]
    block_scale = jnp.broadcast_to(jnp.reshape(gscale, (1, 1)), (gr, gc))
    # Blocks whose live bit-width exceeds the container (bits-1 magnitude
    # bits after the sign) are rescaled by a power of two: exact whenever
    # bw <= bits-1, drops (bw - bits + 1) LSBs otherwise.
    from ..core.blocking import expand_block_map
    bw = jnp.sum(qt.mask, axis=0)                         # (GR, GC)
    shift = jnp.maximum(bw - float(bits - 1), 0.0)
    factor = 2.0 ** shift
    f_full = expand_block_map(factor, spec)
    lim = 2 ** (bits - 1)
    wq = jnp.clip(jnp.round(signed / f_full), -lim, lim - 1).astype(jnp.int32)
    if bits == 8:
        return PackedLayout(wq.astype(jnp.int8), block_scale * factor, 8,
                            spec.wb_rows, spec.wb_cols)
    if bits == 4:
        lo = wq[0::2] & 0xF
        hi = wq[1::2] & 0xF
        packed = (lo | (hi << 4)).astype(jnp.uint8)
        return PackedLayout(packed, block_scale * factor, 4,
                            spec.wb_rows, spec.wb_cols)
    raise ValueError(bits)


def truncate_mask_topk(mask: jnp.ndarray, k: int) -> jnp.ndarray:
    """Draft-model view of a BP2 mask LUT: keep each block's top-k planes.

    ``mask`` is (..., bits, GR, GC) binary f32, prefix-monotone along the
    bit axis (a block with occupancy ``o`` keeps planes ``0..o-1``).  The
    returned LUT keeps planes ``max(o-k, 0)..o-1`` — the k *highest* live
    planes — so composing the same payload through it floors away the low
    ``o-k`` magnitude bits: a coarser read of identical bytes, which is
    what makes bitplane truncation a free draft model.  The result is NOT
    prefix-monotone (it deliberately zeroes low planes), so draft trees
    bypass BP2 validation and are checked by the AT2 contract instead.
    Zero-cost at trace time: the kernel multiplies planes by the mask, so
    ``bitplane_matmul`` consumes the truncated LUT unchanged.
    """
    if k < 0:
        raise ValueError(f"k must be >= 0, got {k}")
    bits = mask.shape[-3]
    occ = jnp.sum(mask, axis=-3, keepdims=True)       # (..., 1, GR, GC)
    idx = jnp.arange(bits, dtype=mask.dtype).reshape((bits, 1, 1))
    return mask * (idx >= occ - float(k)).astype(mask.dtype)


def bwq_dense_bitplane(x, layout: BitplaneLayout,
                       interpret: bool | None = None):
    """y = x @ W from the bit-plane layout (interpret auto-detected)."""
    return bitplane_matmul(x, layout.planes_packed, layout.sign_packed,
                           layout.mask, layout.scale, n_bits=layout.n_bits,
                           wbr=layout.wbr, wbc=layout.wbc,
                           interpret=interpret)


def bwq_dense_packed(x, layout: PackedLayout, interpret: bool | None = None):
    """y = x @ W from the packed-integer layout (interpret auto-detected)."""
    return packed_matmul(x, layout.w_int, layout.scale, bits=layout.bits,
                         wbr=layout.wbr, wbc=layout.wbc, interpret=interpret)
