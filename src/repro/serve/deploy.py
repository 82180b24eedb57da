"""Deployment parameter transform: QAT weights -> compressed serving
weights (the TPU analogue of BWQ-H's compressed crossbar layout).

``to_serving_params`` converts every quantized leaf into one of two wire
formats sharing the exact same integer grid (``_integer_grid``):

* ``layout="packed"`` — :class:`ServingWeight`: int8 (or nibble-packed
  int4) magnitudes plus the per-WB scale/bit-width LUT, consumed by the
  ``packed_matmul`` kernel;
* ``layout="bitplane"`` — :class:`BitplaneServingWeight`: the paper's
  precision-aware mapping.  Each weight block is stored as 1-bit planes
  (8 rows/byte) plus a packed sign plane, a binary (bit, block) mask LUT
  and the per-WB effective scale; a block quantized to b bits occupies
  exactly ``min(b, bits)`` live planes, so streamed bytes track the BWQ-A
  precision assignment (paper Fig. 5c OU mapping).  All tensors keep
  layer-stack dims leading, so stacked leaves ride the transformer layer
  scan and are sliced one layer at a time.

Because both layouts quantize through the same math, ``dense`` execution
composes bit-identical weights from either — the backend-parity matrix in
tests/test_backend_parity.py holds across representations, not just
kernels.  ``weight_stream_bytes`` accounts HBM traffic per step; for the
bitplane layout it counts true per-block plane occupancy (a 2-bit block
streams 2 planes, not a dtype-wide word).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bitrep import QuantizedTensor, _levels
from ..core.blocking import BlockingSpec, expand_block_map, pad_to_blocks
from ..core.fakequant import FakeQuantTensor
from ..core.quantize import pack_int4, unpack_int4

SERVING_LAYOUTS = ("packed", "bitplane")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ServingWeight:
    """Packed integer weight + per-WB dequant metadata."""
    w_int: jnp.ndarray       # (..., Kp, Np) int8  or (..., Kp//2, Np) uint8
    scale: jnp.ndarray       # (..., GR, GC) f32 per-WB effective scale
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    spec: BlockingSpec = dataclasses.field(metadata=dict(static=True))
    bits: int = dataclasses.field(default=8, metadata=dict(static=True))
    # How a mesh-placed engine laid the payload over the 'model' axis:
    # 'col' (output columns), 'row' (input rows) or '' (whole).  Set only
    # by the engine's placement (``serve.engine._mark_splits``); a kernel
    # call under that mesh splits its work the same way.
    split: str = dataclasses.field(default="", metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BitplaneServingWeight:
    """Bit-plane-sliced weight: the paper's precision-aware OU mapping.

    Layer-stack dims lead every tensor (scan-sliceable, unlike the QAT
    ``QuantizedTensor`` whose bit axis leads).  ``Kp8`` is the WB-padded
    row count rounded up to a byte boundary — an odd block-padded K (the
    9x8 paper geometry) packs zero rows up to the next multiple of 8,
    mirroring the packed layout's odd-K nibble trick.  ``mask[b, g, h]``
    is 1 iff block (g, h) keeps plane ``b`` live; dequantization is
    ``(1 - 2*sign) * sum_b 2^b plane_b mask_b * scale`` with the per-WB
    effective ``scale`` pre-folding /(2^n - 1) and each block's
    power-of-two container rescale."""
    planes: jnp.ndarray      # (..., bits, Kp8//8, Np) uint8 packed planes
    sign: jnp.ndarray        # (..., Kp8//8, Np) uint8 packed sign plane
    mask: jnp.ndarray        # (..., bits, GR, GC) f32 in {0., 1.}
    scale: jnp.ndarray       # (..., GR, GC) f32 per-WB effective scale
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    spec: BlockingSpec = dataclasses.field(metadata=dict(static=True))
    bits: int = dataclasses.field(default=8, metadata=dict(static=True))
    # Static identity label (tree path), set by the autotune calibration
    # pass: it survives the per-layer tree_map slicing of scan_or_loop, so
    # the qmatmul activation recorder can key captured statistics back to
    # the stacked deployed leaf.  Empty outside calibration.
    tag: str = dataclasses.field(default="", metadata=dict(static=True))
    split: str = dataclasses.field(default="", metadata=dict(static=True))


def _integer_grid(w, scale, bitwidth, spec, n_bits, bits):
    """Quantization math shared by both serving layouts.

    Returns ``(wq, gscale, shape)``: block-padded signed integers
    (..., Kp, Np) in [-2^(bits-1), 2^(bits-1)-1], the per-WB effective
    scale (..., GR, GC) with each block's power-of-two container rescale
    folded in, and the true (unpadded) shape."""
    shape = tuple(w.shape)
    wp = pad_to_blocks(w, spec)
    s = scale[..., None, None] if scale.ndim else scale
    levels = _levels(n_bits)
    q = jnp.round(jnp.abs(wp) / s * levels)
    cap = expand_block_map(2.0 ** bitwidth - 1.0, spec)
    q = jnp.clip(q, 0.0, cap)
    signed = jnp.where(wp < 0, -1.0, 1.0) * q
    # rescale blocks exceeding the container (bits-1 magnitude bits)
    shift = jnp.maximum(bitwidth - float(bits - 1), 0.0)
    factor = 2.0 ** shift
    f_full = expand_block_map(factor, spec)
    lim = 2 ** (bits - 1)
    wq = jnp.clip(jnp.round(signed / f_full), -lim, lim - 1).astype(jnp.int32)
    gscale = jnp.broadcast_to(
        (scale[..., None, None] if scale.ndim else scale) / levels,
        bitwidth.shape) * factor
    return wq, gscale.astype(jnp.float32), shape


def _pack_packed(wq, gscale, shape, spec, bits) -> ServingWeight:
    if bits == 8:
        w_int = wq.astype(jnp.int8)
    elif bits == 4:
        if wq.shape[-2] % 2:
            # nibble pairs pack along K: pad odd block-padded K with a zero
            # row (serving_compose trims back to ``shape``)
            pad = [(0, 0)] * wq.ndim
            pad[-2] = (0, 1)
            wq = jnp.pad(wq, pad)
        w_int = pack_int4(wq, axis=-2)
    else:
        raise ValueError(bits)
    return ServingWeight(w_int=w_int, scale=gscale, shape=shape, spec=spec,
                         bits=bits)


def _pack_bitplane(wq, gscale, bitwidth, shape, spec,
                   bits) -> BitplaneServingWeight:
    """Slice the shared integer grid into packed 1-bit planes.

    A block whose live bit-width is bw keeps ``min(bw, bits)`` planes:
    below the container every magnitude fits in bw bits; at/above it the
    container rescale leaves at most ``bits`` significant bits (the -2^(
    bits-1) clip endpoint lands exactly on plane ``bits-1``)."""
    from ..kernels.ref import pack_bits
    kp = wq.shape[-2]
    kp8 = -(-kp // 8) * 8
    if kp8 != kp:                    # odd block-padded K: zero byte-pad rows
        pad = [(0, 0)] * wq.ndim
        pad[-2] = (0, kp8 - kp)
        wq = jnp.pad(wq, pad)
    mag = jnp.abs(wq)
    planes = jnp.stack([((mag >> b) & 1).astype(jnp.uint8)
                        for b in range(bits)], axis=-3)
    planes_packed = pack_bits(planes)              # (..., bits, Kp8//8, Np)
    sign_packed = pack_bits((wq < 0).astype(jnp.uint8))
    live = jnp.minimum(bitwidth, float(bits))      # (..., GR, GC)
    plane_idx = jnp.arange(bits, dtype=live.dtype).reshape((bits, 1, 1))
    mask = (plane_idx < live[..., None, :, :]).astype(jnp.float32)
    return BitplaneServingWeight(planes=planes_packed, sign=sign_packed,
                                 mask=mask, scale=gscale, shape=shape,
                                 spec=spec, bits=bits)


def _quantize_leaf(w, scale, bitwidth, spec, n_bits, bits,
                   layout: str = "packed"):
    wq, gscale, shape = _integer_grid(w, scale, bitwidth, spec, n_bits, bits)
    if layout == "bitplane":
        return _pack_bitplane(wq, gscale, bitwidth, shape, spec, bits)
    return _pack_packed(wq, gscale, shape, spec, bits)


def _convert_leaf(x, bits: int, layout: str):
    if isinstance(x, QuantizedTensor):
        from ..core.bitrep import compose
        return _quantize_leaf(compose(x), x.scale,
                              jnp.sum(x.mask, axis=0), x.spec,
                              x.n_bits, bits, layout)
    if isinstance(x, FakeQuantTensor):
        return _quantize_leaf(x.w, x.scale, x.bitwidth, x.spec,
                              x.n_bits, bits, layout)
    return x


def _is_quant(x) -> bool:
    return isinstance(x, (QuantizedTensor, FakeQuantTensor))


def _serving_params_from_ckpt(path: str, bits: int, layout: str,
                              template: Any, stats: Any) -> Any:
    """Stream a checkpoint straight into serving form, leaf by leaf.

    One quantized leaf's f32 working set is resident at a time — the
    dense tree is never materialized on the host, which is what makes a
    fleet cold-start from a multi-GB checkpoint fit in serving-host RAM.
    ``template`` is the *abstract* QAT tree (``api.abstract_params()``)
    that carries the static structure (BlockingSpec, n_bits, ...) the
    checkpoint does not store.  TrainState checkpoints are recognized by
    their ``.params`` key prefix, so the optimizer state is never read."""
    from ..ckpt.checkpoint import CheckpointReader
    reader = CheckpointReader(path)
    try:
        keys = set(reader.keys())
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            template, is_leaf=_is_quant)
        prefix = ""
        probe = jax.tree_util.keystr(flat[0][0]) if flat else ""
        if not any(k.startswith(probe) for k in keys) \
                and any(k.startswith(".params") for k in keys):
            prefix = ".params"

        peak = in_flight = dense = 0
        out_leaves = []
        for p, leaf in flat:
            base = prefix + jax.tree_util.keystr(p)
            cflat, cdef = jax.tree_util.tree_flatten_with_path(leaf)
            arrays = []
            for cp, _ in cflat:
                arr = reader.read(base + jax.tree_util.keystr(cp))
                arrays.append(arr)
                in_flight += arr.nbytes
            peak = max(peak, in_flight)
            rebuilt = jax.tree_util.tree_unflatten(
                cdef, [jnp.asarray(a) for a in arrays])
            out_leaves.append(_convert_leaf(rebuilt, bits, layout))
            for arr in arrays:
                in_flight -= arr.nbytes
                dense += arr.nbytes
            del arrays, rebuilt
        if isinstance(stats, dict):
            stats.update(peak_host_bytes=peak, dense_tree_bytes=dense,
                         leaves=len(flat), source=path)
        return jax.tree_util.tree_unflatten(treedef, out_leaves)
    finally:
        reader.close()


def deploy_from_seed(api, key, bits: int = 8,
                     layout: str = "packed") -> Any:
    """Serving weights for ``api``'s model drawn from ``key``, built
    without ever holding the f32 master tree.

    Plain leaves (embeddings, norms, clip values) come from one program
    that runs the model's init with every quantized leaf dead.  Each
    quantized leaf of ``api.abstract_params()`` is drawn one layer slice
    at a time inside a ``lax.map``: ``make_weight``'s N(0, 1/fan_in)
    draw, then ``_convert_leaf``.  So at most one (K, N) f32 slice is live
    on the device, and each distinct leaf shape compiles once."""
    flat, treedef = jax.tree_util.tree_flatten(api.abstract_params(),
                                               is_leaf=_is_quant)
    plain = [i for i, x in enumerate(flat) if not _is_quant(x)]

    def init_plain(k):
        leaves = jax.tree_util.tree_flatten(api.init(k), is_leaf=_is_quant)[0]
        return [leaves[i] for i in plain]

    out = dict(zip(plain, jax.jit(init_plain)(key)))
    for i, leaf in enumerate(flat):
        if i in out:
            continue
        lead = leaf.shape[:-2]
        keys = jax.random.split(jax.random.fold_in(key, i),
                                int(np.prod(lead, dtype=np.int64)))
        sw = _draw_layers(keys, leaf.shape[-2:], api.cfg.quant, bits, layout)
        sw = jax.tree_util.tree_map(
            lambda a: a.reshape(*lead, *a.shape[1:]), sw)
        out[i] = dataclasses.replace(sw, shape=tuple(leaf.shape))
    tree = jax.tree_util.tree_unflatten(treedef, [out[i]
                                                  for i in range(len(flat))])
    _check_contracts(tree)
    return tree


@functools.partial(jax.jit, static_argnames=("kn", "qc", "bits", "layout"))
def _draw_layers(keys, kn, qc, bits, layout):
    """One deployed (K, N) slice per key, stacked along a new lead dim."""
    from ..models.common import make_weight
    return jax.lax.map(
        lambda k: _convert_leaf(make_weight(k, kn, qc), bits, layout), keys)


def _check_contracts(tree: Any) -> None:
    from ..analysis.contracts import validate_serving_tree
    bad = [f for f in validate_serving_tree(tree) if f.severity == "error"]
    if bad:
        raise ValueError("deployment produced a contract-violating tree:\n"
                         + "\n".join(f.format() for f in bad[:8]))


def to_serving_params(params: Any, bits: int = 8, layout: str = "packed",
                      validate: bool = True, template: Any = None,
                      stats: Any = None) -> Any:
    """Convert all quantized leaves to the chosen serving wire format.

    ``params`` is either a live QAT tree or a **checkpoint directory
    path** — the latter streams shard-by-shard through
    :func:`_serving_params_from_ckpt` (requires ``template``, the
    abstract QAT tree) without ever materializing the dense f32 tree;
    ``stats`` (a dict, mutated in place) then reports
    ``peak_host_bytes`` vs ``dense_tree_bytes``.

    ``validate`` contract-checks the result (``analysis.contracts``) so a
    packing bug is caught at deploy time with a path-qualified diagnostic
    rather than as a parity failure deep in a kernel."""
    if layout not in SERVING_LAYOUTS:
        raise ValueError(f"unknown serving layout {layout!r}; "
                         f"choose from {SERVING_LAYOUTS}")

    if isinstance(params, str):
        if template is None:
            raise ValueError(
                "to_serving_params(checkpoint_path, ...) needs template= "
                "(the abstract QAT tree from api.abstract_params())")
        out = _serving_params_from_ckpt(params, bits, layout, template,
                                        stats)
    else:
        out = jax.tree_util.tree_map(
            lambda x: _convert_leaf(x, bits, layout), params,
            is_leaf=_is_quant)
    if validate:
        _check_contracts(out)
    return out


def serving_to_packed_layout(sw: ServingWeight):
    """Adapt a (2-D) ServingWeight leaf to the kernel-facing PackedLayout.

    Zero-copy: both sides share the wire format (see kernels/ops.py for the
    contract), so deployment packing feeds ``packed_matmul`` directly.  The
    per-WB scale already folds each block's power-of-two rescale factor, so
    blocks quantized to fewer bits dequantize exactly — BWQ's mixed
    precision reaches the kernel instead of being flattened to uniform
    int8.  Stacked leaves (L/E leading dims) are sliced by the layer scan
    before they get here; ``sw.shape`` then still carries the stacked true
    shape, so only the trailing (K, N) may be consulted.
    """
    from ..kernels.ops import PackedLayout
    return PackedLayout(w_int=sw.w_int, scale=sw.scale, bits=sw.bits,
                        wbr=sw.spec.wb_rows, wbc=sw.spec.wb_cols)


def serving_to_bitplane_layout(sw: BitplaneServingWeight):
    """Adapt a (2-D) BitplaneServingWeight leaf to the kernel-facing
    BitplaneLayout.  Zero-copy, like :func:`serving_to_packed_layout`;
    the per-WB effective ``scale`` LUT rides along, selecting the
    kernel's pre-folded per-block dequant path.  Stacked leaves are
    sliced by the layer scan before they get here."""
    from ..kernels.ops import BitplaneLayout
    return BitplaneLayout(planes_packed=sw.planes, sign_packed=sw.sign,
                          mask=sw.mask, scale=sw.scale, n_bits=sw.bits,
                          wbr=sw.spec.wb_rows, wbc=sw.spec.wb_cols)


def default_deploy_bits(backend: str, deploy_bits: int) -> int:
    """CLI rule with one owner: packed execution backends need packed
    weights, so an unset ``--deploy-bits`` defaults to int8 for them."""
    return deploy_bits or (8 if backend != "dense" else 0)


def default_deploy_layout(backend: str) -> str:
    """The wire format a backend executes natively: ``bitplane`` streams
    plane-sliced weights, everything else the packed integer form."""
    return "bitplane" if backend == "bitplane" else "packed"


def bitplane_stream_bytes(sw: BitplaneServingWeight) -> int:
    """Streamed bytes for one pass over a bit-plane leaf, by occupancy.

    Each live (bit, block) mask entry streams one wbr x wbc 1-bit plane
    tile; a block with any live plane also streams its sign tile (fully
    masked blocks are skipped whole, like the OUs the memory controller
    never fetches).  The per-WB scale LUT streams as stored f32 and the
    binary mask LUT at one bit per entry.  Byte-boundary padding rows are
    not billed — they exist only for the packed wire format."""
    wbr, wbc = sw.spec.wb_rows, sw.spec.wb_cols
    mask = np.asarray(sw.mask)
    live_planes = int(mask.sum())
    live_blocks = int((mask.sum(axis=-3) > 0).sum())
    plane_bits = (live_planes + live_blocks) * wbr * wbc
    mask_bits = mask.size
    return int(-(-plane_bits // 8) + -(-mask_bits // 8)
               + int(sw.scale.nbytes))


def weight_stream_bytes(params) -> int:
    """HBM bytes of weight state one full forward/decode step streams.

    BitplaneServingWeight leaves count per-block plane occupancy
    (:func:`bitplane_stream_bytes`) — the first accounting where streamed
    bytes vary with the BWQ-A precision assignment; packed ServingWeight
    leaves count their packed payload (w_int + per-WB scales); QAT
    representations and plain arrays count every array leaf as stored —
    which is exactly what the dense backend reads per step.
    """
    total = 0
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, BitplaneServingWeight))
    for leaf in leaves:
        if isinstance(leaf, BitplaneServingWeight):
            total += bitplane_stream_bytes(leaf)
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total


def serving_compose(sw: ServingWeight, dtype=jnp.bfloat16) -> jnp.ndarray:
    """In-graph dequantization (int8/int4 stream -> bf16 weights)."""
    if sw.bits == 8:
        wq = sw.w_int.astype(jnp.float32)
    else:
        wq = unpack_int4(sw.w_int, axis=-2).astype(jnp.float32)
    s_full = expand_block_map(sw.scale, sw.spec)
    # odd block-padded K packs one zero row; trim back to the scale map
    wq = wq[..., :s_full.shape[-2], :]
    w = wq * s_full
    k, n = sw.shape[-2], sw.shape[-1]
    return w[..., :k, :n].astype(dtype)


def repack_bitplane_leaf(sw: BitplaneServingWeight,
                         new_occ) -> BitplaneServingWeight:
    """Re-pack a bit-plane leaf to reduced per-block plane occupancies.

    ``new_occ`` is an (..., GR, GC) integer-valued array with
    ``0 <= new_occ <= current occupancy``.  A block dropping ``d`` planes
    re-rounds its magnitudes onto the coarser grid — ``q' = clip(round(
    q / 2^d), 0, 2^new_occ - 1)`` — and folds ``2^d`` into its effective
    scale entry, so the emitted leaf is a *valid* deployment: the mask is
    prefix-monotone over the new occupancies (BP2) and byte-pad rows stay
    zero (BP1).  Blocks with ``d == 0`` are reproduced bit-identically,
    so a full-budget allocation round-trips the deployed tree exactly.
    Host-side numpy: this runs in the offline autotune search, never on
    the serving hot path.
    """
    from ..kernels.ref import pack_bits, unpack_bits
    wbr, wbc = sw.spec.wb_rows, sw.spec.wb_cols
    mask = np.asarray(sw.mask, dtype=np.float64)    # (..., bits, GR, GC)
    occ = mask.sum(axis=-3)                         # (..., GR, GC)
    new_occ = np.asarray(new_occ, dtype=np.float64)
    if new_occ.shape != occ.shape:
        raise ValueError(f"new_occ shape {new_occ.shape} != grid {occ.shape}")
    if np.any(new_occ < 0) or np.any(new_occ > occ):
        raise ValueError("new occupancy must lie in [0, deployed occupancy]")
    bits = sw.bits
    gr, gc = mask.shape[-2], mask.shape[-1]
    kp, np_ = gr * wbr, gc * wbc
    planes = np.asarray(unpack_bits(sw.planes), dtype=np.float64)
    kp8 = planes.shape[-2]

    def _expand(block_map):                         # (..., GR, GC) -> (Kp, Np)
        return np.repeat(np.repeat(block_map, wbr, axis=-2), wbc, axis=-1)

    weights = (2.0 ** np.arange(bits)).reshape((bits, 1, 1))
    m_full = _expand(mask)                          # (..., bits, Kp, Np)
    mag = (planes[..., :kp, :] * m_full * weights).sum(axis=-3)
    drop = occ - new_occ
    q = np.round(mag / 2.0 ** _expand(drop))
    # round() can carry into plane new_occ (q near the old ceiling); clip
    # back onto the coarser grid so the prefix mask stays exact.
    q = np.minimum(q, 2.0 ** _expand(new_occ) - 1.0).astype(np.int64)
    new_planes = np.stack([((q >> b) & 1).astype(np.uint8)
                           for b in range(bits)], axis=-3)
    if kp8 != kp:                                   # restore byte-pad rows
        pad = [(0, 0)] * new_planes.ndim
        pad[-2] = (0, kp8 - kp)
        new_planes = np.pad(new_planes, pad)
    plane_idx = np.arange(bits, dtype=np.float64).reshape((bits, 1, 1))
    new_mask = (plane_idx < new_occ[..., None, :, :]).astype(np.float32)
    new_scale = (np.asarray(sw.scale, dtype=np.float64)
                 * 2.0 ** drop).astype(np.float32)
    return dataclasses.replace(
        sw, planes=pack_bits(jnp.asarray(new_planes)),
        mask=jnp.asarray(new_mask), scale=jnp.asarray(new_scale))


def bitplane_serving_compose(sw: BitplaneServingWeight,
                             dtype=jnp.bfloat16) -> jnp.ndarray:
    """In-graph dequantization of the bit-plane layout (dense backend).

    Elementwise identical to :func:`serving_compose` on the packed form
    of the same leaf: the plane sum reproduces each |wq| exactly (integer
    arithmetic below 2^bits is exact in f32) and the per-WB effective
    scale is the same LUT, so the two layouts are interchangeable under
    ``dense`` execution."""
    from ..kernels.ref import unpack_bits
    planes = unpack_bits(sw.planes)                # (..., bits, Kp8, Np)
    sign = 1.0 - 2.0 * unpack_bits(sw.sign)        # (..., Kp8, Np)
    m_full = expand_block_map(sw.mask, sw.spec)    # (..., bits, Kp, Np)
    kp = m_full.shape[-2]
    weights = (2.0 ** jnp.arange(sw.bits, dtype=jnp.float32)
               ).reshape((sw.bits, 1, 1))
    mag = jnp.sum(planes[..., :kp, :] * m_full * weights, axis=-3)
    w = sign[..., :kp, :] * (mag * expand_block_map(sw.scale, sw.spec))
    k, n = sw.shape[-2], sw.shape[-1]
    return w[..., :k, :n].astype(dtype)
