"""Logical-axis sharding rules over a context-managed mesh.

The engine maps *logical* axis names ("batch", "heads", "ff", ...) and
*parameter paths* (regex over ``jax.tree_util.keystr`` strings) onto mesh
axes, maxtext-style.  Everything degrades to replicated ``P()`` no-ops when
no mesh is active, so single-device tests and examples run unchanged.

Conventions (see DESIGN.md and tests/test_sharding_rules.py):
  * column-parallel projections (wq/wk/wv, FFN up/gate) put their output
    dim on 'model';
  * row-parallel projections (wo, FFN down) put their input dim on 'model';
  * big weights additionally get their free dim sharded on 'data'
    (ZeRO-3 FSDP), gated on a size threshold and the ``FSDP`` toggle;
  * MoE routers and quantization metadata (scale / mask / bitwidth LUTs)
    stay replicated;
  * the data-parallel ("batch") logical axis spans every data-ish mesh
    axis present, in ('pod', 'data') order.

Every emitted spec is passed through :func:`fit_spec`, which drops mesh
axes that are absent or already consumed — so rules are written for the
*production* mesh and degrade per-tensor everywhere else.  Axes that
exist but do not divide the dim are handled by **padded sharding**
(``PADDED``): the axis is kept, a :class:`SpecPad` event is recorded, and
the *placement boundary* (``pad_leaf`` before ``device_put``) zero-pads
the dim to the next multiple of the mesh-axis product; the consumer
masks by slicing back to the true shape in-graph (``unpad_leaf``).  Only
boundaries pad — in-graph ``with_sharding_constraint`` sites keep the
legacy drop rule (``pad=False``) because GSPMD silently *replicates*
uneven constraint specs on this jax, which would claim sharding it does
not deliver.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import re
import threading
import warnings
from typing import Any, List, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels.kv_pages import PagePool

# --------------------------------------------------------------------------
# mesh context
# --------------------------------------------------------------------------

_STATE = threading.local()


def get_mesh():
    """The innermost active mesh, or None (single-device / replicated)."""
    stack = getattr(_STATE, "mesh_stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_mesh(mesh):
    """Activate ``mesh`` for sharding rules; ``use_mesh(None)`` is a no-op
    context (kept so launchers can write ``with use_mesh(maybe_mesh):``)."""
    stack = getattr(_STATE, "mesh_stack", None)
    if stack is None:
        stack = _STATE.mesh_stack = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()


# Data-ish mesh axes in their canonical (outer -> inner) order.
DATA_AXES: Tuple[str, ...] = ("pod", "data")

# ZeRO-3 toggle: big weights get their free dim sharded on 'data'.
# benchmarks/hillclimb.py flips "enabled" around lowering variants.
FSDP = {"enabled": True, "min_bytes": 1 << 20}

# Padded-sharding toggle: a mesh axis that does not divide a dim keeps
# the dim sharded via ceil-division padding instead of being dropped
# (vocab / kv-head dims no longer waste the whole model axis).  Callers
# can override per-call with ``fit_spec(..., pad=...)``.
PADDED = {"enabled": True}


def batch_axes(mesh=None) -> Tuple[str, ...]:
    """The data-parallel mesh axes present in ``mesh`` (pod-major)."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in DATA_AXES if a in mesh.shape)


def _batch_entry(mesh):
    dp = batch_axes(mesh)
    if not dp:
        return None
    return dp[0] if len(dp) == 1 else tuple(dp)


def data_size(mesh=None) -> int:
    """Devices along the data-parallel axes of ``mesh`` (1 without one)."""
    mesh = mesh if mesh is not None else get_mesh()
    return 1 if mesh is None else _group_size(_batch_entry(mesh), mesh)


def model_size(mesh=None) -> int:
    """Devices along the 'model' axis of ``mesh`` (1 without one)."""
    mesh = mesh if mesh is not None else get_mesh()
    return 1 if mesh is None else dict(mesh.shape).get("model", 1)


def rows_entry(n: int, mesh=None):
    """The spec entry that splits a dim of ``n`` rows over the data axes
    (None when they do not divide it, or there are none)."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None or n % data_size(mesh):
        return None
    return _batch_entry(mesh)


# logical axis name -> mesh axes (resolved against the active mesh)
_LOGICAL = {
    "batch": _batch_entry,
    "data": lambda mesh: "data",
    "pod": lambda mesh: "pod",
    "model": lambda mesh: "model",
    "heads": lambda mesh: "model",
    "kv_heads": lambda mesh: "model",
    "ff": lambda mesh: "model",
    "expert": lambda mesh: "model",
    "vocab": lambda mesh: "model",
}


class ShardingDropWarning(UserWarning):
    """A requested mesh axis did not divide its dim and was dropped."""


@dataclasses.dataclass(frozen=True)
class SpecDrop:
    """One mesh axis silently removed from a requested PartitionSpec.

    ``reason`` is ``'absent'`` (axis not in the mesh), ``'used'`` (axis
    already consumed by an earlier dim) or ``'indivisible'`` (the axis
    group's combined size does not divide the dim AND padding was
    disabled for the call — with :data:`PADDED` on, indivisible dims
    record a :class:`SpecPad` instead and stay sharded)."""
    label: str                 # leaf keystr, or '<unlabeled>'
    dim: int                   # which dim of the shape
    axis: str                  # the dropped mesh axis
    reason: str                # 'absent' | 'used' | 'indivisible'
    dim_size: int
    axis_size: int             # 0 when the axis is absent from the mesh

    def message(self) -> str:
        if self.reason == "indivisible":
            return (f"{self.label}: dim {self.dim} (size {self.dim_size}) "
                    f"is not divisible by mesh axis {self.axis!r} "
                    f"(size {self.axis_size}); axis dropped, dim serves "
                    f"replicated")
        if self.reason == "absent":
            return (f"{self.label}: dim {self.dim} requested mesh axis "
                    f"{self.axis!r}, which this mesh does not have")
        return (f"{self.label}: dim {self.dim} requested mesh axis "
                f"{self.axis!r}, already used by an earlier dim")


@dataclasses.dataclass(frozen=True)
class SpecPad:
    """One dim kept sharded by ceil-division padding.

    Recorded by :func:`fit_spec` when a requested mesh-axis group does
    not divide the dim but padded sharding is active: the placement
    boundary zero-pads ``dim_size`` up to ``padded_size`` (the next
    multiple of ``group_size``) and the consumer slices back."""
    label: str                 # leaf keystr, or '<unlabeled>'
    dim: int                   # which dim of the shape
    axes: Tuple[str, ...]      # the mesh axes kept on this dim
    dim_size: int
    padded_size: int
    group_size: int            # combined size of the kept axes

    def message(self) -> str:
        return (f"{self.label}: dim {self.dim} (size {self.dim_size}) "
                f"pads to {self.padded_size} for mesh axes "
                f"{'x'.join(self.axes)} (size {self.group_size}); "
                f"sharded via ceil-division, masked at the consumer")


@contextlib.contextmanager
def collect_spec_events():
    """Capture every :class:`SpecDrop` / :class:`SpecPad` recorded by
    :func:`fit_spec` in the dynamic extent (innermost collector wins;
    the sharding lint's event source)."""
    stack = getattr(_STATE, "spec_events", None)
    if stack is None:
        stack = _STATE.spec_events = []
    events: List[Any] = []          # SpecDrop | SpecPad
    stack.append(events)
    try:
        yield events
    finally:
        stack.pop()


_WARNED_DROPS: set = set()


def _record_drop(label: Optional[str], dim: int, axis: str, reason: str,
                 dim_size: int, axis_size: int) -> None:
    drop = SpecDrop(label=label or "<unlabeled>", dim=dim, axis=axis,
                    reason=reason, dim_size=dim_size, axis_size=axis_size)
    stack = getattr(_STATE, "spec_events", None)
    if stack:
        stack[-1].append(drop)
    if reason == "indivisible":
        key = (drop.label, dim, axis)
        if key not in _WARNED_DROPS:
            _WARNED_DROPS.add(key)
            warnings.warn(ShardingDropWarning(drop.message()), stacklevel=3)


def _record_pad(label: Optional[str], dim: int, axes: Tuple[str, ...],
                dim_size: int, padded_size: int, group_size: int) -> None:
    stack = getattr(_STATE, "spec_events", None)
    if stack:
        stack[-1].append(SpecPad(label=label or "<unlabeled>", dim=dim,
                                 axes=axes, dim_size=dim_size,
                                 padded_size=padded_size,
                                 group_size=group_size))


def spec(*logical: Optional[str]) -> P:
    """Logical axis names -> PartitionSpec against the active mesh.

    Unknown names and ``None`` map to replicated dims.  The result is NOT
    divisibility-fitted; pair with :func:`fit_spec` (``constraint`` does)."""
    mesh = get_mesh()
    if mesh is None:
        return P()
    entries = []
    for name in logical:
        fn = _LOGICAL.get(name) if name is not None else None
        entries.append(fn(mesh) if fn else None)
    return P(*entries)


def fit_spec(ps: P, shape: Sequence[int], mesh=None,
             label: Optional[str] = None, pad: Optional[bool] = None) -> P:
    """Fit ``ps`` to ``shape`` under ``mesh``: drop axes that are not in
    the mesh or already used by an earlier dim.  Always returns a spec of
    ``len(shape)`` entries.

    An axis group whose combined size does not divide the dim is kept
    via **ceil-division padded sharding** when ``pad`` is true (default:
    the :data:`PADDED` toggle) — the returned spec then describes the
    *padded* layout and placement must go through :func:`pad_leaf` /
    :func:`unpad_leaf`.  With ``pad=False`` the legacy rule applies: the
    axes are dropped, recorded as :class:`SpecDrop` events (to the
    active :func:`collect_spec_events` collector, if any), and an
    *indivisible* drop warns once per (label, dim, axis) with
    :class:`ShardingDropWarning`.  ``label`` names the tensor in those
    diagnostics (callers with tree paths pass the leaf keystr)."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return P(*([None] * len(shape)))
    do_pad = PADDED["enabled"] if pad is None else pad
    used: set = set()
    out: List[Any] = []
    for i, dim in enumerate(shape):
        entry = ps[i] if i < len(ps) else None
        if entry is None:
            out.append(None)
            continue
        axes = []
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            if a not in mesh.shape:
                _record_drop(label, i, a, "absent", dim, 0)
            elif a in used:
                _record_drop(label, i, a, "used", dim, mesh.shape[a])
            else:
                axes.append(a)
        size = math.prod(mesh.shape[a] for a in axes)
        if axes and size > 1 and dim % size and do_pad:
            # keep sharded: the boundary zero-pads dim -> next multiple
            _record_pad(label, i, tuple(axes), dim,
                        -(-dim // size) * size, size)
            used.update(axes)
            out.append(tuple(axes) if len(axes) > 1 else axes[0])
        elif not axes or size == 0 or dim % size:
            for a in axes:
                _record_drop(label, i, a, "indivisible", dim, mesh.shape[a])
            out.append(None)
        else:
            used.update(axes)
            out.append(tuple(axes) if len(axes) > 1 else axes[0])
    return P(*out)


# --------------------------------------------------------------------------
# padded placement helpers
# --------------------------------------------------------------------------

def _group_size(entry, mesh) -> int:
    axes = entry if isinstance(entry, tuple) else (entry,)
    return math.prod(mesh.shape[a] for a in axes if a in mesh.shape)


def padded_shape(ps: P, shape: Sequence[int], mesh=None) -> Tuple[int, ...]:
    """The ceil-division padded shape ``ps`` implies for ``shape``:
    every sharded dim rounds up to the next multiple of its mesh-axis
    group size (identical to ``shape`` when everything divides)."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return tuple(shape)
    out = []
    for i, dim in enumerate(shape):
        entry = ps[i] if i < len(ps) else None
        if entry is None:
            out.append(dim)
            continue
        size = _group_size(entry, mesh)
        out.append(-(-dim // size) * size if size > 1 else dim)
    return tuple(out)


def pad_leaf(x, ps: P, mesh=None):
    """Zero-pad ``x`` to :func:`padded_shape` so an uneven spec becomes
    placeable with ``device_put`` (identity when nothing pads)."""
    import numpy as np
    shape = tuple(x.shape)
    target = padded_shape(ps, shape, mesh)
    if target == shape:
        return x
    widths = [(0, t - s) for s, t in zip(shape, target)]
    if isinstance(x, np.ndarray):
        return np.pad(x, widths)
    return jax.numpy.pad(x, widths)


def unpad_leaf(x, true_shape: Sequence[int]):
    """Slice a padded leaf back to its true shape (in-graph safe: the
    mask-at-the-consumer side of padded sharding).  Identity when the
    shapes already match."""
    shape = tuple(true_shape)
    if tuple(x.shape) == shape:
        return x
    return x[tuple(slice(0, s) for s in shape)]


def constraint(x, *logical: Optional[str]):
    """``with_sharding_constraint`` by logical axis names; identity with no
    active mesh.  Trailing dims beyond ``logical`` stay replicated.

    Always fits with ``pad=False``: an in-graph constraint cannot pad
    its operand, and GSPMD silently replicates uneven constraint specs
    on this jax — dropping the axis is the honest equivalent."""
    mesh = get_mesh()
    if mesh is None:
        return x
    ps = fit_spec(spec(*logical), x.shape, mesh, pad=False)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, ps))


# --------------------------------------------------------------------------
# parameter rules (path-regex keyed, maxtext logical_axis_rules style)
# --------------------------------------------------------------------------
#
# Each rule is (compiled path regex, kind).  First match wins.
#   'replicated'  -> rank-matched P(None, ...)  (excluded from FSDP too)
#   'meta'        -> P()  (quant scales / masks / bit-width LUTs)
#   'col'         -> trailing (K, N): N on 'model', FSDP candidate dim K
#   'row'         -> trailing (K, N): K on 'model', FSDP candidate dim N
# Leading (stack / bit-plane) dims are never sharded by parameter rules.

_RULES: Tuple[Tuple[re.Pattern, str], ...] = tuple(
    (re.compile(pat), kind) for pat, kind in [
        # MoE routers stay replicated + fp32 (DESIGN.md §5): every data
        # shard routes its own tokens, no weight gather on the hot path.
        (r"router", "replicated"),
        # Quantization metadata: per-layer/per-WB scales, bit masks and
        # bit-width LUTs are tiny; replicate them everywhere.
        (r"\.(scale|mask|bitwidth)$", "meta"),
        (r"\['(k|v)_scale'\]", "meta"),
        # Norms / biases / PACT clip values: small 1-D-ish leaves.
        (r"\['(ln[_a-z0-9]*|final_norm|enc_norm|shared_ln2?|"
         r"beta_[a-z]+|b[qkv]|alpha)'\]", "replicated"),
        # Column-parallel: output dim on 'model'.
        (r"\['(wq|wk|wv|w_gate|w_up|w_in|shared_gate|shared_up|"
         r"expert_gate|expert_up|conv_pw1|lm_head|vision_proj)'\]", "col"),
        # Row-parallel: input dim on 'model'.
        (r"\['(wo|w_down|w_out|shared_down|expert_down|conv_pw2)'\]", "row"),
        # Token embedding (vocab, d): vocab rows on 'model' (matches the
        # tied lm-head orientation), free dim FSDP-able.
        (r"\['embed'\]", "row"),
    ])


def _leaf_bytes(leaf) -> int:
    try:
        return int(math.prod(leaf.shape)) * jax.dtypes.canonicalize_dtype(
            leaf.dtype).itemsize
    except (AttributeError, TypeError):
        return 0


def _leaf_spec(path: str, leaf, pad: Optional[bool] = None) -> P:
    """PartitionSpec for one parameter leaf, keyed by its keystr path.

    ``path`` is a ``jax.tree_util.keystr`` string such as
    ``"['layers']['attn']['wo'].w"``; ``leaf`` is an array or
    ShapeDtypeStruct.  Requires an active mesh (otherwise ``P()``)."""
    mesh = get_mesh()
    shape = tuple(getattr(leaf, "shape", ()))
    if mesh is None or len(shape) < 1:
        return P()
    kind = None
    for pat, k in _RULES:
        if pat.search(path):
            kind = k
            break
    if kind == "meta":
        return P()
    rank = len(shape)
    dims: List[Any] = [None] * rank
    if kind in ("col", "row") and rank >= 2:
        model_dim = rank - 1 if kind == "col" else rank - 2
        fsdp_dim = rank - 2 if kind == "col" else rank - 1
        dims[model_dim] = "model"
        if FSDP["enabled"] and "data" in mesh.shape \
                and _leaf_bytes(leaf) >= FSDP["min_bytes"]:
            dims[fsdp_dim] = "data"
    return fit_spec(P(*dims), shape, mesh, label=path, pad=pad)


def param_pspecs(params, pad: Optional[bool] = None) -> Any:
    """Tree of PartitionSpecs mirroring ``params`` (works on any pytree,
    including TrainState — optimizer moments inherit their weight's rule
    because the weight's dict key appears in their path too).

    ``pad`` selects padded sharding for indivisible dims (default: the
    :data:`PADDED` toggle); a padded spec must be placed through
    :func:`pad_leaf` and consumed through :func:`unpad_leaf`."""
    mesh = get_mesh()
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    if mesh is None:
        specs = [P() for _ in flat]
    else:
        specs = [_leaf_spec(jax.tree_util.keystr(path), leaf, pad=pad)
                 for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def shard_params_tree(params):
    """Constrain every leaf of ``params`` to its rule spec (identity with
    no active mesh).  Called once per step on the materialized tree."""
    mesh = get_mesh()
    if mesh is None:
        return params
    specs = param_pspecs(params, pad=False)   # in-graph wsc cannot pad
    return jax.tree_util.tree_map(
        lambda x, ps: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, ps)),
        params, specs)


def run_kernel(kern, *args, in_specs, out_specs, psum_axis=None):
    """Call a Pallas kernel under the active mesh.

    XLA cannot partition a Mosaic kernel, so on a multi-device mesh the
    call runs inside ``shard_map``: each device applies ``kern`` to the
    blocks ``in_specs`` give it (operands laid out otherwise are moved
    to match), and ``psum_axis`` sums partial products over that axis.
    Mesh axes the specs name but the mesh lacks are ignored.  Without a
    mesh, or on one device, ``kern`` runs as it is."""
    mesh = get_mesh()
    if mesh is None or math.prod(mesh.shape.values()) == 1:
        return kern(*args)

    def fit(ps):
        def keep(e):
            axes = e if isinstance(e, tuple) else (e,)
            return e if all(a in mesh.shape for a in axes) else None
        return P(*[keep(e) for e in ps])

    body = kern
    if psum_axis is not None and psum_axis in mesh.shape:
        body = lambda *a: jax.lax.psum(kern(*a), psum_axis)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=tuple(fit(s) for s in in_specs),
                         out_specs=fit(out_specs), check_vma=False)(*args)


# --------------------------------------------------------------------------
# batch / cache rules
# --------------------------------------------------------------------------

def pool_pspec(pool: PagePool, name: str, pad: Optional[bool] = None,
               label: Optional[str] = None) -> P:
    """Placement of leaf ``name`` of a paged KV pool (stack, blocks,
    rows, lanes): pages on the data axes, and lanes on 'model' where each
    device's share is whole KV heads in whole lane tiles
    (``PagePool.head_split``)."""
    mesh = get_mesh()
    if mesh is None:
        return P()
    dims: List[Any] = [None, _batch_entry(mesh), None, None]
    if pool.head_split(name, model_size(mesh)):
        dims[3] = "model"
    return fit_spec(P(*dims), tuple(pool[name].shape), mesh,
                    label=label or f"pages.{name}", pad=pad)


def constrain_pool(pool: PagePool) -> PagePool:
    """``pool`` held to its placement (:func:`pool_pspec`) inside a
    program, so a step that writes it returns it as it was placed;
    identity with no active mesh."""
    mesh = get_mesh()
    if mesh is None:
        return pool
    return pool.replace(**{
        n: jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, pool_pspec(pool, n, pad=False)))
        for n, leaf in pool.items()})


def batch_pspecs(batch) -> Any:
    """Shard dim 0 (the global batch) of every leaf across the data axes.

    Always fits with ``pad=False``: a batch tensor is placed as-is every
    tick — padding it would fabricate tokens — so an indivisible batch
    serves replicated like before."""
    mesh = get_mesh()
    flat, treedef = jax.tree_util.tree_flatten_with_path(batch)

    def leaf(path, x):
        shape = tuple(getattr(x, "shape", ()))
        if mesh is None or not shape:
            return P()
        dims: List[Any] = [None] * len(shape)
        dims[0] = _batch_entry(mesh)
        return fit_spec(P(*dims), shape, mesh,
                        label=jax.tree_util.keystr(path), pad=False)

    return jax.tree_util.tree_unflatten(
        treedef, [leaf(path, x) for path, x in flat])


def cache_pspecs(state, batch_size: int, pad: Optional[bool] = None) -> Any:
    """Decode-state specs: the batch dim (identified by ``batch_size``; the
    leading dim is the stacked layer axis) shards on the data axes, and the
    KV-head dim of rank>=5 ``(L, B, T, KV, dh)`` cache leaves shards on
    'model' — fitted per ``pad`` (default: the :data:`PADDED` toggle), so
    e.g. 2 KV heads on a 16-way model axis pad-shard under padded mode
    and degrade to replicated with ``pad=False`` (the live engine's
    choice: decode state round-trips through the donated step and cannot
    carry placement padding).

    Paged caches are recognized by path: pool leaves under ``'pages'``
    (stack, blocks, rows, lanes; ``kernels.kv_pages``) shard their *page*
    axis on the data axes (the paged analog of per-slot batch sharding —
    gathers/scatters through the block table reshard as needed), and
    their lanes on 'model' where each device's share is whole KV heads
    in whole lane tiles (``PagePool.head_split``: the fused decode
    kernel then reads its own heads' lanes, ``attention._fused``); the
    per-token scales stay whole.  ``'table'`` block tables are tiny
    int32 maps and stay replicated."""
    mesh = get_mesh()
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    is_pool = lambda x: isinstance(x, PagePool)  # noqa: E731
    pools = {jax.tree_util.keystr(path): x for path, x in
             jax.tree_util.tree_flatten_with_path(state, is_leaf=is_pool)[0]
             if is_pool(x)}

    def _keys(path) -> List[str]:
        return [k.key for k in path
                if isinstance(k, jax.tree_util.DictKey)]

    def leaf(path, x):
        shape = tuple(getattr(x, "shape", ()))
        if mesh is None or not shape:
            return P()
        dims: List[Any] = [None] * len(shape)
        keys = _keys(path)
        if keys and keys[-1] == "table":
            return P(*dims)
        label = jax.tree_util.keystr(path)
        if "pages" in keys:
            return pool_pspec(pools[jax.tree_util.keystr(path[:-1])],
                              keys[-1], pad=pad, label=label)
        # rank>=4 leaves are stacked (L, B, ...): dim 0 is the layer axis,
        # so never batch-shard it even when n_layers == batch_size.
        start = 1 if len(shape) >= 4 else 0
        for i in range(start, len(shape)):
            if shape[i] == batch_size:
                dims[i] = _batch_entry(mesh)
                break
        if len(shape) >= 5 and dims[-2] is None:
            dims[-2] = "model"
        return fit_spec(P(*dims), shape, mesh, label=label, pad=pad)

    specs = [leaf(path, x) for path, x in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)
