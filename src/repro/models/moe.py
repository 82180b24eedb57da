"""Top-k token-choice Mixture-of-Experts with sort-based ragged dispatch.

Tokens are sorted by expert id and pushed through ``jax.lax.ragged_dot``
against the stacked expert weights — no dense (T, E, C) dispatch tensors,
no capacity drops.  Expert weights carry their in-expert TP sharding
('expert' rule: d_ff on the model axis); true cross-device EP with
all-to-all is a perf variant explored in EXPERIMENTS.md §Perf.

The router is kept in float32 and outside BWQ quantization (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import batch_axes, constraint, get_mesh, spec
from .common import make_weight, qdense, qmatmul


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """y[M,N] = per-group x[M,K] @ w[g,K,N] (tokens sorted by group).

    jax.lax.ragged_dot's default VJP densifies to (g, M, K) tensors —
    catastrophic for MoE training memory.  This custom VJP keeps both
    directions ragged: dx is another ragged_dot, dw is the
    ragged-*contracting* mode of ragged_dot_general (per-group outer
    products, no densification).
    """
    return jax.lax.ragged_dot(x, w, group_sizes)


def _gm_fwd(x, w, group_sizes):
    return grouped_matmul(x, w, group_sizes), (x, w, group_sizes)


def _gm_bwd(res, dy):
    x, w, gs = res
    dx = jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), gs)
    dnums = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    dw = jax.lax.ragged_dot_general(x, dy, gs, dnums)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)

# Implementation selector.  'ragged' (jax.lax.ragged_dot + custom VJP) is
# exact/no-drop but XLA lowers it densely to (E, M, K) tensors on backends
# without native ragged-dot support — prohibitive at pod scale.  'capacity'
# is the GShard-style fixed-capacity path: a scan over experts with static
# per-expert capacity; tokens beyond capacity are dropped (standard
# capacity-factor semantics).  The dry-run and the at-scale launcher use
# 'capacity'; small-scale exact runs use 'ragged'.
GROUPED_IMPL = {"impl": "ragged", "capacity_factor": 2.0}


def grouped_matmul_capacity(x, w, group_sizes, capacity: int):
    """Capacity-bounded grouped matmul over sorted tokens.

    x: (M, K) tokens sorted by group; w: (E, K, N) — a plain array or a
    scan-sliceable quantized representation (ServingWeight /
    FakeQuantTensor with E leading): the scan over experts slices one
    expert's (packed) weight per step and ``qmatmul`` executes it, so
    packed experts run on the compressed format.  Returns (M, N) with
    zeros for tokens past their group's capacity (dropped).
    """
    m, k = x.shape
    e, n = w.shape[0], w.shape[-1]
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(group_sizes)[:-1].astype(jnp.int32)])
    x_pad = jnp.concatenate([x, jnp.zeros((capacity, k), x.dtype)], axis=0)

    def body(y, ins):
        w_e, start, size = ins
        xs = jax.lax.dynamic_slice(x_pad, (start, 0), (capacity, k))
        mask = (jnp.arange(capacity) < size)[:, None].astype(x.dtype)
        ys = qmatmul(xs * mask, w_e) * mask
        idx = start + jnp.arange(capacity)
        y = y.at[idx].add(ys, mode="drop")
        return y, None

    y0 = jnp.zeros((m + capacity, n), x.dtype)
    y, _ = jax.lax.scan(body, y0, (w, starts, group_sizes))
    return y[:m]


def _capacity(m: int, e: int) -> int:
    """Per-expert token capacity: factor * mean load, rounded up to 8."""
    cap = int(GROUPED_IMPL["capacity_factor"] * m / e + 0.999)
    return max(8, min(m, -(-cap // 8) * 8))


def _grouped(x, w, group_sizes):
    """Grouped dispatch over possibly-quantized expert weights.

    The capacity scan consumes quantized experts natively (one packed
    expert sliced per scan step); ``ragged_dot`` needs a dense (E, K, N)
    operand, so that path dequantizes through the sanctioned
    ``common.qdense`` entry."""
    from ..core.bitrep import QuantizedTensor
    if isinstance(w, QuantizedTensor):
        w = qdense(w, x.dtype)     # bit axis leads: not scan-sliceable
    if GROUPED_IMPL["impl"] == "capacity":
        return grouped_matmul_capacity(x, w, group_sizes,
                                       _capacity(x.shape[0], w.shape[0]))
    return grouped_matmul(x, qdense(w, x.dtype), group_sizes)


def init_moe(key, d_model: int, d_ff: int, n_experts: int, top_k: int,
             qc, n_shared: int = 0, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 5)
    p = {
        "router_w": jax.random.normal(ks[0], (d_model, n_experts),
                                      jnp.float32) * 0.02,
        "expert_gate": make_weight(ks[1], (n_experts, d_model, d_ff), qc,
                                   dtype=dtype),
        "expert_up": make_weight(ks[2], (n_experts, d_model, d_ff), qc,
                                 dtype=dtype),
        "expert_down": make_weight(ks[3], (n_experts, d_ff, d_model), qc,
                                   dtype=dtype),
    }
    if n_shared:
        p["shared_gate"] = make_weight(ks[4], (d_model, n_shared * d_ff), qc,
                                       dtype=dtype)
        key2 = jax.random.fold_in(ks[4], 1)
        p["shared_up"] = make_weight(key2, (d_model, n_shared * d_ff), qc,
                                     dtype=dtype)
        key3 = jax.random.fold_in(ks[4], 2)
        p["shared_down"] = make_weight(key3, (n_shared * d_ff, d_model), qc,
                                       dtype=dtype)
    return p


def moe_forward(p: Dict, x: jnp.ndarray, top_k: int, *, offset: int = 0,
                renorm: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss).

    The router scores every expert (``router_w``'s width); the expert
    weights hold ``n_held`` of them, ids ``offset .. offset + n_held - 1``,
    and the layer adds only their part of the routed result (plus the
    shared experts, which every holder computes).  ``renorm`` divides the
    top-k gates by their sum.

    Under an active mesh this dispatches to the shard_map path: routing +
    sort stay LOCAL to each data shard (a global argsort under pjit would
    gather every token to every device), expert FFNs run with in-expert TP
    over 'model', partial outputs psum over 'model'.
    """
    mesh = get_mesh()
    if mesh is not None and mesh.devices.size > 1:
        out, aux = _moe_forward_sharded(p, x, top_k, mesh, offset, renorm)
    else:
        out, aux = _moe_forward_local(p, x, top_k, offset, renorm)
    if "shared_gate" in p:
        with jax.named_scope("moe_shared"):
            hs = jax.nn.silu(qmatmul(x, p["shared_gate"])) \
                * qmatmul(x, p["shared_up"])
            hs = constraint(hs, "batch", None, "ff")
            out = out + qmatmul(hs, p["shared_down"])
    return out, aux


def route(xt: jnp.ndarray, router_w, top_k: int, renorm: bool = True):
    """Softmax gates over every expert: (gates (T, k), expert ids (T, k),
    probs (T, E)).  ``renorm`` divides the top-k gates by their sum."""
    logits = xt.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    if renorm:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return gates, idx, probs


def expert_share(xt: jnp.ndarray, gates, idx, wg, wu, wd, offset=0,
                 capacity=None) -> jnp.ndarray:
    """The held experts' part of the routed output, dropless: the sum,
    over the (token, choice) pairs routed to an expert ``offset ..
    offset + n_held - 1`` (``n_held`` = ``wg``'s leading dim), of gate
    times that expert's SwiGLU on the token.  Pairs routed elsewhere sort
    after every held expert's pairs, past the last group, and their rows
    are masked to zero: ``ragged_dot`` leaves the rows past its last
    group unwritten on the TPU (garbage, NaN among it).  ``capacity``
    bounds each expert's tokens (the GShard capacity path, which drops
    the rest); None takes the module's ``GROUPED_IMPL``.  One chip passes
    its held range; an expert-parallel rank passes its own."""
    t, k = idx.shape
    held = wg.shape[-3]
    local = idx.reshape(-1) - offset
    mine = (local >= 0) & (local < held)
    grp = jnp.where(mine, local, held)
    order = jnp.argsort(grp)                                 # stable
    tok = jnp.repeat(jnp.arange(t), k)[order]
    xs = jnp.take(xt, tok, axis=0)                           # (T*k, D)
    sizes = jnp.bincount(grp, length=held + 1)[:held].astype(jnp.int32)
    if capacity is None:
        grouped = _grouped
    else:
        def grouped(x, w, gs):
            return grouped_matmul_capacity(x, w, gs, capacity)
    h = jax.nn.silu(grouped(xs, wg, sizes)) * grouped(xs, wu, sizes)
    ys = grouped(h, wd, sizes)
    gate = jnp.where(mine, gates.reshape(-1), 0.0)[order]
    ys = jnp.where(mine[order][:, None],
                   ys * gate[:, None].astype(ys.dtype), 0)
    return jnp.zeros_like(xt).at[tok].add(ys)


def _aux_loss(probs, idx):
    """Switch-style load-balance loss over every expert."""
    e = probs.shape[-1]
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(idx, e).sum(1) > 0).astype(jnp.float32), 0)
    return e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))


def _moe_forward_local(p: Dict, x: jnp.ndarray, top_k: int, offset: int,
                       renorm: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe_route"):
        gates, idx, probs = route(xt, p["router_w"], top_k, renorm)
    with jax.named_scope("moe_experts"):
        out = expert_share(xt, gates, idx, p["expert_gate"],
                           p["expert_up"], p["expert_down"], offset)
    return out.reshape(b, s, d), _aux_loss(probs, idx)


def _moe_forward_sharded(p: Dict, x: jnp.ndarray, top_k: int, mesh,
                         offset: int, renorm: bool
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map MoE: per-data-shard routing/sort + in-expert TP on 'model'.

    Expert weights are first constrained to drop their FSDP 'data' dim
    (one per-layer all-gather — ZeRO-3 unshard at use), keeping 'model'
    (d_ff) sharded; inside the shard the ragged grouped matmuls run on
    local tokens only and partial d_model outputs are psum'd over 'model'.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = batch_axes(mesh)
    dpa = dp[0] if len(dp) == 1 else tuple(dp)
    has_model = "model" in mesh.axis_names
    e = p["router_w"].shape[-1]
    model_size = mesh.shape.get("model", 1)
    # shard_map needs dense (E, K, N) operands with one spec per array;
    # packed expert execution under shard_map is future work, so quantized
    # experts dequantize here through the sanctioned common.qdense entry.
    wg = qdense(p["expert_gate"], x.dtype)
    wu = qdense(p["expert_up"], x.dtype)
    wd = qdense(p["expert_down"], x.dtype)
    # TRUE expert parallelism when the held experts divide the model axis:
    # each model rank owns held/model experts outright (weights never
    # gathered); tokens are data-sharded and every rank computes only its
    # experts' share.  Otherwise fall back to in-expert tensor
    # parallelism on d_ff.
    ep_mode = has_model and wg.shape[0] % model_size == 0

    def reshard(w, spec):
        return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, spec))

    if ep_mode:
        wspec_g = wspec_u = P("model", None, None)
        wspec_d = P("model", None, None)
    else:
        wspec_g = wspec_u = P(None, None, "model" if has_model else None)
        wspec_d = P(None, "model" if has_model else None, None)
    wg = reshard(wg, wspec_g)
    wu = reshard(wu, wspec_u)
    wd = reshard(wd, wspec_d)
    rw = reshard(p["router_w"], P())

    def local_moe(xs, rw, wg, wu, wd):
        b, s, d = xs.shape
        xt = xs.reshape(b * s, d)
        gates, idx, probs = route(xt, rw, top_k, renorm)
        if ep_mode:
            # this rank's expert range; exact/no-drop unless the capacity
            # impl is selected, and the psum over 'model' reassembles the
            # single-device output
            rank_offset = offset + jax.lax.axis_index("model") * wg.shape[0]
            cap = _capacity(xt.shape[0] * top_k, e) \
                if GROUPED_IMPL["impl"] == "capacity" else None
            out = expert_share(xt, gates, idx, wg, wu, wd, rank_offset, cap)
        else:
            out = expert_share(xt, gates, idx, wg, wu, wd, offset)
        if has_model:
            out = jax.lax.psum(out, "model")
        aux = _aux_loss(probs, idx)
        aux = jax.lax.pmean(aux, dp) if dp else aux
        if has_model:
            aux = jax.lax.pmean(aux, "model")  # replicate for out_specs
        return out.reshape(b, s, d), aux

    if ep_mode:
        w_in_specs = (P("model", None, None), P("model", None, None),
                      P("model", None, None))
    else:
        mdl = "model" if has_model else None
        w_in_specs = (P(None, None, mdl), P(None, None, mdl),
                      P(None, mdl, None))
    in_specs = (P(dpa, None, None), P()) + w_in_specs
    out_specs = (P(dpa, None, None), P())
    with jax.named_scope("moe_experts"):
        return jax.shard_map(local_moe, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
            x, rw, wg, wu, wd)
