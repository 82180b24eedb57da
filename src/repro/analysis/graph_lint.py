"""Hot-path graph lint: trace the serving programs, prove the compressed
wire formats stay compressed.

The BWQ efficiency claim is structural: under a packed execution backend
the compiled prefill/decode program must never hold a dequantized
full-weight-shape float tensor — dequantization belongs inside the Pallas
kernels (per-tile, in VMEM) or, for ``dense``/``ref``, is the sanctioned
in-graph strategy.  This pass traces ``ServeEngine``'s jitted entry
points to jaxprs (``jax.make_jaxpr`` over ShapeDtypeStructs — no compile,
no execute) and applies *taint tracking* from every deployed payload
input (``w_int`` / ``planes`` / ``sign``):

* ``dequant-materialization`` — a float equation output whose trailing
  two dims equal a deployed leaf's block-padded (Kp, Np) / true (K, N)
  footprint and that derives from that leaf's payload.  Error under
  ``pallas``/``bitplane``; info (sanctioned) under ``dense``/``ref``;
  warning for ragged-MoE expert leaves (the documented EP-MoE gap — see
  ROADMAP) and for packed-leaf-under-``bitplane`` fallbacks.
* ``payload-convert`` / ``payload-transpose`` — a direct
  ``convert_element_type``-to-float or ``transpose`` on a packed payload
  var outside any kernel: the start of an in-graph dequant, or a layout
  break the zero-copy kernel adapters forbid.
* ``missing-donation`` — decode state buffers not donated to the jitted
  decode step (``lower(...).args_info``): without donation every decode
  tick double-buffers the whole KV cache.

The same taint machinery audits the *KV cache* read side against the
decode-attention backend (``models.attention.paged_attn_backend``):

* ``kv-dequant-materialization`` — a float tensor with a cache leaf's
  full (T, KV, dh) footprint derives from a quantized (int8/int4) KV
  payload outside any kernel.  Error when the fused kernel was requested
  for decode (``attn_backend='fused'`` + ``fn_name='decode'`` — the
  gather fallback silently ran instead); info (sanctioned) under
  ``gather``/``ref`` and for prefill, where the gather read side is the
  design.
* ``kv-full-width-gather`` — a ``gather`` materializes the contiguous
  (B, nb, rows, lanes) view of a paged pool leaf's stored pages
  (quantized or float): the O(max_len) ``kv_pages.read_pages`` the
  fused kernel exists to delete.
  Same severity policy.
* ``kv-clean`` — fused decode saw KV payloads and materialized neither
  (the footprint census ``benchmarks/decode_bench.py`` asserts on).

Contiguous *float* caches are excluded as taint sources — their in-place
cache write is unavoidably a full-width float op — so only reads that
the fused kernel actually eliminates can fire.

Taint dies at ``pallas_call`` (the sanctioned kernel boundary — in-kernel
dequant is the design) and at ``dot_general``/convs (a matmul output is
an activation, not a weight), so residual-stream activations can never
false-positive against a weight footprint.  Sub-jaxprs (layer ``scan``,
``pjit``, ``cond`` branches, ``while`` bodies, custom-call wrappers) are
walked with positional invar/outvar mapping, so stacked leaves sliced by
the layer scan keep their identity inside the body.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from .report import Finding

_PAYLOAD_FIELDS = ("w_int", "planes", "sign")
_EXPERT_LEAF = re.compile(r"expert_(gate|up|down)")
# shape-preserving-ish prims through which a payload var stays "direct"
_PASSTHROUGH = frozenset({"squeeze", "slice", "dynamic_slice", "gather",
                          "reshape", "copy", "convert_element_type"})
# taint sinks: outputs are activations / kernel results, never weights
_SINKS = frozenset({"pallas_call", "dot_general", "conv_general_dilated",
                    "ragged_dot"})


@dataclasses.dataclass(frozen=True)
class PayloadLeaf:
    """One deployed leaf's identity + the float footprints that would
    betray its in-graph materialization."""
    path: str
    kind: str                 # 'packed' | 'bitplane'
    bits: int
    mat_shapes: frozenset     # of trailing-2-dim (rows, cols) tuples


def _deployed_types():
    from ..serve.deploy import BitplaneServingWeight, ServingWeight
    return ServingWeight, BitplaneServingWeight


def _leaf_info(path: str, leaf) -> PayloadLeaf:
    if path.startswith("[0]"):       # traced args tuple: params is arg 0
        path = path[3:]
    _, bp_t = _deployed_types()
    wbr, wbc = leaf.spec.wb_rows, leaf.spec.wb_cols
    gr, gc = leaf.scale.shape[-2], leaf.scale.shape[-1]
    kp, np_ = gr * wbr, gc * wbc
    k, n = leaf.shape[-2], leaf.shape[-1]
    shapes = {(kp, np_), (k, n)}
    if isinstance(leaf, bp_t):
        kind = "bitplane"
        shapes.add((-(-kp // 8) * 8, np_))        # byte-padded Kp8 rows
    else:
        kind = "packed"
        if leaf.bits == 4:
            shapes.add((kp + kp % 2, np_))        # nibble-unpack even rows
    return PayloadLeaf(path=path, kind=kind, bits=leaf.bits,
                       mat_shapes=frozenset(shapes))


def deployed_leaves(params: Any) -> Dict[str, Any]:
    """keystr path -> deployed leaf object, over the whole tree."""
    sw_t, bp_t = _deployed_types()
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, (sw_t, bp_t)))
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat
            if isinstance(leaf, (sw_t, bp_t))}


def fallback_leaf_paths(params: Any, backend: str) -> List[str]:
    """Deployed leaves ``backend`` cannot execute natively (they fall back
    to the in-graph dense dequant dot): packed ServingWeight leaves under
    the ``bitplane`` backend.  Static — no tracing required."""
    if backend != "bitplane":
        return []
    sw_t, _ = _deployed_types()
    return [p for p, leaf in deployed_leaves(params).items()
            if isinstance(leaf, sw_t)]


def _payload_invars(jaxpr, args: tuple) -> Tuple[Dict, Optional[str]]:
    """Map jaxpr invars to the PayloadLeaf they carry (w_int/planes/sign).

    ``args`` is the exact tuple the jaxpr was traced from — its flattened
    leaves correspond 1:1, in order, to ``jaxpr.jaxpr.invars``."""
    owners = deployed_leaves(args)
    flat, _ = jax.tree_util.tree_flatten_with_path(args)
    invars = jaxpr.jaxpr.invars
    if len(flat) != len(invars):
        return {}, (f"cannot map payload leaves to jaxpr inputs: "
                    f"{len(flat)} arg leaves vs {len(invars)} invars")
    payload = {}
    for (path, _leaf), var in zip(flat, invars):
        last = path[-1]
        name = getattr(last, "name", None)
        if name not in _PAYLOAD_FIELDS:
            continue
        owner_path = jax.tree_util.keystr(path[:-1])
        owner = owners.get(owner_path)
        if owner is not None:
            payload[var] = _leaf_info(owner_path, owner)
    return payload, None


def _sub_jaxprs(eqn):
    """[(sub jaxpr, invar pairs, outvar pairs)] for container primitives.

    ``pallas_call`` also carries a ``jaxpr`` param but is deliberately NOT
    recursed: in-kernel dequantization is the sanctioned design."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "pallas_call":
        return []
    subs = []
    if name == "cond":
        for br in p.get("branches", ()):
            jx = br.jaxpr
            subs.append((jx, list(zip(jx.invars, eqn.invars[1:])),
                         list(zip(jx.outvars, eqn.outvars))))
        return subs
    if name == "while":
        body = p["body_jaxpr"].jaxpr
        outer = eqn.invars[p["cond_nconsts"]:]
        subs.append((body, list(zip(body.invars, outer)),
                     list(zip(body.outvars, eqn.outvars))))
        return subs
    sub = None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in p:
            sub = p[key]
            break
    if sub is None:
        return []
    jx = sub.jaxpr if hasattr(sub, "jaxpr") else sub
    if len(jx.invars) == len(eqn.invars):        # scan/pjit: positional
        subs.append((jx, list(zip(jx.invars, eqn.invars)),
                     list(zip(jx.outvars, eqn.outvars))))
    return subs


def _is_var(v) -> bool:
    return not hasattr(v, "val")                 # Literal carries .val


def _float_out(v) -> bool:
    try:
        return jnp.issubdtype(v.aval.dtype, jnp.floating)
    except Exception:
        return False


class _Walk:
    """One traced function's walk state: findings, dedup, severity policy."""

    def __init__(self, fn_name: str, backend: str):
        self.fn = fn_name
        self.backend = backend
        self.findings: List[Finding] = []
        self._seen: Set[tuple] = set()
        from ..models.moe import GROUPED_IMPL
        self._ragged_moe = GROUPED_IMPL.get("impl") == "ragged"

    def _severity(self, leaf: PayloadLeaf, rule: str) -> Tuple[str, str]:
        """(severity, rule) under the backend's materialization policy."""
        if self.backend == "bitplane" and leaf.kind == "packed":
            return "warning", "bitplane-dense-fallback"
        if self.backend in ("pallas", "bitplane"):
            if self._ragged_moe and _EXPERT_LEAF.search(leaf.path):
                return "warning", "sanctioned-moe-dequant"
            return "error", rule
        return "info", "sanctioned-dequant"

    def emit(self, leaf: PayloadLeaf, rule: str, message: str) -> None:
        severity, rule = self._severity(leaf, rule)
        key = (rule, leaf.path)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(
            severity=severity, pass_name="graph", rule=rule,
            path=f"{self.fn}:{leaf.path}", message=message))

    def walk(self, jaxpr, payload: Dict, taint: Dict) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_payload = [payload[v] for v in eqn.invars
                          if _is_var(v) and v in payload]
            in_taint: Set[PayloadLeaf] = set()
            for v in eqn.invars:
                if _is_var(v):
                    in_taint |= taint.get(v, set())
            subs = _sub_jaxprs(eqn)
            if subs:
                for jx, inmap, outmap in subs:
                    sub_p = {sv: payload[ov] for sv, ov in inmap
                             if _is_var(ov) and ov in payload}
                    sub_t = {sv: set(taint.get(ov, set()))
                             for sv, ov in inmap if _is_var(ov)}
                    self.walk(jx, sub_p, sub_t)
                    for sv, ov in outmap:
                        if _is_var(sv) and _is_var(ov):
                            got = set(sub_t.get(sv, set()))
                            if sv in sub_p:
                                got.add(sub_p[sv])
                            if got:
                                taint.setdefault(ov, set()).update(got)
                continue
            if name in _SINKS:
                continue                         # activations, not weights
            if in_payload:
                if name == "convert_element_type" \
                        and any(_float_out(ov) for ov in eqn.outvars):
                    for leaf in in_payload:
                        self.emit(leaf, "payload-convert",
                                  f"convert_element_type to "
                                  f"{eqn.outvars[0].aval.dtype} on packed "
                                  f"payload ({leaf.kind}, int{leaf.bits}) "
                                  f"outside any kernel")
                if name == "transpose":
                    for leaf in in_payload:
                        self.emit(leaf, "payload-transpose",
                                  f"transpose on packed payload "
                                  f"({leaf.kind}): breaks the zero-copy "
                                  f"kernel layout contract")
                if name in _PASSTHROUGH:
                    for ov in eqn.outvars:
                        payload[ov] = in_payload[0]
            if not (in_taint or in_payload):
                continue
            out_taint = in_taint | set(in_payload)
            for ov in eqn.outvars:
                taint.setdefault(ov, set()).update(out_taint)
                if not _float_out(ov):
                    continue
                shape = tuple(getattr(ov.aval, "shape", ()))
                if len(shape) < 2:
                    continue
                t2 = shape[-2:]
                for leaf in out_taint:
                    if t2 in leaf.mat_shapes:
                        self.emit(
                            leaf, "dequant-materialization",
                            f"float {ov.aval.dtype} tensor {shape} "
                            f"materializes the {leaf.kind} int{leaf.bits} "
                            f"leaf's {t2} weight footprint in-graph "
                            f"(eqn '{name}') under backend="
                            f"{self.backend!r}")


# ---------------------------------------------------------------------------
# KV-cache read-side lint (decode-attention backend)
# ---------------------------------------------------------------------------

_KV_FIELDS = ("k", "v")
# the decode-step scatter/slice writes preserve a cache leaf's identity
# (operand 0 in, same-shape buffer out) — the read side must still see
# the written pool as *the* payload for the full-width-gather rule
_KV_PASSTHROUGH = _PASSTHROUGH | {"scatter", "dynamic_update_slice"}


@dataclasses.dataclass(frozen=True)
class KVLeaf:
    """One KV cache leaf's identity + the footprints that betray a
    full-width read outside the fused kernel."""
    path: str
    bits: int                 # 8 / 4 (quantized-at-rest) or 32 (float)
    paged: bool
    kv: int                   # KV heads
    dh: int                   # dequantized head dim (2x stored for int4)
    span: int                 # tokens a read covers at least: page | T
    block: tuple              # a paged leaf's stored (rows, lanes) page


def _path_keys(path) -> List[Optional[str]]:
    return [getattr(e, "key", getattr(e, "name", None)) for e in path]


def _kv_payload_invars(jaxpr, args: tuple) -> Dict:
    """Map jaxpr invars to the KVLeaf they carry (cache ``k``/``v``).

    A paged leaf's heads and head dim come from its ``PagePool``'s
    per-token shapes (the stored rows are lane-dense); a contiguous
    leaf's from its (…, T, KV, dh) shape.  Contiguous float caches are
    skipped: their in-place write is an unavoidable full-width float op,
    so they cannot be lint sources."""
    from ..kernels.kv_pages import PagePool
    pools = {}
    for path, node in jax.tree_util.tree_flatten_with_path(
            args, is_leaf=lambda x: isinstance(x, PagePool))[0]:
        if isinstance(node, PagePool):
            for name in _KV_FIELDS:
                if name in node:
                    key = path + (jax.tree_util.DictKey(name),)
                    pools[jax.tree_util.keystr(key)] = (node, name)
    flat, _ = jax.tree_util.tree_flatten_with_path(args)
    invars = jaxpr.jaxpr.invars
    if len(flat) != len(invars):
        return {}
    payload = {}
    for (path, leaf), var in zip(flat, invars):
        keys = _path_keys(path)
        if not keys or keys[-1] not in _KV_FIELDS or "cache" not in keys:
            continue
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) < 3:
            continue                             # recurrent state rows
        dt = jnp.dtype(leaf.dtype)
        bits = {jnp.dtype(jnp.int8): 8, jnp.dtype(jnp.uint8): 4}.get(dt, 32)
        label = jax.tree_util.keystr(path)
        paged = label in pools
        if bits == 32 and (not paged
                           or not jnp.issubdtype(dt, jnp.floating)):
            continue
        if paged:
            pool, name = pools[label]
            kv, dh_s = pool.tails[name]
            span, block = pool.page, shape[-2:]
        else:
            kv, dh_s = shape[-2:]
            span, block = shape[-3], ()
        payload[var] = KVLeaf(path=label, bits=bits, paged=paged, kv=kv,
                              dh=dh_s * 2 if bits == 4 else dh_s,
                              span=span, block=tuple(block))
    return payload


class _KVWalk:
    """Taint walk over the KV-cache read side (severity keyed on the
    decode-attention backend, not the matmul backend)."""

    def __init__(self, fn_name: str, attn_backend: str):
        self.fn = fn_name
        self.attn = attn_backend
        self.findings: List[Finding] = []
        self._seen: Set[tuple] = set()

    def _emit(self, leaf: KVLeaf, rule: str, sanctioned: str,
              message: str) -> None:
        if self.attn == "fused" and self.fn == "decode":
            severity = "error"          # fused requested, fallback ran
        else:
            severity, rule = "info", sanctioned
        key = (rule, leaf.path)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(
            severity=severity, pass_name="graph", rule=rule,
            path=f"{self.fn}:{leaf.path}", message=message))

    def walk(self, jaxpr, payload: Dict, taint: Dict) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_payload = [payload[v] for v in eqn.invars
                          if _is_var(v) and v in payload]
            in_taint: Set[KVLeaf] = set()
            for v in eqn.invars:
                if _is_var(v):
                    in_taint |= taint.get(v, set())
            subs = _sub_jaxprs(eqn)
            if subs:
                for jx, inmap, outmap in subs:
                    sub_p = {sv: payload[ov] for sv, ov in inmap
                             if _is_var(ov) and ov in payload}
                    sub_t = {sv: set(taint.get(ov, set()))
                             for sv, ov in inmap if _is_var(ov)}
                    self.walk(jx, sub_p, sub_t)
                    for sv, ov in outmap:
                        if _is_var(sv) and _is_var(ov):
                            got = set(sub_t.get(sv, set()))
                            if got:
                                taint.setdefault(ov, set()).update(got)
                continue
            if name == "gather":
                for v in eqn.invars:
                    if not (_is_var(v) and v in payload
                            and payload[v].paged):
                        continue
                    leaf = payload[v]
                    for ov in eqn.outvars:
                        osh = tuple(ov.aval.shape)
                        # whole stored pages, one a (slot, block) entry
                        if len(osh) >= 4 and osh[-2:] == leaf.block:
                            self._emit(
                                leaf, "kv-full-width-gather",
                                "sanctioned-kv-gather",
                                f"gather materializes the contiguous "
                                f"{osh} view of the paged KV pool "
                                f"(O(max_len) per decode step) under "
                                f"attn_backend={self.attn!r}")
            if name in _SINKS:
                continue                 # pallas_call: in-kernel dequant
            if in_payload and name in _KV_PASSTHROUGH:
                src = eqn.invars[0]      # operand 0 carries the identity
                if _is_var(src) and src in payload:
                    for ov in eqn.outvars:
                        payload[ov] = payload[src]
            quant_in = {l for l in in_payload if l.bits < 32}
            if not (in_taint or quant_in):
                continue
            out_taint = in_taint | quant_in
            for ov in eqn.outvars:
                taint.setdefault(ov, set()).update(out_taint)
                if not _float_out(ov):
                    continue
                osh = tuple(getattr(ov.aval, "shape", ()))
                if len(osh) < 3:
                    continue
                for leaf in out_taint:
                    if osh[-2:] == (leaf.kv, leaf.dh) \
                            and osh[-3] >= leaf.span:
                        self._emit(
                            leaf, "kv-dequant-materialization",
                            "sanctioned-kv-dequant",
                            f"float {ov.aval.dtype} tensor {osh} "
                            f"materializes the int{leaf.bits} KV cache "
                            f"leaf's full (T, KV, dh) tree outside any "
                            f"kernel (eqn {name!r}) under attn_backend="
                            f"{self.attn!r}")


def lint_traced_fn(fn, args: tuple, *, fn_name: str, backend: str,
                   attn_backend: str = "gather") -> List[Finding]:
    """Trace ``fn(*args)`` under ``backend``/``attn_backend`` and lint
    the jaxpr (weight materialization + KV-cache read side).

    ``args`` may mix concrete arrays, ShapeDtypeStructs and deployed
    dataclasses; the trace is abstract (no compile, no execute)."""
    from ..models.attention import paged_attn_backend
    from ..models.common import matmul_backend

    def wrapped(*a):
        with matmul_backend(backend), paged_attn_backend(attn_backend):
            return fn(*a)

    findings: List[Finding] = []
    try:
        jaxpr = jax.make_jaxpr(wrapped)(*args)
    except Exception as e:
        findings.append(Finding(
            severity="error", pass_name="graph", rule="trace-failure",
            path=fn_name,
            message=f"tracing failed ({type(e).__name__}: {e})"))
        return findings
    payload, problem = _payload_invars(jaxpr, args)
    if problem:
        findings.append(Finding(severity="error", pass_name="graph",
                                rule="invar-mapping", path=fn_name,
                                message=problem))
        return findings
    if not payload:
        findings.append(Finding(
            severity="info", pass_name="graph", rule="no-payload",
            path=fn_name,
            message="no deployed packed leaves reach this function; "
                    "materialization lint is vacuous"))
    else:
        w = _Walk(fn_name, backend)
        w.walk(jaxpr.jaxpr, dict(payload), {v: set() for v in payload})
        if not w.findings:
            findings.append(Finding(
                severity="info", pass_name="graph", rule="clean",
                path=fn_name,
                message=f"{len(payload)} packed payload inputs; no "
                        f"in-graph materialization under backend="
                        f"{backend!r}"))
        findings += w.findings
    kv_payload = _kv_payload_invars(jaxpr, args)
    if kv_payload:
        kw = _KVWalk(fn_name, attn_backend)
        kw.walk(jaxpr.jaxpr, dict(kv_payload),
                {v: set() for v in kv_payload})
        if not kw.findings and attn_backend == "fused" \
                and fn_name == "decode":
            findings.append(Finding(
                severity="info", pass_name="graph", rule="kv-clean",
                path=fn_name,
                message=f"{len(kv_payload)} KV cache payload inputs; "
                        f"fused decode materializes neither the "
                        f"contiguous KV view nor the f32 KV tree"))
        findings += kw.findings
    return findings


# ---------------------------------------------------------------------------
# donation check
# ---------------------------------------------------------------------------

def check_decode_donation(engine, tokens, state, index) -> List[Finding]:
    """Verify the decode state is donated to the jitted decode step.

    Uses ``Lowered.args_info`` (per-leaf ``.donated``) — a lowering-level
    fact, independent of whether the platform honors donation."""
    findings: List[Finding] = []
    try:
        lowered = engine._decode_j.lower(engine.params, tokens, state, index)
        state_info = lowered.args_info[0][2]
    except Exception as e:
        findings.append(Finding(
            severity="error", pass_name="graph", rule="donation-lowering",
            path="decode", message=f"could not lower decode to inspect "
                                   f"donation ({type(e).__name__}: {e})"))
        return findings
    flat, _ = jax.tree_util.tree_flatten_with_path(state_info)
    missing = [jax.tree_util.keystr(p) for p, a in flat if not a.donated]
    if missing:
        findings.append(Finding(
            severity="error", pass_name="graph", rule="missing-donation",
            path="decode:state",
            message=f"{len(missing)}/{len(flat)} decode-state buffers are "
                    f"not donated (double-buffered KV cache per tick): "
                    f"{missing[:5]}"))
    else:
        findings.append(Finding(
            severity="info", pass_name="graph", rule="donation-ok",
            path="decode:state",
            message=f"all {len(flat)} decode-state buffers donated"))
    return findings
