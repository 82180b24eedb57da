"""Shared building blocks: quantizable Dense, norms, embeddings — and the
quantized-linear dispatch layer.

Params are plain nested dicts.  Weight matrices may be stored as
``QuantizedTensor`` (paper-faithful bit planes), ``FakeQuantTensor``
(memory-scalable BWQ mode), ``ServingWeight`` (deployed packed integers),
``BitplaneServingWeight`` (deployed 1-bit planes) or raw arrays.  Layer
code never dequantizes a weight itself: every ``x @ W`` goes through
:func:`qmatmul`, which dispatches on the weight representation and the
active execution backend:

* ``dense``    — dequantize the leaf in-graph and run a plain ``jnp`` dot
  (works for every representation; the only backend that training uses);
* ``pallas``   — stream the deployed leaf through its Pallas kernel
  (``packed_matmul`` for ServingWeight, ``bitplane_matmul`` for
  BitplaneServingWeight; interpret mode off-TPU), so the compiled
  program never holds a dequantized weight;
* ``ref``      — the pure-jnp kernel oracle of whichever layout the leaf
  carries (``kernels/ref.py``), for cross-checking;
* ``bitplane`` — the paper's precision-aware OU mapping on the hot path:
  BitplaneServingWeight leaves run through the ``bitplane_matmul`` Pallas
  kernel (per-block plane occupancy = streamed bytes); other
  representations fall back to the dense dequant dot.

The backend is selected per call (``backend=``), or ambiently with
``matmul_backend("pallas")`` — the serving engine wraps its jitted
prefill/decode in that context.  ``prepare_params`` is the once-per-step
tree prep (cast plain floats, compose bit-plane tensors that cannot ride a
layer scan); packed representations stay packed until qmatmul consumes
them one layer at a time inside the scan.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.bitrep import QuantizedTensor, compose, from_float
from ..core.blocking import BlockingSpec
from ..core.fakequant import FakeQuantTensor, fq_compose, fq_from_float
from ..core.pact import pact_sym_quant
from ..dist.sharding import rows_entry, run_kernel

MATMUL_BACKENDS = ("dense", "pallas", "ref", "bitplane")
_BACKEND_STACK = ["dense"]


@contextlib.contextmanager
def matmul_backend(name: str):
    """Ambient execution backend for :func:`qmatmul` (trace-time)."""
    if name not in MATMUL_BACKENDS:
        raise ValueError(f"unknown matmul backend {name!r}; "
                         f"choose from {MATMUL_BACKENDS}")
    _BACKEND_STACK.append(name)
    try:
        yield
    finally:
        _BACKEND_STACK.pop()


def current_matmul_backend() -> str:
    return _BACKEND_STACK[-1]


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    mode: str = "none"            # 'none' | 'bitplane' | 'fake'
    n_bits: int = 8
    wb_rows: int = 9
    wb_cols: int = 8
    per_block_scale: bool = False  # paper-faithful: per-layer scale
    act_bits: int = 32            # 32 => no activation quantization
    pact_init: float = 6.0
    quantize_embeddings: bool = False

    @property
    def spec(self) -> BlockingSpec:
        return BlockingSpec(self.wb_rows, self.wb_cols)

    @property
    def enabled(self) -> bool:
        return self.mode != "none"


NO_QUANT = QuantConfig()


def make_weight(key, shape, qc: QuantConfig, scale: float = 1.0,
                dtype=jnp.float32, quantize: bool = True) -> Any:
    """Initialize one (possibly stacked) weight matrix (..., K, N)."""
    fan_in = shape[-2]
    w = jax.random.normal(key, shape, dtype) * (scale / jnp.sqrt(fan_in))
    if not quantize or not qc.enabled:
        return w
    if qc.mode == "bitplane":
        return from_float(w, qc.n_bits, qc.spec,
                          per_block_scale=qc.per_block_scale)
    if qc.mode == "fake":
        return fq_from_float(w, qc.n_bits, qc.spec)
    raise ValueError(qc.mode)


def _is_quant(x) -> bool:
    from ..serve.deploy import BitplaneServingWeight, ServingWeight
    return isinstance(x, (QuantizedTensor, FakeQuantTensor, ServingWeight,
                          BitplaneServingWeight))


def materialize(params: Any, dtype=None) -> Any:
    """Quantized leaves -> plain weight arrays (whole-tree dequant).

    Retained for offline tooling (checkpoint export, analysis); the model
    forward paths use :func:`prepare_params` + :func:`qmatmul` instead and
    never materialize a whole tree per step."""
    from ..serve.deploy import (BitplaneServingWeight, ServingWeight,
                                bitplane_serving_compose, serving_compose)

    def conv(x):
        if isinstance(x, QuantizedTensor):
            return compose(x, dtype)
        if isinstance(x, FakeQuantTensor):
            return fq_compose(x, dtype)
        if isinstance(x, ServingWeight):
            return serving_compose(x, dtype or jnp.bfloat16)
        if isinstance(x, BitplaneServingWeight):
            return bitplane_serving_compose(x, dtype or jnp.bfloat16)
        if dtype is not None and isinstance(x, jnp.ndarray) \
                and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(conv, params, is_leaf=_is_quant)


def qdense(w: Any, dtype=None) -> jnp.ndarray:
    """Dequantize ONE weight leaf to a plain array (the dense backend).

    The only sanctioned dequantization entry point outside ``kernels/``:
    call sites that genuinely need a dense weight (ragged MoE dispatch,
    the lax-conv CNN path) go through here so the packed format keeps a
    single owner."""
    from ..serve.deploy import (BitplaneServingWeight, ServingWeight,
                                bitplane_serving_compose, serving_compose)
    if isinstance(w, QuantizedTensor):
        return compose(w, dtype)
    if isinstance(w, FakeQuantTensor):
        return fq_compose(w, dtype)
    if isinstance(w, ServingWeight):
        return serving_compose(w, dtype or jnp.bfloat16)
    if isinstance(w, BitplaneServingWeight):
        return bitplane_serving_compose(w, dtype or jnp.bfloat16)
    if dtype is not None and isinstance(w, jnp.ndarray) \
            and jnp.issubdtype(w.dtype, jnp.floating):
        return w.astype(dtype)
    return w


def _run_split(kern, split: str, x2, *weight):
    """``kern(x2, *weight)`` with its work split over the mesh's 'model'
    axis the way the weight is laid out (``ServingWeight.split``): by
    output columns, or by input rows with the partial products summed.
    Every weight array (payload or per-WB LUT) splits on the same dim.
    The rows of ``x2`` (batch x tokens) split over the data axes where
    they divide, so each data replica multiplies only its own rows."""
    lead = lambda a, spec: P(*([None] * (a.ndim - len(spec)) + spec))
    rows = rows_entry(x2.shape[0])
    if split == "col":
        specs = [P(rows)] + [lead(a, ["model"]) for a in weight]
        return run_kernel(kern, x2, *weight, in_specs=specs,
                          out_specs=P(rows, "model"))
    if split == "row":
        specs = [P(rows, "model")] + [lead(a, ["model", None])
                                      for a in weight]
        return run_kernel(kern, x2, *weight, in_specs=specs,
                          out_specs=P(rows), psum_axis="model")
    return run_kernel(kern, x2, *weight,
                      in_specs=[P(rows)] + [P()] * len(weight),
                      out_specs=P(rows))


def _qmatmul_packed(x: jnp.ndarray, sw, backend: str) -> jnp.ndarray:
    """x (..., K) @ packed ServingWeight (Kp, Np) -> (..., N)."""
    from ..kernels.packed_matmul import packed_matmul
    from ..kernels.ref import packed_matmul_ref
    from ..serve.deploy import serving_to_packed_layout
    pk = serving_to_packed_layout(sw)
    n = sw.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if backend == "pallas":
        y = _run_split(
            lambda x, w, s: packed_matmul(x, w, s, bits=pk.bits, wbr=pk.wbr,
                                          wbc=pk.wbc),
            sw.split, x2, pk.w_int, pk.scale)
    else:                                                  # 'ref'
        y = packed_matmul_ref(x2, pk.w_int, pk.scale, pk.bits,
                              pk.wbr, pk.wbc)
    return y[:, :n].reshape(*lead, n).astype(x.dtype)


def _qmatmul_bitplane(x: jnp.ndarray, sw, backend: str) -> jnp.ndarray:
    """x (..., K) @ bit-plane BitplaneServingWeight (Kp, Np) -> (..., N)."""
    from ..kernels.bitplane_matmul import bitplane_matmul
    from ..kernels.ref import bitplane_matmul_ref
    from ..serve.deploy import serving_to_bitplane_layout
    bl = serving_to_bitplane_layout(sw)
    n = sw.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if backend == "ref":
        y = bitplane_matmul_ref(x2, bl.planes_packed, bl.sign_packed,
                                bl.mask, bl.scale, bl.wbr, bl.wbc)
    else:                                      # 'bitplane' / 'pallas'
        y = _run_split(
            lambda x, p, g, m, s: bitplane_matmul(
                x, p, g, m, s, n_bits=bl.n_bits, wbr=bl.wbr, wbc=bl.wbc),
            sw.split, x2, bl.planes_packed, bl.sign_packed, bl.mask,
            bl.scale)
    return y[:, :n].reshape(*lead, n).astype(x.dtype)


def qmatmul(x: jnp.ndarray, w: Any, *, backend: Optional[str] = None
            ) -> jnp.ndarray:
    """y = x @ W for any weight representation (the model-side matmul).

    ``x``: (..., K) activations; ``w``: plain array, QuantizedTensor,
    FakeQuantTensor, ServingWeight or BitplaneServingWeight with trailing
    (K-ish, N) dims.  Deployed leaves execute on their compressed form
    under a non-dense backend — ``pallas`` runs the leaf's Pallas kernel,
    ``ref`` its jnp oracle, ``bitplane`` the plane-sliced kernel (and
    only that: a packed ServingWeight under ``bitplane`` falls back to
    the dense dequant dot, keeping the backend's byte accounting honest).
    Every other combination dequantizes the single leaf in-graph and
    runs a plain dot."""
    from ..serve.deploy import BitplaneServingWeight, ServingWeight
    backend = backend or current_matmul_backend()
    if _ACT_RECORDERS and isinstance(w, BitplaneServingWeight) and w.tag:
        # Autotune calibration (serve.autotune.sensitivity): capture the
        # per-input-feature second moment of the activations feeding each
        # tagged bit-plane leaf.  Appends are in layer order because the
        # calibration forward runs the layer loop eagerly (scan_layers
        # off), so the recorder can restack per-layer slices.
        x2 = jnp.mean(jnp.square(
            x.reshape(-1, x.shape[-1]).astype(jnp.float32)), axis=0)
        _ACT_RECORDERS[-1].setdefault(w.tag, []).append(x2)
    if isinstance(w, BitplaneServingWeight) and backend != "dense" \
            and w.sign.ndim == 2:
        return _qmatmul_bitplane(x, w, backend)
    if isinstance(w, ServingWeight) and backend in ("pallas", "ref") \
            and w.w_int.ndim == 2:
        return _qmatmul_packed(x, w, backend)
    if isinstance(w, ServingWeight) and backend == "bitplane" \
            and "bitplane-packed-fallback" not in _WARNED_FALLBACKS:
        # once per process (trace-time): the engine warns with leaf paths
        # at construction and the graph lint reports every affected leaf
        _WARNED_FALLBACKS.add("bitplane-packed-fallback")
        import warnings
        warnings.warn(
            "qmatmul: packed ServingWeight under backend='bitplane' falls "
            "back to the in-graph dense dequant dot (the bitplane kernel "
            "streams only the plane-sliced layout; deploy with "
            "layout='bitplane')", stacklevel=2)
    return x @ qdense(w, x.dtype)


_WARNED_FALLBACKS: set = set()

# Stack of active calibration stores (dicts tag -> [x2 per consuming
# call, in call order]).  A list-as-stack so nested calibrations stay
# isolated; empty in normal serving, so the hot path pays one falsy
# check per qmatmul.
_ACT_RECORDERS: list = []


@contextlib.contextmanager
def record_qmatmul_inputs(store: Optional[dict] = None):
    """Capture activation second moments for tagged bit-plane leaves.

    Inside the context every ``qmatmul`` against a ``tag``-labelled
    BitplaneServingWeight appends the (K,)-shaped mean-square of its
    input activations to ``store[tag]``.  Meant for eager (un-scanned)
    calibration forwards — under a traced scan the captured values would
    be tracers.  Yields the store."""
    store = {} if store is None else store
    _ACT_RECORDERS.append(store)
    try:
        yield store
    finally:
        _ACT_RECORDERS.pop()


def prepare_params(params: Any, dtype=None) -> Any:
    """Once-per-step param prep (before the layer scan).

    Casts plain float leaves to the compute dtype and composes bit-plane
    ``QuantizedTensor`` leaves up-front (their bit axis leads, so they
    cannot be sliced by the layer scan).  FakeQuantTensor / ServingWeight
    / BitplaneServingWeight leaves stay in their (scan-sliceable) storage
    — :func:`qmatmul` consumes them one layer at a time, so the serving
    path never holds a whole dequantized param tree."""
    from ..serve.deploy import BitplaneServingWeight, ServingWeight

    def conv(x):
        if isinstance(x, QuantizedTensor):
            return compose(x, dtype)
        if isinstance(x, (FakeQuantTensor, ServingWeight,
                          BitplaneServingWeight)):
            return x
        if dtype is not None and isinstance(x, jnp.ndarray) \
                and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(conv, params, is_leaf=_is_quant)


def act_quant(x: jnp.ndarray, beta: Optional[jnp.ndarray],
              qc: QuantConfig) -> jnp.ndarray:
    """Symmetric PACT activation quantization in front of a quantized matmul."""
    if not qc.enabled or qc.act_bits >= 32 or beta is None:
        return x
    return pact_sym_quant(x, beta.astype(x.dtype), qc.act_bits)


def make_beta(qc: QuantConfig, dtype=jnp.float32):
    return jnp.asarray(qc.pact_init, dtype) if qc.enabled and qc.act_bits < 32 \
        else None


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return jax.random.normal(key, (vocab, d), dtype) * 0.02


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(x / cap) if cap and cap > 0 else x
