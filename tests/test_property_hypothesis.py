"""Property-based tests: BWQ-A invariants and qmatmul backend parity.

Runs under `hypothesis` when installed; otherwise the deterministic
fallback driver (`repro.testing.proptest`) draws a bounded seeded case
set, so these properties are exercised in every environment instead of
silently skipping."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                        # optional dep: seeded fallback
    from repro.testing import proptest as _pt
    given, settings, st = _pt.given, _pt.settings, _pt

from repro.core import (BlockingSpec, adjust_precision, bitwidths, compose,
                        from_float, layer_bit_count, requantize)
from repro.core.blocking import block_elem_counts
from repro.core.fakequant import fq_from_float, fq_maintenance, fq_compose
from repro.kernels.ref import pack_bits, unpack_bits
from repro.models.common import QuantConfig, make_weight, qmatmul
from repro.serve.deploy import bitplane_stream_bytes, to_serving_params

# the whole module is randomized sweeps: full-tier / local-only
pytestmark = pytest.mark.slow

SETTINGS = dict(max_examples=20, deadline=None)


@st.composite
def weight_case(draw):
    k = draw(st.integers(5, 40))
    n = draw(st.integers(5, 40))
    n_bits = draw(st.sampled_from([2, 4, 8]))
    wbr = draw(st.sampled_from([3, 8, 9]))
    wbc = draw(st.sampled_from([4, 8]))
    seed = draw(st.integers(0, 2 ** 16))
    scale = draw(st.floats(1e-3, 10.0))
    return k, n, n_bits, wbr, wbc, seed, scale


@given(weight_case())
@settings(**SETTINGS)
def test_reconstruction_bound(case):
    """|compose(from_float(w)) - w| <= scale / (2^n - 1) / 2 elementwise."""
    k, n, n_bits, wbr, wbc, seed, scale = case
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * scale
    qt = from_float(w, n_bits, BlockingSpec(wbr, wbc))
    err = np.max(np.abs(np.asarray(compose(qt) - w)))
    bound = float(jnp.max(jnp.abs(w))) / (2 ** n_bits - 1) / 2
    assert err <= bound * (1 + 1e-5) + 1e-9


@given(weight_case())
@settings(**SETTINGS)
def test_precision_adjustment_monotone_and_prefix(case):
    k, n, n_bits, wbr, wbc, seed, scale = case
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * scale
    qt = requantize(from_float(w, n_bits, BlockingSpec(wbr, wbc)))
    qt1 = adjust_precision(qt)
    mask = np.asarray(qt1.mask)
    # prefix property: once a bit is off, all higher bits are off
    for b in range(1, n_bits):
        assert np.all(mask[b] <= mask[b - 1] + 1e-9)
    # monotone under repetition
    qt2 = adjust_precision(requantize(qt1))
    assert np.all(np.asarray(bitwidths(qt2)) <= np.asarray(bitwidths(qt1)))


@given(weight_case())
@settings(**SETTINGS)
def test_requantize_composes_exactly_representable(case):
    """After requantize, compose is on the exact scale grid."""
    k, n, n_bits, wbr, wbc, seed, scale = case
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * scale
    qt = requantize(from_float(w, n_bits, BlockingSpec(wbr, wbc)))
    wq = np.asarray(compose(qt), dtype=np.float64)
    s = float(qt.scale) / (2 ** n_bits - 1)
    q = wq / s
    assert np.max(np.abs(q - np.round(q))) < 1e-3


@given(weight_case())
@settings(**SETTINGS)
def test_live_bits_match_numpy_reference(case):
    k, n, n_bits, wbr, wbc, seed, scale = case
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * scale
    spec = BlockingSpec(wbr, wbc)
    qt = adjust_precision(requantize(from_float(w, n_bits, spec)))
    elems = np.asarray(block_elem_counts((k, n), spec))
    bw = np.asarray(bitwidths(qt))
    assert float(layer_bit_count(qt)) == float((bw * elems).sum())


@given(weight_case())
@settings(**SETTINGS)
def test_fakequant_tracks_bitplane(case):
    """fake-quant compose == bit-plane compose for exact-binary states."""
    k, n, n_bits, wbr, wbc, seed, scale = case
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * scale
    spec = BlockingSpec(wbr, wbc)
    qt = requantize(adjust_precision(requantize(from_float(w, n_bits, spec))))
    fq = fq_from_float(w, n_bits, spec)
    fq = dataclasses.replace(
        fq, bitwidth=jnp.sum(qt.mask, axis=0).astype(fq.bitwidth.dtype))
    fq = fq_maintenance(fq)
    np.testing.assert_allclose(np.asarray(fq_compose(fq)),
                               np.asarray(compose(qt)),
                               atol=float(qt.scale) * 1e-5 + 1e-6)


@given(st.integers(1, 8), st.integers(1, 6), st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_pack_unpack_bits_roundtrip(rows8, cols, seed):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, size=(rows8 * 8, cols)).astype(np.float32)
    packed = pack_bits(jnp.asarray(bits))
    out = np.asarray(unpack_bits(packed))
    np.testing.assert_array_equal(out, bits)


# ---------------------------------------------------------------------------
# qmatmul backend parity (pad-and-trim kernel paths)
# ---------------------------------------------------------------------------
#
# The PR 3 kernels pad non-tile-divisible M/K/N and trim the result; until
# now only hand-picked shapes were covered (tests/test_kernels.py).  These
# draw random matmul problems — decode-shaped M=1..16, ragged N, and K
# values whose block padding is odd under the paper's 9x8 WB geometry (the
# int4 nibble-pack must add a zero row) — and assert the dense in-graph
# dequant, the Pallas kernel (interpret mode on CPU), and the pure-jnp
# oracle agree on deployed packed weights.

@st.composite
def matmul_case(draw):
    m = draw(st.sampled_from([1, 2, 3, 5, 7, 8, 13, 16, 33, 64]))
    # 9-row WBs (paper geometry) make K=9/27/63 block-pad to odd rows
    wbr, wbc = draw(st.sampled_from([(9, 8), (3, 8), (8, 128)]))
    k = draw(st.sampled_from([9, 17, 27, 63, 64, 72, 128]))
    n = draw(st.sampled_from([8, 24, 56, 96, 128, 200]))
    bits = draw(st.sampled_from([8, 4]))
    seed = draw(st.integers(0, 2 ** 16))
    return m, k, n, bits, wbr, wbc, seed


@given(matmul_case())
@settings(max_examples=10, deadline=None)
def test_qmatmul_backend_parity_random_shapes(case):
    m, k, n, bits, wbr, wbc, seed = case
    qc = QuantConfig(mode="fake", n_bits=8, wb_rows=wbr, wb_cols=wbc)
    w = make_weight(jax.random.PRNGKey(seed), (k, n), qc)
    sw = to_serving_params({"w": w}, bits=bits)["w"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (m, k))
    y_dense = np.asarray(qmatmul(x, sw, backend="dense"))
    y_ref = np.asarray(qmatmul(x, sw, backend="ref"))
    y_pal = np.asarray(qmatmul(x, sw, backend="pallas"))
    assert y_dense.shape == y_ref.shape == y_pal.shape == (m, n)
    scale = np.abs(y_ref).max() + 1e-9
    np.testing.assert_allclose(y_pal / scale, y_ref / scale, atol=1e-5)
    np.testing.assert_allclose(y_dense / scale, y_ref / scale, atol=1e-4)


@given(matmul_case(), st.integers(1, 4))
@settings(max_examples=6, deadline=None)
def test_qmatmul_batched_inputs_match_flat(case, extra_dim):
    """qmatmul flattens leading dims before the kernel and restores them —
    a (B, S, K) activation must equal row-by-row 2-D calls."""
    m, k, n, bits, wbr, wbc, seed = case
    qc = QuantConfig(mode="fake", n_bits=8, wb_rows=wbr, wb_cols=wbc)
    w = make_weight(jax.random.PRNGKey(seed), (k, n), qc)
    sw = to_serving_params({"w": w}, bits=bits)["w"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (extra_dim, m, k))
    y = np.asarray(qmatmul(x, sw, backend="ref"))
    assert y.shape == (extra_dim, m, n)
    for b in range(extra_dim):
        yb = np.asarray(qmatmul(x[b], sw, backend="ref"))
        np.testing.assert_allclose(y[b], yb, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# bit-plane serving layout: bitplane_matmul vs its jnp oracle vs the dense
# compose, under random decode-shaped M, ragged N, odd block-padded K and
# *mixed per-block bit-widths* (the paper's whole point: each block's live
# bit count is what the kernel streams and what the bytes accounting bills)
# ---------------------------------------------------------------------------

def _mixed_fq(k, n, qc, seed):
    """FakeQuantTensor with a random per-WB bit-width assignment (0..8),
    snapped onto its grid by fq_maintenance."""
    fq = make_weight(jax.random.PRNGKey(seed), (k, n), qc)
    gr, gc = qc.spec.grid(k, n)
    bws = jax.random.randint(jax.random.PRNGKey(seed + 1), (gr, gc), 0, 9)
    fq = dataclasses.replace(fq, bitwidth=bws.astype(fq.bitwidth.dtype))
    return fq_maintenance(fq)


@st.composite
def bitplane_case(draw):
    m = draw(st.sampled_from([1, 2, 3, 5, 8, 13, 16, 33]))
    # 9x8 is the paper OU geometry; 9-row WBs block-pad K to odd rows
    wbr, wbc = draw(st.sampled_from([(9, 8), (3, 8), (8, 128)]))
    k = draw(st.sampled_from([9, 17, 27, 63, 64, 72, 128]))
    n = draw(st.sampled_from([8, 24, 56, 100, 128]))
    bits = draw(st.sampled_from([8, 4]))
    seed = draw(st.integers(0, 2 ** 16))
    return m, k, n, bits, wbr, wbc, seed


@given(bitplane_case())
@settings(max_examples=10, deadline=None)
def test_bitplane_backend_parity_mixed_bitwidths(case):
    """Pallas bitplane kernel == jnp oracle == dense compose on the
    plane-sliced serving weight, for mixed per-block bit-widths."""
    m, k, n, bits, wbr, wbc, seed = case
    qc = QuantConfig(mode="fake", n_bits=8, wb_rows=wbr, wb_cols=wbc)
    fq = _mixed_fq(k, n, qc, seed)
    bp = to_serving_params({"w": fq}, bits=bits, layout="bitplane")["w"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (m, k))
    y_dense = np.asarray(qmatmul(x, bp, backend="dense"))
    y_ref = np.asarray(qmatmul(x, bp, backend="ref"))
    y_bp = np.asarray(qmatmul(x, bp, backend="bitplane"))
    assert y_dense.shape == y_ref.shape == y_bp.shape == (m, n)
    scale = np.abs(y_ref).max() + 1e-9
    np.testing.assert_allclose(y_bp / scale, y_ref / scale, atol=1e-5)
    np.testing.assert_allclose(y_dense / scale, y_ref / scale, atol=1e-5)


@given(bitplane_case())
@settings(max_examples=10, deadline=None)
def test_bitplane_composes_identical_to_packed(case):
    """Cross-representation invariant: both serving layouts quantize
    through the same integer grid, so their dense composes — and hence
    dense-backend outputs — are BIT-IDENTICAL, and the bit-plane layout
    never streams more plane-bytes than the packed container would
    (min(bw, bits) + sign planes <= (bits+...) worth of payload for every
    mixed assignment; fully-masked blocks stream nothing)."""
    m, k, n, bits, wbr, wbc, seed = case
    qc = QuantConfig(mode="fake", n_bits=8, wb_rows=wbr, wb_cols=wbc)
    fq = _mixed_fq(k, n, qc, seed)
    bp = to_serving_params({"w": fq}, bits=bits, layout="bitplane")["w"]
    pk = to_serving_params({"w": fq}, bits=bits)["w"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (m, k))
    y_bp = np.asarray(qmatmul(x, bp, backend="dense"))
    y_pk = np.asarray(qmatmul(x, pk, backend="dense"))
    np.testing.assert_array_equal(y_bp, y_pk)
    # occupancy accounting: mask rows mirror min(bw, bits) exactly
    live = np.asarray(bp.mask).sum(axis=0)
    want = np.minimum(np.asarray(fq.bitwidth), bits)
    np.testing.assert_array_equal(live, want)
    assert bitplane_stream_bytes(bp) > 0


# ---------------------------------------------------------------------------
# fused paged-attention kernel vs jnp oracle (randomized shapes)
# ---------------------------------------------------------------------------

@st.composite
def paged_attn_case(draw):
    b = draw(st.integers(1, 3))
    kv = draw(st.sampled_from([1, 2, 4]))
    g = draw(st.sampled_from([1, 2, 4]))
    dh = draw(st.sampled_from([8, 16, 32]))
    page = draw(st.sampled_from([2, 4, 8]))
    nb = draw(st.integers(1, 4))
    bits = draw(st.sampled_from([8, 4, 32]))
    window = draw(st.sampled_from([None, 3, 7]))
    block_kv = draw(st.sampled_from([1, 2]))
    seed = draw(st.integers(0, 2 ** 16))
    return b, kv, g, dh, page, nb, bits, window, block_kv, seed


@given(paged_attn_case())
@settings(max_examples=10, deadline=None)
def test_paged_attention_kernel_matches_ref(case):
    """Fused Pallas decode kernel (in-kernel dequant, block-table walk)
    == jnp gather+softmax oracle for random pools, ragged per-slot fill
    levels, GQA ratios, kv-bits, windows, and block_kv tiles."""
    from repro.kernels.kv_pages import PageFormat, to_rows
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref
    from repro.models.attention import quantize_kv

    b, kv, g, dh, page, nb, bits, window, block_kv, seed = case
    if block_kv > kv or kv % block_kv:
        block_kv = 1
    n_pages, stack, layer = 1 + b * nb, 2, seed % 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, kv, g, dh), jnp.float32)
    shape = (stack, n_pages, page, kv, dh)
    kf = jax.random.normal(ks[1], shape, jnp.float32)
    vf = jax.random.normal(ks[2], shape, jnp.float32)
    if bits < 32:
        kq, ksc = quantize_kv(kf, bits)
        vq, vsc = quantize_kv(vf, bits)
    else:
        kq, vq, ksc, vsc = kf, vf, None, None

    def lane_dense(x):                   # the stored pool (kernels.kv_pages)
        if x is None:
            return None
        w = int(np.prod(x.shape[3:]))
        return to_rows(x.reshape(stack, n_pages, page, w),
                       PageFormat(page, w))
    kq, vq, ksc, vsc = map(lane_dense, (kq, vq, ksc, vsc))
    table = jnp.arange(1, 1 + b * nb, dtype=jnp.int32).reshape(b, nb)
    kv_len = jax.random.randint(ks[3], (b,), 1,
                                nb * page + 1).astype(jnp.int32)
    got = paged_attention(q, kq, vq, ksc, vsc, table, kv_len, page=page,
                          layer=layer, window=window, block_kv=block_kv)
    want = paged_attention_ref(q, kq, vq, ksc, vsc, table, kv_len,
                               page=page, layer=layer, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
