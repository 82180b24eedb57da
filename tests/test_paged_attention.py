"""Fused paged-attention decode kernel: unit, oracle, lint, and parity.

* ``page_coords`` / ``kv_pages.read_pages`` edge cases —
  clamp-into-last-block past the table end, trash-page (page 0) routing,
  (B,) vs scalar fill levels — previously covered only indirectly
  through serving parity;
* kernel vs ``paged_attention_ref`` allclose across kv-bits, GQA ratios,
  ragged fill levels, sliding windows, softcap, and ``block_kv`` tiles;
  fills at and around page and multi-page step boundaries, a parked row
  (an all-trash table), and the contiguous cache's identity view; the
  stacked lane-dense pool read at a layer other than 0, and the cache
  write then read back on that storage;
* graph-lint footprint census: the fused decode jaxpr holds neither a
  full-width KV gather nor an f32 KV materialization (``kv-clean``), and
  a forced gather fallback under a fused engine is an ERROR;
* decode token parity: greedy decodes are bit-identical across
  ``attn_backend`` in {gather, fused, ref}, contiguous and paged, at
  kv_bits in {8, 4} (phi3 fast tier; granite-moe in the slow tier).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.kernels.kv_pages import (PageFormat, make_pool, read_pages,
                                    to_rows)
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.models.api import build
from repro.models.attention import (PAGED_ATTN_BACKENDS, _decode_read,
                                    _gather_read, _write_kv, dequantize_kv,
                                    page_coords, paged_attn_backend,
                                    quantize_kv)
from repro.models.common import QuantConfig
from repro.serve import Request, SamplingParams, ServeEngine


# ---------------------------------------------------------------------------
# page_coords / paged_gather edge cases
# ---------------------------------------------------------------------------

def test_page_coords_basic_mapping():
    table = jnp.asarray([[3, 1], [2, 5]], jnp.int32)     # (B=2, nb=2)
    pids, offs = page_coords(table, jnp.asarray([0, 5]), seq=2, page=4)
    # slot 0 writes positions 0,1 -> block 0 (page 3), offsets 0,1
    assert pids[0].tolist() == [3, 3] and offs[0].tolist() == [0, 1]
    # slot 1 writes positions 5,6 -> block 1 (page 5), offsets 1,2
    assert pids[1].tolist() == [5, 5] and offs[1].tolist() == [1, 2]


def test_page_coords_past_table_end_is_inert():
    table = jnp.asarray([[7, 9]], jnp.int32)             # nb=2, page=4: T=8
    pids, offs = page_coords(table, 7, seq=2, page=4)
    # position 7 is the last real slot and lands in the last block;
    # position 8 is past the table end — callers only ever send masked
    # scratch writes there, so its page id must never alias a live page
    # other than the clamp target (scatter drops out-of-range ids)
    assert int(pids[0, 0]) == 9 and offs[0].tolist() == [3, 0]
    tail = int(pids[0, 1])
    assert tail == 9 or not (0 <= tail <= 8)
    # the write path stays inert: scattering through these coords must not
    # touch any page other than the last block (out-of-range ids drop)
    pool = jnp.zeros((10, 4), jnp.float32)
    wrote = pool.at[pids[0], offs[0]].set(1.0)
    assert float(wrote[:9].sum()) == 0.0


def test_page_coords_scalar_vs_vector_fill_levels():
    table = jnp.asarray([[4, 2], [6, 8]], jnp.int32)
    ps, os_ = page_coords(table, 3, seq=2, page=4)
    pv, ov = page_coords(table, jnp.asarray([3, 3]), seq=2, page=4)
    np.testing.assert_array_equal(np.asarray(ps), np.asarray(pv))
    np.testing.assert_array_equal(np.asarray(os_), np.asarray(ov))


def test_page_coords_trash_page_for_unallocated_blocks():
    # a parked slot's table is all zeros: every write routes to page 0
    table = jnp.zeros((1, 3), jnp.int32)
    pids, _ = page_coords(table, 5, seq=3, page=4)
    assert pids.tolist() == [[0, 0, 0]]


def test_paged_gather_layout_and_trash_masking():
    # 5 pages of 2 tokens of 3 values, stored lane-dense (a row of 128
    # lanes holds 32 tokens: 16 pages share one row block)
    vals = jnp.arange(5 * 2 * 3, dtype=jnp.float32).reshape(1, 5, 2, 3)
    fmt = PageFormat(2, 3)
    pool = to_rows(vals, fmt)
    assert pool.shape == (1, 1, 1, 128)
    table = jnp.asarray([[2, 0], [4, 1]], jnp.int32)
    out = read_pages(pool, fmt, 0, table)                # (B, nb*page, 3)
    assert out.shape == (2, 4, 3)
    np.testing.assert_array_equal(np.asarray(out[0, :2]),
                                  np.asarray(vals[0, 2]))
    np.testing.assert_array_equal(np.asarray(out[0, 2:]),
                                  np.asarray(vals[0, 0]))  # trash page
    np.testing.assert_array_equal(np.asarray(out[1, 2:]),
                                  np.asarray(vals[0, 1]))


# ---------------------------------------------------------------------------
# kernel vs reference oracle
# ---------------------------------------------------------------------------

def _lane_dense(x, page, width):
    """(stack, n_pages, page, ...) values -> the stacked lane-dense pool
    leaf the kernels read (``kernels.kv_pages``)."""
    return to_rows(x.reshape(*x.shape[:3], width), PageFormat(page, width))


def _quantized_pools(kf, vf, bits, page):
    """Stacked (stack, P, page, KV, dh) K/V values -> lane-dense int8 /
    int4 / f32 pool leaves and scales (or None)."""
    kv, dh = kf.shape[-2:]
    if bits < 32:
        kq, ksc = quantize_kv(kf, bits)
        vq, vsc = quantize_kv(vf, bits)
        ksc, vsc = _lane_dense(ksc, page, kv), _lane_dense(vsc, page, kv)
    else:
        kq, vq, ksc, vsc = kf, vf, None, None
    w = kv * kq.shape[-1]
    return _lane_dense(kq, page, w), _lane_dense(vq, page, w), ksc, vsc


def _pool_case(key, b, kv, g, dh, page, nb, bits, stack=1):
    """Random page pool + table + ragged fill levels for one case."""
    n_pages = 1 + b * nb
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, kv, g, dh), jnp.float32)
    kf = jax.random.normal(ks[1], (stack, n_pages, page, kv, dh), jnp.float32)
    vf = jax.random.normal(ks[2], (stack, n_pages, page, kv, dh), jnp.float32)
    kq, vq, ksc, vsc = _quantized_pools(kf, vf, bits, page)
    table = jnp.arange(1, 1 + b * nb, dtype=jnp.int32).reshape(b, nb)
    kv_len = (jax.random.randint(jax.random.fold_in(key, 9), (b,), 1,
                                 nb * page + 1).astype(jnp.int32))
    return q, kq, vq, ksc, vsc, table, kv_len


# page 16 and 20 blocks: the kernel copies 8 pages (128 tokens) a step.
# Fills of 1 token, one page, one block, one block + 1, two blocks and
# the whole table; 8 and 16 live pages are whole steps, 9 and 20 are not.
WALK_PAGE, WALK_NB = 16, 20
WALK = (1, 16, 128, 129, 256, 320)
CONTIG_T = 256                # the contiguous view: 2 pages of 128 a row
CONTIG = (1, 128, 129, 256)


def _walk_case(key, bits, g, fills, *, kv=4, dh=16, page=WALK_PAGE,
               nb=WALK_NB, parked=False, stack=1):
    """A page pool whose rows hold ``fills`` tokens in shuffled pages,
    the rest of each table pointing at the trash page; ``parked`` adds a
    last row as the scheduler sends a parked slot (an all-trash table,
    ``kv_len = nb * page``)."""
    n_live = [-(-f // page) for f in fills]
    n_pages = 1 + sum(n_live)
    b = len(fills) + parked
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, kv, g, dh), jnp.float32)
    kf = jax.random.normal(ks[1], (stack, n_pages, page, kv, dh), jnp.float32)
    vf = jax.random.normal(ks[2], (stack, n_pages, page, kv, dh), jnp.float32)
    kq, vq, ksc, vsc = _quantized_pools(kf, vf, bits, page)
    ids = 1 + np.asarray(jax.random.permutation(ks[3], n_pages - 1))
    table = np.zeros((b, nb), np.int32)
    starts = np.cumsum([0] + n_live)
    for r, n in enumerate(n_live):
        table[r, :n] = ids[starts[r]:starts[r] + n]
    kv_len = np.asarray(list(fills) + [nb * page] * parked, np.int32)
    return q, kq, vq, ksc, vsc, jnp.asarray(table), jnp.asarray(kv_len)


def _contiguous_read(bits, g, fills, window, softcap, *, kv=4, dh=16):
    """Fused and reference decode reads of a contiguous cache through
    ``_decode_read``'s page-128 view (identity table, page 0 is data)."""
    b = len(fills)
    ks = jax.random.split(jax.random.PRNGKey(bits + g), 3)
    q = jax.random.normal(ks[0], (b, 1, kv * g, dh), jnp.float32)
    cache = {}
    for name, k in (("k", ks[1]), ("v", ks[2])):
        x = jax.random.normal(k, (1, b, CONTIG_T, kv, dh), jnp.float32)
        if bits < 32:
            cache[name], cache[name + "_scale"] = quantize_kv(x, bits)
        else:
            cache[name] = x
    kv_len = jnp.asarray(fills, jnp.int32)
    win = 0 if window is None else window
    return [_decode_read(q, cache, kv_len, win, softcap, backend)
            for backend in ("fused", "ref")]


@pytest.mark.parametrize("bits,g,window,softcap,block_kv,fills,layout", [
    pytest.param(8, 1, None, 0.0, 1, None, "paged", id="8-1-None-0.0-1"),
    # GQA grouping + kv-head tiling
    pytest.param(8, 4, None, 0.0, 2, None, "paged", id="8-4-None-0.0-2"),
    # nibble-packed int4 in-kernel unpack
    pytest.param(4, 2, None, 0.0, 1, None, "paged", id="4-2-None-0.0-1"),
    # float pool (paged, unquantized)
    pytest.param(32, 2, None, 0.0, 1, None, "paged", id="32-2-None-0.0-1"),
    # sliding window + softcap
    pytest.param(8, 2, 5, 30.0, 1, None, "paged", id="8-2-5-30.0-1"),
    # rows of every fill in one call: the per-row walk bound
    *[pytest.param(bits, g, None, 0.0, None, WALK, "paged",
                   id=f"walk-{name}-g{g}")
      for bits, name in ((8, "int8"), (4, "int4"), (32, "f32"))
      for g in (1, 2)],
    pytest.param(8, 1, 40, 30.0, None, WALK, "paged",
                 id="walk-window-softcap-g1"),
    pytest.param(8, 2, 40, 30.0, None, WALK, "paged",
                 id="walk-window-softcap-g2"),
    # a parked row among them: walks nothing, returns zeros
    pytest.param(8, 1, None, 0.0, None, WALK, "parked", id="parked-g1"),
    pytest.param(4, 2, None, 0.0, None, WALK, "parked", id="parked-g2"),
    pytest.param(8, 1, None, 0.0, None, (), "parked", id="parked-only"),
    # the contiguous cache's identity view, page 0 holding data
    pytest.param(8, 1, None, 0.0, None, CONTIG, "contiguous",
                 id="contiguous-int8-g1"),
    pytest.param(4, 2, 100, 30.0, None, CONTIG, "contiguous",
                 id="contiguous-int4-g2-window-softcap"),
])
def test_kernel_matches_ref(bits, g, window, softcap, block_kv, fills,
                            layout):
    if layout == "contiguous":
        got, want = _contiguous_read(bits, g, fills, window, softcap)
    else:
        key = jax.random.PRNGKey(bits * 7 + g)
        case = (_pool_case(key, 2, 4, g, 16, 4, 3, bits) if fills is None
                else _walk_case(key, bits, g, fills,
                                parked=layout == "parked"))
        page = 4 if fills is None else WALK_PAGE
        got = paged_attention(*case, page=page, window=window,
                              softcap=softcap, block_kv=block_kv)
        want = paged_attention_ref(*case, page=page, window=window,
                                   softcap=softcap)
    got, want = np.asarray(got), np.asarray(want)
    if layout == "parked":
        # the reference walks the trash page for the parked row; the
        # kernel walks nothing there (zeros), and the row is discarded
        assert np.isfinite(got[-1]).all()
        np.testing.assert_array_equal(got[-1], 0.0)
        got, want = got[:-1], want[:-1]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# the stacked pool read in place: a layer other than 0 of a 3-layer stack,
# at head dims whose tokens share a 128-lane row (dh 16: 4 heads x 16 =
# 64 values) or fill one (dh 32: 128 values, int4 64)
STACKED = [pytest.param(bits, g, dh, window, softcap, parked,
                        id=f"{name}-g{g}-dh{dh}" + ("-window-softcap"
                                                     if window else "")
                        + ("-parked" if parked else ""))
           for bits, name in ((8, "int8"), (4, "int4"), (32, "f32"))
           for g in (1, 2) for dh in (16, 32)
           for window, softcap, parked in ((None, 0.0, True),
                                           (40, 30.0, False))]


@pytest.mark.parametrize("bits,g,dh,window,softcap,parked", STACKED)
def test_kernel_reads_stacked_pool_at_layer(bits, g, dh, window, softcap,
                                            parked):
    """Fills of 1, 16, 129 and 320 tokens (and a parked row) in a stacked
    lane-dense pool, read at layer 2 of 3 against the oracle."""
    key = jax.random.PRNGKey(100 + bits * 7 + g + dh)
    case = _walk_case(key, bits, g, (1, 16, 129, 320), dh=dh,
                      parked=parked, stack=3)
    kw = dict(page=WALK_PAGE, layer=2, window=window, softcap=softcap)
    got = np.asarray(paged_attention(*case, **kw))
    want = np.asarray(paged_attention_ref(*case, **kw))
    if parked:
        np.testing.assert_array_equal(got[-1], 0.0)
        got, want = got[:-1], want[:-1]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # another layer's pages give another answer: the layer index is read
    other = np.asarray(paged_attention(*case, **dict(kw, layer=0)))
    assert not np.allclose(other[:len(got)], got, atol=1e-3)


@pytest.mark.parametrize("bits", [8, 4])
def test_kernel_bf16_queries_match_ref(bits):
    """bf16 queries against an int8 / int4 pool take the one-pass bf16
    score dot; its products are exact, so the kernel still meets the
    oracle at f32 tolerance."""
    case = _walk_case(jax.random.PRNGKey(bits), bits, 2, (16, 129),
                      dh=32, stack=2)
    q = case[0].astype(jnp.bfloat16)
    kw = dict(page=WALK_PAGE, layer=1)
    got = paged_attention(q, *case[1:], **kw)
    want = paged_attention_ref(q, *case[1:], **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("bits,kv,dh,page", [
    (8, 4, 16, 4), (8, 32, 96, 16), (4, 4, 16, 3), (4, 2, 16, 8),
    (32, 4, 32, 4)])
@pytest.mark.parametrize("vector_index", [False, True])
def test_write_then_read_round_trip(bits, kv, dh, page, vector_index):
    """``_write_kv`` scatters a layer's new tokens into the lane-dense
    stacked pool and ``_gather_read`` reads them back: the stored values
    (rounded once) return at their positions, in that layer alone."""
    b, nb, stack, layer, seq = 2, 3, 3, 1, 5
    dt = {8: jnp.int8, 4: jnp.uint8, 32: jnp.float32}[bits]
    dh_s = dh // 2 if bits == 4 else dh
    tails = {"k": ((kv, dh_s), dt), "v": ((kv, dh_s), dt)}
    if bits < 32:
        tails.update(k_scale=((kv,), jnp.float32),
                     v_scale=((kv,), jnp.float32))
    table = jnp.asarray([[3, 1, 6], [2, 5, 4]], jnp.int32)
    cache = {"pages": make_pool(tails, stack, 1 + b * nb, page),
             "table": jnp.broadcast_to(table, (stack, b, nb))}
    ks = jax.random.split(jax.random.PRNGKey(bits + page), 2)
    k = jax.random.normal(ks[0], (b, seq, kv, dh), jnp.float32)
    v = jax.random.normal(ks[1], (b, seq, kv, dh), jnp.float32)
    idx = jnp.asarray([0, page + 1]) if vector_index else jnp.asarray(2)
    new = _write_kv(cache, k, v, idx, bits, layer)
    got_k, got_v = _gather_read(new, jnp.float32, layer)
    if bits < 32:
        want_k = dequantize_kv(*quantize_kv(k, bits), jnp.float32)
        want_v = dequantize_kv(*quantize_kv(v, bits), jnp.float32)
    else:
        want_k, want_v = k, v
    starts = np.broadcast_to(np.asarray(idx), (b,))
    for row in range(b):
        at = slice(int(starts[row]), int(starts[row]) + seq)
        np.testing.assert_array_equal(np.asarray(got_k[row, at]),
                                      np.asarray(want_k[row]))
        np.testing.assert_array_equal(np.asarray(got_v[row, at]),
                                      np.asarray(want_v[row]))
    for other in (0, 2):                 # the other layers stay zero
        for leaf in new["pages"].values():
            assert not np.asarray(leaf[other]).any()


def test_kernel_trash_page_stays_inert():
    """Blocks past a slot's fill level point at real-but-stale pages or
    the trash page; both must be masked identically."""
    b, kv, g, dh, page, nb = 1, 2, 2, 8, 4, 3
    case = _pool_case(jax.random.PRNGKey(0), b, kv, g, dh, page, nb, 8)
    q, kq, vq, ksc, vsc, table, _ = case
    kv_len = jnp.asarray([page], jnp.int32)      # only block 0 is live
    trash_table = table.at[0, 1:].set(0)         # blocks 1.. -> trash page
    a = paged_attention(q, kq, vq, ksc, vsc, table, kv_len, page=page)
    t = paged_attention(q, kq, vq, ksc, vsc, trash_table, kv_len, page=page)
    np.testing.assert_allclose(np.asarray(a), np.asarray(t), atol=1e-6)


# ---------------------------------------------------------------------------
# footprint census (graph lint)
# ---------------------------------------------------------------------------

def _census_engine(kv_bits=8):
    cfg = REGISTRY["phi3-mini-3.8b"].tiny(dtype="float32").with_quant(
        QuantConfig(mode="fake", n_bits=8, act_bits=8))
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return ServeEngine(api, params, kv_quant_bits=kv_bits,
                       attn_backend="fused", page_size=4)


def _decode_args(eng, n_slots=2, max_len=24, page_size=4):
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}
    state = jax.eval_shape(
        lambda p, b: eng.api.init_decode_state(p, b, n_slots, max_len,
                                               page_size=page_size),
        eng.params, batch)
    tokens = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)
    index = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    return eng.params, tokens, state, index


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_fused_decode_jaxpr_is_kv_clean(kv_bits):
    from repro.analysis.graph_lint import lint_traced_fn
    eng = _census_engine(kv_bits)
    findings = lint_traced_fn(eng.api.decode_step, _decode_args(eng),
                              fn_name="decode", backend="dense",
                              attn_backend="fused")
    assert not [f for f in findings if f.severity == "error"], \
        [f.format() for f in findings]
    assert any(f.rule == "kv-clean" for f in findings)


def test_gather_fallback_under_fused_is_error():
    from repro.analysis.graph_lint import lint_traced_fn
    eng = _census_engine(8)

    def gather_decode(p, t, s, i):
        with paged_attn_backend("gather"):       # the silent fallback
            return eng.api.decode_step(p, t, s, i)

    findings = lint_traced_fn(gather_decode, _decode_args(eng),
                              fn_name="decode", backend="dense",
                              attn_backend="fused")
    errs = {f.rule for f in findings if f.severity == "error"}
    assert {"kv-full-width-gather", "kv-dequant-materialization"} <= errs


def test_gather_backend_is_sanctioned():
    from repro.analysis.graph_lint import lint_traced_fn
    eng = _census_engine(8)
    findings = lint_traced_fn(eng.api.decode_step, _decode_args(eng),
                              fn_name="decode", backend="dense",
                              attn_backend="gather")
    assert not [f for f in findings if f.severity == "error"]
    assert any(f.rule.startswith("sanctioned-kv") for f in findings)


# ---------------------------------------------------------------------------
# PA* contracts
# ---------------------------------------------------------------------------

def test_pa_contracts_flag_bad_pools():
    from repro.analysis.contracts import validate_decode_state
    tails = {"k": ((2, 8), jnp.int8), "v": ((2, 8), jnp.int8),
             "k_scale": ((2,), jnp.float32), "v_scale": ((2,), jnp.float32)}
    pool = make_pool(tails, stack=1, n_pages=4, page=2)
    table = jnp.zeros((1, 3, 2), jnp.int32)
    good = {"cache": {"layer": {"pages": pool, "table": table}}}
    assert not [f for f in validate_decode_state(good, n_slots=3)
                if f.severity == "error"]
    # PA1: k/v dtype disagreement
    bad = {"cache": {"layer": {
        "pages": pool.replace(v=pool["v"].astype(jnp.uint8)),
        "table": table}}}
    assert any(f.rule == "PA1" for f in validate_decode_state(bad)
               if f.severity == "error")
    # PA2: pool with only the trash page
    bad = {"cache": {"layer": {
        "pages": make_pool(tails, stack=1, n_pages=1, page=2),
        "table": table}}}
    assert any(f.rule == "PA2" for f in validate_decode_state(bad)
               if f.severity == "error")
    # PA3: live page after a trash-page hole
    holey = table.at[0, 0, 1].set(2)          # row [0, 2]: hole at block 0
    bad = {"cache": {"layer": {"pages": pool, "table": holey}}}
    assert any(f.rule == "PA3" for f in validate_decode_state(bad)
               if f.severity == "error")


# ---------------------------------------------------------------------------
# decode token parity across attn backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,kv_bits", [
    ("phi3-mini-3.8b", 8),
    ("phi3-mini-3.8b", 4),
    pytest.param("granite-moe-3b-a800m", 8, marks=pytest.mark.slow),
    pytest.param("granite-moe-3b-a800m", 4, marks=pytest.mark.slow),
])
def test_decode_token_parity_across_attn_backends(arch, kv_bits):
    cfg = REGISTRY[arch].tiny(dtype="float32").with_quant(
        QuantConfig(mode="fake", n_bits=8, act_bits=8))
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(3), (2, 8), 0, cfg.vocab).astype(jnp.int32)}
    outs = {}
    for ab in PAGED_ATTN_BACKENDS:
        eng = ServeEngine(api, params, kv_quant_bits=kv_bits,
                          attn_backend=ab)
        outs[ab] = np.asarray(eng.generate(batch, max_new=6))
        reqs = [Request(uid=i,
                        inputs={"tokens": batch["tokens"][i:i + 1]},
                        sampling=SamplingParams(max_new_tokens=5),
                        arrival=i)
                for i in range(2)]
        res = eng.serve(reqs, n_slots=2, page_size=4)
        outs[ab + "_paged"] = [r.tokens for r in res]
    for ab in ("fused", "ref"):
        np.testing.assert_array_equal(outs[ab], outs["gather"])
        assert outs[ab + "_paged"] == outs["gather_paged"]
