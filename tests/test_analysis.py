"""Static serving-graph analysis (repro.analysis) — the lint subsystem.

* contract validator NEGATIVE paths: each corruption of a deployed tree
  (trailing stack dims, wrong scale-LUT shape, non-binary / non-monotone
  bitplane mask, truncated sign plane, orphaned block-table page ids)
  produces path-qualified error findings, never a crash — and the engine
  refuses to construct on such a tree;
* graph lint acceptance: an injected whole-tree dequant under
  ``backend="pallas"`` is a lint FAILURE (dequant-materialization /
  payload-convert), while the real engine lints clean on both wire
  formats;
* ``chunk_widths`` stays in lockstep with ``Scheduler._plan_chunks``,
  chunk-for-chunk, and the footprint census flags recompile blowups;
* sharding lint surfaces every ``fit_spec`` drop (satellite: the
  structured ShardingDropWarning) against deviceless meshes;
* decode-state donation is verified via ``Lowered.args_info`` and the
  ``missing-donation`` finding fires when donation is disabled;
* HLO-text helpers: ``input_output_aliases`` / ``shape_census``.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import (ShapeOnlyMesh, chunk_widths,
                            check_decode_donation, fallback_leaf_paths,
                            footprint_findings, generate_signatures,
                            lint_engine, lint_sharding, lint_traced_fn,
                            production_mesh_shape, serve_signatures,
                            validate_decode_state, validate_scheduler,
                            validate_serving_tree)
from repro.configs import REGISTRY
from repro.dist.hlo_analysis import input_output_aliases, shape_census
from repro.dist.sharding import (ShardingDropWarning, collect_spec_events,
                                 fit_spec)
from repro.kernels.kv_pages import make_pool
from repro.models import common as common_mod
from repro.models.api import build
from repro.models.common import (QuantConfig, make_weight, matmul_backend,
                                 qdense, qmatmul)
from repro.serve import Request, SamplingParams, ServeEngine
from repro.serve.deploy import (BitplaneServingWeight, ServingWeight,
                                to_serving_params)

QC = QuantConfig(mode="fake", n_bits=8, act_bits=8)
_DEPLOYED = (ServingWeight, BitplaneServingWeight)


@pytest.fixture(scope="module")
def phi3():
    cfg = REGISTRY["phi3-mini-3.8b"].tiny(dtype="float32").with_quant(QC)
    api = build(cfg)
    return api, api.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def packed_params(phi3):
    return to_serving_params(phi3[1], 8, layout="packed")


@pytest.fixture(scope="module")
def bitplane_params(phi3):
    return to_serving_params(phi3[1], 8, layout="bitplane")


def _mutate_one(params, leaf_type, fn):
    """Corrupt the first ``leaf_type`` leaf of the tree with ``fn``."""
    hit = []

    def conv(x):
        if isinstance(x, leaf_type) and not hit:
            hit.append(True)
            return fn(x)
        return x

    out = jax.tree_util.tree_map(
        conv, params, is_leaf=lambda x: isinstance(x, _DEPLOYED))
    assert hit, f"tree holds no {leaf_type.__name__} leaf"
    return out


def _errors(findings, rule=None):
    return [f for f in findings if f.severity == "error"
            and (rule is None or f.rule == rule)]


# ---------------------------------------------------------------------------
# contract validator: clean trees and negative paths
# ---------------------------------------------------------------------------

def test_deployed_trees_validate_clean(packed_params, bitplane_params):
    assert not _errors(validate_serving_tree(packed_params))
    assert not _errors(validate_serving_tree(bitplane_params))


def test_undeployed_tree_is_vacuous(phi3):
    findings = validate_serving_tree(phi3[1])
    assert not _errors(findings)
    assert any(f.rule == "SW0" and f.severity == "info" for f in findings)


BP_CORRUPTIONS = [
    # (name, rule, path suffix, mutation)
    ("trailing-stack-dims", "BP1", ".planes",
     lambda bp: dataclasses.replace(
         bp, planes=jnp.moveaxis(bp.planes, 0, -1))),
    ("wrong-mask-lut-shape", "BP2", ".mask",
     lambda bp: dataclasses.replace(bp, mask=bp.mask[..., :1])),
    ("non-binary-mask", "BP2", ".mask",
     lambda bp: dataclasses.replace(bp, mask=bp.mask * 2.0)),
    ("non-monotone-mask", "BP2", ".mask",
     lambda bp: dataclasses.replace(
         bp, mask=bp.mask.at[..., 0, :, :].set(0.0))),
    ("truncated-sign-plane", "BP1", ".sign",
     lambda bp: dataclasses.replace(bp, sign=bp.sign[..., :-1, :])),
]


@pytest.mark.parametrize("name,rule,suffix,mutate", BP_CORRUPTIONS,
                         ids=[c[0] for c in BP_CORRUPTIONS])
def test_bitplane_corruption_is_one_diagnostic(bitplane_params, name, rule,
                                               suffix, mutate):
    """Each corruption: path-qualified error finding(s), no crash."""
    bad = _mutate_one(bitplane_params, BitplaneServingWeight, mutate)
    findings = validate_serving_tree(bad)          # must not raise
    errs = _errors(findings)
    assert len(errs) == 1, [f.format() for f in errs]
    assert errs[0].rule == rule
    assert errs[0].path.endswith(suffix)


PACKED_CORRUPTIONS = [
    ("wrong-scale-lut-shape", "SW2", ".scale",
     lambda sw: dataclasses.replace(sw, scale=sw.scale[..., :1])),
    ("wrong-payload-dtype", "SW4", ".w_int",
     lambda sw: dataclasses.replace(
         sw, w_int=sw.w_int.astype(jnp.int32))),
    ("trailing-stack-dims", "SW4", ".w_int",
     lambda sw: dataclasses.replace(
         sw, w_int=jnp.moveaxis(sw.w_int, 0, -1))),
]


@pytest.mark.parametrize("name,rule,suffix,mutate", PACKED_CORRUPTIONS,
                         ids=[c[0] for c in PACKED_CORRUPTIONS])
def test_packed_corruption_is_diagnosed(packed_params, name, rule, suffix,
                                        mutate):
    bad = _mutate_one(packed_params, ServingWeight, mutate)
    findings = validate_serving_tree(bad)
    errs = _errors(findings, rule)
    assert errs, [f.format() for f in findings]
    assert all(f.path.endswith(suffix) for f in errs)


def test_uninterpretable_leaf_is_sw0_not_crash(packed_params):
    bad = _mutate_one(packed_params, ServingWeight,
                      lambda sw: dataclasses.replace(sw, shape=None))
    findings = validate_serving_tree(bad)          # must not raise
    assert _errors(findings)


def test_engine_refuses_corrupt_tree(phi3, bitplane_params):
    api, _ = phi3
    bad = _mutate_one(bitplane_params, BitplaneServingWeight,
                      lambda bp: dataclasses.replace(bp, mask=bp.mask * 2.0))
    with pytest.raises(ValueError, match="serving contract"):
        ServeEngine(api, bad, backend="bitplane")
    # validate=False restores the old construct-then-crash behavior
    eng = ServeEngine(api, bad, backend="bitplane", validate=False)
    assert eng.backend == "bitplane"


# ---------------------------------------------------------------------------
# paged decode-state validation
# ---------------------------------------------------------------------------

def _paged_state(table):
    pages = make_pool({"k": ((2, 3), np.float32), "v": ((2, 3), np.float32)},
                      stack=1, n_pages=8, page=4)
    return {"cache": {"layer0": {"table": table, "pages": pages}}}


def test_paged_state_clean():
    table = np.zeros((1, 2, 4), np.int32)
    assert not _errors(validate_decode_state(_paged_state(table), n_slots=2))


def test_orphaned_page_ids_are_pc2():
    table = np.zeros((1, 2, 4), np.int32)
    table[0, 1, 2] = 99                            # pool has 8 pages
    findings = validate_decode_state(_paged_state(table), n_slots=2)
    errs = _errors(findings, "PC2")
    assert len(errs) == 1
    assert "orphaned" in errs[0].message and "99" in errs[0].message
    assert errs[0].path.endswith("['table']")


def test_shared_page_is_pc2_warning():
    table = np.zeros((1, 2, 4), np.int32)
    table[0, 0, 0] = table[0, 1, 0] = 3            # two slots own page 3
    findings = validate_decode_state(_paged_state(table), n_slots=2)
    assert not _errors(findings)
    assert any(f.severity == "warning" and f.rule == "PC2"
               for f in findings)


def test_quantized_pool_needs_scales():
    pages = make_pool({"k": ((2, 3), np.int8), "v": ((2, 3), np.int8)},
                      stack=1, n_pages=8, page=4)
    state = {"cache": {"l": {"table": np.zeros((1, 2, 4), np.int32),
                             "pages": pages}}}
    assert _errors(validate_decode_state(state, n_slots=2), "PC3")


@pytest.mark.parametrize("pool", ["plain-dict", "page-minor-shape",
                                  "wrong-stack"])
def test_pool_must_be_lane_dense_pc1(pool):
    """PC1 states the storage: a PagePool whose leaves are the lane-dense
    (stack, blocks, rows, lanes) pages of their per-token shape."""
    table = np.zeros((1, 2, 4), np.int32)
    state = _paged_state(table)
    good = state["cache"]["layer0"]["pages"]
    if pool == "plain-dict":          # the pre-lane-dense (…, page, KV, dh)
        bad = {n: np.zeros((1, 8, 4, 2, 3), np.float32) for n in good}
    elif pool == "page-minor-shape":
        bad = good.replace(k=np.zeros((1, 8, 4, 2, 3), np.float32))
    else:
        bad = good.replace(**{n: np.zeros((2,) + leaf.shape[1:], leaf.dtype)
                              for n, leaf in good.items()})
    state["cache"]["layer0"]["pages"] = bad
    assert _errors(validate_decode_state(state, n_slots=2), "PC1")


def test_wrong_slot_count_is_pc1():
    table = np.zeros((1, 3, 4), np.int32)
    assert _errors(validate_decode_state(_paged_state(table), n_slots=2),
                   "PC1")


def test_refcounted_shared_page_is_not_pc2():
    """Multi-slot ownership is deliberate when the prefix cache's
    refcount ledger books the page — PC2 stays silent."""
    table = np.zeros((1, 2, 4), np.int32)
    table[0, 0, 0] = table[0, 1, 0] = 3
    findings = validate_decode_state(_paged_state(table), n_slots=2,
                                     refcounts={3: 2})
    assert not [f for f in findings if f.rule == "PC2"]


# ---------------------------------------------------------------------------
# scheduler ledger validation (PX1-PX3)
# ---------------------------------------------------------------------------

def _ledger_sched():
    """Duck-typed scheduler fixture: slot 0 aliases one registered shared
    page and owns one private page, slot 1 is free — every ledger closes."""
    import types

    from repro.serve.scheduler import PageAllocator, PrefixCache, _Slot
    alloc = PageAllocator(16)
    shared, private = alloc.alloc(2)
    pc = PrefixCache()
    pc.register(b"h0", shared)
    slot = _Slot(req=None, index=6, last_tok=0, generated=[],
                 admitted_tick=0, pages=[private], shared_pages=[shared],
                 prefix_hashes=[b"h0"])
    tables = np.zeros((2, 4), np.int32)
    tables[0, :2] = [shared, private]
    return types.SimpleNamespace(paged=True, page_size=4, n_slots=2,
                                 tables=tables, slots=[slot, None],
                                 allocator=alloc, prefix_cache=pc)


def test_scheduler_ledger_clean():
    assert not validate_scheduler(_ledger_sched())


def test_refcount_mismatch_is_px1():
    sched = _ledger_sched()
    sched.prefix_cache.acquire(1)          # phantom reference, no aliaser
    errs = _errors(validate_scheduler(sched), "PX1")
    assert errs and "refcount" in errs[0].message


def test_unregistered_shared_page_is_px1():
    sched = _ledger_sched()
    pc = sched.prefix_cache
    page = pc._page_of.pop(b"h0")          # drop the registry entry only
    pc._hash_of.pop(page), pc._refs.pop(page)
    assert any("not registered" in f.message
               for f in _errors(validate_scheduler(sched), "PX1"))


def test_double_owned_page_is_px1():
    from repro.serve.scheduler import _Slot
    sched = _ledger_sched()
    thief = _Slot(req=None, index=4, last_tok=0, generated=[],
                  admitted_tick=1, pages=[sched.slots[0].pages[0]])
    sched.slots[1] = thief
    sched.tables[1, 0] = thief.pages[0]
    assert any("more than once" in f.message
               for f in _errors(validate_scheduler(sched), "PX1"))


def test_allocator_drift_is_px1():
    sched = _ledger_sched()
    sched.allocator.alloc(1)               # drawn but booked nowhere
    assert any("allocator" in f.message
               for f in _errors(validate_scheduler(sched), "PX1"))


def test_write_frontier_inside_shared_region_is_px2():
    sched = _ledger_sched()
    sched.slots[0].index = 3               # shared region is [0, 4)
    assert _errors(validate_scheduler(sched), "PX2")


def test_stale_parked_row_is_px3():
    sched = _ledger_sched()
    sched.tables[1, 0] = 5                 # free slot still references it
    assert _errors(validate_scheduler(sched), "PX3")


def test_table_ledger_mismatch_is_px3():
    sched = _ledger_sched()
    sched.tables[0, [0, 1]] = sched.tables[0, [1, 0]]   # swapped order
    assert _errors(validate_scheduler(sched), "PX3")


def test_nonpaged_scheduler_validates_trivially():
    import types
    sched = types.SimpleNamespace(paged=False, tables=None)
    assert not validate_scheduler(sched)


# ---------------------------------------------------------------------------
# graph lint: injected violations are lint FAILURES; real engine is clean
# ---------------------------------------------------------------------------

def test_injected_dequant_is_lint_failure(phi3, packed_params):
    """Acceptance: dense-compose under backend='pallas' must FAIL."""
    api, _ = phi3
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}

    def bad_prefill(p, b):
        dense = jax.tree_util.tree_map(
            lambda x: qdense(x, jnp.float32), p,
            is_leaf=lambda x: isinstance(x, _DEPLOYED))
        return api.prefill(dense, b, extra_slots=64)

    findings = lint_traced_fn(bad_prefill, (packed_params, batch),
                              fn_name="prefill", backend="pallas")
    assert _errors(findings, "dequant-materialization")
    assert _errors(findings, "payload-convert")


def test_same_dequant_is_sanctioned_under_dense(phi3, packed_params):
    api, _ = phi3
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}

    def bad_prefill(p, b):
        dense = jax.tree_util.tree_map(
            lambda x: qdense(x, jnp.float32), p,
            is_leaf=lambda x: isinstance(x, _DEPLOYED))
        return api.prefill(dense, b, extra_slots=64)

    findings = lint_traced_fn(bad_prefill, (packed_params, batch),
                              fn_name="prefill", backend="dense")
    assert not _errors(findings)
    assert any(f.rule == "sanctioned-dequant" for f in findings)


def test_lint_engine_clean_packed_pallas(phi3, packed_params):
    eng = ServeEngine(phi3[0], packed_params, backend="pallas")
    rep = lint_engine(eng, prompt_len=8, n_slots=2, max_new=8)
    assert rep.ok, rep.format()
    assert any(f.pass_name == "graph" and f.rule == "clean"
               for f in rep.findings)
    assert any(f.rule == "donation-ok" for f in rep.findings)
    assert rep.context["backend"] == "pallas"


def test_lint_engine_clean_bitplane(phi3, bitplane_params):
    eng = ServeEngine(phi3[0], bitplane_params, backend="bitplane")
    rep = lint_engine(eng, prompt_len=8, n_slots=2, max_new=8)
    assert rep.ok, rep.format()
    assert any(f.pass_name == "graph" and f.rule == "clean"
               for f in rep.findings)


def test_lint_engine_corrupt_mask_is_failure(phi3, bitplane_params):
    """Acceptance: a corrupted bitplane mask is a lint FAILURE."""
    bad = _mutate_one(bitplane_params, BitplaneServingWeight,
                      lambda bp: dataclasses.replace(bp, mask=bp.mask * 2.0))
    eng = ServeEngine(phi3[0], bad, backend="bitplane", validate=False)
    rep = lint_engine(eng, prompt_len=8, n_slots=2, max_new=8)
    assert not rep.ok
    assert _errors(rep.findings, "BP2")
    assert "FAIL" in rep.summary()


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "granite-moe-3b-a800m",
                                  "seamless-m4t-large-v2"])
@pytest.mark.parametrize("backend,layout", [("pallas", "packed"),
                                            ("bitplane", "bitplane")])
@pytest.mark.parametrize("bits", [8, 4])
def test_lint_matrix_clean(arch, backend, layout, bits):
    """Acceptance matrix: every family x kernel backend x precision lints
    clean (dense/ref are sanctioned by construction; the packed backends
    are where materialization would be a regression)."""
    cfg = REGISTRY[arch].tiny(dtype="float32").with_quant(QC)
    api = build(cfg)
    params = to_serving_params(api.init(jax.random.PRNGKey(0)), bits,
                               layout=layout)
    eng = ServeEngine(api, params, backend=backend)
    rep = lint_engine(eng, prompt_len=8, n_slots=2, max_new=8)
    assert rep.ok, rep.format()


# ---------------------------------------------------------------------------
# bitplane dense-fallback surfacing (satellite)
# ---------------------------------------------------------------------------

def test_fallback_leaf_paths(packed_params, bitplane_params):
    assert fallback_leaf_paths(packed_params, "bitplane")
    assert fallback_leaf_paths(packed_params, "pallas") == []
    assert fallback_leaf_paths(bitplane_params, "bitplane") == []


def test_engine_warns_on_packed_under_bitplane(phi3, packed_params):
    with pytest.warns(UserWarning, match="fall back"):
        ServeEngine(phi3[0], packed_params, backend="bitplane")


def test_qmatmul_warns_once_on_bitplane_fallback():
    sw = to_serving_params(
        {"w": make_weight(jax.random.PRNGKey(0), (32, 16), QC)}, 8)["w"]
    assert isinstance(sw, ServingWeight)
    x = jnp.ones((2, 32))
    common_mod._WARNED_FALLBACKS.clear()
    with pytest.warns(UserWarning, match="falls back"):
        with matmul_backend("bitplane"):
            y = qmatmul(x, sw)
    assert y.shape == (2, 16)
    with warnings.catch_warnings():                # second call is silent
        warnings.simplefilter("error")
        with matmul_backend("bitplane"):
            qmatmul(x, sw)


def test_fallback_lint_is_warning_not_error(phi3, packed_params):
    api, _ = phi3
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        findings = lint_traced_fn(
            lambda p, b: api.prefill(p, b, extra_slots=64),
            (packed_params, batch), fn_name="prefill", backend="bitplane")
    assert not _errors(findings)
    assert any(f.rule == "bitplane-dense-fallback"
               and f.severity == "warning" for f in findings)


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

def test_missing_donation_is_lint_failure(phi3, packed_params):
    api, _ = phi3
    eng = ServeEngine(api, packed_params, backend="pallas",
                      donate_state=False)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}
    state = jax.eval_shape(
        lambda p, b: api.init_decode_state(p, b, 2, 16), eng.params, batch)
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    index = jax.ShapeDtypeStruct((2,), jnp.int32)
    findings = check_decode_donation(eng, tokens, state, index)
    assert _errors(findings, "missing-donation")


# ---------------------------------------------------------------------------
# compile footprint
# ---------------------------------------------------------------------------

def test_chunk_widths_match_scheduler(phi3, packed_params):
    """chunk_widths must mirror Scheduler._plan_chunks chunk-for-chunk."""
    api, _ = phi3
    eng = ServeEngine(api, packed_params, backend="pallas", prefill_chunk=8)
    for p in (5, 8, 11, 16, 21):
        req = Request(uid=0,
                      inputs={"tokens": jnp.zeros((1, p), jnp.int32)},
                      sampling=SamplingParams(max_new_tokens=8))
        sched = eng.make_scheduler([req], n_slots=2)
        plan = sched._plan_chunks(req)
        got = [(b["tokens"].shape[1], start) for b, start, _col in plan]
        want = chunk_widths(p, sched.prefill_chunk, sched.total_len,
                            family=api.cfg.family)
        assert got == want, f"p={p}: {got} != {want}"


def test_footprint_census_and_blowup():
    # 12 distinct widths through the legacy monolithic path: 25 signatures
    widths = list(range(5, 17))
    sigs = serve_signatures(widths, max_new=16, n_slots=4)
    assert len(sigs) == 2 * len(widths) + 1
    findings = footprint_findings(sigs, budget=8)
    assert _errors(findings, "recompile-blowup")
    # the same workload chunked: prompts wider than the chunk all compile
    # to the (1, 8) chunk program -> {5,6,7,8}-wide chunks + decode
    sigs = serve_signatures(widths, max_new=16, n_slots=4, prefill_chunk=8)
    assert len(sigs) == 5
    assert not _errors(footprint_findings(sigs, budget=8))
    assert any(f.rule == "census" for f in findings)


def test_generate_signatures():
    sigs = generate_signatures(batch=4, prompt_width=16, max_new=10)
    assert [s.fn for s in sigs] == ["prefill", "decode"]
    assert sigs[0].static == (64,)                 # 64-rounded headroom
    assert sigs[1].shape == (4, 1)


def test_scheduler_compile_footprint(phi3, packed_params):
    api, _ = phi3
    eng = ServeEngine(api, packed_params, backend="pallas")
    req = Request(uid=0, inputs={"tokens": jnp.zeros((1, 7), jnp.int32)},
                  sampling=SamplingParams(max_new_tokens=8))
    sched = eng.make_scheduler([req], n_slots=2)
    sched.submit(req)
    sigs = sched.compile_footprint()
    assert any(s.fn == "decode" and s.shape == (2, 1) for s in sigs)
    assert any(s.shape[-1] == 7 for s in sigs if s.fn != "decode")


# ---------------------------------------------------------------------------
# sharding lint (satellite: structured fit_spec drops)
# ---------------------------------------------------------------------------

def test_fit_spec_records_and_warns_on_indivisible():
    # pad=False call sites (donated in-graph buffers) keep the drop path
    mesh = ShapeOnlyMesh({"data": 2, "model": 4})
    with collect_spec_events() as events:
        with pytest.warns(ShardingDropWarning, match="w7"):
            got = fit_spec(P("data", "model"), (7, 8), mesh, label="w7",
                           pad=False)
    assert got == P(None, "model")
    drops = [d for d in events if d.reason == "indivisible"]
    assert len(drops) == 1
    d = drops[0]
    assert (d.label, d.dim, d.axis) == ("w7", 0, "data")
    assert d.dim_size == 7 and d.axis_size == 2
    assert "w7" in d.message() and "data" in d.message()


def test_fit_spec_silent_drops_are_recorded_not_warned():
    mesh = ShapeOnlyMesh({"model": 4})
    with collect_spec_events() as events:
        with warnings.catch_warnings():
            warnings.simplefilter("error", ShardingDropWarning)
            got = fit_spec(P("data", "model"), (8, 8), mesh, label="w8")
    assert got == P(None, "model")
    assert any(d.reason == "absent" and d.axis == "data" for d in events)


def test_lint_sharding_production_mesh(phi3, packed_params):
    mesh = ShapeOnlyMesh(production_mesh_shape())
    assert mesh.shape == {"data": 16, "model": 16}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", ShardingDropWarning)
        findings = lint_sharding(packed_params, mesh)
    assert not _errors(findings)                   # pads degrade, not fail
    # the tiny config's dims are not 16-divisible: padded sharding keeps
    # them on the axis and surfaces each pad as an info finding
    assert any(f.rule == "axis-padded" for f in findings)
    assert not any(f.rule == "axis-indivisible" for f in findings)


def test_lint_sharding_clean_on_trivial_mesh(phi3, packed_params):
    findings = lint_sharding(packed_params,
                             ShapeOnlyMesh({"data": 1, "model": 1}))
    assert not _errors(findings)
    assert not any(f.rule == "mesh-axis-unused" for f in findings)


# ---------------------------------------------------------------------------
# HLO-text helpers
# ---------------------------------------------------------------------------

_HLO = """\
HloModule jit_decode, input_output_alias={ {0,1}: (2, {0}, may-alias) }

ENTRY main {
  %p0 = f32[4,8]{1,0} parameter(0)
  %c = s8[16,32]{1,0} convert(%p0)
  %d = f32[4,32]{1,0} dot(%p0, %c)
  ROOT %t = (f32[4,32]{1,0}) tuple(%d)
}
"""


def test_input_output_aliases_parse():
    aliases = input_output_aliases(_HLO)
    assert aliases == [((0, 1), 2, (0,))]
    assert input_output_aliases("HloModule nothing\n") == []


def test_shape_census():
    census = shape_census(_HLO)
    assert census["s8"] == 16 * 32
    assert census["f32"] == 4 * 8 * 4 + 4 * 32 * 4 * 2
    assert shape_census(_HLO, min_bytes=10 ** 6) == {}
