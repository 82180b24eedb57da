"""Sharding-rule unit tests (1-device mesh: axes exist, sizes are 1)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import REGISTRY
from repro.dist.sharding import (batch_pspecs, cache_pspecs, fit_spec,
                                 param_pspecs, use_mesh)
from repro.launch.mesh import make_mesh
from repro.models.api import build
from repro.models.common import QuantConfig


@pytest.fixture
def mesh1():
    return make_mesh((1, 1), ("data", "model"))


def _find(specs, params, needle):
    flat_s = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    return {jax.tree_util.keystr(p): s for p, s in flat_s
            if needle in jax.tree_util.keystr(p)}


def test_param_rules_dense(mesh1):
    cfg = REGISTRY["phi3-mini-3.8b"].tiny().with_quant(
        QuantConfig(mode="fake", n_bits=8, wb_rows=8, wb_cols=8))
    api = build(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    with use_mesh(mesh1):
        specs = param_pspecs(params)
    wq = _find(specs, params, "wq")
    assert any("model" in str(s) for s in wq.values())
    wo = list(_find(specs, params, "['attn']['wo'].w").values())[0]
    assert wo[-2] == "model"                      # row-parallel
    # quant metadata scale replicated
    sc = list(_find(specs, params, "wq.scale").values())
    assert all(s == P() for s in sc)


def test_fsdp_on_big_weights(mesh1):
    """Big weights get their free dim data-sharded (ZeRO-3)."""
    from repro.dist.sharding import _leaf_spec
    with use_mesh(mesh1):
        ps = _leaf_spec("['layers']['attn']['wo'].w",
                        jax.ShapeDtypeStruct((2048, 1024), jnp.float32))
        assert ps == P("model", "data")
        ps_small = _leaf_spec("['layers']['attn']['wo'].w",
                              jax.ShapeDtypeStruct((64, 64), jnp.float32))
        assert "data" not in str(ps_small)
        # router excluded from FSDP
        ps_r = _leaf_spec("['moe']['router_w']",
                          jax.ShapeDtypeStruct((4096, 512), jnp.float32))
        assert ps_r == P(None, None)


def test_expert_and_router_rules(mesh1):
    cfg = REGISTRY["granite-moe-3b-a800m"].tiny().with_quant(
        QuantConfig(mode="none"))
    api = build(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    with use_mesh(mesh1):
        specs = param_pspecs(params)
    gate = list(_find(specs, params, "expert_gate").values())[0]
    assert gate[-1] == "model"
    router = list(_find(specs, params, "router_w").values())[0]
    assert "model" not in str(router)             # router replicated


def test_fit_spec_divisibility():
    mesh = make_mesh((1, 1), ("data", "model"))
    ps = fit_spec(P("data", "model"), (7, 8), mesh)
    assert ps == P("data", "model")               # axis size 1 divides all


def test_batch_and_cache_pspecs(mesh1):
    with use_mesh(mesh1):
        b = batch_pspecs({"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)})
        assert b["tokens"][0] is not None
        cache = {"cache": {
            "k": jax.ShapeDtypeStruct((4, 8, 128, 2, 16), jnp.float32)}}
        cs = cache_pspecs(cache, batch_size=8)
        assert cs["cache"]["k"][3] == "model"     # kv heads on model


def test_no_mesh_is_noop():
    cfg = REGISTRY["phi3-mini-3.8b"].tiny()
    api = build(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    specs = param_pspecs(params)
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))


def test_multipod_fit_and_cache_pspecs():
    """3-axis (pod, data, model) mesh: batch spans (pod, data); non-dividing
    dims drop their mesh axes instead of failing."""
    from repro.dist.sharding import spec
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    with use_mesh(mesh):
        ps = spec("batch", None, "ff")
        assert ps[0] == ("pod", "data")
        assert ps[2] == "model"
        # 7 is not divisible by a >1 axis; with size-1 axes everything fits
        assert fit_spec(P(("pod", "data"), "model"), (7, 8), mesh) == \
            P(("pod", "data"), "model")
        cache = {"cache": {
            "k": jax.ShapeDtypeStruct((4, 16, 128, 2, 64), jnp.float32),
            "k_scale": jax.ShapeDtypeStruct((4, 16, 128, 2), jnp.float32)}}
        cs = cache_pspecs(cache, batch_size=16)
        assert cs["cache"]["k"][1] == ("pod", "data")
        assert cs["cache"]["k"][3] == "model"
        assert cs["cache"]["k_scale"][1] == ("pod", "data")
        # n_layers == batch_size: the leading stacked-layer dim must not
        # steal the batch sharding
        cs2 = cache_pspecs(
            {"k": jax.ShapeDtypeStruct((16, 16, 64, 2, 8), jnp.float32)},
            batch_size=16)
        assert cs2["k"][0] is None and cs2["k"][1] == ("pod", "data")
    # a >1 mesh axis that does NOT divide the dim: padded sharding (the
    # default) keeps it — the placement boundary zero-pads the dim — and
    # pad=False restores the legacy drop; fit_spec only reads mesh.shape,
    # so a stand-in covers >1 sizes on 1 device
    class _Mesh22:
        shape = {"data": 2, "model": 2}

    mesh2 = _Mesh22()
    assert fit_spec(P("data", "model"), (7, 8), mesh2) == P("data", "model")
    assert fit_spec(P("data", "model"), (7, 8), mesh2, pad=False) == \
        P(None, "model")
    assert fit_spec(P("data", "model"), (8, 7), mesh2, pad=False) == \
        P("data", None)
    # axes absent from the mesh are dropped regardless of padding
    assert fit_spec(P(("pod", "data"), None), (8, 8), mesh2) == \
        P("data", None)


def test_padded_fit_spec_and_helpers():
    """Ceil-division padded sharding: spec kept, SpecPad recorded, the
    pad/unpad boundary helpers round-trip exactly."""
    import numpy as np
    from repro.dist.sharding import (SpecPad, collect_spec_events, pad_leaf,
                                     padded_shape, unpad_leaf)

    class _Mesh22:
        shape = {"data": 2, "model": 2}

    mesh = _Mesh22()
    with collect_spec_events() as events:
        ps = fit_spec(P("data", "model"), (7, 8), mesh, label="x")
    assert ps == P("data", "model")
    pads = [e for e in events if isinstance(e, SpecPad)]
    assert len(pads) == 1 and pads[0].dim == 0 \
        and pads[0].padded_size == 8 and pads[0].group_size == 2
    assert padded_shape(ps, (7, 8), mesh) == (8, 8)
    x = np.arange(7 * 8, dtype=np.float32).reshape(7, 8)
    xp = pad_leaf(x, ps, mesh)
    assert xp.shape == (8, 8) and not xp[7].any()
    np.testing.assert_array_equal(unpad_leaf(xp, (7, 8)), x)
    # in-graph / donated call sites opt out and keep the legacy drop
    assert fit_spec(P("data", "model"), (7, 8), mesh,
                    pad=False) == P(None, "model")


def test_hlo_mixed_dtypes_and_no_collectives():
    from repro.dist.hlo_analysis import collective_stats
    txt = """
  %ar0 = f32[128,16]{1,0} all-reduce(%a), channel_id=1
  %ar1 = bf16[64]{0} all-reduce(%b), channel_id=2
  %rs = s8[256,4]{1,0} reduce-scatter(%c), dimensions={0}
  %ag-start = (f32[32], f32[256]) all-gather-start(%d), dimensions={0}
  %ag-done = f32[256]{0} all-gather-done(%ag-start)
  ROOT %r = f32[8]{0} add(%x, %y)
"""
    st = collective_stats(txt)
    assert st.counts == {"all-reduce": 2, "reduce-scatter": 1,
                         "all-gather": 1}
    assert st.bytes_by_op["all-reduce"] == 128 * 16 * 4 + 64 * 2
    assert st.bytes_by_op["reduce-scatter"] == 256 * 4 * 1
    assert st.bytes_by_op["all-gather"] == 256 * 4   # start skipped
    assert st.total_bytes == sum(st.bytes_by_op.values())
    # collective-free HLO (pure compute) -> empty stats
    empty = collective_stats("  ROOT %m = f32[64,64]{1,0} dot(%a, %b)")
    assert empty.counts == {} and empty.total_bytes == 0


def test_hlo_collective_parser():
    from repro.dist.hlo_analysis import collective_stats
    txt = """
  %all-reduce.1 = f32[256,512]{1,0} all-reduce(%dot), channel_id=1
  %ag = bf16[1024,64]{1,0} all-gather(%p0), dimensions={0}
  ROOT %x = f32[8]{0} add(%a, %b)
"""
    st = collective_stats(txt)
    assert st.counts == {"all-reduce": 1, "all-gather": 1}
    assert st.bytes_by_op["all-reduce"] == 256 * 512 * 4
    assert st.bytes_by_op["all-gather"] == 1024 * 64 * 2


@pytest.mark.parametrize("kv,dh,bits,split", [
    (32, 96, 8, True),       # phi3 int8: 8 heads x 96 = 768 lanes a chip
    (32, 96, 4, True),       # phi3 int4: 8 heads x 48 = 384 lanes a chip
    (2, 128, 4, False),      # 1 head x 64 lanes: not a whole lane tile
    (6, 128, 8, False),      # 6 heads do not split 4 ways
])
def test_paged_pool_lanes_split_by_heads(kv, dh, bits, split):
    """On a (data=1, model=4) mesh a page pool's K/V lanes shard on
    'model' where each chip's share is whole KV heads in whole lane
    tiles; the per-token scales stay whole on every chip."""
    from repro.kernels.kv_pages import make_pool

    class _Mesh14:
        shape = {"data": 1, "model": 4}
        axis_names = ("data", "model")

    dt, w = (jnp.int8, dh) if bits == 8 else (jnp.uint8, dh // 2)
    pool = jax.eval_shape(lambda: make_pool(
        {"k": ((kv, w), dt), "v": ((kv, w), dt),
         "k_scale": ((kv,), jnp.float32), "v_scale": ((kv,), jnp.float32)},
        2, 65, 16))
    state = {"cache": {"pages": pool,
                       "table": jax.ShapeDtypeStruct((2, 4, 16), jnp.int32)}}
    with use_mesh(_Mesh14()):
        cs = cache_pspecs(state, batch_size=4, pad=False)["cache"]
    lanes = "model" if split else None
    assert cs["pages"]["k"] == P(None, "data", None, lanes)
    assert cs["pages"]["v"] == P(None, "data", None, lanes)
    assert cs["pages"]["k_scale"] == P(None, "data", None, None)
    assert cs["table"] == P(None, None, None)
