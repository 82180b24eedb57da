"""Request-level serving tests.

* continuous-batching parity: staggered requests through the scheduler are
  token-identical to one-shot ``generate`` for decoder-only, VLM, and
  enc-dec families (incl. quantized-at-rest caches and slot reuse);
* paged-cache parity: the block-table page pool (with slot reuse, chunked
  prefill, int8/int4 at-rest storage) reproduces the same tokens and
  drains without leaking pages, at lower resident bytes than fixed-width
  slots (randomized workloads: tests/test_serving_stress.py);
* KV bit-stability: a written slot's stored K/V never changes on later
  decode steps (the old engine re-quantized the whole cache every step);
* per-slot index vectors match the legacy scalar-index decode path;
* int4 odd-K deployment packing round-trips through serving_compose;
* sharded decode on a 2-device mesh matches single-device (subprocess:
  the test session is pinned to one CPU device), contiguous and paged.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models.api import build
from repro.models.common import QuantConfig, make_weight
from repro.serve import Request, SamplingParams, ServeEngine
from repro.serve.deploy import (deploy_from_seed, serving_compose,
                                to_serving_params)

KEY = jax.random.PRNGKey(3)


def _setup(arch, kv_bits=32, quant_mode="fake"):
    cfg = REGISTRY[arch].tiny(dtype="float32").with_quant(
        QuantConfig(mode=quant_mode, n_bits=8, act_bits=8))
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, ServeEngine(api, params, kv_quant_bits=kv_bits)


def _batch(cfg, b=4, p=8):
    batch = {"tokens": jax.random.randint(
        KEY, (b, p), 0, cfg.vocab).astype(jnp.int32)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            jax.random.fold_in(KEY, 1),
            (b, cfg.vision_tokens, cfg.d_model)) * 0.1
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(KEY, 1), (b, p, cfg.d_model)) * 0.1
    return batch


# ---------------------------------------------------------------------------
# continuous batching == one-shot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,kv_bits", [
    ("phi3-mini-3.8b", 32), ("phi3-mini-3.8b", 8), ("phi3-mini-3.8b", 4),
    ("qwen2-vl-2b", 32), ("qwen2-vl-2b", 8),
    ("seamless-m4t-large-v2", 32), ("seamless-m4t-large-v2", 8),
    ("granite-moe-3b-a800m", 32),   # exact 'ragged' dispatch (default)
])
def test_staggered_requests_match_oneshot(arch, kv_bits):
    """Requests arriving mid-decode (with slot reuse: 3 slots, 4 requests)
    must reproduce the static-batch greedy tokens exactly."""
    cfg, eng = _setup(arch, kv_bits)
    b, max_new = 4, 6
    batch = _batch(cfg, b=b)
    oneshot = np.asarray(eng.generate(batch, max_new=max_new))
    reqs = [Request(uid=i,
                    inputs={k: v[i:i + 1] for k, v in batch.items()},
                    sampling=SamplingParams(max_new_tokens=max_new),
                    arrival=2 * i)
            for i in range(b)]
    results = eng.serve(reqs, n_slots=3)
    for i, r in enumerate(results):
        assert r.tokens == oneshot[i].tolist(), f"slot-parity broke @req {i}"
        assert r.finish_reason == "length"
        assert r.admitted_tick >= reqs[i].arrival


@pytest.mark.parametrize("arch,kv_bits,page,chunk", [
    ("phi3-mini-3.8b", 8, 4, 0),
    ("phi3-mini-3.8b", 4, 4, 3),
    ("seamless-m4t-large-v2", 8, 4, 3),
    ("seamless-m4t-large-v2", 4, 4, 0),
    ("granite-moe-3b-a800m", 8, 4, 0),
    ("granite-moe-3b-a800m", 4, 4, 3),
    ("qwen2-vl-2b", 8, 4, 3),
    ("zamba2-1.2b", 8, 4, 0),     # hybrid: paged attn + recurrent rows
])
def test_paged_staggered_requests_match_oneshot(arch, kv_bits, page, chunk):
    """The paged cache (block tables over a shared page pool, slot reuse,
    optional chunked prefill) must reproduce one-shot greedy tokens
    exactly, and drain with every page back on the free list."""
    cfg, eng = _setup(arch, kv_bits)
    b, max_new = 4, 6
    batch = _batch(cfg, b=b)
    oneshot = np.asarray(eng.generate(batch, max_new=max_new))
    reqs = [Request(uid=i,
                    inputs={k: v[i:i + 1] for k, v in batch.items()},
                    sampling=SamplingParams(max_new_tokens=max_new),
                    arrival=2 * i)
            for i in range(b)]
    sched = eng.make_scheduler(reqs, n_slots=3, page_size=page,
                               prefill_chunk=chunk)
    results = sched.run(reqs)
    for i, r in enumerate(results):
        assert r.tokens == oneshot[i].tolist(), f"paged parity @req {i}"
    report = sched.cache_report()
    assert report["pages_in_use"] == 0, f"leaked pages: {report}"
    assert report["peak_pages_in_use"] > 0
    assert (sched.tables == 0).all()


def test_paged_resident_bytes_below_fixed_width():
    """Mixed-length requests: the paged pool's peak resident bytes must
    undercut the fixed-width layout's always-resident rows."""
    cfg, eng = _setup("phi3-mini-3.8b", 8)
    reqs = []
    for i, (pl, mn) in enumerate([(2, 2), (8, 4), (16, 4), (4, 2)]):
        toks = jax.random.randint(jax.random.fold_in(KEY, 10 + i),
                                  (1, pl), 0, cfg.vocab).astype(jnp.int32)
        reqs.append(Request(uid=i, inputs={"tokens": toks},
                            sampling=SamplingParams(max_new_tokens=mn),
                            arrival=i))
    paged = eng.make_scheduler(reqs, n_slots=4, max_len=64, page_size=4)
    res_p = paged.run(list(reqs))
    fixed = eng.make_scheduler(reqs, n_slots=4, max_len=64, page_size=0)
    res_f = fixed.run(list(reqs))
    assert all(a.tokens == b.tokens for a, b in zip(res_p, res_f))
    rp, rf = paged.cache_report(), fixed.cache_report()
    assert rp["bytes_in_use_peak"] < rf["resident_bytes"], (rp, rf)


def test_eos_retirement_frees_slot():
    """A request retiring on EOS frees its slot for a waiting request."""
    cfg, eng = _setup("phi3-mini-3.8b")
    batch = _batch(cfg, b=3)
    oneshot = np.asarray(eng.generate(batch, max_new=8))
    eos = int(oneshot[0, 2])                    # force an early stop on req 0
    reqs = [Request(uid=i, inputs={"tokens": batch["tokens"][i:i + 1]},
                    sampling=SamplingParams(
                        max_new_tokens=8, eos_id=eos if i == 0 else None),
                    arrival=i)
            for i in range(3)]
    results = eng.serve(reqs, n_slots=1)        # single slot: strict reuse
    assert results[0].finish_reason == "stop"
    assert results[0].tokens == oneshot[0, :3].tolist()
    for i in (1, 2):
        assert results[i].tokens == oneshot[i].tolist()
        assert results[i].finish_reason == "length"


def test_sampling_reproducible_and_respects_top_k():
    cfg, eng = _setup("phi3-mini-3.8b")
    batch = _batch(cfg, b=2)
    sp = SamplingParams(max_new_tokens=6, temperature=0.7, top_k=5, seed=11)
    reqs = [Request(uid=i, inputs={"tokens": batch["tokens"][i:i + 1]},
                    sampling=sp) for i in range(2)]
    r1 = eng.serve(list(reqs), n_slots=2)
    r2 = eng.serve(list(reqs), n_slots=2)
    for a, b_ in zip(r1, r2):
        assert a.tokens == b_.tokens            # per-request seeded PRNG
    greedy = eng.serve(
        [Request(uid=0, inputs={"tokens": batch["tokens"][:1]},
                 sampling=SamplingParams(max_new_tokens=6))], n_slots=1)
    assert len(r1[0].tokens) == len(greedy[0].tokens) == 6


def test_top_k_keeps_exactly_k_on_tied_logits():
    """Regression: the old ``l < kth`` threshold mask kept EVERY logit
    tied with the k-th value, so a plateau of equal logits widened the
    filter past top_k.  The rank mask must keep exactly k candidates,
    breaking ties by token id."""
    from repro.serve.sampling import sample_token
    v = 12
    # logits [9, 9, 9, 9, 8, 8, 8, 0, ...]: with k=2 the old mask kept 4
    # (tiers one logit apart so every survivor is drawn with probability
    # >= ~8% — 400 seeds cover the full surviving set with margin)
    logits = jnp.asarray([9., 9., 9., 9., 8., 8., 8.] + [0.] * (v - 7))
    sp = SamplingParams(temperature=1.0, top_k=2)
    hits = set()
    for s in range(200):
        hits.add(int(sample_token(logits, sp, jax.random.PRNGKey(s))))
    assert hits == {0, 1}, f"tied logits leaked past top_k: {hits}"
    # plateau straddling the cut: k=5 must stop inside the 8s, by token id
    sp5 = SamplingParams(temperature=1.0, top_k=5)
    hits5 = set()
    for s in range(400):
        hits5.add(int(sample_token(logits, sp5, jax.random.PRNGKey(s))))
    assert hits5 == {0, 1, 2, 3, 4}, hits5
    # untied logits: unchanged behavior (the k best survive)
    distinct = jnp.asarray([float(i) for i in range(v)])
    hits_d = set()
    for s in range(400):
        hits_d.add(int(sample_token(distinct, sp5, jax.random.PRNGKey(s))))
    assert hits_d <= {v - 1, v - 2, v - 3, v - 4, v - 5}, hits_d


# ---------------------------------------------------------------------------
# quantized-at-rest cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [8, 4])
def test_kv_cache_slots_bit_stable_across_decode(kv_bits):
    """Regression for the old ``_maybe_quant_cache``: stored K/V (and
    scales) of already-written positions must be bit-identical after any
    number of subsequent decode steps — each slot is quantized once."""
    cfg, eng = _setup("phi3-mini-3.8b", kv_bits)
    p = 8
    batch = _batch(cfg, b=2, p=p)
    logits, state = eng.prefill(batch, extra_slots=8)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    def written(s, upto):
        c = s["cache"]
        return {k: np.asarray(c[k][:, :, :upto]).copy()
                for k in ("k", "v", "k_scale", "v_scale")}

    snap = written(state, p)
    assert state["cache"]["k"].dtype == (jnp.int8 if kv_bits == 8
                                         else jnp.uint8)
    for i in range(4):
        logits, state = eng.decode(tok, state,
                                   jnp.full((2,), p + i, jnp.int32))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        assert np.isfinite(np.asarray(logits)).all()
        after = written(state, p)
        for name, ref in snap.items():
            np.testing.assert_array_equal(
                after[name], ref,
                err_msg=f"{name} re-quantized at decode step {i}")


def test_int8_kv_close_to_float_greedy():
    cfg, eng32 = _setup("phi3-mini-3.8b", 32)
    _, eng8 = _setup("phi3-mini-3.8b", 8)
    batch = _batch(cfg, b=2)
    out32 = np.asarray(eng32.generate(batch, max_new=8))
    out8 = np.asarray(eng8.generate(batch, max_new=8))
    assert (out32 == out8).mean() > 0.7


# ---------------------------------------------------------------------------
# per-slot index vector vs legacy scalar index
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "qwen2-vl-2b",
                                  "seamless-m4t-large-v2", "zamba2-1.2b"])
def test_vector_index_matches_scalar_decode(arch):
    cfg, eng = _setup(arch)
    api = eng.api
    p, b = 8, 2
    batch = _batch(cfg, b=b, p=p)
    tv = cfg.vision_tokens if cfg.family == "vlm" else 0
    logits, st_s = api.prefill(eng.params, batch, extra_slots=8)
    st_v = jax.tree_util.tree_map(lambda x: x, st_s)
    tok_s = tok_v = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(3):
        lg_s, st_s = api.decode_step(eng.params, tok_s, st_s,
                                     jnp.asarray(p + tv + i, jnp.int32))
        lg_v, st_v = api.decode_step(eng.params, tok_v, st_v,
                                     jnp.full((b,), p + tv + i, jnp.int32))
        np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))
        tok_s = jnp.argmax(lg_s, -1)[:, None].astype(jnp.int32)
        tok_v = jnp.argmax(lg_v, -1)[:, None].astype(jnp.int32)


# ---------------------------------------------------------------------------
# deployment packing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["fake", "bitplane"])
@pytest.mark.parametrize("k", [9, 16])
def test_int4_pack_roundtrip_odd_and_even_k(mode, k):
    """Nibble packing must handle odd block-padded K (regression: the old
    interleave silently dropped the unpaired row) and round-trip through
    serving_compose to the int8 path's values within int4 rescale error."""
    qc = QuantConfig(mode=mode, n_bits=8, wb_rows=3, wb_cols=8)
    w = make_weight(jax.random.PRNGKey(0), (k, 24), qc)
    sw8 = to_serving_params({"w": w}, bits=8)["w"]
    sw4 = to_serving_params({"w": w}, bits=4)["w"]
    kp = -(-k // 3) * 3                         # block-padded K (wb_rows=3)
    assert sw4.w_int.shape[-2] == (kp + 1) // 2
    w8 = np.asarray(serving_compose(sw8, jnp.float32))
    w4 = np.asarray(serving_compose(sw4, jnp.float32))
    assert w8.shape == w4.shape == (k, 24)
    scale = np.abs(w8).max() + 1e-9
    assert np.abs(w8 - w4).max() / scale < 0.25


# ---------------------------------------------------------------------------
# leaf-wise deployment from a seed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "granite-moe-3b-a800m"])
def test_deploy_from_seed_matches_whole_tree_layout(arch):
    """The leaf-wise deploy yields the tree the whole-tree path would:
    same structure, shapes and dtypes; plain leaves are the model's own
    init; each quantized layer slice is an N(0, 1/K) draw; same seed,
    same bytes."""
    api = build(REGISTRY[arch].tiny(dtype="float32"))
    key = jax.random.PRNGKey(0)
    got = deploy_from_seed(api, key, 8)
    want = to_serving_params(api.init(key), 8)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    init = jax.jit(api.init)(key)
    np.testing.assert_array_equal(np.asarray(got["embed"]),
                                  np.asarray(init["embed"]))
    w = np.asarray(serving_compose(got["layers"]["attn"]["wq"], jnp.float32))
    k = w.shape[-2]
    std = w.reshape(w.shape[0], -1).std(axis=1)
    np.testing.assert_allclose(std, 1 / np.sqrt(k), rtol=0.1)
    assert not np.allclose(w[0], w[1])        # layers drawn independently
    again = deploy_from_seed(api, key, 8)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# sharded serving (2 host devices, subprocess)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import REGISTRY
from repro.models.api import build
from repro.models.common import QuantConfig
from repro.dist.sharding import use_mesh
from repro.launch.mesh import make_mesh
from repro.serve import ServeEngine, Request, SamplingParams

assert jax.device_count() == 2, jax.device_count()
cfg = REGISTRY["phi3-mini-3.8b"].tiny(dtype="float32").with_quant(
    QuantConfig(mode="fake", n_bits=8, act_bits=8))
api = build(cfg)
params = api.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(
    jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab).astype(jnp.int32)}
ref = np.asarray(ServeEngine(api, params, kv_quant_bits=8)
                 .generate(batch, max_new=6))
for shape in [(2, 1), (1, 2)]:
    with use_mesh(make_mesh(shape, ("data", "model"))):
        eng = ServeEngine(api, params, kv_quant_bits=8)
        out = np.asarray(eng.generate(batch, max_new=6))
        res = eng.serve(
            [Request(uid=i, inputs={"tokens": batch["tokens"][i:i+1]},
                     sampling=SamplingParams(max_new_tokens=6), arrival=i)
             for i in range(4)], n_slots=4)
    assert (out == ref).all(), shape
    assert all(res[i].tokens == ref[i].tolist() for i in range(4)), shape
print("SHARDED_OK")

# paged cache placed via cache_pspecs (page pool on the data axes, block
# tables replicated; a stored token row holds every KV head) must decode
# token-identically to single-device, int8 and int4 at-rest
for kv_bits in (8, 4):
    ref_res = ServeEngine(api, params, kv_quant_bits=kv_bits).serve(
        [Request(uid=i, inputs={"tokens": batch["tokens"][i:i+1]},
                 sampling=SamplingParams(max_new_tokens=6), arrival=i)
         for i in range(4)], n_slots=3, page_size=4, prefill_chunk=4)
    for shape in [(2, 1), (1, 2)]:
        with use_mesh(make_mesh(shape, ("data", "model"))):
            eng = ServeEngine(api, params, kv_quant_bits=kv_bits)
            res = eng.serve(
                [Request(uid=i, inputs={"tokens": batch["tokens"][i:i+1]},
                         sampling=SamplingParams(max_new_tokens=6),
                         arrival=i)
                 for i in range(4)], n_slots=3, page_size=4,
                prefill_chunk=4)
        assert all(res[i].tokens == ref_res[i].tokens for i in range(4)), (
            kv_bits, shape)
print("SHARDED_PAGED_OK")
"""


def test_sharded_decode_matches_single_device():
    """Data- and model-sharded 2-device serving must emit the exact tokens
    of the single-device engine (generate + scheduler + paged paths)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")] +
                   sys.path))
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout
    assert "SHARDED_PAGED_OK" in out.stdout


_SHARDED_KERNELS_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import REGISTRY
from repro.models.api import build
from repro.dist.sharding import use_mesh
from repro.launch.mesh import make_mesh
from repro.serve import ServeEngine, Request, SamplingParams
from repro.serve.deploy import deploy_from_seed

assert jax.device_count() == 2, jax.device_count()
cfg = REGISTRY["phi3-mini-3.8b"].tiny(dtype="float32", d_model=256,
                                      d_ff=512, n_heads=2, n_kv_heads=2,
                                      d_head=128)
api = build(cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (3, 12), 0,
                          cfg.vocab).astype(jnp.int32)
for layout, kv_bits in (("packed", 8), ("bitplane", 4)):
    params = deploy_from_seed(api, jax.random.PRNGKey(0), 8, layout=layout)
    kw = dict(kv_quant_bits=kv_bits, attn_backend="fused", page_size=8,
              prefill_chunk=8,
              backend="bitplane" if layout == "bitplane" else "pallas")
    reqs = lambda: [Request(uid=i, inputs={"tokens": toks[i:i+1]},
                            sampling=SamplingParams(max_new_tokens=5),
                            arrival=i) for i in range(3)]
    ref = ServeEngine(api, params, **kw).serve(reqs(), n_slots=3)
    with use_mesh(make_mesh((1, 2), ("data", "model"))):
        eng = ServeEngine(api, params, **kw)
        sched = eng.make_scheduler(reqs(), n_slots=3)
        res = sched.run(reqs())
    # K/V lanes split on 'model' where each device's share is whole heads
    # in whole lane tiles (int8: 2 heads of 128 lanes, one a device);
    # int4's 2 x 64 lanes, and the scales, stay whole on every device
    pool = sched.state["cache"]["pages"]
    for name, leaf in pool.items():
        split = kv_bits == 8 and name in ("k", "v")
        lanes = leaf.shape[-1] // (2 if split else 1)
        assert all(s.data.shape == leaf.shape[:-1] + (lanes,)
                   for s in leaf.addressable_shards), (name, kv_bits)
    # and the decode program gathers no layer of the pool to one device
    with use_mesh(eng.mesh):
        hlo = eng.compiled_text("decode", jnp.zeros((3, 1), jnp.int32),
                                sched.state, jnp.zeros((3,), jnp.int32))
    layer_shapes = {",".join(map(str, leaf.shape[1:]))
                    for leaf in pool.values()}
    assert not [l for l in hlo.splitlines() if "all-gather(" in l
                and any(f"[{s}]" in l or f",{s}]" in l
                        for s in layer_shapes)], kv_bits
    lay = eng.params["layers"]
    splits = {n: lay[g][n].split for g in ("attn", "mlp") for n in lay[g]}
    assert splits == {"wq": "col", "wk": "col", "wv": "col", "wo": "row",
                      "w_gate": "col", "w_up": "col", "w_down": "row"}, splits
    assert all(res[i].tokens == ref[i].tokens for i in range(3)), layout
    # data=2: each replica's matmul kernels take its own rows; the fused
    # attention kernel cannot read a data-sharded page pool and refuses
    with use_mesh(make_mesh((2, 1), ("data", "model"))):
        res = ServeEngine(api, params, **dict(kw, attn_backend="gather")
                          ).serve(reqs(), n_slots=3)
        try:
            ServeEngine(api, params, **kw).serve(reqs(), n_slots=3)
            raise AssertionError("fused attention ran on data=2")
        except NotImplementedError as e:
            assert "data size 1" in str(e), e
    assert all(res[i].tokens == ref[i].tokens for i in range(3)), layout
print("SHARDED_KERNELS_OK")
"""


def test_sharded_kernels_match_single_device():
    """Under a (data=1, model=2) mesh the Pallas matmuls split by output
    columns or input rows and the paged-attention kernel by KV heads
    (``shard_map``, since Mosaic kernels cannot be auto-partitioned),
    each device holding its heads' lanes of the page pool where they
    are whole lane tiles and gathering no layer of it;
    under (data=2, model=1) the matmuls split their rows.  The served
    tokens equal the single-device engine's, and the fused attention
    kernel refuses a data-sharded mesh."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")] +
                   sys.path))
    out = subprocess.run([sys.executable, "-c", _SHARDED_KERNELS_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_KERNELS_OK" in out.stdout
