"""DeepSeek-V2-Lite's mechanisms at a tiny same-family size on the CPU:
multi-head latent attention (MLA) over an int8 latent cache, yarn RoPE,
and an expert layer that holds a share of the routed experts.

The served program (int8 packed weights from the seed, the paged latent
cache, chunked prefill, ``ServeEngine`` + ``Scheduler``) is compared by
logits with the plain float32 reference the benchmark uses
(``bench/references/mla_moe.py``).  Compute here is float32, so the two
differ only where a float32 rounding flips a PACT level or a routing
choice; each tolerance below says what it admits.
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.kernels.kv_pages import PageFormat, make_pool, to_rows
from repro.kernels.latent_attention import latent_attention
from repro.kernels.ref import latent_attention_ref
from repro.models import mla
from repro.models.attention import dequantize_kv, quantize_kv
from repro.models.api import build
from repro.models.common import QuantConfig
from repro.models.moe import moe_forward
from repro.models.rope import (YarnScaling, yarn_bounds, yarn_inv_freq,
                               yarn_softmax_scale)
from repro.serve import Request, SamplingParams, ServeEngine
from repro.serve.deploy import deploy_from_seed

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from references.mla_moe import MlaMoe  # noqa: E402

KEY = jax.random.PRNGKey(2024)
# routed experts 2-3 of 8 held (a share with a non-zero offset), top-2
CFG = REGISTRY["deepseek-v2-lite"].tiny(dtype="float32", n_experts=8,
                                         top_k=2, n_held_experts=2,
                                         expert_offset=2)


def ref_conf(cfg, compute="float32"):
    """The benchmark's configuration file cut to ``cfg``'s widths."""
    conf = json.loads((BENCH / "configs" / "deepseek-v2-lite.json")
                      .read_text())
    w = conf["program"]["widths"]
    for k in w:
        w[k] = getattr(cfg, k)
    conf["numerics"]["compute"] = compute
    return conf


def test_yarn_constants():
    """DeepSeek-V2's yarn: factor 40 over 4096 positions, beta 32/1,
    mscale 0.707 on a 64-dim rope part of a 192-dim query."""
    y = REGISTRY["deepseek-v2-lite"].yarn
    assert yarn_bounds(y, 64, 1e4) == (10, 23)
    assert yarn_softmax_scale(y, 192) == pytest.approx(0.11472138679,
                                                       abs=1e-11)
    i = np.arange(32)
    extra = 1e4 ** (-2 * i / 64)
    m = 1 - np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * (1 - m) + extra * m
    np.testing.assert_allclose(yarn_inv_freq(y, 64, 1e4), want, rtol=1e-6)
    ref = MlaMoe(ref_conf(REGISTRY["deepseek-v2-lite"], "bfloat16"))
    inv, bounds = ref.inv_freq()
    assert bounds == (10, 23)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert ref.softmax_scale() == pytest.approx(0.11472138679, abs=1e-11)
    # no scaling: the plain rotary frequencies
    plain = YarnScaling(1.0, 4096, 32, 1, 1.0, 1.0)
    assert yarn_softmax_scale(plain, 192) == pytest.approx(192 ** -0.5)


def _layer(cfg, bits=8):
    cfg = dataclasses.replace(cfg, kv_cache_bits=bits, quant=QuantConfig())
    p = jax.tree_util.tree_map(
        lambda a: a[0], mla.init_mla(jax.random.PRNGKey(3), cfg, 1))
    cache = mla.make_latent_cache(cfg, 1, 2, 32, jnp.float32)  # 1 layer
    return cfg, p, cache


@pytest.mark.parametrize("backend", ["fused", "gather"])
def test_absorbed_decode_equals_expanded(backend):
    """A decode token in the absorbed form (``q_lat = q_nope W_UK^T``,
    scores and sum over the stored latents, ``W_UV`` after) equals the
    same token taken in the expanded form of a several-token step, both
    reading the same int8 cache.  Float32 throughout: 2e-5 of the
    output's scale is float32 summation order."""
    from repro.models.attention import paged_attn_backend
    cfg, p, cache = _layer(CFG)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(20)[None], (2, 20))
    with paged_attn_backend(backend):
        whole, _ = mla.mla_forward(p, x, pos, cfg, cache, 0)
        _, c19 = mla.mla_forward(p, x[:, :19], pos[:, :19], cfg, cache, 0)
        last, _ = mla.mla_forward(p, x[:, 19:], pos[:, 19:], cfg, c19, 19)
    scale = float(jnp.std(whole[:, -1]))
    np.testing.assert_allclose(last[:, 0], whole[:, -1],
                               atol=2e-5 * scale, rtol=0)


def _latent_pool(rng, n_pages, page, r, dr, stack=1, quant=True):
    """Random stacked latent pool in the lane-dense storage the kernel
    reads: int8 values with per-token scales, or float32 and None."""
    def leaf(width, lo, hi, dtype):
        x = (rng.integers(lo, hi, (stack, n_pages, page, width))
             if dtype == jnp.int8 else
             rng.uniform(lo, hi, (stack, n_pages, page, width)))
        return to_rows(jnp.asarray(x, dtype), PageFormat(page, width))
    if quant:
        return (leaf(r, -127, 128, jnp.int8),
                leaf(1, 0.001, 0.02, jnp.float32),
                leaf(dr, -127, 128, jnp.int8),
                leaf(1, 0.001, 0.02, jnp.float32))
    return (leaf(r, -1, 1, jnp.float32), None,
            leaf(dr, -1, 1, jnp.float32), None)


def test_latent_kernel_matches_oracle():
    """The Pallas kernel (interpret mode) against its gather oracle on a
    paged int8 pool: rows of 0 (parked), part of one page and several
    pages; a pool of 13 pages (not a multiple of the 8 pages whose
    scales share a stored row)."""
    rng = np.random.default_rng(0)
    h, r, dr, page, n_pages = 4, 64, 16, 8, 13
    ckv, cs, kpe, ps = _latent_pool(rng, n_pages, page, r, dr)
    table = jnp.asarray([[0, 0, 0, 0], [3, 0, 0, 0], [5, 12, 1, 7]],
                        jnp.int32)
    kv_len = jnp.asarray([31, 5, 27], jnp.int32)
    ql = jnp.asarray(rng.normal(size=(3, h, r)), jnp.float32)
    qp = jnp.asarray(rng.normal(size=(3, h, dr)), jnp.float32)
    args = (ql, qp, ckv, cs, kpe, ps, table, kv_len)
    got = latent_attention(*args, page=page, sm_scale=0.1)
    want = latent_attention_ref(*args, page=page, sm_scale=0.1)
    np.testing.assert_array_equal(got[0], 0.0)
    np.testing.assert_allclose(got[1:], want[1:], rtol=1e-5, atol=1e-6)


# DeepSeek-V2-Lite's pages of 16 at its widths (r 512: one token a row;
# dr 64: two a row; the scales: 8 pages a row) and the tiny config's
@pytest.mark.parametrize("quant", [True, False], ids=["int8", "f32"])
@pytest.mark.parametrize("h", [1, 4])
@pytest.mark.parametrize("r,dr", [(512, 64), (64, 16)])
def test_latent_kernel_reads_stacked_pool_at_layer(quant, h, r, dr):
    """Rows of 1, 16, 129 and 320 tokens and a parked row (an all-trash
    table) in a 3-layer stacked pool, read at layer 2."""
    rng = np.random.default_rng(h + r)
    page, nb, fills = 16, 20, (1, 16, 129, 320)
    n_live = [-(-f // page) for f in fills]
    n_pages = 1 + sum(n_live)
    pool = _latent_pool(rng, n_pages, page, r, dr, stack=3, quant=quant)
    ids = 1 + rng.permutation(n_pages - 1)
    table = np.zeros((len(fills) + 1, nb), np.int32)
    starts = np.cumsum([0] + n_live)
    for row, n in enumerate(n_live):
        table[row, :n] = ids[starts[row]:starts[row] + n]
    kv_len = jnp.asarray(list(fills) + [nb * page], jnp.int32)
    b = len(fills) + 1
    ql = jnp.asarray(rng.normal(size=(b, h, r)), jnp.float32)
    qp = jnp.asarray(rng.normal(size=(b, h, dr)), jnp.float32)
    args = (ql, qp, pool[0], pool[1], pool[2], pool[3],
            jnp.asarray(table), kv_len)
    kw = dict(page=page, sm_scale=0.1, layer=2)
    got = np.asarray(latent_attention(*args, **kw))
    want = np.asarray(latent_attention_ref(*args, **kw))
    np.testing.assert_array_equal(got[-1], 0.0)
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=1e-5, atol=1e-5)
    other = np.asarray(latent_attention(*args, **dict(kw, layer=0)))
    assert not np.allclose(other[:-1], got[:-1], atol=1e-3)


@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("page", [8, 16])
def test_latent_write_then_read_round_trip(bits, page):
    """``mla._write`` scatters a layer's new latents into the stacked
    lane-dense pool, ``mla._read`` returns them (int8: rounded once) at
    their positions, and the other layers stay untouched."""
    cfg = dataclasses.replace(CFG, kv_cache_bits=bits)
    stack, layer, b, nb, seq = 3, 2, 2, 3, 6
    contiguous = mla.make_latent_cache(cfg, stack, b, nb * page,
                                       jnp.float32)
    tails = {n: (l.shape[3:], l.dtype) for n, l in contiguous.items()}
    table = jnp.asarray([[4, 1, 6], [2, 5, 3]], jnp.int32)
    cache = {"pages": make_pool(tails, stack, 1 + b * nb, page),
             "table": jnp.broadcast_to(table, (stack, b, nb))}
    ks = jax.random.split(jax.random.PRNGKey(page), 2)
    c = jax.random.normal(ks[0], (b, seq, cfg.kv_lora_rank))
    pe = jax.random.normal(ks[1], (b, seq, cfg.qk_rope_dim))
    idx = jnp.asarray([1, page + 2])
    new = mla._write(cache, c, pe, idx, layer)
    got_c, got_pe = mla._read(new, jnp.float32, layer)
    want = [c, pe]
    if bits == 8:
        want = [dequantize_kv(*quantize_kv(x[:, :, None], 8),
                              jnp.float32)[:, :, 0] for x in want]
    for row, start in enumerate((1, page + 2)):
        for g, w in zip((got_c, got_pe), want):
            np.testing.assert_array_equal(
                np.asarray(g[row, start:start + seq]), np.asarray(w[row]))
    for other in (0, 1):
        for leaf in new["pages"].values():
            assert not np.asarray(leaf[other]).any()


def _plain_moe(p, x, top_k, n_routed):
    """The uncut MoE layer, written out: softmax over every expert, top-k
    gates not renormalized, every expert computed densely."""
    probs = jax.nn.softmax(x @ p["router_w"], -1)
    g, idx = jax.lax.top_k(probs, top_k)
    y = jnp.zeros_like(x)
    for e in range(n_routed):
        w = jnp.sum(jnp.where(idx == e, g, 0.0), -1)
        h = jax.nn.silu(x @ p["expert_gate"][e]) * (x @ p["expert_up"][e])
        y = y + w[..., None] * (h @ p["expert_down"][e])
    hs = jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])
    return y + hs @ p["shared_down"]


def test_shares_sum_to_the_uncut_layer():
    """Four shares of 2 of 8 routed experts (offsets 0, 2, 4, 6), each
    routing over all 8 and adding only its own experts' part, with the
    shared experts counted once, add up to the uncut layer.  Float32:
    1e-5 relative is summation order."""
    d, f, e = 32, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    p = {"router_w": jax.random.normal(ks[0], (d, e)),
         "expert_gate": jax.random.normal(ks[1], (e, d, f)) / d ** 0.5,
         "expert_up": jax.random.normal(ks[2], (e, d, f)) / d ** 0.5,
         "expert_down": jax.random.normal(ks[3], (e, f, d)) / f ** 0.5,
         "shared_gate": jax.random.normal(ks[4], (d, 2 * f)) / d ** 0.5,
         "shared_up": jax.random.normal(ks[5], (d, 2 * f)) / d ** 0.5,
         "shared_down": jax.random.normal(ks[6], (2 * f, d)) / f ** 0.5}
    x = jax.random.normal(ks[7], (2, 10, d))
    uncut = _plain_moe(p, x, 2, e)
    parts = []
    for off in (0, 2, 4, 6):
        share = {k: v[off:off + 2] if k.startswith("expert_") else v
                 for k, v in p.items() if not k.startswith("shared_")}
        parts.append(moe_forward(share, x, 2, offset=off, renorm=False)[0])
    shared_only = jax.nn.silu(x @ p["shared_gate"]) \
        * (x @ p["shared_up"]) @ p["shared_down"]
    total = sum(parts) + shared_only
    scale = float(jnp.std(uncut))
    np.testing.assert_allclose(total, uncut, atol=1e-5 * scale, rtol=0)
    # one share alone is not the layer
    assert float(jnp.max(jnp.abs(parts[0] + shared_only - uncut))) \
        > 100 * 1e-5 * scale


def test_share_never_reads_rows_past_the_groups(monkeypatch):
    """On the TPU ``ragged_dot`` leaves the rows past its last group
    unwritten.  With such rows filled with NaN here, a share of 2 of 8
    experts still equals the share computed with zero-filled rows."""
    from repro.models import moe
    d, f, e = 16, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    w = [jax.random.normal(k, shape) for k, shape in
         zip(ks[:3], ((2, d, f), (2, d, f), (2, f, d)))]
    xt = jax.random.normal(ks[3], (12, d))
    gates, idx, _ = moe.route(xt, jax.random.normal(ks[4], (d, e)), 2,
                              renorm=False)
    want = moe.expert_share(xt, gates, idx, *w, offset=4)
    ragged = jax.lax.ragged_dot

    def unwritten(x, w, group_sizes, **kw):
        y = ragged(x, w, group_sizes, **kw)
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(group_sizes), y, jnp.nan)
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = moe.expert_share(xt, gates, idx, *w, offset=4)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


PROMPTS = (12, 40)        # one chunk; three chunks (16-token chunks)
MAX_NEW = 5


def serve_logits(cfg, attn_backend, kv_bits=8):
    """Logits the served path produced at each position, per request:
    the prompt's chunks, then each decode step (greedy)."""
    api = build(cfg)
    params = deploy_from_seed(api, KEY, 8, "packed")
    eng = ServeEngine(api, params, backend="pallas",
                      attn_backend=attn_backend, kv_quant_bits=kv_bits,
                      page_size=8, prefill_chunk=16)
    sched = eng.make_scheduler([], n_slots=2, max_len=64, n_pages=17)
    rec = {}
    decode, chunk = eng.decode, eng.prefill_chunk_at

    def on_decode(tokens, state, index):
        logits, state = decode(tokens, state, index)
        idx = np.asarray(index)
        for i, s in enumerate(sched.slots):
            if s is not None and idx[i] != sched.total_len - 1:
                rec[s.req.uid][int(idx[i])] = np.asarray(logits[i])
        return logits, state

    def on_chunk(batch, state, slot, start):
        logits, state = chunk(batch, state, slot, start)
        uid = sched.slots[slot].req.uid
        n = sched.slots[slot].req.inputs["tokens"].shape[1]
        for j in range(batch["tokens"].shape[1]):
            if start + j < n:
                rec[uid][start + j] = np.asarray(logits[0, j])
        return logits, state
    eng.decode, eng.prefill_chunk_at = on_decode, on_chunk
    rng = np.random.default_rng(5)
    prompts = {}
    for uid, n in enumerate(PROMPTS):
        prompts[uid] = rng.integers(0, cfg.vocab, n).astype(np.int32)
        rec[uid] = {}
        sched.submit(Request(uid=uid, inputs={"tokens": prompts[uid][None]},
                             sampling=SamplingParams(max_new_tokens=MAX_NEW)))
    while len(sched.results) < len(PROMPTS):
        sched.step()
    return prompts, {u: list(sched.results[u].tokens) for u in prompts}, rec


def ref_logits(cfg, prompts, served, fp8=False):
    """The reference's logits at the positions the served path scored:
    each prompt followed by its served tokens (all but the last)."""
    ref = MlaMoe(ref_conf(cfg))
    width = max(len(prompts[u]) + MAX_NEW for u in prompts)
    toks = np.zeros((len(prompts), width), np.int32)
    for b, u in enumerate(sorted(prompts)):
        seq = np.concatenate([prompts[u], served[u][:-1]])
        toks[b, :len(seq)] = seq
    lg = ref.logits(KEY, toks, np.arange(toks.size), fp8=fp8)
    return lg.reshape(len(prompts), width, -1)


def rel_error(rec, want):
    """Largest |served - reference| logit over every scored position, in
    units of that position's reference logit std."""
    worst = 0.0
    for b, u in enumerate(sorted(rec)):
        for pos, got in rec[u].items():
            ref = want[b, pos]
            worst = max(worst, float(np.max(np.abs(got - ref)) / ref.std()))
    return worst


# Served against reference, largest logit error in logit-std units.  The
# sound program reads 2.2e-6 here (float32 throughout); the limit leaves
# room for a PACT level that a float32 rounding flips (~1e-3 std).  A
# float latent cache where the configuration says int8 reads 0.37 (the
# reference keeps int8's rounding of c_kv and k_pe), the fp8 control 1.5.
LIMIT = 3e-3


@pytest.fixture(scope="module")
def reference_run():
    prompts, served, rec = serve_logits(CFG, "fused")
    return prompts, served, rec, ref_logits(CFG, prompts, served)


def test_served_fused_matches_reference(reference_run):
    """Prefill (one chunk, and three chunks), then decode through the
    paged int8 latent cache and the fused latent kernel, against the
    reference's full forward over the same tokens."""
    prompts, served, rec, want = reference_run
    assert all(len(r) == len(prompts[u]) + MAX_NEW - 1
               for u, r in rec.items())
    assert rel_error(rec, want) < LIMIT


def test_served_gather_matches_reference():
    """The same through the gather backend: the latent pages gathered
    and attended in jnp."""
    prompts, served, rec = serve_logits(CFG, "gather")
    assert rel_error(rec, ref_logits(CFG, prompts, served)) < LIMIT


def test_float_latent_cache_fails_the_limit(reference_run):
    """Storing the latents unrounded (a float cache, where the
    configuration says int8) reads past the limit."""
    prompts = reference_run[0]
    _, served, rec = serve_logits(CFG, "fused", kv_bits=32)
    assert rel_error(rec, ref_logits(CFG, prompts, served)) > LIMIT


def test_fp8_control_fails_the_limit(reference_run):
    """The reference itself with every matmul in fp8 reads past the
    limit against the float32 reference."""
    prompts, served, _, want = reference_run
    lc = ref_logits(CFG, prompts, served, fp8=True)
    rec = {u: {p: lc[b, p] for p in range(len(prompts[u]) + MAX_NEW - 1)}
           for b, u in enumerate(sorted(prompts))}
    assert rel_error(rec, want) > LIMIT


def test_prefix_sharing_and_parking_keep_tokens():
    """The latent pages ride the same allocator as K/V pages: shared
    prompt pages (prefix cache; one arrival a tick, so later requests
    find the first's pages registered) and parked-then-restored slots
    (pool overcommit) serve the same greedy tokens as a roomy plain
    pool."""
    api = build(CFG)
    params = deploy_from_seed(api, KEY, 8, "packed")
    eng = ServeEngine(api, params, backend="pallas", attn_backend="fused",
                      kv_quant_bits=8, page_size=8, prefill_chunk=16)
    rng = np.random.default_rng(9)
    prefix = rng.integers(0, CFG.vocab, 24).astype(np.int32)

    def requests():
        return [Request(uid=u, inputs={"tokens": np.concatenate(
                    [prefix, rng_u.integers(0, CFG.vocab, 3 + u)
                     .astype(np.int32)])[None]},
                        sampling=SamplingParams(max_new_tokens=8),
                        arrival=u)
                for u, rng_u in ((u, np.random.default_rng(u))
                                 for u in range(4))]

    def serve(**kw):
        sched = eng.make_scheduler(requests(), n_slots=4, max_len=48, **kw)
        out = sched.run(requests())
        return [list(r.tokens) for r in out], sched.sched_stats

    plain, _ = serve(n_pages=33)
    shared, st = serve(n_pages=33, prefix_cache=True)
    assert shared == plain and st["prefix_hits"] > 0, st
    parked, st = serve(n_pages=13, overcommit=2.0)
    assert parked == plain and st["preemptions"] > 0, st
