"""Serving throughput benchmark: prefill + decode tokens/sec across
batch sizes, KV-cache precisions and matmul execution backends, plus a
paged-vs-fixed-width cache-residency comparison, JSON output.

``--backend {dense,pallas,ref,bitplane}`` selects how deployed weights
execute (models.common.qmatmul); ``bitplane`` deploys the plane-sliced
layout, whose ``weight_bytes_per_step`` counts true per-block plane
occupancy — the only backend whose streamed bytes vary with the BWQ-A
precision assignment.  Every row reports the per-step HBM weight-bytes
the backend streams, so the roofline column stays comparable across
backends — on CPU the wall-clock of interpret-mode pallas is NOT TPU
time, the bytes column is the transferable quantity.

Also times the OLD engine's per-step whole-tree requantization (the
pre-redesign ``_maybe_quant_cache`` behavior, reproduced inline) against
the quantized-at-rest int8 cache at the same batch — the acceptance
criterion is that at-rest decode is no slower at batch >= 8, since it
replaces O(cache) requant work per token with a one-time write-side
rounding.

The ``paged_utilization`` row drives a mixed-length request workload
through the continuous-batching scheduler twice — paged pool vs
fixed-width slots — and reports resident cache bytes (peak pages in use x
per-page footprint vs the ``n_slots * max_len`` rows a fixed layout keeps
alive) plus a parity check that both produced identical tokens.

The ``prefix_sharing`` row serves a hot shared system prompt to 16
concurrent requests twice — refcounted content-addressed prefix caching
vs the plain per-slot paged pool — reporting the prefix-hit rate,
preemption count, and the peak-resident-bytes drop from holding the
shared prompt pages exactly once (tokens must match the baseline run).

    PYTHONPATH=src python benchmarks/serve_bench.py [--quick] [--out f.json]
        [--backend pallas] [--deploy-bits 8] [--page-size 8]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY
from repro.core.pact import quantize_signed
from repro.models.api import build
from repro.launch.serve import fake_quant
from repro.serve import Request, SamplingParams, ServeEngine
from repro.serve.deploy import (default_deploy_bits, default_deploy_layout,
                                to_serving_params, weight_stream_bytes)


def _sync(tree):
    jax.block_until_ready(tree)


def _bench(fn, iters: int):
    fn()                                        # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    _sync(out)
    return (time.perf_counter() - t0) / iters


def bench_point(api, params, batch_size: int, kv_bits: int,
                prompt_len: int = 32, decode_steps: int = 8,
                iters: int = 3, backend: str = "dense") -> dict:
    cfg = api.cfg
    eng = ServeEngine(api, params, kv_quant_bits=kv_bits, backend=backend)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, prompt_len), 0,
        cfg.vocab).astype(jnp.int32)}

    logits, state = eng.prefill(batch, extra_slots=64)
    _sync(state)
    t_prefill = _bench(lambda: eng.prefill(batch, extra_slots=64), iters)

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    idx = jnp.full((batch_size,), prompt_len, jnp.int32)

    def decode_once():
        # decode donates its state argument; rebind so the next iteration
        # hands the engine a live buffer, not the donated-away one
        nonlocal state
        lg, state = eng.decode(tok, state, idx)
        return lg
    t_decode = _bench(decode_once, iters * decode_steps)

    return {
        "batch": batch_size,
        "kv_bits": kv_bits,
        "backend": backend,
        "prompt_len": prompt_len,
        "prefill_tokens_per_s": batch_size * prompt_len / t_prefill,
        "decode_tokens_per_s": batch_size / t_decode,
        "prefill_ms": t_prefill * 1e3,
        "decode_step_ms": t_decode * 1e3,
        # every decode step streams the full weight state once; this is
        # the roofline-relevant column that stays comparable across
        # backends (interpret-mode wall-clock is not TPU time)
        "weight_bytes_per_step": weight_stream_bytes(params),
    }


def bench_legacy_requant(api, params, batch_size: int,
                         prompt_len: int = 32, decode_steps: int = 8,
                         iters: int = 3, backend: str = "dense") -> dict:
    """The pre-redesign path: float cache + whole-tree re-quantization of
    every >=4-dim leaf after each decode step.  Runs on the same matmul
    backend as the at-rest rows so the speedup summary compares cache
    strategies, not backends."""
    eng = ServeEngine(api, params, kv_quant_bits=32, backend=backend)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, prompt_len), 0,
        api.cfg.vocab).astype(jnp.int32)}
    logits, state = eng.prefill(batch, extra_slots=64)

    @jax.jit
    def requant(st):
        def q(x):
            if isinstance(x, jnp.ndarray) and x.ndim >= 4:
                return quantize_signed(x, 8)
            return x
        return jax.tree_util.tree_map(q, st)

    state = requant(state)
    _sync(state)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    idx = jnp.full((batch_size,), prompt_len, jnp.int32)

    def decode_once():
        nonlocal state
        lg, state = eng.decode(tok, state, idx)
        state = requant(state)
        return state
    t_decode = _bench(decode_once, iters * decode_steps)
    return {
        "batch": batch_size,
        "kv_bits": "legacy-requant-8",
        "backend": backend,
        "prompt_len": prompt_len,
        "decode_tokens_per_s": batch_size / t_decode,
        "decode_step_ms": t_decode * 1e3,
    }


def bench_paged_utilization(api, params, n_requests: int, kv_bits: int = 8,
                            page_size: int = 8,
                            backend: str = "dense") -> dict:
    """Mixed-length workload, paged vs fixed-width resident cache bytes."""
    cfg = api.cfg
    eng = ServeEngine(api, params, kv_quant_bits=kv_bits, backend=backend)
    p_lens = [4, 8, 16, 32]
    new_toks = [4, 16, 8, 4]
    reqs = []
    for i in range(n_requests):
        pl, mn = p_lens[i % 4], new_toks[i % 4]
        toks = jax.random.randint(jax.random.PRNGKey(100 + i), (1, pl), 0,
                                  cfg.vocab).astype(jnp.int32)
        reqs.append(Request(uid=i, inputs={"tokens": toks},
                            sampling=SamplingParams(max_new_tokens=mn),
                            arrival=i // 2))
    paged = eng.make_scheduler(reqs, n_slots=n_requests,
                               page_size=page_size)
    res_p = paged.run(list(reqs))
    rep_p = paged.cache_report()
    fixed = eng.make_scheduler(reqs, n_slots=n_requests, page_size=0)
    res_f = fixed.run(list(reqs))
    rep_f = fixed.cache_report()
    return {
        "benchmark": "paged_utilization",
        "batch": n_requests,
        "kv_bits": kv_bits,
        "page_size": page_size,
        "max_len": paged.max_len,
        "peak_pages_in_use": rep_p["peak_pages_in_use"],
        "pool_capacity_pages": rep_p["pool_capacity_pages"],
        "page_bytes": rep_p["page_bytes"],
        "paged_bytes_in_use_peak": rep_p["bytes_in_use_peak"],
        "fixed_resident_bytes": rep_f["resident_bytes"],
        "cache_utilization_vs_fixed": round(
            rep_p["bytes_in_use_peak"] / max(rep_f["resident_bytes"], 1), 4),
        "tokens_match_fixed": all(a.tokens == b.tokens
                                  for a, b in zip(res_p, res_f)),
    }


def bench_prefix_sharing(api, params, n_requests: int = 16,
                         page_size: int = 4, kv_bits: int = 8,
                         shared_tokens: int = 16, unique_tokens: int = 2,
                         max_new: int = 24, backend: str = "dense") -> dict:
    """A hot shared system prompt across ``n_requests`` concurrent
    requests: refcounted prefix caching vs the per-slot paged baseline.

    Every request carries the same ``shared_tokens``-token prefix plus a
    short unique tail; arrivals are staggered one tick apart so the first
    request's registered prompt pages are visible to every later
    admission.  With ``prefix_cache`` the pool holds the shared prefix
    pages exactly ONCE (refcounted); the baseline re-prefills and stores
    them per slot — the peak-resident-bytes gap is the headline, and both
    runs must emit identical tokens."""
    cfg = api.cfg
    shared = jax.random.randint(jax.random.PRNGKey(7), (1, shared_tokens),
                                0, cfg.vocab).astype(jnp.int32)

    def reqs():
        out = []
        for i in range(n_requests):
            tail = jax.random.randint(jax.random.PRNGKey(300 + i),
                                      (1, unique_tokens), 0,
                                      cfg.vocab).astype(jnp.int32)
            out.append(Request(
                uid=i, inputs={"tokens": jnp.concatenate([shared, tail], 1)},
                sampling=SamplingParams(max_new_tokens=max_new),
                arrival=i))
        return out

    eng = ServeEngine(api, params, kv_quant_bits=kv_bits, backend=backend)
    base = eng.make_scheduler(reqs(), n_slots=n_requests,
                              page_size=page_size, prefix_cache=False)
    res_b = base.run(reqs())
    rep_b = base.cache_report()
    cached = eng.make_scheduler(reqs(), n_slots=n_requests,
                                page_size=page_size,
                                n_pages=base.allocator.n_pages,
                                prefix_cache=True)
    res_c = cached.run(reqs())
    rep_c = cached.cache_report()
    prefix_blocks = shared_tokens // page_size
    return {
        "benchmark": "prefix_sharing",
        "batch": n_requests,
        "kv_bits": kv_bits,
        "page_size": page_size,
        "shared_prefix_tokens": shared_tokens,
        "prefix_hit_rate": round(
            rep_c["prefix_hits"] / max(rep_c["prefix_lookups"], 1), 4),
        "prefix_hits": rep_c["prefix_hits"],
        "prefix_pages_registered": rep_c["prefix_pages_registered"],
        "preemptions": rep_c["preemptions"],
        "page_bytes": rep_c["page_bytes"],
        "peak_pages_cached": rep_c["peak_pages_in_use"],
        "peak_pages_baseline": rep_b["peak_pages_in_use"],
        "cached_bytes_in_use_peak": rep_c["bytes_in_use_peak"],
        "baseline_bytes_in_use_peak": rep_b["bytes_in_use_peak"],
        "resident_bytes_vs_baseline": round(
            rep_c["bytes_in_use_peak"] / max(rep_b["bytes_in_use_peak"], 1),
            4),
        # the shared prefix is resident exactly once: the cached run's
        # peak drops by (n_requests - 1) aliased copies of its pages
        "prefix_pages_held_once": bool(
            rep_c["prefix_pages_registered"] == prefix_blocks
            and rep_b["peak_pages_in_use"] - rep_c["peak_pages_in_use"]
            >= (n_requests - 1) * prefix_blocks),
        "tokens_match_baseline": all(a.tokens == b.tokens
                                     for a, b in zip(res_c, res_b)),
    }


def bench_speculative(api, params, ks, gamma: int = 4, n_requests: int = 4,
                      max_new: int = 16, backend: str = "bitplane") -> list:
    """Self-speculative decoding: acceptance rate and drafted-vs-verified
    weight bytes per truncation depth ``k``.

    Each row drives the same greedy request workload through the
    continuous-batching scheduler with ``speculate_planes=k`` and checks
    the emitted tokens against the non-speculative engine (the greedy
    protocol is token-identical by construction, so a mismatch is a bug,
    not a quality tradeoff).  ``draft_bytes_per_step`` is what a draft
    decode step streams (top-k planes only); ``weight_bytes_per_token``
    amortizes ``drafted x draft + rounds x full`` over emitted tokens —
    below ``full_bytes_per_step`` means speculation saved weight traffic.
    """
    cfg = api.cfg

    def reqs():
        return [Request(uid=i,
                        inputs={"tokens": jax.random.randint(
                            jax.random.PRNGKey(200 + i), (1, 8 + 2 * i), 0,
                            cfg.vocab).astype(jnp.int32)},
                        sampling=SamplingParams(max_new_tokens=max_new,
                                                temperature=0.0),
                        arrival=i)
                for i in range(n_requests)]

    base = ServeEngine(api, params, backend=backend)
    sched = base.make_scheduler(reqs(), n_slots=n_requests)
    ref = {r.uid: r.tokens for r in sched.run(reqs())}
    full_bytes = weight_stream_bytes(params)

    rows = []
    for k in ks:
        eng = ServeEngine(api, params, backend=backend,
                          speculate_planes=k, draft_gamma=gamma)
        sched = eng.make_scheduler(reqs(), n_slots=n_requests)
        out = {r.uid: r.tokens for r in sched.run(reqs())}
        st = sched.spec_stats
        draft_bytes = weight_stream_bytes(eng.draft_params)
        streamed = st["drafted"] * draft_bytes + st["rounds"] * full_bytes
        rows.append({
            "benchmark": "speculative",
            "speculate_planes": k,
            "draft_gamma": gamma,
            "rounds": st["rounds"],
            "drafted": st["drafted"],
            "accepted_drafts": st["accepted_drafts"],
            "emitted": st["emitted"],
            "acceptance_rate": round(
                st["accepted_drafts"] / max(st["drafted"], 1), 4),
            "draft_bytes_per_step": draft_bytes,
            "full_bytes_per_step": full_bytes,
            "weight_bytes_per_token": round(streamed / max(st["emitted"], 1)),
            "tokens_match_baseline": out == ref,
        })
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--quick", action="store_true",
                    help="single small point (CI smoke)")
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--backend", default="dense",
                    choices=["dense", "pallas", "ref", "bitplane"],
                    help="matmul execution backend (non-dense implies "
                         "--deploy-bits 8 unless set; bitplane deploys "
                         "the plane-sliced layout)")
    ap.add_argument("--deploy-bits", type=int, default=0, choices=[0, 4, 8],
                    help="pack weights to int8/int4 serving form first "
                         "(0 = QAT weights)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="page size for the paged-utilization row "
                         "(0 skips it)")
    ap.add_argument("--speculate", action="store_true",
                    help="add self-speculative decoding rows (acceptance "
                         "rate + drafted-vs-verified weight bytes per "
                         "truncation depth k); bitplane backend only")
    ap.add_argument("--draft-gamma", type=int, default=4,
                    help="draft tokens per speculative round")
    args = ap.parse_args()

    cfg = REGISTRY[args.arch].tiny(dtype="float32")
    cfg = cfg.with_quant(fake_quant(cfg))
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    args.deploy_bits = default_deploy_bits(args.backend, args.deploy_bits)
    if args.deploy_bits:
        params = to_serving_params(params, args.deploy_bits,
                                   layout=default_deploy_layout(args.backend))

    # the requant-vs-at-rest comparison is only meaningful once the cache
    # dominates the step (batch >= 8), so quick mode benches there too
    batches = [8] if args.quick else [2, 8, 16]
    kv_bits = [32, 8] if args.quick else [32, 8, 4]
    rows = []
    for b in batches:
        for bits in kv_bits:
            rows.append(bench_point(api, params, b, bits,
                                    backend=args.backend))
            print(json.dumps(rows[-1]), flush=True)
    # legacy comparison at the largest batch (same backend: the summary
    # isolates the cache strategy, not the matmul execution path)
    b_cmp = batches[-1]
    legacy = bench_legacy_requant(api, params, b_cmp, backend=args.backend)
    rows.append(legacy)
    print(json.dumps(legacy), flush=True)
    at_rest = next(r for r in rows
                   if r["batch"] == b_cmp and r["kv_bits"] == 8)
    speedup = legacy["decode_step_ms"] / at_rest["decode_step_ms"]
    summary = {"legacy_vs_at_rest_decode_speedup": round(speedup, 3),
               "at_rest_no_slower": bool(speedup >= 1.0),
               "compare_batch": b_cmp}
    if args.page_size:
        # residency comparison at batch 16 (8 in quick mode): the paged
        # pool only keeps pages that hold live tokens resident
        util = bench_paged_utilization(api, params,
                                       n_requests=8 if args.quick else 16,
                                       page_size=args.page_size,
                                       backend=args.backend)
        rows.append(util)
        print(json.dumps(util), flush=True)
        summary["paged_cache_utilization"] = \
            util["cache_utilization_vs_fixed"]
        summary["paged_tokens_match_fixed"] = util["tokens_match_fixed"]
        # hot shared system prompt at batch >= 16: refcounted prefix pages
        # held once vs the per-slot paged baseline
        share = bench_prefix_sharing(api, params, n_requests=16,
                                     page_size=min(args.page_size, 4),
                                     max_new=20 if args.quick else 24,
                                     backend=args.backend)
        rows.append(share)
        print(json.dumps(share), flush=True)
        summary["prefix_hit_rate"] = share["prefix_hit_rate"]
        summary["prefix_resident_bytes_vs_baseline"] = \
            share["resident_bytes_vs_baseline"]
        summary["prefix_pages_held_once"] = share["prefix_pages_held_once"]
        summary["prefix_tokens_match"] = share["tokens_match_baseline"]
    if args.speculate:
        if args.backend != "bitplane":
            raise SystemExit("--speculate requires --backend bitplane")
        bits = args.deploy_bits or 8
        ks = [bits - 2] if args.quick else [2, bits - 2, bits - 1]
        spec_rows = bench_speculative(api, params, [k for k in ks if k >= 1],
                                      gamma=args.draft_gamma,
                                      n_requests=4 if args.quick else 8,
                                      backend=args.backend)
        rows.extend(spec_rows)
        best = min(spec_rows, key=lambda r: r["weight_bytes_per_token"])
        summary["speculative_tokens_match"] = all(
            r["tokens_match_baseline"] for r in spec_rows)
        summary["speculative_best_k"] = best["speculate_planes"]
        summary["speculative_best_bytes_per_token"] = \
            best["weight_bytes_per_token"]
    result = {"rows": rows, "summary": summary,
              "note": "interpret-mode wall-clock is not TPU time; "
                      "weight_bytes_per_step is the roofline column"}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)


if __name__ == "__main__":
    main()
