#!/usr/bin/env python3
"""Serve phi3-mini-3.8b at its published widths on a TPU: the quickest
proof that the serving main path still runs on the chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only the sharded four-chip phase

One process, no child processes.  Without a TPU, or when any phase
fails, it exits non-zero and prints no result line.  Otherwise the last
line of stdout is ``{"ok": true, "device": {...}}``.

One chip (the default):

1. deploy: int8 packed weights with per-(8x128)-block scales, drawn
   from seed 0 one leaf at a time (``serve.deploy.deploy_from_seed``);
2. serve: 4 requests (prompts of 128, 256, 512 and 1024 random tokens,
   32 greedy new tokens each) through ``Scheduler`` with 4 slots, an
   int8 paged KV cache of 16-token pages and 128-token prefill chunks,
   arriving one tick apart so prompt chunks interleave with decode;
   matmuls on ``backend="pallas"``, decode attention on ``"fused"``;
3. check, on the same weights: a prefill chunk's last-position logits,
   ``pallas`` against ``dense``, and one decode step's logits,
   ``fused`` against ``gather``; that the compiled prefill and decode
   programs hold ``tpu_custom_call`` (the kernels ran compiled); and
   ``packed_matmul`` and ``paged_attention`` alone against their jnp
   oracles at full precision.

``--four-chips``: the same deploy and requests served by a ``ServeEngine``
built under a (data=1, model=4) mesh, its prefill logits compared with
a one-chip engine on device 0, and the stacked weights checked to be
split over all four chips.

Times printed here are a smoke test's, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "phi3-mini-3.8b"
PROMPT_LENS = (128, 256, 512, 1024)
MAX_NEW = 32
N_SLOTS = 4
PAGE = 16
CHUNK = 128
SEED = 0                 # weights, prompts and kernel-check inputs
# Tolerances on max |a - b| / max |b|, each between the largest reading
# of two correct paths and the smallest reading of a planted fault, both
# on a TPU v5e.  Logits: correct pairs read 0.033-0.060 whether or not a
# kernel is in the pair (dense vs an f32-exact reference reads 0.059);
# a weight tile read from the wrong K step reads 1.7 on prefill, a
# misrouted KV page or one page's negated K scales 0.38-0.43 on decode.
# Kernels against their jnp oracles at `highest`: correct 2.3e-3; a
# per-WB scale read from the wrong block 0.41-0.43, a negated page scale
# 0.011.  An int8 deploy's per-WB scales are equal within a layer, so no
# logit check can see a scale-routing fault: the kernel check does.
PREFILL_RTOL = 0.1       # pallas (f32 dequant in VMEM) vs dense (bf16 dots)
DECODE_RTOL = 0.1        # fused (f32 in-kernel) vs gather
SHARDED_RTOL = 0.1       # four-chip engine vs one chip
KERNEL_RTOL = 1e-2       # compiled kernel vs its oracle


class CompileLog:
    """Backend compiles seen by this process, from JAX's own events."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.programs += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def make_requests(cfg, prompt_lens=PROMPT_LENS, max_new=MAX_NEW):
    """Random-token prompts arriving one scheduler tick apart."""
    import jax
    import jax.numpy as jnp
    from repro.serve import Request, SamplingParams
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), len(prompt_lens))
    return [Request(uid=i,
                    inputs={"tokens": jax.random.randint(
                        k, (1, n), 0, cfg.vocab).astype(jnp.int32)},
                    sampling=SamplingParams(max_new_tokens=max_new),
                    arrival=i)
            for i, (k, n) in enumerate(zip(keys, prompt_lens))]


def make_engine(api, params, backend="pallas", attn="fused", **kw):
    from repro.serve import ServeEngine
    return ServeEngine(api, params, kv_quant_bits=8, backend=backend,
                       attn_backend=attn, page_size=PAGE,
                       prefill_chunk=CHUNK, **kw)


def serve(eng, reqs, n_slots=N_SLOTS):
    """Run ``reqs`` to completion; every logit the scheduler reads is
    checked finite on the device."""
    import jax
    import jax.numpy as jnp
    finite = []
    decode, chunk = eng.decode, eng.prefill_chunk_at

    def checked(step):
        def run(*args):
            logits, state = step(*args)
            finite.append(jnp.isfinite(logits).all())
            return logits, state
        return run

    eng.decode, eng.prefill_chunk_at = checked(decode), checked(chunk)
    try:
        sched = eng.make_scheduler(reqs, n_slots=n_slots)
        t0 = time.perf_counter()
        results = sched.run(reqs)
        jax.block_until_ready(sched.state)
        wall = time.perf_counter() - t0
    finally:
        del eng.decode, eng.prefill_chunk_at
    for r, req in zip(results, reqs):
        want = req.sampling.max_new_tokens
        if r.finish_reason != "length" or len(r.tokens) != want:
            raise AssertionError(f"request {r.uid}: {len(r.tokens)} tokens "
                                 f"({r.finish_reason}), want {want}")
    if not all(bool(f) for f in finite):
        raise AssertionError("non-finite logits while serving")
    return results, sched, wall


def rel_delta(got, want):
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise AssertionError("non-finite logits in a check")
    d = float(np.max(np.abs(got - want)))
    return d, d / float(np.max(np.abs(want)))


def worst(*deltas):
    return max(deltas, key=lambda d: d[1])


def fill_slots(sched, reqs):
    """Block tables giving each slot fresh pages, the served scheduler's
    state carrying them, and each request's first prompt chunk."""
    import numpy as np
    tables = np.zeros((sched.n_slots, sched.nb), np.int32)
    for i in range(len(reqs)):
        tables[i] = 1 + i * sched.nb + np.arange(sched.nb)
    state = sched.engine.set_tables(sched.state, tables)
    return tables, state, [{"tokens": r.inputs["tokens"][:, :CHUNK]}
                           for r in reqs]


def check_logits(api, params, eng, sched, reqs):
    """Kernel paths against the engine's in-graph paths, same weights.

    Prefill: each request's first chunk through ``pallas`` and ``dense``
    (last-position logits).  Decode: one step from those prefixes through
    ``fused`` and ``gather``.  Returns the largest deltas and the
    custom-call counts of the compiled prefill and decode programs."""
    import jax.numpy as jnp
    dense = make_engine(api, params, backend="dense", validate=False)
    gather = make_engine(api, params, attn="gather", validate=False,
                         donate_state=False)
    _, state, batches = fill_slots(sched, reqs)
    out = {"prefill": (0.0, 0.0)}
    first = []
    for i, batch in enumerate(batches):
        ld, _ = dense.prefill_chunk_at(batch, state, i, 0)
        lp, state = eng.prefill_chunk_at(batch, state, i, 0)
        out["prefill"] = worst(out["prefill"], rel_delta(lp[0, -1], ld[0, -1]))
        first.append(jnp.argmax(lp[0, -1]))
    texts = {"prefill_chunk": eng.compiled_text(
        "prefill_chunk", batches[0], state, jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32))}
    n = sched.n_slots
    tok = jnp.zeros((n, 1), jnp.int32).at[:len(first), 0].set(
        jnp.stack(first).astype(jnp.int32))
    idx = jnp.full((n,), sched.total_len - 1, jnp.int32).at[
        :len(first)].set(CHUNK)
    texts["decode"] = eng.compiled_text("decode", tok, state, idx)
    lg, _ = gather.decode(tok, state, idx)
    lf, _ = eng.decode(tok, state, idx)         # donates ``state``
    out["decode"] = rel_delta(lf[:len(first)], lg[:len(first)])
    out["custom_calls"] = {k: t.count("tpu_custom_call")
                           for k, t in texts.items()}
    return out


def check_kernels(cfg, params, nb):
    """The compiled kernels against their jnp oracles (``kernels.ref``)
    at full precision: layer 0's deployed ``w_gate`` payload through
    ``packed_matmul``, and a random int8 pool of ``nb`` pages per slot
    at the model's KV shape, pages out of order and slots filled to
    ragged levels, through ``paged_attention``.

    An int8 deploy puts every weight block at 8 bits, so its per-WB scale
    LUT holds one value per layer and a kernel reading another block's
    scale would go unseen; the matmul check scales each block by its
    own random factor instead."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import packed_matmul, paged_attention
    from repro.kernels.kv_pages import PageFormat, to_rows
    from repro.kernels.ref import packed_matmul_ref, paged_attention_ref
    from repro.models.attention import quantize_kv
    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 6)
    w = params["layers"]["mlp"]["w_gate"]
    geo = dict(bits=w.bits, wbr=w.spec.wb_rows, wbc=w.spec.wb_cols)
    scale = w.scale[0] * jax.random.uniform(ks[5], w.scale.shape[1:],
                                            minval=0.5, maxval=1.5)
    mm = (jax.random.normal(ks[0], (CHUNK, w.shape[-2])), w.w_int[0], scale)
    kv_len = jnp.asarray([1, PAGE + 3, nb * PAGE // 2, nb * PAGE], jnp.int32)
    b, kv, dh = len(kv_len), cfg.n_kv_heads, cfg.head_dim
    pool = (2, 1 + b * nb, PAGE, kv, dh)          # two layers, read layer 1

    def stored(x):                                # kernels.kv_pages storage
        w = x[0, 0, 0].size
        return to_rows(x.reshape(*pool[:3], w), PageFormat(PAGE, w))
    k, k_scale = map(stored, quantize_kv(jax.random.normal(ks[1], pool), 8))
    v, v_scale = map(stored, quantize_kv(jax.random.normal(ks[2], pool), 8))
    table = 1 + jax.random.permutation(ks[3], b * nb).reshape(b, nb)
    pa = (jax.random.normal(ks[4], (b, kv, cfg.n_heads // kv, dh)), k, v,
          k_scale, v_scale, table.astype(jnp.int32), kv_len)
    kw = dict(page=PAGE, layer=1)
    got = {"packed_matmul": jax.jit(lambda *a: packed_matmul(*a, **geo))(*mm),
           "paged_attention": jax.jit(
               lambda *a: paged_attention(*a, **kw))(*pa)}
    with jax.default_matmul_precision("highest"):
        want = {"packed_matmul": jax.jit(lambda *a: packed_matmul_ref(
                    *a, **geo))(*mm),
                "paged_attention": jax.jit(
                    lambda *a: paged_attention_ref(*a, **kw))(*pa)}
    return {name: rel_delta(got[name], want[name]) for name in got}


def deploy(cfg):
    import jax
    from repro.models.api import build
    from repro.serve.deploy import deploy_from_seed
    api = build(cfg)
    t0 = time.perf_counter()
    params = deploy_from_seed(api, jax.random.PRNGKey(SEED), 8)
    jax.block_until_ready(params)
    return api, params, time.perf_counter() - t0


def one_chip(cfg, prompt_lens=PROMPT_LENS, max_new=MAX_NEW):
    api, params, deploy_s = deploy(cfg)
    eng = make_engine(api, params)
    reqs = make_requests(cfg, prompt_lens, max_new)
    results, sched, wall = serve(eng, reqs)
    checks = check_logits(api, params, eng, sched, reqs)
    kernels = check_kernels(cfg, params, max(prompt_lens) // PAGE)
    return dict(deploy_s=deploy_s, serve_wall_s=wall, kernels=kernels,
                tokens=sum(len(r.tokens) for r in results), **checks)


def four_chips(cfg, prompt_lens=PROMPT_LENS, max_new=MAX_NEW):
    """The sharded engine serves the requests; its prefill logits are
    compared with a one-chip engine's, and a stacked weight must be
    split over every device of the mesh."""
    import jax
    import jax.numpy as jnp
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_mesh
    api, params, deploy_s = deploy(cfg)
    mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
    with use_mesh(mesh):
        sharded = make_engine(api, params)
    leaf = sharded.params["layers"]["mlp"]["w_gate"].w_int
    devices = {s.device for s in leaf.addressable_shards}
    shard_shapes = {s.data.shape for s in leaf.addressable_shards}
    if len(devices) != len(jax.devices()) or shard_shapes == {leaf.shape}:
        raise AssertionError(f"w_gate not split over the mesh: {devices}, "
                             f"shards {shard_shapes} of {leaf.shape}")
    reqs = make_requests(cfg, prompt_lens, max_new)
    results, sched, wall = serve(sharded, reqs)
    # the page pool's K/V lanes split over the mesh by KV heads
    pool_k = sched.state["cache"]["pages"]["k"]
    pool_shapes = {s.data.shape for s in pool_k.addressable_shards}
    if pool_shapes == {pool_k.shape}:
        raise AssertionError(f"page pool not split over the mesh: shards "
                             f"{pool_shapes} of {pool_k.shape}")
    single = make_engine(api, params, validate=False)
    state1 = single.init_decode_state(reqs[0].inputs, sched.n_slots,
                                      sched.max_len, page_size=PAGE)
    tables, state, batches = fill_slots(sched, reqs)
    state1 = single.set_tables(state1, tables)
    delta = (0.0, 0.0)
    for i, batch in enumerate(batches):
        ls, state = sharded.prefill_chunk_at(batch, state, i, 0)
        l1, state1 = single.prefill_chunk_at(batch, state1, i, 0)
        delta = worst(delta, rel_delta(ls[0, -1], l1[0, -1]))
    text = sharded.compiled_text(
        "prefill_chunk", batches[0], state, jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32))
    return dict(deploy_s=deploy_s, serve_wall_s=wall,
                tokens=sum(len(r.tokens) for r in results),
                sharded_prefill=delta, shard_shape=sorted(shard_shapes)[0],
                full_shape=tuple(leaf.shape),
                pool_shard_shape=sorted(pool_shapes)[0],
                custom_calls={"prefill_chunk": text.count("tpu_custom_call")})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded (data=1, model=4) phase")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} chips, found {len(jax.devices())}",
              file=sys.stderr)
        return 1
    from repro.configs import REGISTRY
    cfg = REGISTRY[ARCH]
    log = CompileLog()
    print(f"device_kind: {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {cache_dir}")
    if args.four_chips:
        r = four_chips(cfg)
    else:
        r = one_chip(cfg)
    print(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}x{cfg.head_dim} heads, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}; int8 packed weights, int8 paged KV")
    print(f"deploy: {r['deploy_s']!r} s (compiles included)")
    print(f"compile: {log.seconds!r} s over {log.programs} programs "
          f"({log.cache_hits} persistent-cache hits)")
    print(f"serve (smoke timing, not a benchmark): {r['tokens']} tokens "
          f"in {r['serve_wall_s']!r} s wall, compiles included")
    stats = [d.memory_stats() or {} for d in jax.devices()]
    print(f"peak_bytes_in_use: {[s.get('peak_bytes_in_use') for s in stats]} "
          f"(of {stats[0].get('bytes_limit')} per chip)")
    print(f"tpu_custom_call counts: {r['custom_calls']}")
    if args.four_chips:
        print(f"w_gate int8 payload {r['full_shape']} split as "
              f"{r['shard_shape']} per chip")
        checks = [("logit delta sharded prefill vs one chip",
                   r["sharded_prefill"], SHARDED_RTOL)]
    else:
        checks = [("logit delta prefill pallas vs dense", r["prefill"],
                   PREFILL_RTOL),
                  ("logit delta decode fused vs gather", r["decode"],
                   DECODE_RTOL)]
        checks += [(f"{name} vs oracle", delta, KERNEL_RTOL)
                   for name, delta in r["kernels"].items()]
    for name, (d, rel), tol in checks:
        print(f"{name}: max abs {d!r}, rel {rel!r} (tolerance {tol})")
        if not rel <= tol:
            raise AssertionError(f"{name}: rel {rel} > {tol}")
    if not all(r["custom_calls"].values()):
        raise AssertionError(f"a program ran without Pallas kernels: "
                             f"{r['custom_calls']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
