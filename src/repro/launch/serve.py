"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Loads (or initializes) a model, optionally converts it to packed integer
serving weights (BWQ deployment), and decodes either as one static batch
(default) or as staggered requests through the continuous-batching
scheduler (``--requests``).  ``--ckpt DIR`` cold-starts straight from a
sharded training checkpoint: each QAT leaf streams from its shard files
into the serving wire format one at a time, so the dense f32 tree is
never resident (the BWQ-H deployment unit is the packed artifact).  ``--kv-bits {4,8}`` selects the
quantized-at-rest KV cache; ``--temperature``/``--top-k`` enable sampling.

Scheduler production knobs (``--requests`` + ``--page-size`` mode):
``--priority`` assigns cycling per-request priority classes,
``--overcommit`` admits past pool capacity (preempting victims to host
memory when growth runs dry), ``--prefix-cache`` shares identical prompt
prefix pages by content hash, ``--shared-prefix N`` makes the first N
prompt tokens a common system prompt across the batch, and
``--stats-out`` dumps the scheduler's cache/preemption/prefix stats as
JSON for CI smoke assertions.
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from ..configs import REGISTRY
from ..models.api import build
from ..serve import Request, SamplingParams, ServeEngine
from ..serve.deploy import (default_deploy_bits, default_deploy_layout,
                            deploy_from_seed, to_serving_params)
from .cache import enable_compile_cache


def _prompts(cfg, args):
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab).astype(jnp.int32)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.vision_tokens, cfg.d_model)) * 0.1
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, args.prompt_len, cfg.d_model)) * 0.1
    return batch


def fake_quant(cfg):
    """The launchers' QAT setting: 8-bit fake quantization of weights and
    activations, on the config's own weight-block geometry."""
    return dataclasses.replace(cfg.quant, mode="fake", n_bits=8, act_bits=8)


def resolve_ckpt_dir(path: str, step: int = -1) -> str:
    """Resolve ``--ckpt`` to a concrete checkpoint directory: either the
    path itself (it holds a META) or a ``step_<N>`` child of a
    CheckpointManager directory (``step`` = -1 picks the latest)."""
    import os
    if os.path.exists(os.path.join(path, "META")):
        return path
    if step < 0:
        from ..ckpt.checkpoint import CheckpointManager
        latest = CheckpointManager(path).latest_step()
        if latest is None:
            raise SystemExit(f"--ckpt {path}: no step_N checkpoints found")
        step = latest
    out = os.path.join(path, f"step_{step}")
    if not os.path.exists(os.path.join(out, "META")):
        raise SystemExit(f"--ckpt: {out} is not a checkpoint directory")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(REGISTRY))
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--no-tiny", dest="tiny", action="store_false")
    ap.add_argument("--deploy-bits", type=int, default=0,
                    choices=[0, 4, 8], help="0 = QAT weights")
    ap.add_argument("--kv-bits", type=int, default=32, choices=[4, 8, 32],
                    help="quantized-at-rest KV cache precision")
    ap.add_argument("--backend", default="dense",
                    choices=["dense", "pallas", "ref", "bitplane"],
                    help="matmul execution backend for deployed weights "
                         "(non-dense implies --deploy-bits 8 unless set; "
                         "bitplane deploys the plane-sliced layout)")
    ap.add_argument("--attn-backend", default="gather",
                    choices=["gather", "fused", "ref"],
                    help="decode-attention read side: gather materializes "
                         "the contiguous KV view per step; fused runs the "
                         "Pallas paged-attention kernel over the stored "
                         "(quantized) cache; ref is its jnp oracle")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed (each request adds its uid)")
    ap.add_argument("--requests", action="store_true",
                    help="feed the batch as staggered requests through the "
                         "continuous-batching scheduler")
    ap.add_argument("--n-slots", type=int, default=0,
                    help="decode slots for --requests (0 = batch size)")
    ap.add_argument("--arrival-gap", type=int, default=2,
                    help="ticks between request arrivals in --requests mode")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: tokens per page (0 = contiguous "
                         "fixed-width slots); --requests mode only")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page-pool capacity (0 = worst case + trash page)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="insert prompts in chunks this wide, interleaved "
                         "with decode (0 = monolithic prefill)")
    ap.add_argument("--priority", default="",
                    help="comma-separated priority classes cycled over the "
                         "batch, e.g. '0,1' alternates low/high ('' = all "
                         "equal); higher admits first, parks last")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="admit worst-case page reservations up to this "
                         "multiple of pool capacity; > 1 preempts (parks "
                         "to host memory) lowest-priority victims when "
                         "decode growth exhausts the free list")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed prompt-prefix page sharing: "
                         "identical full prompt pages are held once, "
                         "refcounted, across concurrent requests")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="make the first N prompt tokens identical across "
                         "the batch (a shared system prompt) to exercise "
                         "--prefix-cache")
    ap.add_argument("--stats-out", default="",
                    help="write the scheduler stats JSON (cache report + "
                         "preemption / prefix-hit counters) to this file")
    ap.add_argument("--lint", action="store_true",
                    help="run the static serving-graph lint before serving "
                         "and abort if it reports errors")
    ap.add_argument("--autotune-budget-bytes", type=int, default=0,
                    help="search per-block bit-widths under this "
                         "weight-stream-bytes budget before serving "
                         "(bitplane layout only; 0 = off)")
    ap.add_argument("--speculate-planes", type=int, default=0,
                    help="self-speculative decoding: draft with only the "
                         "top-k live planes of each block (0 = off)")
    ap.add_argument("--draft-gamma", type=int, default=4,
                    help="draft tokens proposed per speculative round")
    ap.add_argument("--ckpt", default="",
                    help="cold-start from a checkpoint directory (a "
                         "CheckpointManager dir or a single step_N dir): "
                         "weights stream shard-by-shard straight into the "
                         "serving wire format, never materializing the "
                         "dense f32 tree")
    ap.add_argument("--ckpt-step", type=int, default=-1,
                    help="with --ckpt on a manager dir: the step to load "
                         "(-1 = latest)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = REGISTRY[args.arch]
    if args.tiny:
        cfg = cfg.tiny(dtype="float32")
    cfg = cfg.with_quant(fake_quant(cfg))
    api = build(cfg)
    args.deploy_bits = default_deploy_bits(args.backend, args.deploy_bits)
    if args.ckpt:
        path = resolve_ckpt_dir(args.ckpt, args.ckpt_step)
        layout = default_deploy_layout(args.backend)
        stats = {}
        params = to_serving_params(path, args.deploy_bits or 8,
                                   layout=layout,
                                   template=api.abstract_params(),
                                   stats=stats)
        print(f"cold-start: {path} -> {layout} "
              f"int{args.deploy_bits or 8} serving weights "
              f"(peak {stats['peak_host_bytes']} B host vs "
              f"{stats['dense_tree_bytes']} B dense tree)")
    elif args.deploy_bits:
        # leaf by leaf from the seed: the f32 master tree never exists
        layout = default_deploy_layout(args.backend)
        params = deploy_from_seed(api, jax.random.PRNGKey(0),
                                  args.deploy_bits, layout=layout)
        print(f"deployed: {layout} int{args.deploy_bits} serving weights")
    else:
        params = api.init(jax.random.PRNGKey(0))

    batch = _prompts(cfg, args)
    if args.shared_prefix:
        import numpy as np
        toks = np.array(batch["tokens"])
        toks[:, :args.shared_prefix] = toks[0, :args.shared_prefix]
        batch["tokens"] = jnp.asarray(toks)

    if args.autotune_budget_bytes:
        from ..serve.autotune import autotune_params
        from ..serve.deploy import weight_stream_bytes
        before = weight_stream_bytes(params)
        alloc = autotune_params(api, params, args.autotune_budget_bytes,
                                batch=batch)
        params = alloc.params
        print(f"autotuned: {before} -> {alloc.total_bytes} B/step under a "
              f"{alloc.budget_bytes} B budget "
              f"({alloc.steps_taken}/{alloc.steps_available} plane "
              f"increments kept); gate {alloc.gate}")

    eng = ServeEngine(api, params, kv_quant_bits=args.kv_bits,
                      backend=args.backend, attn_backend=args.attn_backend,
                      page_size=args.page_size,
                      n_pages=args.n_pages or None,
                      prefill_chunk=args.prefill_chunk,
                      overcommit=args.overcommit,
                      prefix_cache=args.prefix_cache,
                      speculate_planes=args.speculate_planes,
                      draft_gamma=args.draft_gamma)

    if args.lint:
        from ..analysis import lint_engine
        report = lint_engine(
            eng, prompt_len=args.prompt_len,
            n_slots=args.n_slots or args.batch, max_new=args.max_new,
            autotune_budget_bytes=args.autotune_budget_bytes or None)
        print(report.format(max_info=0))
        if not report.ok:
            raise SystemExit("serving-graph lint failed; aborting launch")

    if args.requests:
        prios = [int(p) for p in args.priority.split(",") if p != ""] or [0]
        reqs = [Request(uid=i,
                        inputs={k: v[i:i + 1] for k, v in batch.items()},
                        sampling=SamplingParams(
                            max_new_tokens=args.max_new,
                            temperature=args.temperature,
                            top_k=args.top_k, eos_id=args.eos_id,
                            seed=args.seed + i,
                            priority=prios[i % len(prios)]),
                        arrival=i * args.arrival_gap)
                for i in range(args.batch)]
        sched = eng.make_scheduler(reqs, n_slots=args.n_slots or args.batch)
        results = sched.run(reqs)
        for r in results:
            print(f"[{r.uid}] arrived@{reqs[r.uid].arrival} "
                  f"admitted@{r.admitted_tick} done@{r.finished_tick} "
                  f"({r.finish_reason}): {r.tokens}")
        if args.page_size or args.stats_out:
            import json
            stats = sched.cache_report()
            print(json.dumps(stats))
            if args.stats_out:
                findings = sched.validate()
                stats["contract_findings"] = [f.format() for f in findings]
                with open(args.stats_out, "w") as f:
                    json.dump(stats, f, indent=2)
        if args.speculate_planes:
            print(f"speculative: {sched.spec_stats}")
        return

    key = jax.random.PRNGKey(args.seed) if args.temperature > 0 else None
    out = eng.generate(batch, max_new=args.max_new,
                       greedy=args.temperature <= 0, key=key,
                       temperature=args.temperature, top_k=args.top_k)
    for i, row in enumerate(out.tolist()):
        print(f"[{i}] {row}")


if __name__ == "__main__":
    main()
