"""§Perf hillclimb driver: lower named variants of the three chosen cells
and record hypothesis -> change -> before/after roofline terms.

Run:  PYTHONPATH=src python -m benchmarks.hillclimb --cell <name>
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json


from repro.configs import REGISTRY
from repro.configs.base import ShapeCell
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_production_mesh

OUT = "experiments/perf"

CELLS = {
    "phi3_decode": ("phi3-mini-3.8b", ShapeCell("decode_32k", 32768, 128,
                                                "decode")),
    "llama4_decode": ("llama4-scout-17b-a16e",
                      ShapeCell("decode_32k", 32768, 128, "decode")),
    "gemma2_train": ("gemma2-27b", ShapeCell("train_4k", 4096, 256,
                                             "train")),
}

# kernels/paged_attention.py tile candidates (KV heads per grid cell) —
# the genuine autotuning knob the fused decode kernel exposes.  On CPU
# the sweep times interpret mode (relative only); rerun on a real TPU
# to pick the deployed default.
PAGED_ATTN_TILES = [dict(block_kv=1), dict(block_kv=2), dict(block_kv=4)]


def run_paged_attn_variant(tag: str, block_kv: int, b: int = 8,
                           kv: int = 8, g: int = 4, dh: int = 128,
                           page: int = 64, nb: int = 8):
    """Time the fused decode kernel at one ``block_kv`` tile setting."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import paged_attention
    from repro.kernels.kv_pages import PageFormat, to_rows

    n_pages = 1 + b * nb
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q = jax.random.normal(ks[0], (b, kv, g, dh), jnp.float32)

    def stored(x):                       # one layer, kv_pages storage
        w = x[0, 0].size
        return to_rows(x.reshape(1, n_pages, page, w), PageFormat(page, w))
    kp = stored(jax.random.randint(ks[1], (n_pages, page, kv, dh),
                                   -127, 128).astype(jnp.int8))
    vp = stored(jax.random.randint(ks[2], (n_pages, page, kv, dh),
                                   -127, 128).astype(jnp.int8))
    ksc = stored(jax.random.uniform(ks[3], (n_pages, page, kv), jnp.float32,
                                    0.005, 0.02))
    vsc = stored(jax.random.uniform(ks[4], (n_pages, page, kv), jnp.float32,
                                    0.005, 0.02))
    table = jnp.arange(1, 1 + b * nb, dtype=jnp.int32).reshape(b, nb)
    kv_len = jnp.full((b,), nb * page, jnp.int32)

    def step():
        return paged_attention(q, kp, vp, ksc, vsc, table, kv_len,
                               page=page, block_kv=block_kv)

    jax.block_until_ready(step())            # compile
    t0 = time.perf_counter()
    for _ in range(3):
        r = step()
    jax.block_until_ready(r)
    us = (time.perf_counter() - t0) / 3 * 1e6
    rec = dict(variant=tag, block_kv=block_kv, us=round(us, 1),
               shape=dict(b=b, kv=kv, g=g, dh=dh, page=page, nb=nb),
               backend=jax.default_backend())
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"paged_attn__{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[paged_attn/{tag}] block_kv={block_kv} {us:.1f} us "
          f"({jax.default_backend()})", flush=True)
    return rec


def run_variant(cell_key: str, tag: str, cfg_over=None, fsdp=True, **kw):
    from repro.dist import sharding as sh
    arch, cell = CELLS[cell_key]
    cfg = REGISTRY[arch]
    if cfg_over:
        cfg = dataclasses.replace(cfg, **cfg_over)
    mesh = make_production_mesh()
    old = sh.FSDP["enabled"]
    sh.FSDP["enabled"] = fsdp
    try:
        rec = lower_cell(cfg, cell, mesh, **kw)
    finally:
        sh.FSDP["enabled"] = old
    rec["variant"] = tag
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{cell_key}__{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    t = rec["roofline"]
    print(f"[{cell_key}/{tag}] compute={t['compute_s']:.4g} "
          f"memory={t['memory_s']:.4g} coll={t['collective_s']:.4g} "
          f"dominant={rec['dominant']} hbm={rec['per_device']['peak_hbm_gib']}"
          f"GiB useful={rec['useful_flops_frac']:.3f}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True,
                    choices=list(CELLS) + ["paged_attn", "all"])
    ap.add_argument("--variant", default="all")
    args = ap.parse_args()

    if args.cell == "paged_attn":
        for cand in PAGED_ATTN_TILES:
            tag = f"block_kv{cand['block_kv']}"
            if args.variant not in ("all", tag):
                continue
            run_paged_attn_variant(tag, **cand)
        return

    plans = {
        # (tag, cfg overrides, fsdp, lower_cell kwargs)
        "phi3_decode": [
            ("baseline_f32", None, True, {}),
            ("int8_weights", None, True, dict(deploy_bits=8)),
            ("kv8_cache", dict(kv_cache_bits=8), True, {}),
            ("kv8_int8_resident", dict(kv_cache_bits=8), False,
             dict(deploy_bits=8)),
            ("kv8_int4_resident", dict(kv_cache_bits=8), False,
             dict(deploy_bits=4)),
        ],
        "llama4_decode": [
            ("baseline_f32", None, True, {}),
            ("int8_weights", None, True, dict(deploy_bits=8)),
            ("int8_resident", None, False, dict(deploy_bits=8)),
            ("int4_resident", None, False, dict(deploy_bits=4)),
            ("kv8_int4_resident", dict(kv_cache_bits=8), False,
             dict(deploy_bits=4)),
            ("kv8_int8_resident", dict(kv_cache_bits=8), False,
             dict(deploy_bits=8)),
        ],
        "gemma2_train": [
            ("baseline_mb16", None, True, {}),
            ("mb8", None, True, dict(microbatches=8)),
            ("mb4", None, True, dict(microbatches=4)),
            ("mb8_noremat", dict(remat=False), True, dict(microbatches=8)),
        ],
    }
    cells = list(CELLS) if args.cell == "all" else [args.cell]
    for c in cells:
        for tag, over, fsdp, kw in plans[c]:
            if args.variant not in ("all", tag):
                continue
            try:
                run_variant(c, tag, over, fsdp=fsdp, **kw)
            except Exception as e:
                print(f"[{c}/{tag}] FAIL {type(e).__name__}: {e}",
                      flush=True)


if __name__ == "__main__":
    main()
