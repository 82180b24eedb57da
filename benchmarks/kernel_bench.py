"""Kernel-level checks off the chip: HBM weight bytes per layout
(computed) and one run of each kernel path at small shapes.  Times come
only from the chip benchmark (``bench/run.py``), never from here.

    PYTHONPATH=src python benchmarks/kernel_bench.py [--quick] [--out f.json]

emits one JSON object (``layout_bytes``; ``kernel_timings`` names each
kernel path that ran) — the CI smoke step runs ``--quick`` so a
kernel-backend regression fails fast.
"""
from __future__ import annotations

import argparse
import json
import math
from typing import Dict, List

import jax

from repro.core import BlockingSpec, adjust_precision, from_float, requantize
from repro.kernels import (bwq_dense_bitplane, bwq_dense_packed,
                           to_bitplane_layout, to_packed_layout)
from repro.serve.deploy import to_serving_params, weight_stream_bytes


def _mixed_qt(k: int, n: int, pruned_frac: float = 0.5, seed: int = 0):
    """A QuantizedTensor with a genuinely mixed precision assignment."""
    import dataclasses
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * 0.05
    qt = requantize(from_float(w, 8, BlockingSpec(8, 128)))
    cut = int(n * pruned_frac) // 128 * 128
    planes = qt.planes.at[4:, :, :cut].set(0.0)
    return requantize(adjust_precision(dataclasses.replace(qt,
                                                           planes=planes)))


def layout_bytes(k: int = 1024, n: int = 1024, pruned_frac: float = 0.5
                 ) -> List[Dict]:
    """Weight bytes streamed from HBM per matmul for each storage layout."""
    qt = _mixed_qt(k, n, pruned_frac)

    bl = to_bitplane_layout(qt)
    pk8 = to_packed_layout(qt, 8)
    pk4 = to_packed_layout(qt, 4)
    # serving wire formats (what ServeEngine actually streams per step)
    bp8 = to_serving_params({"w": qt}, 8, layout="bitplane")
    bp4 = to_serving_params({"w": qt}, 4, layout="bitplane")
    rows = [
        dict(layout="bf16 dense", bytes_per_weight=2.0),
        dict(layout="f32 dense", bytes_per_weight=4.0),
        dict(layout="bwq bitplane(packed)+sign",
             bytes_per_weight=round(
                 (bl.planes_packed.size + bl.sign_packed.size
                  + bl.mask.size * 4) / (k * n), 4)),
        dict(layout="bwq int8 + per-WB scale",
             bytes_per_weight=round(
                 (pk8.w_int.size + pk8.scale.size * 4) / (k * n), 4)),
        dict(layout="bwq int4 + per-WB scale",
             bytes_per_weight=round(
                 (pk4.w_int.size + pk4.scale.size * 4) / (k * n), 4)),
        # per-block plane occupancy: only live (bit, block) planes stream,
        # so bytes track the precision assignment (backend="bitplane")
        dict(layout="bwq bitplane serving int8 (plane occupancy)",
             bytes_per_weight=round(weight_stream_bytes(bp8) / (k * n), 4)),
        dict(layout="bwq bitplane serving int4 (plane occupancy)",
             bytes_per_weight=round(weight_stream_bytes(bp4) / (k * n), 4)),
    ]
    return rows


def _run(kernel: str, f) -> Dict:
    """Run ``f`` once to completion; the row names the kernel path."""
    jax.block_until_ready(f())
    return dict(kernel=kernel)


def kernel_timings(m: int = 64, k: int = 512, n: int = 512) -> List[Dict]:
    from repro.models.common import qmatmul
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n)) * 0.05
    qt = requantize(from_float(w, 8, BlockingSpec(8, 128)))
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    bl = to_bitplane_layout(qt)
    pk8 = to_packed_layout(qt, 8)
    pk4 = to_packed_layout(qt, 4)
    bp8 = to_serving_params({"w": _mixed_qt(k, n)}, 8,
                            layout="bitplane")["w"]

    return [
        _run("bitplane_matmul(interp)", lambda: bwq_dense_bitplane(x, bl)),
        _run("bitplane_serving_matmul(interp)",
             lambda: qmatmul(x, bp8, backend="bitplane")),
        _run("packed_matmul8(interp)", lambda: bwq_dense_packed(x, pk8)),
        _run("packed_matmul4(interp)", lambda: bwq_dense_packed(x, pk4)),
        _run("jnp_dense_ref", lambda: x @ w),
    ]


def paged_attention_timings(b: int = 4, kv: int = 4, g: int = 2,
                            dh: int = 64, page: int = 16,
                            nb: int = 8) -> List[Dict]:
    """Decode attention over an int8 page pool: the fused kernel
    (interpret), the gather composite it replaces and the jnp oracle."""
    import jax.numpy as jnp

    from repro.kernels import paged_attention
    from repro.kernels.kv_pages import PageFormat, read_pages, to_rows
    from repro.kernels.ref import paged_attention_ref
    from repro.models.attention import attention_core, dequantize_kv

    n_pages = 1 + b * nb
    t_len = nb * page
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
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
    table = jax.numpy.arange(1, 1 + b * nb,
                             dtype=jnp.int32).reshape(b, nb)
    kv_len = jnp.full((b,), t_len, jnp.int32)

    def gather(leaf, *tail):
        w = math.prod(tail)
        return read_pages(leaf, PageFormat(page, w), 0, table).reshape(
            b, t_len, *tail)

    @jax.jit
    def gather_composite():
        k = dequantize_kv(gather(kp, kv, dh), gather(ksc, kv), jnp.float32)
        v = dequantize_kv(gather(vp, kv, dh), gather(vsc, kv), jnp.float32)
        q_core = q.reshape(b, 1, kv * g, dh)
        q_pos = jnp.full((b, 1), t_len - 1, jnp.int32)
        kv_pos = jnp.broadcast_to(jnp.arange(t_len)[None, :], (b, t_len))
        return attention_core(q_core, k, v, q_pos, kv_pos,
                              kv_len=kv_len)

    return [
        _run("paged_attention_fused(interp)",
             lambda: paged_attention(q, kp, vp, ksc, vsc, table, kv_len,
                                     page=page)),
        _run("paged_attention_gather", gather_composite),
        _run("paged_attention_ref",
             lambda: paged_attention_ref(q, kp, vp, ksc, vsc, table,
                                         kv_len, page=page)),
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes (CI smoke)")
    ap.add_argument("--out", default=None, help="write JSON here")
    args = ap.parse_args()
    if args.quick:
        layouts = layout_bytes(k=256, n=256)
        timings = kernel_timings(m=16, k=256, n=256)
        timings += paged_attention_timings(b=2, kv=2, g=2, dh=32,
                                           page=8, nb=4)
    else:
        layouts = layout_bytes()
        timings = kernel_timings()
        timings += paged_attention_timings()
    result = {"layout_bytes": layouts, "kernel_timings": timings,
              "note": "kernel paths that ran off the chip; times come "
                      "from bench/run.py on the chip only"}
    print(json.dumps(result, indent=2), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)


if __name__ == "__main__":
    main()
