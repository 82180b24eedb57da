"""Decode checks off the chip: one decode step at each point of the
compile-shape grid (batch x context x page-size x kv-bits) for each
decode-attention backend, plus the fused-path footprint census.  Times
come only from the chip benchmark (``bench/run.py``), never from here.

    PYTHONPATH=src python benchmarks/decode_bench.py [--quick] [--out f.json]

The census is the structural claim: the fused decode jaxpr contains
neither a full-width KV gather nor an f32 KV materialization
(``graph_lint`` rules ``kv-full-width-gather`` /
``kv-dequant-materialization``).  The CI smoke step runs ``--quick`` and
asserts the census is clean and that the grid's steps ran, so a silent
fallback to the gather read side or a broken shape fails fast.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.graph_lint import lint_traced_fn
from repro.launch.lint import build_engine

ARCH = "phi3-mini-3.8b"
NOTE = ("decode steps that ran off the chip; times come from "
        "bench/run.py on the chip only")


def _state_for(eng, batch: int, context: int, page_size: int):
    """Zeroed decode state at fill level ``context`` (cache contents do
    not change the step's compile shape or FLOPs)."""
    example = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    max_len = context + 8
    state = eng.init_decode_state(example, batch, max_len,
                                  page_size=page_size)
    if page_size:
        nb = -(-max_len // page_size)
        tables = np.arange(1, 1 + batch * nb,
                           dtype=np.int32).reshape(batch, nb)
        state = eng.set_tables(state, tables)
    return state


def run_decode_step(eng, batch: int, context: int, page_size: int) -> None:
    """One decode step at this grid point, run to completion."""
    state = _state_for(eng, batch, context, page_size)
    tok = jnp.ones((batch, 1), jnp.int32)
    index = jnp.full((batch,), context, jnp.int32)
    logits, state = eng.decode(tok, state, index)
    jax.block_until_ready(logits)


def decode_sweep(batches, contexts, page_sizes, kv_bits_list,
                 backends) -> List[Dict]:
    rows: List[Dict] = []
    for kv_bits in kv_bits_list:
        for ab in backends:
            eng = build_engine(ARCH, "dense", kv_bits=kv_bits,
                               attn_backend=ab)
            for b in batches:
                for ctx in contexts:
                    for page in page_sizes:
                        run_decode_step(eng, b, ctx, page)
                        rows.append(dict(
                            batch=b, context=ctx, page_size=page,
                            kv_bits=kv_bits, attn_backend=ab))
                        print(f"  b={b} ctx={ctx} page={page} "
                              f"kv={kv_bits} {ab}: ran", flush=True)
    return rows


def fused_decode_census(kv_bits: int = 8, page_size: int = 16,
                        batch: int = 2, context: int = 32) -> Dict:
    """Deviceless proof that the fused decode program never materializes
    the contiguous KV view or the f32 KV tree (jaxpr taint census)."""
    eng = build_engine(ARCH, "dense", kv_bits=kv_bits,
                       attn_backend="fused")
    example = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}
    state = jax.eval_shape(
        lambda p, b: eng.api.init_decode_state(
            p, b, batch, context + 8, page_size=page_size),
        eng.params, example)
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    index = jax.ShapeDtypeStruct((batch,), jnp.int32)
    findings = lint_traced_fn(
        eng.api.decode_step, (eng.params, tokens, state, index),
        fn_name="decode", backend=eng.backend, attn_backend="fused")
    return {
        "kv_payload_rules": sorted({f.rule for f in findings
                                    if "kv" in f.rule}),
        "errors": [f.format() for f in findings if f.severity == "error"],
        "clean": all(f.severity != "error" for f in findings)
        and any(f.rule == "kv-clean" for f in findings),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny grid (CI smoke): one shape, fused+gather")
    ap.add_argument("--out", default=None, help="write JSON here")
    args = ap.parse_args()
    if args.quick:
        rows = decode_sweep(batches=(2,), contexts=(32,),
                            page_sizes=(0, 16), kv_bits_list=(8,),
                            backends=("fused", "gather"))
    else:
        rows = decode_sweep(batches=(2, 4), contexts=(32, 128),
                            page_sizes=(0, 16), kv_bits_list=(8, 4),
                            backends=("fused", "gather", "ref"))
    census = fused_decode_census()
    result = {"decode_steps": rows, "fused_decode_census": census,
              "note": NOTE}
    print(json.dumps(result, indent=2), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)


if __name__ == "__main__":
    main()
