# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark driver: python -m benchmarks.run [--full]

Covers every paper table/figure (Table II, Figs 7-13) computed from
actually-trained quantization state, plus kernel layouts and the roofline
aggregation from the dry-run artifacts.  Kernel and step times come
from the chip benchmark (``bench/run.py``) only.
"""
from __future__ import annotations

import argparse
import sys
import time


def _emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="longer training runs for the paper tables")
    ap.add_argument("--skip-train", action="store_true",
                    help="only the fast benches (kernels, roofline)")
    args = ap.parse_args()
    quick = not args.full

    print("name,us_per_call,derived")

    from . import kernel_bench
    for row in kernel_bench.layout_bytes():
        _emit(f"layout/{row['layout']}", 0.0,
              f"bytes_per_weight={row['bytes_per_weight']}")

    if not args.skip_train:
        from . import paper_tables
        t0 = time.time()
        rows = paper_tables.table2_compression(quick)
        us = (time.time() - t0) * 1e6 / max(len(rows), 1)
        for r in rows:
            _emit(f"table2/{r['model']}/{r['scheme']}", us,
                  f"quality={r['quality']};comp={r['compression_x']}x;"
                  f"avg_bits={r['avg_bitwidth']}")

        for r in paper_tables.fig9_speedup_energy():
            _emit(f"fig9/{r['model']}/{r['accel']}", 0.0,
                  f"speedup={r['speedup_x']}x;energy={r['energy_saving_x']}x")

        br = paper_tables.fig10_breakdown()
        _emit("fig10/energy_saving", 0.0, f"saving={br['saving_x']:.2f}x")
        for comp, e in br["bwq"].items():
            _emit(f"fig10/bwq/{comp}", 0.0, f"energy_j={e:.3e}")

        for r in paper_tables.fig11_indexing():
            _emit(f"fig11/{r['model']}/{r['accel']}", 0.0,
                  f"index_KB={r['index_KB']}")

        for r in paper_tables.fig12_ablation(quick):
            _emit(f"fig12/a{r['alpha']}/i{r['requant_interval']}", 0.0,
                  f"quality={r['quality']};comp={r['compression_x']}x")

        for r in paper_tables.fig13_ou_size():
            _emit(f"fig13/ou{r['ou']}", 0.0,
                  f"avg_bits={r['avg_bits']};runtime_s={r['runtime_s']:.3e};"
                  f"energy_j={r['energy_j']:.3e}")

        for name, mean_bits in paper_tables.fig7_bitmaps().items():
            _emit(f"fig7/{name}", 0.0, f"mean_bits={mean_bits:.2f}")

    # roofline (requires dry-run artifacts; skip silently if absent)
    try:
        from . import roofline
        rows = roofline.roofline_rows()
        for r in rows:
            _emit(f"roofline/{r['arch']}/{r['cell']}", 0.0,
                  f"dominant={r['dominant']};useful_frac={r['useful_frac']};"
                  f"hbm_gib={r['peak_hbm_gib']}")
    except Exception as e:  # pragma: no cover
        print(f"# roofline skipped: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
