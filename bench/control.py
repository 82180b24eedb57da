#!/usr/bin/env python3
"""The control's readings: the fp8 reference put in the served tokens'
place and judged by a run's own decision, on several seeds in one
process.

    python3 bench/control.py --workload phi3-chat --seeds 1,2,3 --seconds 30

Each seed is one run of the cell (``run.run`` with ``control``): its
weights are deployed, its traffic served at the cell's own load for the
mix's ramp and ``--seconds``, finished requests sampled, the program
freed and the reference run over the sample in float32 and in fp8.  The
tokens the fp8 reference ranks first at the served positions are judged
against the cell's limit in place of the served ones, so ``correct``
should come out false; the served tokens' own mean gap (a sound reading)
is printed beside it.  The benchmark's own runs never run the control.
One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path.insert(1, str(run.ROOT / "src"))
    spec = run.load_spec(args.workload)
    run.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = run.run(spec, seed, args.seconds, False, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "served_mean_gap": out["served_mean_gap"],
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
