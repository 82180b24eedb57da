#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate the system
sustains, and the rate the cell offers, 0.8 x the knee.

    python3 bench/sweep.py --workload phi3-chat --rates 0.22,1.0 \\
        --seconds 120 --seed 5

One process on one chip: the cell's model is deployed once, then each
rate, in the order given, runs the cell's traffic for the mix's ramp and
a window of ``--seconds``, which should outlast a request's life.  A
rate is sustained when the requests in the system (in a slot or queued)
at the window's end exceed those at its start by at most
``max(2, 10% of the requests due in it)`` and the window served at least
90% of the output tokens its due requests asked for.  The sweep stops at
the first rate that is not sustained: its served tokens per second are
the system's capacity, and capacity over the mix's mean output length
is the knee.  One JSON line per rate, then the knee by rates run, the
knee by capacity and the cell's rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import loadgen  # noqa: E402
import run  # noqa: E402
from layerctx import percentile  # noqa: E402

POOL = 100000       # requests drawn for the mix's mean output length


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=120.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(1, str(run.ROOT / "src"))
    spec = run.load_spec(args.workload)
    run.enable_cache()
    run.find_chips(spec.chips)
    log = run.CompileLog()
    from adapter import Served
    served = Served(spec.conf, spec.cell, run.make_key(args.seed))
    mean_out = float(loadgen.pool_lengths(spec.mix, POOL)[:, 1].mean())
    knee = capacity = None
    rates = [float(r) for r in args.rates.split(",")]
    for i, rate in enumerate(rates):
        cell = dict(spec.cell, rate_per_s=rate)
        traffic = loadgen.Traffic(spec.mix, cell, served.cfg.vocab,
                                  args.seed, args.seconds)
        while served.busy():             # drain the last rate's requests
            served.step()
        run.warm(served, traffic, cell, args.seed)   # this rate's widths
        w = run.drive(served, traffic, spec.mix, args.seconds, log,
                      uid0=i * 10**6)
        length = w.end - w.start
        inside = lambda t: w.start < t <= w.end
        due = [(u, r) for u, r in w.reqs.items()
               if w.start <= r.due < w.end]
        s0, s1 = run.occupancy(w, w.start), run.occupancy(w, w.end)
        served_tok_s = sum(k for t, k in w.token_times if inside(t)) / length
        offered_tok_s = sum(served.max_new(u) for u, _ in due) / length
        ok = (sum(s1) - sum(s0) <= max(2, 0.1 * len(due))
              and served_tok_s >= 0.9 * offered_tok_s)
        lives = [r.finished - r.due for r in w.reqs.values()
                 if r.finished is not None and inside(r.finished)]
        row = {"rate_per_s": rate, "sustained": ok, "due": len(due),
               "in_slots_start": s0[0], "queued_start": s0[1],
               "in_slots_end": s1[0], "queued_end": s1[1],
               "served_tok_s": served_tok_s,
               "offered_tok_s": offered_tok_s,
               "life_s_mean": sum(lives) / len(lives) if lives else None,
               "life_s_max": max(lives, default=None),
               "itl_p95_ms": percentile([g * 1e3 for t, g in w.gaps
                                         if inside(t)], 95),
               "compiles_in_window": w.compiles}
        print(json.dumps(row), flush=True)
        if not ok:
            capacity = served_tok_s
            break                # higher rates only queue more
        knee = rate
    by_capacity = capacity and capacity / mean_out
    print(json.dumps({"knee_per_s": knee, "capacity_tok_s": capacity,
                      "mean_output_tokens": mean_out,
                      "knee_by_capacity_per_s": by_capacity,
                      "cell_rate_per_s": by_capacity and 0.8 * by_capacity}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
