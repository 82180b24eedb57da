#!/usr/bin/env python3
"""Reduce the small recorded trace in ``selfcheck_trace.json`` and check
each number against the answer worked out by hand.

    python3 bench/selfcheck.py

Busy time is the union of the device operations' intervals inside the
``bench.window`` span (an operation that runs past the window counts up
to its end; a scan's ``while``, which holds its body's ops, does not
count itself); idle share is one minus busy over the window; each idle
gap is charged to the innermost harness span open at its midpoint; a
kernel's time is the sum of the ops named after it (``packed_matmul.7``);
a program's time per call is the mean length of its XLA module events.
Prints each number and exits non-zero on the first that differs.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import tracereduce as tr  # noqa: E402
from layerctx import Ctx, load_module  # noqa: E402


def _close(a, b) -> bool:
    """Equal, numbers within a relative 1e-12."""
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(map(_close, a, b))
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(_close(a[k], b[k]) for k in a)
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        return abs(a - b) <= 1e-12 * max(abs(a), abs(b), 1e-300)
    return a == b


def main() -> int:
    raw = json.loads((HERE / "selfcheck_trace.json").read_text())
    want = raw["expect"]
    trace = tr.Trace.from_json(raw)
    win = tr.window(trace)
    ctx = Ctx(trace, win, [], {}, {}, {})
    busy = tr.busy_ns(trace, win)
    got = {
        "window_ns": win[1] - win[0],
        "busy_ns": busy,
        "idle_share": load_module("layer_metrics", "idle_share").read(ctx),
        "idle_gaps": [list(g) for g in tr.gaps_by_span(trace, win)],
        "kernel_ns": {n: ctx.kernel_ns(n) for n in want["kernel_ns"]},
        "module_ms": {f: ctx.module_ms(f) for f in want["module_ms"]},
        "top_op": list(tr.top_ops(trace, win, 1)[0]),
    }
    bad = 0
    for k, v in want.items():
        ok = _close(got[k], v)
        bad += not ok
        print(f"{'ok ' if ok else 'BAD'} {k}: got {got[k]}, want {v}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
