#!/usr/bin/env python3
"""Reduce the small recorded trace in ``selfcheck_program_trace.json``,
which holds the program's own spans and op scopes, and check each number
against the answer worked out by hand.

    python3 bench/selfcheck_program.py

A tick's host time is the device's idle time inside its ``sched.tick``
span (wholly inside the window); ``tick_host_ms`` is the median over the
window's ticks.  The counters' note reads every span arg and compares
the decode fill levels with the harness's call log.
``decode_kv_move_ms`` is the device time per ``decode_step`` call of the
ops that call's program scopes ``kv_slice`` or ``kv_update`` (an op is
looked up in the scopes of the program it ran in, so ``copy.3`` of the
prefill program does not count).  With the
program's spans attached, each idle gap is charged to the innermost span
of either kind open at its midpoint.  Against a program with no spans
or scopes both readers give nothing and the gaps fall to the harness's
spans as before.  Prints each number and exits non-zero on the first
that differs.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import programtrace  # noqa: E402
import tracereduce as tr  # noqa: E402
from adapter import Call  # noqa: E402
from layerctx import Ctx, load_module  # noqa: E402
from selfcheck import _close  # noqa: E402


def reduce(raw: dict, program: bool) -> dict:
    trace = tr.Trace.from_json(raw)
    win = tr.window(trace)
    calls = [Call(fn, rows, np.asarray(live)) for fn, rows, live in
             raw["calls"]]
    ctx = Ctx(trace, win, calls, {}, {}, {})
    spans = [programtrace.Span(*s) for s in raw["program_spans"]]
    programtrace.attach(ctx, spans if program else [],
                        raw["scopes"] if program else {})
    got = {m: load_module("layer_metrics", m).read(ctx)
           for m in ("tick_host_ms", "decode_kv_move_ms")}
    got["idle_gaps"] = [list(g) for g in tr.gaps_by_span(trace, win)]
    if program:
        got["decode_scope_ns"], got["decode_calls"] = \
            programtrace.scope_ns(ctx, "decode")
        got["ticks"] = [[t.args["tick"], ns]
                        for t, ns in programtrace.ticks(ctx)]
        got["counters"] = programtrace.counters(ctx)
    return got


def main() -> int:
    raw = json.loads((HERE / "selfcheck_program_trace.json").read_text())
    want = dict(raw["expect"])
    without = want.pop("without_program")
    bad = 0
    for label, got, expect in (("", reduce(raw, True), want),
                               ("without program: ", reduce(raw, False),
                                without)):
        for k, v in expect.items():
            ok = _close(got[k], v)
            bad += not ok
            print(f"{'ok ' if ok else 'BAD'} {label}{k}: got {got[k]}, "
                  f"want {v}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
