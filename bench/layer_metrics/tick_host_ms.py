"""Scheduler: the host's time in one tick that the device waits through:
the device's idle time inside a ``sched.tick`` span wholly inside the
traced window, median over the window's ticks.  Host work that overlaps
device work does not count, and a dispatch held by the runtime counts
only while the device has nothing to run.  Notes split the ticks by the
prefill chunks they run (the tick's ``chunks`` arg) and give the
program's other counters."""
from collections import defaultdict

from layerctx import percentile
import programtrace


def read(ctx):
    ticks = programtrace.ticks(ctx)
    if not ticks:
        return None
    idle = [ns / 1e6 for _, ns in ticks]
    by_chunks = defaultdict(list)
    for t, ns in ticks:
        by_chunks[t.args["chunks"]].append(ns / 1e6)
    ctx.notes.append(
        f"tick device-idle ms over {len(idle)} ticks: max {max(idle)!r}, "
        f"p90 {percentile(idle, 90)!r}; median by prefill chunks in the "
        "tick: " + ", ".join(f"{k}: {percentile(v, 50)!r} ({len(v)} ticks)"
                             for k, v in sorted(by_chunks.items())))
    ctx.notes.append(programtrace.counters(ctx))
    return percentile(idle, 50)
