#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<mix>.json``) and has a file of its own sizes
(``bench/cells/<cell>.json``).  One run:

1. fails unless JAX finds a TPU with as many chips as the cell asks for;
2. deploys the configuration's int8 packed weights from ``--seed`` on the
   device and builds the engine and its scheduler (``adapter.Served``);
3. warms the programs the cell's traffic uses, through the scheduler, and
   fails if a compiled decode or prefill-chunk program has no Pallas
   kernel in it;
4. starts the traffic, lets the mix's ramp bring the queue to a steady
   state, and measures for ``--seconds``; set-up is everything before the
   window, the ramp included;
5. checks the served tokens against the plain reference (``correctness``);
6. prints the guards and each compared number beside its limit on
   standard error, and the result as one JSON line on standard output.

``--trace 1`` profiles the first ``trace_seconds`` of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = ROOT / ".bench_cache"            # listed in .gitignore
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import loadgen  # noqa: E402
import tracereduce  # noqa: E402
from layerctx import Ctx, load_module, percentile  # noqa: E402


class Refused(RuntimeError):
    """The run cannot measure this cell here; no result is printed."""


@dataclasses.dataclass
class Spec:
    workload: str
    chips: int
    conf: dict
    mix: dict
    cell: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = [w for w in bench["workloads"] if w["name"] == workload]
    if len(entry) != 1:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    entry = entry[0]

    def mine(m):
        return workload in m.get("workloads", [workload])
    return Spec(
        workload, entry["chips"],
        json.loads((HERE / "configs" / f"{entry['config']}.json")
                   .read_text()),
        json.loads((HERE / "traffic" / f"{entry['traffic']}.json")
                   .read_text()),
        json.loads((HERE / "cells" / f"{workload}.json").read_text()),
        [m for m in bench["end_to_end"] if mine(m)],
        [m for m in bench["per_layer"] if mine(m)])


class CompileLog:
    """Backend compiles and persistent-cache loads seen by this process,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.programs += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def count(self) -> int:
        return self.programs


def make_key(seed: int):
    """A JAX key from any non-negative seed (all of its bits count)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def enable_cache() -> None:
    """JAX's persistent compilation cache in the checkout, at a fixed
    path, holding every program (the program's own cache helper takes
    the directory from the environment)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def warm(served, traffic, cell, seed: int) -> None:
    """Serve one request of every prompt width the traffic will insert in
    one piece (widths up to the chunk), and one of several chunks, so
    every program of the window is loaded before it starts."""
    chunk = served.engine.prefill_chunk
    widths = [w for w in traffic.prompt_widths() if w <= chunk]
    widths.append(max(traffic.prompt_widths()))
    rng = np.random.default_rng([seed, 7])
    for i, w in enumerate(widths):
        served.submit(loadgen.Job(10**9 + i, rng.integers(
            0, served.cfg.vocab, w, dtype=np.int32), 2))
    while served.busy():
        served.step()
    served.sync()


@dataclasses.dataclass
class Req:
    due: float
    admitted: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    n: int = 0
    finished: Optional[float] = None


@dataclasses.dataclass
class Window:
    start: float
    end: float
    reqs: Dict[int, Req]
    token_times: List[tuple]          # (step end, tokens emitted)
    gaps: List[tuple]                 # (later token time, gap s)
    lateness: List[float]
    compiles: int
    steps: int
    calls: list
    trace_dir: Optional[str]


def drive(served, traffic, mix, seconds: float, log: CompileLog,
          trace_s: float = 0.0, trace_dir: Optional[str] = None,
          uid0: int = 0) -> Window:
    """Offer the traffic, ramp, then measure for ``seconds``.  Request
    uids start at ``uid0``."""
    import jax
    clock = time.perf_counter
    reqs: Dict[int, Req] = {}
    token_times, gaps, lateness = [], [], []
    jobs = traffic.jobs
    n_jobs = len(jobs)
    sent = 0
    t0 = clock()
    ramp_end = t0 + mix["ramp_s"]
    win_start = stop_at = None
    compiles0 = steps = 0
    trace_open = None
    calls: list = []
    while True:
        now = clock()
        if win_start is None and now >= ramp_end:
            if trace_dir:
                served.sync()
                served.calls = []
                jax.profiler.start_trace(trace_dir)
                trace_open = jax.profiler.TraceAnnotation(
                    tracereduce.WINDOW_SPAN)
                trace_open.__enter__()
                now = clock()
            win_start, stop_at = now, now + seconds
            compiles0 = log.count()
        if trace_open is not None and now >= win_start + trace_s:
            served.sync()
            trace_open.__exit__(None, None, None)
            jax.profiler.stop_trace()
            trace_open = None
            calls, served.calls = served.calls, None
            now = clock()
        if stop_at is not None and now >= stop_at:
            break
        # the generator: send what is due
        with served.span("generator"):
            while sent < n_jobs and t0 + traffic.due[sent] <= now:
                due = t0 + traffic.due[sent]
                j = jobs[sent]
                served.submit(loadgen.Job(uid0 + sent, j.prompt, j.max_new))
                reqs[uid0 + sent] = Req(due)
                lateness.append(now - due)
                sent += 1
            next_due = t0 + traffic.due[sent] if sent < n_jobs \
                else float("inf")
        if not served.busy():
            limit = stop_at if stop_at is not None else ramp_end
            time.sleep(max(0.0, min(next_due, limit) - clock()))
            continue
        a = clock()
        out = served.step()
        b = clock()
        steps += win_start is not None
        for uid in out.admitted:
            if uid in reqs:
                reqs[uid].admitted = a
        emitted = 0
        for uid, k in out.new_tokens.items():
            r = reqs.get(uid)
            if r is None:
                continue
            if r.n:
                gaps.append((b, b - r.last))
            else:
                r.first = b
            gaps.extend((b, 0.0) for _ in range(k - 1))
            r.n += k
            r.last = b
            emitted += k
        token_times.append((b, emitted))
        for uid in out.finished:
            if uid in reqs:
                reqs[uid].finished = b
    win_end = clock()
    if trace_open is not None:
        served.sync()
        trace_open.__exit__(None, None, None)
        jax.profiler.stop_trace()
        calls, served.calls = served.calls, None
    return Window(win_start, win_end, reqs, token_times, gaps, lateness,
                  log.count() - compiles0, steps,
                  calls, trace_dir)


def occupancy(w: Window, t: float) -> tuple:
    """(requests in a slot, requests due but not yet in one) at ``t``."""
    in_slot = sum(1 for r in w.reqs.values()
                  if r.admitted is not None and r.admitted <= t
                  and (r.finished is None or r.finished > t))
    queued = sum(1 for r in w.reqs.values()
                 if r.due <= t and (r.admitted is None or r.admitted > t))
    return in_slot, queued


def end_to_end(w: Window, spec: Spec, setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric the harness knows; the result line takes
    those the cell's entries in ``BENCHMARK.json`` name."""
    inside = lambda t: w.start < t <= w.end
    length = w.end - w.start
    due_in = [r for r in w.reqs.values() if w.start <= r.due < w.end]
    ttft = [((r.first if r.first is not None and r.first <= w.end
              else w.end) - r.due) * 1e3 for r in due_in]
    itl = [g * 1e3 for t, g in w.gaps if inside(t)]
    values = {
        "out_tok_s": sum(k for t, k in w.token_times if inside(t)) / length,
        "itl_p95_ms": percentile(itl, 95),
        "ttft_p90_ms": percentile(ttft, 90),
        "setup_s": setup_s,
    }
    print(f"window: {length!r} s, {w.steps} steps, {len(due_in)} requests "
          f"due, {len(itl)} token gaps, {values['out_tok_s']!r} tokens/s, "
          f"ttft p90 {values['ttft_p90_ms']!r} ms", file=sys.stderr)
    return {m["name"]: values[m["name"]] for m in spec.end_to_end}


def per_layer(w: Window, spec: Spec, peak: dict):
    trace = tracereduce.load(w.trace_dir, ["scheduler.step", "generator"]
                             + [f"engine.{s}" for s in
                                ("decode", "prefill_chunk_at",
                                 "set_tables")])
    win = tracereduce.window(trace)
    ctx = Ctx(trace, win, w.calls, spec.conf, spec.cell, peak)
    out = {}
    for m in spec.per_layer:
        v = load_module("layer_metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = float(v)
    busy = tracereduce.busy_ns(trace, win) / 1e9
    window_s = (win[1] - win[0]) / 1e9
    breakdown = {"device_ops": [list(x) for x in
                                tracereduce.top_ops(trace, win)],
                 "idle_gaps": [list(x) for x in
                               tracereduce.gaps_by_span(trace, win)[:10]]}
    for note in ctx.notes:
        print(note, file=sys.stderr)
    return out, busy, window_s, breakdown


def check(spec: Spec, served, w: Window, key, seed: int,
          control: bool = False):
    """Sample finished requests, free the program, run the reference
    (and, with ``control``, the fp8 control beside it).  Returns the
    gaps, the uids served short of their tokens and the tokens
    compared."""
    import correctness
    done = {u: r for u, r in w.reqs.items()
            if r.finished is not None and w.start < r.finished <= w.end}
    seqs = {u: (served.prompt(u), served.tokens(u)) for u in done}
    short = [u for u in seqs if len(seqs[u][1]) != served.max_new(u)]
    c = spec.cell["check"]
    picked = correctness.sample(seqs, seed, c["tokens"], c["sequences"])
    served.close()
    if not picked:
        return None, short, 0
    ref = correctness.reference(spec.conf)
    gap = correctness.served_gap(ref, key, [seqs[u] for u in picked],
                                 c["sequences"], spec.cell["max_len"],
                                 control)
    n_tok = sum(len(seqs[u][1]) for u in picked)
    return gap, short, n_tok


def device_peaks(kind: str) -> dict:
    """This device's row of ``bench/peaks.json``; an unknown device is an
    error."""
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise Refused(f"no peaks for device {kind!r} in bench/peaks.json")
    return peaks[kind]


def run(spec: Spec, seed: int, seconds: float, trace: bool,
        served_factory=None, control: bool = False) -> dict:
    """One run of the cell; returns the result line's object.  With
    ``control`` the fp8 control's tokens are judged in place of the
    served ones, by the same decision."""
    devs = find_chips(spec.chips)
    log = CompileLog()
    from adapter import Served
    key = make_key(seed)
    served = (served_factory or Served)(spec.conf, spec.cell, key,
                                        spans=trace)
    traffic = loadgen.Traffic(spec.mix, spec.cell, served.cfg.vocab, seed,
                              seconds)
    warm(served, traffic, spec.cell, seed)
    calls = served.custom_calls()
    print(f"tpu_custom_call in compiled programs: {calls}", file=sys.stderr)
    if not all(calls.values()):
        raise Refused(f"a program runs without its Pallas kernels: {calls}")
    trace_dir = None
    if trace:
        trace_dir = str(CACHE / "trace" / spec.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = drive(served, traffic, spec.mix, seconds, log,
              spec.cell.get("trace_seconds", seconds), trace_dir)
    setup_s = w.start - T_PROC
    served.sync()
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    print(f"compiles in the window: {w.compiles} (set-up: "
          f"{log.programs} programs built in {log.seconds!r} s, "
          f"{log.cache_hits} of them from the persistent cache)",
          file=sys.stderr)
    late = np.asarray(w.lateness or [0.0]) * 1e3
    print(f"generator lateness ms: p50 {np.percentile(late, 50)!r} "
          f"max {late.max()!r}", file=sys.stderr)
    print(f"peak_bytes_in_use: {peak} of "
          f"{stats[0].get('bytes_limit')}", file=sys.stderr)
    print(f"sched_stats: {served.stats()}", file=sys.stderr)
    print("occupancy (in slots, queued): window start "
          f"{occupancy(w, w.start)}, end {occupancy(w, w.end)}",
          file=sys.stderr)
    if w.compiles:
        raise Refused(f"{w.compiles} programs compiled or loaded inside "
                      f"the window: warm-up missed a shape")
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        metrics, busy, window_s, breakdown = per_layer(
            w, spec, device_peaks(dev.device_kind))
        device.update(busy_s=busy, window_s=window_s)
    else:
        metrics = end_to_end(w, spec, setup_s)
    due_in = sum(1 for r in w.reqs.values() if w.start <= r.due < w.end)
    gap, short, n_tok = check(spec, served, w, key, seed, control)
    judged = gap and gap["control" if control else "served"]
    limit = spec.cell["check"]["limit"]
    mean = judged and judged["mean"]
    correct = gap is not None and mean <= limit and not short
    print(f"compared {n_tok} served tokens"
          f"{' (fp8 control in their place)' if control else ''}; "
          f"requests short of their tokens: {len(short)}; widest gap "
          f"(not compared): {judged and judged['widest']!r}; served mean "
          f"gap {gap and gap['served']['mean']!r}", file=sys.stderr)
    print(f"check mean_gap: {mean!r} (limit {limit})", file=sys.stderr)
    units = {m["name"]: m["unit"] for m in spec.end_to_end + spec.per_layer}
    out = {"correct": bool(correct), "attempted": due_in, "failed": 0,
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in metrics.items() if v is not None},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["served_mean_gap"] = gap and gap["served"]["mean"]
    out["checks"] = {"mean_gap": {"value": mean, "limit": limit}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    sys.path.insert(1, str(ROOT / "src"))
    try:
        spec = load_spec(args.workload)
        enable_cache()
        out = run(spec, args.seed, args.seconds, bool(args.trace))
    except (Refused, FileNotFoundError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
