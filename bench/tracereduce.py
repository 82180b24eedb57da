"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into a neutral
:class:`Trace`: device operations and device programs (XLA modules) with
start and end in nanoseconds, and the harness's host spans on the same
clock.  Everything after ``load`` is plain arithmetic on intervals, so
``bench/selfcheck.py`` can run it on a small recorded trace with known
answers.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]

WINDOW_SPAN = "bench.window"       # host span around the traced window


@dataclasses.dataclass
class Op:
    start: int                       # ns
    end: int                         # ns
    name: str                        # HLO op name ("packed_matmul.90") or
    meta: str = ""                   # program name; the op's HLO text

    @property
    def kind(self) -> str:
        """The op name without its numeric suffix: a Pallas call's kernel
        name ("packed_matmul"), "fusion", "copy", ..."""
        head, _, tail = self.name.rpartition(".")
        return head if tail.isdigit() else self.name


# ops whose interval holds other ops of the same line (a scan's loop)
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Trace:
    ops: List[Op]                    # device operations, all chips
    modules: List[Op]                # device programs (XLA modules)
    spans: List[Op]                  # harness host spans
    chips: int = 1

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        mk = lambda rows: [Op(*r) for r in rows]
        return cls(mk(d["ops"]), mk(d["modules"]), mk(d["spans"]),
                   d.get("chips", 1))


def _op(ev) -> Op:
    """A device op event: the profiler names it by its HLO text,
    ``%name = shape op(...)``."""
    text = ev.name
    name = text.split(" = ", 1)[0].lstrip("%") if " = " in text else text
    return Op(ev.start_ns, ev.start_ns + ev.duration_ns, name, text)


def load(trace_dir: str, span_names: Iterable[str]) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    wanted = set(span_names) | {WINDOW_SPAN}
    ops, modules, spans, chips = [], [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chips += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(_op(ev) for ev in line.events)
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append(Op(ev.start_ns, ev.start_ns
                                          + ev.duration_ns, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append(Op(ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        ev.name))
    return Trace(ops, modules, spans, max(chips, 1))


def window(tr: Trace) -> Interval:
    w = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
    return w[0].start, w[0].end


def clip(items: List[Op], win: Interval) -> List[Op]:
    lo, hi = win
    return [Op(max(o.start, lo), min(o.end, hi), o.name, o.meta)
            for o in items if o.end > lo and o.start < hi]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace, win: Interval) -> int:
    """Nanoseconds of the window in which an operation ran on the device
    (the union of the ops that hold no others), averaged over the chips
    (the ops of all chips share one list)."""
    return sum(b - a for a, b in union(
        (o.start, o.end) for o in leaf_ops(clip(tr.ops, win)))) \
        // max(tr.chips, 1)


def idle_gaps(tr: Trace, win: Interval) -> List[Interval]:
    busy = union((o.start, o.end) for o in leaf_ops(clip(tr.ops, win)))
    gaps, t = [], win[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < win[1]:
        gaps.append((t, win[1]))
    return gaps


def span_at(tr: Trace, t: int) -> str:
    """The innermost harness span open at host time ``t``."""
    best: Optional[Op] = None
    for s in tr.spans:
        if s.name != WINDOW_SPAN and s.start <= t < s.end and (
                best is None or s.start >= best.start):
            best = s
    return best.name if best is not None else "harness"


def gaps_by_span(tr: Trace, win: Interval) -> List[Tuple[str, float]]:
    """Idle seconds grouped by what the host was doing, longest first."""
    acc: Dict[str, int] = defaultdict(int)
    for a, b in idle_gaps(tr, win):
        acc[span_at(tr, (a + b) // 2)] += b - a
    return sorted(((k, v / 1e9) for k, v in acc.items()),
                  key=lambda kv: -kv[1])


def leaf_ops(items: List[Op]) -> List[Op]:
    """The ops that hold no others (a scan's ``while`` holds its body)."""
    return [o for o in items if o.kind not in CONTAINERS]


def top_ops(tr: Trace, win: Interval, n: int = 10) -> List[Tuple[str, float]]:
    """Device seconds by op kind, largest first."""
    acc: Dict[str, int] = defaultdict(int)
    for o in leaf_ops(clip(tr.ops, win)):
        acc[o.kind] += o.end - o.start
    return sorted(((k, v / 1e9) for k, v in acc.items()),
                  key=lambda kv: -kv[1])[:n]


def of_kind(items: List[Op], kinds: Iterable[str]) -> List[Op]:
    """The ops of the given kinds: a kernel's Pallas calls are ops named
    after it."""
    kinds = set(kinds)
    return [o for o in items if o.kind in kinds]


def total_ns(items: Iterable[Op]) -> int:
    return sum(o.end - o.start for o in items)
