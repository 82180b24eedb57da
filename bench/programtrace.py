"""The serving program's own spans and scopes in a traced run, for the
per-layer readers that read them.

The program emits host spans named in ``repro.serve.SPANS``, with
keyword args, and wraps the parts of its model step in the
``jax.named_scope`` names of ``repro.models.transformer.SCOPES``.
``tracereduce.load`` keeps neither.  ``attach(ctx)`` reads both from the
run's own profile once and hangs them on the readers' context:

* ``ctx.program_spans``: the program's host spans, with their args;
* ``ctx.scopes``: XLA module (as the profile names it,
  ``jit_decode_step(<program id>)``) -> {HLO op name: scope}.  The
  profile keeps each program's optimized HLO in its ``/host:metadata``
  plane; the scopes come from it by the program's own rule
  (``repro.dist.hlo_analysis.hlo_op_scopes``).

The run's profile is the one ``bench/run.py`` writes under
``.bench_cache/trace/<workload>``, the workload being the cell whose
file holds ``ctx.cell``, and only if its window span is ``ctx.win``.
``attach`` also adds the program spans to ``ctx.trace.spans``, so the
result line's idle gaps are charged to the innermost span of either
kind; that happens only in a cell that lists a reader of this module.
Against a program that has no spans or scopes both stay empty, and the
readers that need them return None.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
from collections import defaultdict
from pathlib import Path
from statistics import mean
from typing import Dict, Iterator, List, Optional, Tuple

import tracereduce as tr

HERE = Path(__file__).resolve().parent
CELLS = HERE / "cells"
# where bench/run.py has the profiler write a traced run's profile
TRACES = HERE.parent / ".bench_cache" / "trace"


@dataclasses.dataclass
class Span:
    start: int                       # ns, on the device ops' clock
    end: int                         # ns
    name: str                        # "sched.tick", ...
    args: dict                       # the span's keyword args


def attach(ctx, spans: Optional[List[Span]] = None,
           scopes: Optional[Dict[str, Dict[str, str]]] = None) -> None:
    """Hang the program's spans and op scopes on ``ctx`` (once): the
    given ones, else those of the run's profile."""
    if hasattr(ctx, "program_spans"):
        return
    if spans is None or scopes is None:
        found = _from_profile(ctx)
        spans = found[0] if spans is None else spans
        scopes = found[1] if scopes is None else scopes
    ctx.program_spans = sorted(spans, key=lambda s: s.start)
    ctx.scopes = scopes
    ctx.trace.spans.extend(tr.Op(s.start, s.end, s.name)
                           for s in ctx.program_spans)


def inside(ctx, name: str) -> List[Span]:
    """The program's ``name`` spans wholly inside the traced window."""
    attach(ctx)
    lo, hi = ctx.win
    return [s for s in ctx.program_spans
            if s.name == name and lo <= s.start and s.end <= hi]


def ticks(ctx) -> List[Tuple[Span, int]]:
    """Each ``sched.tick`` wholly inside the traced window, with the
    nanoseconds inside it in which no device op ran: the host work of
    the tick that the device waits through, whether the host computes,
    reads a result or is held in a dispatch."""
    ticks_in = inside(ctx, "sched.tick")
    gaps = tr.idle_gaps(ctx.trace, ctx.win) if ticks_in else []
    ends = [b for _, b in gaps]
    out = []
    for t in ticks_in:
        idle = 0
        for a, b in gaps[bisect.bisect_right(ends, t.start):]:
            if a >= t.end:
                break
            idle += min(b, t.end) - max(a, t.start)
        out.append((t, idle))
    return out


def counters(ctx) -> Optional[str]:
    """What the program's counters say about the window, one line; the
    decode fill levels are compared with the harness's call log."""
    args = lambda name: [s.args for s in inside(ctx, name)]
    tick = args("sched.tick")
    if not tick:
        return None
    admit, chunk, first, grow, dec = (args(f"sched.{n}") for n in (
        "admit", "prefill_chunk", "first_token_wait", "grow_pages",
        "decode"))
    logged = [(len(c.live), int(c.live.sum()), int(c.live.max()))
              for c in ctx.calls_of("decode") if len(c.live)]
    told = [(d["live"], d["fill_sum"], d["fill_max"]) for d in dec]
    return (
        f"program counters: {len(tick)} ticks, live slots mean "
        f"{mean(t['live'] for t in tick)!r}, queued max "
        f"{max(t['queued'] for t in tick)}; admitted "
        f"{sum(a['admitted'] for a in admit)} (queued after max "
        f"{max((a['queued'] for a in admit), default=0)}); "
        f"{len(chunk)} prefill chunks of {len({c['uid'] for c in chunk})} "
        f"requests in {len({c['slot'] for c in chunk})} slots, "
        f"{sum(c['real'] for c in chunk)} prompt tokens of "
        f"{sum(c['width'] for c in chunk)} (last chunk starts max "
        f"{max((c['start'] for c in chunk), default=0)}); "
        f"{len({f['uid'] for f in first})} first tokens; pages in use max "
        f"{max((g['pages_in_use'] for g in grow), default=0)}; "
        f"{len(dec)} decodes of {max((d['rows'] for d in dec), default=0)}"
        f" rows, fill max {max((d['fill_max'] for d in dec), default=0)};"
        f" decode fill levels as the call log's: {told == logged}")


def scope_ns(ctx, fn: str) -> Tuple[Dict[str, int], int]:
    """Device nanoseconds by scope of the ops inside the traced calls of
    the engine program ``fn`` ('decode', 'prefill_chunk_at'), and the
    number of calls.  An op the program's HLO does not scope is
    ``unscoped``."""
    attach(ctx)
    ops = sorted(tr.leaf_ops(ctx.ops()), key=lambda o: o.start)
    starts = [o.start for o in ops]
    mods = ctx.modules(fn)
    acc: Dict[str, int] = defaultdict(int)
    for m in mods:
        scopes = ctx.scopes.get(m.name, {})
        for o in ops[bisect.bisect_left(starts, m.start):]:
            if o.start >= m.end:
                break
            if o.end <= m.end:
                acc[scopes.get(o.name, "unscoped")] += o.end - o.start
    return dict(acc), len(mods)


# ---- reading the profile ------------------------------------------------
def _from_profile(ctx):
    """The program's spans and op scopes in the run's profile, or none
    where the program names neither or the profile is not the run's."""
    try:
        from repro.dist.hlo_analysis import hlo_op_scopes
        from repro.models.transformer import SCOPES
        from repro.serve import SPANS
    except ImportError:
        return [], {}
    cells = [p.stem for p in CELLS.glob("*.json")
             if json.loads(p.read_text()) == ctx.cell]
    if len(cells) != 1:
        return [], {}
    files = sorted(glob.glob(str(TRACES / cells[0] / "**" / "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return [], {}
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(files[-1])
    wanted = set(SPANS) | {tr.WINDOW_SPAN}
    spans = [Span(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                  dict(ev.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name in wanted]
    if [(s.start, s.end) for s in spans if s.name == tr.WINDOW_SPAN] \
            != [tuple(ctx.win)]:
        return [], {}
    with open(files[-1], "rb") as f:
        hlo = _module_texts(f.read())
    return ([s for s in spans if s.name != tr.WINDOW_SPAN],
            {name: hlo_op_scopes(text, SCOPES)
             for name, text in hlo.items()})


def _module_texts(xspace: bytes) -> Dict[str, str]:
    """{XLA module as the profile names it: optimized HLO text with op
    metadata} from the ``Hlo Proto`` stats of a serialized XSpace's
    ``/host:metadata`` plane (tsl's xplane.proto: XSpace.planes 1;
    XPlane.name 2, event_metadata 4, stat_metadata 5; XEventMetadata
    .name 2, .stats 5; XStatMetadata.name 2; XStat.metadata_id 1,
    .bytes_value 6; HloProto.hlo_module 1)."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    out = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if bytes(next((v for k, v in parts if k == 2), b"")) \
                != b"/host:metadata":
            continue
        stat_names = {}
        for k, v in parts:
            if k == 5:
                meta = dict(_fields(_entry_value(v)))
                stat_names[meta.get(1)] = bytes(meta.get(2, b""))
        for k, v in parts:
            if k != 4:
                continue
            meta = list(_fields(_entry_value(v)))
            name = bytes(next((x for f, x in meta if f == 2), b"")).decode()
            for f, x in meta:
                stat = dict(_fields(x)) if f == 5 else {}
                if stat_names.get(stat.get(1)) == b"Hlo Proto" and 6 in stat:
                    module = dict(_fields(stat[6]))[1]
                    out[name] = xla_client.XlaComputation(
                        bytes(module)).get_hlo_module().to_string(opts)
    return out


def _entry_value(entry):
    """The value of a serialized protobuf map entry (key 1, value 2)."""
    return dict(_fields(entry)).get(2, b"")


def _fields(buf) -> Iterator[tuple]:
    """(field number, value) of a serialized protobuf message: varints
    as ints, length-delimited fields as memoryviews."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i

