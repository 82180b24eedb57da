"""What a per-layer metric reader is given, and how readers are found.

A reader is ``bench/layer_metrics/<metric>.py`` with ``read(ctx)``
returning the metric's value, or None where the run holds nothing for it
to read (the harness then leaves the metric out of the result line).
Kernel costs are ``bench/kernel_costs/<name>.py``, found the same way.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Dict, List

import numpy as np

import tracereduce as tr

HERE = Path(__file__).resolve().parent

# XLA module names of the engine's jitted programs
MODULES = {"decode": "jit_decode_step",
           "prefill_chunk_at": "jit_prefill_chunk_at"}


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Ctx:
    trace: tr.Trace
    win: tuple                    # traced window, ns on the trace's clock
    calls: List                   # adapter.Call made inside the window
    conf: dict                    # configuration file
    cell: dict                    # cell file
    peak: Dict[str, float]        # this device's row of peaks.json
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def widths(self) -> dict:
        return self.conf["program"]["widths"]

    def cost(self, name: str):
        return load_module("kernel_costs", name)

    def ops(self) -> List[tr.Op]:
        return tr.clip(self.trace.ops, self.win)

    def modules(self, fn: str) -> List[tr.Op]:
        want = MODULES[fn]
        return [m for m in tr.clip(self.trace.modules, self.win)
                if m.name.split("(")[0] == want]

    def module_ms(self, fn: str):
        mods = self.modules(fn)
        if not mods:
            return None
        return tr.total_ns(mods) / len(mods) / 1e6

    def kernel_ns(self, kernel: str) -> int:
        return tr.total_ns(tr.of_kind(self.ops(), self.cost(kernel).NAMES))

    def calls_of(self, fn: str) -> List:
        return [c for c in self.calls if c.fn == fn]


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None
