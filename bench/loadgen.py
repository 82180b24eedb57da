"""Traffic for one run, made from a mix file, a cell file and the seed.

A mix (``bench/traffic/<mix>.json``) states the loop (``open``: arrivals
on a Poisson schedule at the cell's rate, the only loop there is), the
prompt and output length distributions and the ramp before the window.

Every seed serves the same requests in the same order: their (prompt,
output) lengths and their arrival times are drawn once from the mix's
own ``pool_seed``.  The run's seed draws only the token ids (and, in the
harness, the weights).  The arrivals fall in three phases, the ramp, the
window and a tail after it; each phase holds a fixed number of requests
(its length times the rate) with exponential gaps scaled to fill it
exactly: Poisson arrivals conditioned on their count.  A window holds
about a dozen requests that each live about as long as the window, so a
different order per seed would change the work measured (it moved the
window's output tokens per second by 28% between seeds on the chip); a
fixed schedule leaves the seed nothing to change but the values.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Job:
    """One request: its prompt token ids and how many tokens to serve."""
    uid: int
    prompt: np.ndarray          # (P,) int32
    max_new: int


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def pool_lengths(mix: dict, n: int) -> np.ndarray:
    """(n, 2) prompt and output lengths: the multiset every seed serves."""
    rng = np.random.default_rng(mix["pool_seed"])
    return np.stack([_lengths(mix["prompt"], n, rng),
                     _lengths(mix["output"], n, rng)], axis=1)


TAIL_S = 10.0          # arrivals drawn past the window's end


def phases(mix: dict, cell: dict, seconds: float) -> List[tuple]:
    """(length in seconds, requests) of each arrival phase: the ramp, the
    window and the tail."""
    rate = cell["rate_per_s"]
    return [(s, max(1, int(round(rate * s))))
            for s in (mix["ramp_s"], seconds, TAIL_S)]


class Traffic:
    """The run's requests in sending order, and their due times in
    seconds after the traffic starts."""

    def __init__(self, mix: dict, cell: dict, vocab: int, seed: int,
                 seconds: float):
        self.mix, self.cell = mix, cell
        if mix["loop"] != "open":
            raise ValueError(f"unknown loop {mix['loop']!r}")
        ph = phases(mix, cell, seconds)
        n = sum(k for _, k in ph)
        lengths = pool_lengths(mix, n)
        gap_rng = np.random.default_rng(mix["pool_seed"] + 1)
        gaps = []
        for span, k in ph:
            g = gap_rng.exponential(1.0, k)
            gaps += list(g * (span / g.sum()))
        rng = np.random.default_rng(seed)
        self.jobs: List[Job] = []
        for uid, (p, o) in enumerate(lengths):
            self.jobs.append(Job(uid, rng.integers(0, vocab, p,
                                                   dtype=np.int32), int(o)))
        self.due = np.cumsum(gaps)

    def prompt_widths(self) -> List[int]:
        return sorted({len(j.prompt) for j in self.jobs})
