"""The one file that touches the program under test.

It builds the served model the way a deployment would (int8 packed
weights drawn on the device from the seed, ``ServeEngine`` and its
``Scheduler``), submits requests, drives ``Scheduler.step()`` and reads
what each step produced.  It reads program state only through public
``ServeEngine`` methods, which it wraps (with ``TraceAnnotation`` spans in
traced runs), and through ``Scheduler.slots`` and ``Scheduler.results``.
A later change to the scheduler's internals touches this file alone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
from typing import Dict, List, Optional

import numpy as np

# engine methods wrapped with a span of the same name in traced runs
SPANS = ("decode", "prefill_chunk_at", "set_tables")


@dataclasses.dataclass
class Call:
    """One engine call made while the call log was on."""
    fn: str                       # 'decode' | 'prefill_chunk_at'
    rows: int                     # decode: batch rows; prefill: chunk width
    live: np.ndarray              # decode: fill levels of live rows
    start: int = 0                # prefill: cache position of the chunk
    real: int = 0                 # prefill: prompt tokens in the chunk


@dataclasses.dataclass
class StepOut:
    """What one scheduler step did, as seen from outside."""
    new_tokens: Dict[int, int]    # uid -> tokens emitted in this step
    admitted: List[int]           # uids that entered a slot in this step
    finished: List[int]           # uids that completed in this step


def check_widths(cfg, widths: Dict[str, int]) -> None:
    """The program's registered config must have the widths the
    configuration file states; the benchmark never serves another."""
    bad = {k: (getattr(cfg, k), v) for k, v in widths.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"configuration file (program, file): {bad}")


class Served:
    """One deployed model behind one scheduler."""

    def __init__(self, conf: dict, cell: dict, key, spans: bool = False):
        import jax
        from repro.models.api import build
        from repro.serve import ServeEngine
        from repro.serve.deploy import deploy_from_seed
        cfg = self.model_config(conf["program"])
        q = cfg.quant
        dep = conf["deploy"]
        if (q.wb_rows, q.wb_cols, q.n_bits, q.act_bits) != (
                dep["wb_rows"], dep["wb_cols"], dep["bits"],
                conf["numerics"]["act_bits"]):
            raise ValueError(f"program quantization {q} differs from the "
                             f"configuration file's deploy {dep}")
        self.cfg = cfg
        api = build(cfg)
        params = deploy_from_seed(api, key, dep["bits"], dep["layout"])
        jax.block_until_ready(params)
        eng = ServeEngine(api, params, **conf["engine"])
        self.engine = eng
        self.sched = eng.make_scheduler(
            [], n_slots=cell["n_slots"], max_len=cell["max_len"],
            n_pages=cell["pool_pages"])
        self.calls: Optional[List[Call]] = None   # call log when on
        self.shapes: Dict[str, tuple] = {}        # first call's arguments
        self._count: Dict[int, int] = {}          # uid -> tokens seen
        self._waiting: set = set()
        self._jobs: Dict[int, object] = {}
        self._spans = spans
        self._wrap(spans)

    def span(self, name: str):
        """A host span of the harness in traced runs, else nothing."""
        if not self._spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @staticmethod
    def model_config(prog: dict):
        """The program's registered config of the configuration's
        architecture, held to the file's widths."""
        from repro.configs import REGISTRY
        cfg = REGISTRY[prog["arch"]]
        check_widths(cfg, prog["widths"])
        return cfg

    # ---- wrapping the engine's public methods ---------------------------
    def _wrap(self, spans: bool) -> None:
        import jax
        eng = self.engine
        sched = self.sched

        def abstract(args):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), getattr(a, "dtype", np.int32)), args)

        def first_full(name, args):
            # the prefill program checked is the full-chunk one
            return name == "decode" or (
                name == "prefill_chunk_at"
                and args[0]["tokens"].shape[1] == eng.prefill_chunk)

        def wrap(name, before):
            fn = getattr(eng, name)

            def run(*args):
                if name not in self.shapes and first_full(name, args):
                    self.shapes[name] = abstract(args)
                if self.calls is not None and before is not None:
                    self.calls.append(before(*args))
                if not spans:
                    return fn(*args)
                with jax.profiler.TraceAnnotation(f"engine.{name}"):
                    return fn(*args)
            setattr(eng, name, run)

        def on_decode(tokens, state, index):
            idx = np.asarray(index)
            live = idx[idx != sched.total_len - 1]
            return Call("decode", int(tokens.shape[0]), live + 1)

        def on_chunk(batch, state, slot, start):
            w = int(batch["tokens"].shape[1])
            p = int(sched.slots[slot].req.inputs["tokens"].shape[1])
            return Call("prefill_chunk_at", w, np.zeros(0), int(start),
                        max(0, min(w, p - start)))

        wrap("decode", on_decode)
        wrap("prefill_chunk_at", on_chunk)
        wrap("set_tables", None)

    # ---- driving ---------------------------------------------------------
    def submit(self, job) -> None:
        from repro.serve import Request, SamplingParams
        self.sched.submit(Request(
            uid=job.uid, inputs={"tokens": job.prompt[None, :]},
            sampling=SamplingParams(max_new_tokens=job.max_new)))
        self._waiting.add(job.uid)
        self._count[job.uid] = 0
        self._jobs[job.uid] = job

    def busy(self) -> bool:
        return bool(self._waiting) or any(
            s is not None for s in self.sched.slots)

    def step(self) -> StepOut:
        sched = self.sched
        done_before = len(sched.results)
        with self.span("scheduler.step"):
            sched.step()
        new, admitted = {}, []
        seen = {}
        for s in sched.slots:
            if s is not None:
                seen[s.req.uid] = len(s.generated)
        finished = list(sched.results)[done_before:]
        for uid in finished:
            seen[uid] = len(sched.results[uid].tokens)
        for uid, n in seen.items():
            if uid in self._waiting:
                self._waiting.discard(uid)
                admitted.append(uid)
            if n > self._count[uid]:
                new[uid] = n - self._count[uid]
                self._count[uid] = n
        return StepOut(new, admitted, finished)

    def tokens(self, uid: int) -> List[int]:
        return list(self.sched.results[uid].tokens)

    def prompt(self, uid: int) -> np.ndarray:
        return self._jobs[uid].prompt

    def max_new(self, uid: int) -> int:
        return self._jobs[uid].max_new

    def sync(self) -> None:
        """Wait for the device work the scheduler has queued."""
        import jax
        if self.sched.state is not None:
            jax.block_until_ready(self.sched.state)

    def stats(self) -> dict:
        """The scheduler's own counters and its cache report."""
        return {**self.sched.sched_stats, **{
            k: v for k, v in self.sched.cache_report().items()
            if k in ("pool_capacity_pages", "peak_pages_in_use",
                     "bytes_in_use_peak")}}

    # ---- guards ------------------------------------------------------------
    def custom_calls(self) -> Dict[str, int]:
        """``tpu_custom_call`` count in the compiled decode and prefill
        chunk programs, for the shapes the cell ran."""
        eng = self.engine
        out = {}
        for name, step in (("decode", "decode"),
                           ("prefill_chunk_at", "prefill_chunk")):
            text = eng.compiled_text(step, *self.shapes[name])
            out[name] = text.count("tpu_custom_call")
        return out

    def close(self) -> None:
        """Free the program's device state: the weights and the cache."""
        import jax
        leaves = jax.tree_util.tree_leaves(
            (self.sched.state, self.engine.params))
        self.sched.state = None
        self.engine.params = None
        for x in leaves:
            if hasattr(x, "delete") and not x.is_deleted():
                x.delete()
        gc.collect()
