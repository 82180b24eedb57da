"""The correctness check of ``bench/run.py`` at a size the CPU holds.

A run drives a tiny phi3 (two layers, width 128) through the whole
harness but the look for a chip and the count of Pallas calls (the CPU
runs the kernels in interpret mode): served sound, ``correct`` is true;
with the timed path broken underneath it is false, once for each fault a
served cell can have (a token altered where it is produced, a decode
step that returns its state unchanged).  The control, the reference in
fp8 judged in the served tokens' place, comes out not correct.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import adapter  # noqa: E402
import run  # noqa: E402

SEED = 2**33 + 7          # above 32 bits, as the driver's seeds are


def tiny_spec(workload="phi3-chat", **over):
    """The cell's files with the model cut to ``tiny(**over)`` widths and
    the traffic scaled to it; the limit is the cell's own."""
    from repro.configs import REGISTRY
    spec = run.load_spec(workload)
    cfg = REGISTRY[spec.conf["program"]["arch"]].tiny(**over)
    spec.conf = copy.deepcopy(spec.conf)
    w = spec.conf["program"]["widths"]
    for k in w:
        w[k] = getattr(cfg, k)
    spec.conf["engine"]["prefill_chunk"] = 32
    spec.mix = copy.deepcopy(spec.mix)
    spec.mix["prompt"].update(median=40, min=8, max=100)
    spec.mix["output"].update(median=8, min=4, max=24)
    spec.mix.update(ramp_s=1.0)
    spec.cell = dict(spec.cell, n_slots=4, max_len=128, pool_pages=33,
                     rate_per_s=2.0, trace_seconds=1.0,
                     check=dict(spec.cell["check"], sequences=2,
                                tokens=32))

    class Tiny(adapter.Served):
        @staticmethod
        def model_config(prog):
            return cfg

        def custom_calls(self):
            return {"decode": 1, "prefill_chunk_at": 1}
    return spec, Tiny


def broken(base, fault):
    """``base`` with its engine's decode broken by ``fault``."""
    class Broken(base):
        def _wrap(self, spans):
            decode = self.engine.decode

            def bad(tokens, state, index):
                old = jax.tree_util.tree_map(jnp.copy, state)
                logits, new = decode(tokens, state, index)
                if fault == "token":      # the next token wins instead
                    return jnp.roll(logits, 1, axis=-1), new
                return logits, old        # the step keeps its old state
            self.engine.decode = bad
            super()._wrap(spans)
    return Broken


@pytest.fixture(autouse=True)
def cpu_chips(monkeypatch):
    """The look for a chip finds the CPU's devices."""
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())


def run_tiny(factory, spec, control=False):
    return run.run(spec, SEED, 3.0, False, served_factory=factory,
                   control=control)


@pytest.fixture(scope="module")
def spec():
    return tiny_spec()


def test_sound_run_is_correct(spec):
    s, tiny = spec
    out = run_tiny(tiny, s)
    assert out["correct"], out["checks"]
    assert out["checks"]["mean_gap"]["value"] < s.cell["check"]["limit"]


@pytest.mark.parametrize("fault", ["token", "stale_state"])
def test_fault_fails(spec, fault):
    s, tiny = spec
    out = run_tiny(broken(tiny, fault), s)
    assert not out["correct"], out["checks"]


def test_control_reads_above_limit():
    """The fp8 reference's tokens, judged in place of the served ones by
    a run's own decision, read above the limit and the run is not
    correct, while the served tokens read below it.  Eight layers of
    width 256: fp8's error grows with depth and width, and two layers of
    128 stay under the limit that 32 layers exceed."""
    s, tiny = tiny_spec(n_layers=8, d_model=256, d_ff=512)
    out = run_tiny(tiny, s, control=True)
    limit = s.cell["check"]["limit"]
    assert out["served_mean_gap"] < limit, out
    assert out["checks"]["mean_gap"]["value"] > limit, out
    assert not out["correct"], out


def test_end_to_end_reads_the_window():
    """Each end-to-end metric from a window with known tokens: a stalled
    request counts its wait so far, a gap counts when its later token
    falls in the window, and occupancy counts slots and queue at a
    time."""
    reqs = {0: run.Req(due=0.0, admitted=0.5, first=1.0, last=12.0, n=3,
                       finished=12.0),
            1: run.Req(due=2.0, admitted=3.0, first=4.0, last=4.0, n=1),
            2: run.Req(due=5.0)}
    w = run.Window(start=1.0, end=11.0, reqs=reqs,
                   token_times=[(1.0, 1), (4.0, 1), (6.0, 2), (12.0, 1)],
                   gaps=[(6.0, 5.0), (6.0, 0.0), (12.0, 6.0)], lateness=[],
                   compiles=0, steps=4, calls=[], trace_dir=None)
    spec = run.Spec("w", 1, {}, {}, {}, [{"name": n} for n in (
        "out_tok_s", "itl_p95_ms", "ttft_p90_ms", "setup_s")], [])
    got = run.end_to_end(w, spec, 7.5)
    assert got["out_tok_s"] == pytest.approx(3 / 10)
    assert got["itl_p95_ms"] == pytest.approx(np.percentile([5e3, 0], 95))
    assert got["ttft_p90_ms"] == pytest.approx(
        np.percentile([2e3, 6e3], 90))
    assert got["setup_s"] == 7.5
    assert run.occupancy(w, 3.5) == (2, 0)
    assert run.occupancy(w, 11.0) == (2, 1)


def test_traced_run_reads_the_trace(spec, monkeypatch):
    """A ``--trace 1`` run reduces its profile through every reader of the
    cell and reports the traced window; the CPU has no entry in the table
    of peaks, so the test gives it v5e's."""
    s, tiny = spec
    v5e = run.device_peaks("TPU v5 lite")
    monkeypatch.setattr(run, "device_peaks", lambda kind: v5e)
    out = run.run(s, SEED, 3.0, True, served_factory=tiny)
    assert out["correct"], out["checks"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
