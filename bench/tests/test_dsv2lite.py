"""The ``dsv2lite-doc`` cell's files and its correctness check at a size
the CPU holds: a tiny DeepSeek-V2-Lite (MLA over the paged int8 latent
cache, a share of the routed experts) through the whole harness.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(1, str(BENCH.parent / "src"))

import adapter  # noqa: E402
import run  # noqa: E402
from test_correctness import cpu_chips, run_tiny, tiny_spec  # noqa: E402,F401

CELL = "dsv2lite-doc"


def test_configuration_file_holds_the_published_widths():
    """Every width the program serves is the registry's; the only cut is
    the routed experts held, and the file states the deployment."""
    from repro.configs import REGISTRY
    spec = run.load_spec(CELL)
    conf = spec.conf
    cfg = REGISTRY[conf["program"]["arch"]]
    adapter.check_widths(cfg, conf["program"]["widths"])
    w = conf["program"]["widths"]
    assert (w["n_layers"], w["d_model"], w["vocab"]) == (
        conf["num_hidden_layers"], conf["hidden_size"], conf["vocab_size"])
    assert w["n_experts"] == conf["published"]["n_routed_experts"] == 64
    assert w["n_held_experts"] == conf["n_routed_experts"] == 16
    assert conf["reduced"] and conf["deployment"]
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"] if c["name"] == conf["name"]][0]
    assert entry["reduced"] == ["n_routed_experts"]
    assert entry["file"] == f"bench/configs/{conf['name']}.json"


def test_sound_run_is_correct():
    s, tiny = tiny_spec(CELL)
    out = run_tiny(tiny, s)
    assert out["correct"], out["checks"]


def test_control_reads_above_limit():
    """The fp8 reference's tokens, judged in place of the served ones,
    read above the cell's limit while the served tokens read below it
    (at this size 0.105 and 0.0075 against 0.07)."""
    s, tiny = tiny_spec(CELL)
    out = run_tiny(tiny, s, control=True)
    limit = s.cell["check"]["limit"]
    assert out["served_mean_gap"] < limit, out
    assert out["checks"]["mean_gap"]["value"] > limit, out
    assert not out["correct"], out


def test_readers_find_nothing_without_the_program_s_names():
    """On a program without MLA widths (the phi3 cell's), the new
    readers return nothing rather than raise."""
    from layerctx import Ctx, load_module
    spec = run.load_spec("phi3-chat")
    ctx = Ctx(None, (0, 1), [], spec.conf, spec.cell,
              run.device_peaks("TPU v5 lite"))
    for name in ("latent_attention_roofline", "step_mfu.mla_moe"):
        assert load_module("layer_metrics", name).read(ctx) is None
