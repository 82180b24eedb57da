"""``chip_smoke.py`` off the chip: the script refuses to run, and its
phases (deploy, serve, logit checks) hold at a tiny width on the CPU.

The script itself has no CPU fallback; these tests drive its phase
functions directly, with the kernels in interpret mode.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import REGISTRY
from repro.launch import cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


@pytest.fixture
def tiny_chunks(monkeypatch):
    monkeypatch.setattr(chip_smoke, "CHUNK", 16)
    return REGISTRY[chip_smoke.ARCH].tiny()


def test_one_chip_phases_at_tiny_width(tiny_chunks):
    r = chip_smoke.one_chip(tiny_chunks, prompt_lens=(16, 32, 48, 64),
                            max_new=6)
    assert r["tokens"] == 4 * 6
    assert r["prefill"][1] <= chip_smoke.PREFILL_RTOL
    assert r["decode"][1] <= chip_smoke.DECODE_RTOL
    assert set(r["kernels"]) == {"packed_matmul", "paged_attention"}
    assert all(rel <= 1e-5 for _, rel in r["kernels"].values()), r["kernels"]
    # off the chip the kernels are interpreted: no custom calls
    assert r["custom_calls"] == {"prefill_chunk": 0, "decode": 0}


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = cache.enable_compile_cache()
        if env_dir:      # JAX reads the variable itself; nothing is set
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:            # a fixed directory inside the checkout
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
