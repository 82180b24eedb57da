"""The serving kernels compile for a TPU v5e at phi3-mini-3.8b widths.

No chip is needed: the TPU compiler targets a described ``v5e:2x2``
topology and refuses what the chip would refuse (tiling, VMEM, Mosaic
legalization), which interpret mode never checks.  The topology is
described inside a fixture, never at import, so every pytest-xdist
worker collects the same tests and only the one running them loads the
TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (bitplane_matmul, packed_matmul, paged_attention,
                           pact_quant_pallas)

K, N = 3072, 8192                        # d_model -> d_ff
KV, DH, PAGE, SLOTS, BLOCKS = 32, 96, 16, 4, 68
POOL = 1 + SLOTS * BLOCKS                # trash page + worst case
# bench/cells/phi3-chat.json: 16 slots, max_len 2048 (128 blocks of 16),
# the 1281-page pool the cell builds
CHAT_SLOTS, CHAT_BLOCKS, CHAT_POOL = 16, 128, 1281


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, kernel, one_chip, *shapes):
    """Compile ``fn`` and check that its Pallas call runs as a Mosaic
    kernel whose instruction carries the kernel's own name (a profile
    names the device op after it, whatever wraps the call)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = re.findall(
        r'%([\w.\-]+) = .*custom_call_target="tpu_custom_call"', text)
    assert calls, "no Mosaic kernel in the compiled program"
    assert all(re.fullmatch(rf"{kernel}(\.\d+)?", c) for c in calls), calls


@pytest.mark.parametrize("bits,m", [(8, 8), (8, 128), (4, 8)])
def test_packed_matmul_compiles(one_chip, bits, m):
    rows, dt = (K, jnp.int8) if bits == 8 else (K // 2, jnp.uint8)
    _compile(lambda x, w, s: packed_matmul(x, w, s, bits=bits,
                                           interpret=False),
             "packed_matmul", one_chip, ((m, K), jnp.bfloat16), ((rows, N), dt),
             ((K // 8, N // 128), jnp.float32))


def test_bitplane_matmul_compiles(one_chip):
    _compile(lambda x, p, g, m, s: bitplane_matmul(x, p, g, m, s, n_bits=8,
                                                   interpret=False),
             "bitplane_matmul", one_chip, ((8, K), jnp.bfloat16), ((8, K // 8, N), jnp.uint8),
             ((K // 8, N), jnp.uint8), ((8, K // 8, N // 128), jnp.float32),
             ((K // 8, N // 128), jnp.float32))


@pytest.mark.parametrize("slots,blocks,n_pages", [
    (SLOTS, BLOCKS, POOL), (CHAT_SLOTS, CHAT_BLOCKS, CHAT_POOL)])
@pytest.mark.parametrize("bits", [8, 4])
def test_paged_attention_compiles(one_chip, bits, slots, blocks, n_pages):
    dh_s, dt = (DH, jnp.int8) if bits == 8 else (DH // 2, jnp.uint8)
    pool, scale = ((n_pages, PAGE, KV, dh_s), dt), ((n_pages, PAGE, KV),
                                                    jnp.float32)
    _compile(lambda q, k, ks, v, vs, t, n: paged_attention(
                 q, k, v, ks, vs, t, n, interpret=False),
             "paged_attention", one_chip, ((slots, KV, 1, DH), jnp.float32),
             pool, scale, pool, scale, ((slots, blocks), jnp.int32),
             ((slots,), jnp.int32))


def test_pact_compiles(one_chip):
    _compile(lambda x, b: pact_quant_pallas(x, b, interpret=False), "pact",
             one_chip, ((16, K), jnp.float32), ((1,), jnp.float32))


# bench/cells/dsv2lite-doc.json: DeepSeek-V2-Lite's latent cache (rank
# 512, rope 64, 16 heads), 32 slots of 8192 positions in 16-token pages,
# the 18432-page pool the cell builds; and a pool whose page count is not
# a multiple of the 8-page scale block
@pytest.mark.parametrize("slots,blocks,n_pages", [(32, 512, 18432),
                                                  (SLOTS, BLOCKS, POOL)])
def test_latent_attention_compiles(one_chip, slots, blocks, n_pages):
    from repro.kernels.latent_attention import latent_attention
    _compile(lambda ql, qp, c, cs, p, ps, t, n: latent_attention(
                 ql, qp, c, cs, p, ps, t, n, sm_scale=0.1147,
                 interpret=False),
             "latent_attention", one_chip,
             ((slots, 16, 512), jnp.float32), ((slots, 16, 64), jnp.float32),
             ((n_pages, PAGE, 512), jnp.int8), ((n_pages, PAGE), jnp.float32),
             ((n_pages, PAGE, 64), jnp.int8), ((n_pages, PAGE), jnp.float32),
             ((slots, blocks), jnp.int32), ((slots,), jnp.int32))
