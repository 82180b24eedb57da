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
from repro.kernels.kv_pages import PageFormat

K, N = 3072, 8192                        # d_model -> d_ff
KV, DH, PAGE, SLOTS, BLOCKS = 32, 96, 16, 4, 68
POOL = 1 + SLOTS * BLOCKS                # trash page + worst case
# bench/cells/phi3-chat.json: 16 slots, max_len 2048 (128 blocks of 16),
# the 1281-page pool the cell builds
CHAT_SLOTS, CHAT_BLOCKS, CHAT_POOL = 16, 128, 1281
LAYERS = 32                              # phi3's layer stack


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
    dt, width = (jnp.int8, KV * DH) if bits == 8 else (jnp.uint8,
                                                       KV * DH // 2)
    pool = (PageFormat(PAGE, width).shape(LAYERS, n_pages), dt)
    scale = (PageFormat(PAGE, KV).shape(LAYERS, n_pages), jnp.float32)
    _compile(lambda q, k, ks, v, vs, t, n, li: paged_attention(
                 q, k, v, ks, vs, t, n, page=PAGE, layer=li,
                 interpret=False),
             "paged_attention", one_chip, ((slots, KV, 1, DH), jnp.float32),
             pool, scale, pool, scale, ((slots, blocks), jnp.int32),
             ((slots,), jnp.int32), ((), jnp.int32))


def test_pact_compiles(one_chip):
    _compile(lambda x, b: pact_quant_pallas(x, b, interpret=False), "pact",
             one_chip, ((16, K), jnp.float32), ((1,), jnp.float32))


# bench/cells/dsv2lite-doc.json: DeepSeek-V2-Lite's latent cache (rank
# 512, rope 64, 16 heads, 27 layers), 32 slots of 8192 positions in
# 16-token pages, the 18432-page pool the cell builds; and a pool whose
# page count is not a multiple of the 8 pages whose scales share a row
@pytest.mark.parametrize("slots,blocks,n_pages", [(32, 512, 18432),
                                                  (SLOTS, BLOCKS, POOL)])
def test_latent_attention_compiles(one_chip, slots, blocks, n_pages):
    from repro.kernels.latent_attention import latent_attention

    def pool(width, dt):
        return PageFormat(PAGE, width).shape(27, n_pages), dt
    _compile(lambda ql, qp, c, cs, p, ps, t, n, li: latent_attention(
                 ql, qp, c, cs, p, ps, t, n, page=PAGE, sm_scale=0.1147,
                 layer=li, interpret=False),
             "latent_attention", one_chip,
             ((slots, 16, 512), jnp.float32), ((slots, 16, 64), jnp.float32),
             pool(512, jnp.int8), pool(1, jnp.float32), pool(64, jnp.int8),
             pool(1, jnp.float32), ((slots, blocks), jnp.int32),
             ((slots,), jnp.int32), ((), jnp.int32))


# ---------------------------------------------------------------------------
# the layer walk reads and writes the stacked pool in place
# ---------------------------------------------------------------------------
#
# A decode-like scan over the layers of each cell's paged cache, through
# the model's own cache write (``attention._write_kv`` / ``mla._write``)
# and fused decode read, the stacked cache riding the carry and donated
# as ``decode_step`` donates it.  Nothing may copy, slice out or write
# back a whole layer's pool or the whole stack, and the temporaries stay
# small.  On the page-minor storage this replaced, the phi3 walk held a
# layer-sized copy and the DeepSeek-V2-Lite ``kpe`` leaf needed
# 1,019,434,496 B of temporaries.

_DEF = re.compile(r"%([\w.\-]+) = \w+\[([\d,]*)\]")
_MOVE = re.compile(r"%[\w.\-]+ = \w+\[([\d,]*)\]\S* (copy|dynamic-slice|"
                   r"dynamic-update-slice)(?:-start)?\(%[\w.\-]+"
                   r"(?:, %([\w.\-]+))?")


def _pool_moves(text, pools):
    """Ops of compiled HLO ``text`` that move a pool: a copy or a
    dynamic slice whose result, or a dynamic-update-slice whose update,
    is one of the ``pools`` shapes (an in-place write of a few tokens
    into the stack is a dynamic-update-slice too, with a small update)."""
    shapes = {m.group(1): tuple(int(d) for d in m.group(2).split(",") if d)
              for m in _DEF.finditer(text)}
    moves = []
    for m in _MOVE.finditer(text):
        moved = tuple(int(d) for d in m.group(1).split(",") if d)
        if m.group(2) == "dynamic-update-slice":
            moved = shapes.get(m.group(3))
        if moved in pools:
            moves.append(m.group(0))
    return moves


TEMP_LIMIT = 64 << 20


def _walk_program(cell):
    """(function, abstract args) of one cell's decode-like layer walk."""
    from repro.kernels.kv_pages import make_pool
    from repro.models import attention, mla
    if cell == "phi3-chat":
        layers, n_pages, slots, nb = LAYERS, CHAT_POOL, CHAT_SLOTS, \
            CHAT_BLOCKS
        tails = {"k": ((KV, DH), jnp.int8), "v": ((KV, DH), jnp.int8),
                 "k_scale": ((KV,), jnp.float32),
                 "v_scale": ((KV,), jnp.float32)}
        xs = {n: (layers, slots, 1, KV, DH) for n in ("q", "k", "v")}

        def layer(cache, li, x):
            cache = attention._write_kv(cache, x["k"], x["v"], idx, 8, li)
            kv_len = idx + 1
            return cache, attention._decode_read(x["q"], cache, kv_len, 0,
                                                 0.0, "fused", li)
    else:
        layers, n_pages, slots, nb = 27, 18432, 32, 512
        tails = {"ckv": ((512,), jnp.int8), "kpe": ((64,), jnp.int8),
                 "ckv_scale": ((), jnp.float32),
                 "kpe_scale": ((), jnp.float32)}
        xs = {"c": (layers, slots, 1, 512), "pe": (layers, slots, 1, 64),
              "ql": (layers, slots, 16, 512), "qp": (layers, slots, 16, 64)}

        def layer(cache, li, x):
            cache = mla._write(cache, x["c"], x["pe"], idx, li)
            return cache, mla._decode_read(x["ql"], x["qp"], cache, idx + 1,
                                           0.1147, "fused", li)
    pool = jax.eval_shape(lambda: make_pool(tails, layers, n_pages, PAGE))
    cache = {"pages": pool,
             "table": jax.ShapeDtypeStruct((layers, slots, nb), jnp.int32)}
    idx = None

    def walk(cache, index, xs):
        nonlocal idx
        idx = index

        def body(carry, x):
            c, li = carry
            c, out = layer(c, li, x)
            return (c, li + 1), out
        (cache, _), outs = jax.lax.scan(
            body, (cache, jnp.asarray(0, jnp.int32)), xs)
        return cache, outs
    args = (cache, jax.ShapeDtypeStruct((slots,), jnp.int32),
            {n: jax.ShapeDtypeStruct(s, jnp.float32) for n, s in xs.items()})
    return walk, args, pool


@pytest.mark.parametrize("cell", ["phi3-chat", "dsv2lite-doc"])
def test_layer_walk_moves_no_pool(one_chip, monkeypatch, cell):
    from repro.kernels import pallas_utils
    monkeypatch.setattr(pallas_utils, "default_interpret", lambda: False)
    walk, args, pool = _walk_program(cell)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled = jax.jit(walk, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    pools = {tuple(leaf.shape) for leaf in pool.values()}
    # the stack, or one layer of it (with or without its stack dim)
    pools |= {s[1:] for s in pools} | {(1,) + s[1:] for s in pools}
    moves = _pool_moves(text, pools)
    assert not moves, moves
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_LIMIT, temp


# A whole-prompt prefill writes a slot's every token at once, and parking,
# restoring or copying a slot moves every layer's share of its pages.  At
# each cell's widest slot (max_len tokens) these stay native row scatters
# or a loop of block-sized moves: no temporaries that grow with the
# tokens squared, and no copy of a leaf.

_CELL_POOLS = {
    "phi3-chat": (LAYERS, CHAT_POOL, CHAT_BLOCKS * PAGE,
                  {"k": ((KV, DH), jnp.int8), "v": ((KV, DH), jnp.int8),
                   "k_scale": ((KV,), jnp.float32),
                   "v_scale": ((KV,), jnp.float32)}),
    "dsv2lite-doc": (27, 18432, 8192,
                     {"ckv": ((512,), jnp.int8), "kpe": ((64,), jnp.int8),
                      "ckv_scale": ((), jnp.float32),
                      "kpe_scale": ((), jnp.float32)}),
}


@pytest.mark.parametrize("op", ["write", "take", "put"])
@pytest.mark.parametrize("cell", sorted(_CELL_POOLS))
def test_slot_sized_page_moves_stay_small(one_chip, cell, op):
    from repro.kernels.kv_pages import (make_pool, put_pages, take_pages,
                                        write_tokens)
    layers, n_pages, n, tails = _CELL_POOLS[cell]
    pool = jax.eval_shape(lambda: make_pool(tails, layers, n_pages, PAGE))
    ids = jax.ShapeDtypeStruct((n // PAGE,), jnp.int32, sharding=one_chip)

    def arg(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    for name, leaf in pool.items():
        fmt = pool.fmt(name)
        if op == "write":
            fn = lambda lf, p, o, v: write_tokens(lf, fmt, 3, p, o, v)
            args = (arg(leaf), ids.update(shape=(n,)), ids.update(shape=(n,)),
                    arg(jax.ShapeDtypeStruct((n, fmt.width), jnp.float32)))
        elif op == "take":
            fn = lambda lf, i: take_pages(lf, fmt, i)
            args = (arg(leaf), ids)
        else:
            vals = jax.eval_shape(lambda lf, i: take_pages(lf, fmt, i),
                                  leaf, ids)
            fn = lambda lf, i, v: put_pages(lf, fmt, i, v)
            args = (arg(leaf), ids, arg(vals))
        compiled = jax.jit(fn, donate_argnums=0 if op != "take" else ()
                           ).lower(*args).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 1 << 20, (name, temp)
        assert not _pool_moves(compiled.as_text(), {tuple(leaf.shape)}), \
            name
