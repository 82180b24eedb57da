"""Lane-dense storage of a paged KV pool: the one rule every reader and
writer of the pool uses.

A pool leaf holds, per layer and page, the ``page`` tokens' values of
one kind (K, V, a per-token scale, a latent, ...), each token ``width``
values (its trailing dims flattened).  The TPU keeps an array in its
default row-major layout only when the minor dim is lane-dense; a
(…, page, KV, dh) leaf with ``dh`` = 96, or a (…, page, KV) scale, is
laid out page-index-minor instead, so every per-layer slice of it is a
re-layout.  So a leaf is stored ``(stack, blocks, rows, lanes)``:

* a token takes ``slot`` lanes: its ``width``, zero-padded up to a
  divisor of 128 (below 128) or a multiple of 128 (above), so no token
  straddles a row;
* a row is ``lanes`` wide: the slot when that is a multiple of 128 (one
  token a row: phi3's K of 32 x 96 int8 is a (16, 3072) page), else 128
  lanes holding ``per_row`` tokens, token-major;
* a page whose tokens fill less than one row shares it: ``group`` pages
  a row block (DeepSeek-V2-Lite's per-token latent scales: 8 pages of
  16 a row of 128), so pool page ``p`` lives in block ``p // group``;
* ``rows`` rows a block.

The shapes alone decide, never a model's name.  :class:`PagePool` carries
a paged node's leaves with the page size, the page count and each leaf's
per-token shape, so readers need no config.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

LANES = 128


def slot_width(width: int) -> int:
    """Lanes one token takes: ``width`` padded so tokens tile rows."""
    if width % LANES == 0 or LANES % width == 0:
        return width
    if width < LANES:
        return 1 << (width - 1).bit_length()
    return -(-width // LANES) * LANES


@dataclasses.dataclass(frozen=True)
class PageFormat:
    """Where token ``t`` of pool page ``p`` lies in a lane-dense leaf."""
    page: int                 # tokens a page holds
    width: int                # values a token holds

    @property
    def slot(self) -> int:
        return slot_width(self.width)

    @property
    def lanes(self) -> int:
        return max(self.slot, LANES)

    @property
    def per_row(self) -> int:
        return self.lanes // self.slot

    @property
    def group(self) -> int:
        return max(1, self.per_row // self.page)

    @property
    def rows(self) -> int:
        return -(-self.group * self.page // self.per_row)

    def blocks(self, n_pages: int) -> int:
        return -(-n_pages // self.group)

    def shape(self, stack: int, n_pages: int) -> Tuple[int, ...]:
        return (stack, self.blocks(n_pages), self.rows, self.lanes)

    def coords(self, pids, offs):
        """(block, row, first lane) of token ``offs`` of page ``pids``."""
        u = (pids % self.group) * self.page + offs
        return pids // self.group, u // self.per_row, \
            (u % self.per_row) * self.slot


# ---------------------------------------------------------------------------
# the pool node
# ---------------------------------------------------------------------------

class PagePool(dict):
    """Lane-dense pool leaves of one paged KV node (a dict of leaves) with
    the static geometry: ``page`` tokens a page, ``n_pages`` pages, and
    each leaf's per-token shape (``tails``).  A pytree whose aux data is
    the geometry, so it survives ``jit``, ``eval_shape`` and tree maps."""

    def __init__(self, leaves, page: int, n_pages: int, tails):
        super().__init__(leaves)
        self.page, self.n_pages = int(page), int(n_pages)
        self.tails = {n: tuple(int(d) for d in t)
                      for n, t in dict(tails).items()}

    def fmt(self, name: str) -> PageFormat:
        return PageFormat(self.page, math.prod(self.tails[name]))

    def head_split(self, name: str, m: int) -> bool:
        """Whether leaf ``name`` shards its lanes over ``m`` devices, each
        holding whole KV heads in whole lane tiles: a (heads, ...)
        per-token shape whose heads ``m`` divides, one token a row, and
        each share of the row a multiple of 128 lanes.  Such a leaf is
        split on a tensor-parallel mesh's 'model' axis, each device's
        share itself the lane-dense storage of its heads; other leaves
        (scales, latents) stay whole on every device."""
        tail, fmt = self.tails[name], self.fmt(name)
        return (m > 1 and len(tail) >= 2 and tail[0] % m == 0
                and fmt.lanes == fmt.width and (fmt.width // m) % LANES == 0)

    def replace(self, **leaves) -> "PagePool":
        return PagePool({**self, **leaves}, self.page, self.n_pages,
                        self.tails)

    def _aux(self):
        return (tuple(sorted(self)), self.page, self.n_pages,
                tuple(sorted(self.tails.items())))


def _flatten_with_keys(pool):
    keys = sorted(pool)
    return ([(jax.tree_util.DictKey(k), pool[k]) for k in keys],
            pool._aux())


def _unflatten(aux, children):
    keys, page, n_pages, tails = aux
    return PagePool(dict(zip(keys, children)), page, n_pages, dict(tails))


jax.tree_util.register_pytree_with_keys(
    PagePool, _flatten_with_keys, _unflatten,
    lambda pool: ([pool[k] for k in sorted(pool)], pool._aux()))


def make_pool(tails: Dict[str, Tuple[Tuple[int, ...], object]], stack: int,
              n_pages: int, page: int) -> PagePool:
    """Zero pool: ``tails`` maps a leaf name to (per-token shape, dtype)."""
    return PagePool(
        {n: jnp.zeros(PageFormat(page, math.prod(t)).shape(stack, n_pages),
                      dt) for n, (t, dt) in tails.items()},
        page, n_pages, {n: t for n, (t, _) in tails.items()})


# ---------------------------------------------------------------------------
# values <-> storage (jnp; outside the kernels)
# ---------------------------------------------------------------------------

def _pad_last(x, size: int):
    if x.shape[-1] == size:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, size - x.shape[-1])]
    return jnp.pad(x, pad)


def to_rows(x, fmt: PageFormat):
    """(..., n_pages, page, width) values -> (..., blocks, rows, lanes)."""
    *lead, n_pages, page, width = x.shape
    assert (page, width) == (fmt.page, fmt.width), (x.shape, fmt)
    flat = _pad_last(x, fmt.slot).reshape(*lead, n_pages, page * fmt.slot)
    blocks = fmt.blocks(n_pages)
    flat = jnp.pad(flat, [(0, 0)] * len(lead)
                   + [(0, blocks * fmt.group - n_pages), (0, 0)])
    flat = flat.reshape(*lead, blocks, fmt.group * page * fmt.slot)
    flat = _pad_last(flat, fmt.rows * fmt.lanes)
    return flat.reshape(*lead, blocks, fmt.rows, fmt.lanes)


def write_tokens(leaf, fmt: PageFormat, layer, pids, offs, vals):
    """``leaf`` with token values ``vals`` (..., width) written at
    (``layer``, page ``pids``, token ``offs``), in place under donation.

    One token a row: a scatter of whole rows.  Several tokens a row: a
    scatter that clears each token's slot of its row, then one that adds
    the token's value there, both whole rows whose duplicates (tokens of
    this write sharing a row) combine, so no token overwrites another and
    the work stays linear in the tokens written.  Either way the compiler
    emits native row scatters (a scatter addressed down to single lanes
    would be expanded into a loop over the elements)."""
    vals = _pad_last(vals.reshape(-1, fmt.width), fmt.slot)
    block, row, lane = fmt.coords(pids.reshape(-1), offs.reshape(-1))
    if fmt.per_row == 1:
        return leaf.at[layer, block, row].set(vals.astype(leaf.dtype))
    at = jnp.arange(fmt.lanes, dtype=jnp.int32)[None, :]
    mine = (at >= lane[:, None]) & (at < lane[:, None] + fmt.slot)
    placed = jnp.where(mine, jnp.tile(vals, (1, fmt.per_row)), 0)
    leaf = leaf.at[layer, block, row].multiply((~mine).astype(leaf.dtype))
    return leaf.at[layer, block, row].add(placed.astype(leaf.dtype))


def read_pages(leaf, fmt: PageFormat, layer, table):
    """Pages ``table`` (B, nb) of layer ``layer`` in token order:
    (B, nb * page, width), gathered in one op."""
    b, nb = table.shape
    n = fmt.page * fmt.slot
    blocks = leaf[layer, table // fmt.group]            # (B, nb, rows, lanes)
    flat = blocks.reshape(b, nb, fmt.rows * fmt.lanes)
    if fmt.group > 1:
        start = (table % fmt.group) * n
        flat = jnp.take_along_axis(
            flat, start[..., None] + jnp.arange(n, dtype=jnp.int32), axis=2)
    else:
        flat = flat[..., :n]
    return flat.reshape(b, nb * fmt.page, fmt.slot)[..., :fmt.width]


def _pages_of(block, fmt: PageFormat):
    """A (stack, 1, rows, lanes) block as its pages' values (stack,
    group, page * slot) and the lanes past them (stack, rest)."""
    flat = block.reshape(block.shape[0], -1)
    n = fmt.group * fmt.page * fmt.slot
    return flat[:, :n].reshape(-1, fmt.group, n // fmt.group), flat[:, n:]


@functools.partial(jax.jit, static_argnames="fmt")
def take_pages(leaf, fmt: PageFormat, ids):
    """Every layer's stored values of pool pages ``ids``, bit for bit:
    (stack, n, rows, lanes) blocks where a page is a whole block
    (``group`` 1), else (stack, n, page * slot), each page's values in
    its block.  A loop over the pages, so no op moves more than a
    block."""
    ids = jnp.asarray(ids, jnp.int32)
    stack = leaf.shape[0]
    tail = leaf.shape[2:] if fmt.group == 1 else (fmt.page * fmt.slot,)
    out = jnp.zeros((stack, ids.shape[0]) + tail, leaf.dtype)

    def body(i, out):
        x = jax.lax.dynamic_slice_in_dim(leaf, ids[i] // fmt.group, 1, 1)
        if fmt.group > 1:
            x = jax.lax.dynamic_slice_in_dim(_pages_of(x, fmt)[0],
                                             ids[i] % fmt.group, 1, 1)
        return jax.lax.dynamic_update_slice_in_dim(out, x, i, 1)
    return jax.lax.fori_loop(0, ids.shape[0], body, out)


@functools.partial(jax.jit, static_argnames="fmt")
def put_pages(leaf, fmt: PageFormat, ids, vals):
    """``leaf`` with :func:`take_pages` values written to pages ``ids``,
    a page at a time: whole blocks where a page is one, else the page's
    values in its block (pages sharing a block keep each other's)."""
    ids = jnp.asarray(ids, jnp.int32)
    vals = jnp.asarray(vals, leaf.dtype)

    def body(i, leaf):
        x = jax.lax.dynamic_slice_in_dim(vals, i, 1, 1)
        blk = ids[i] // fmt.group
        if fmt.group > 1:
            old = jax.lax.dynamic_slice_in_dim(leaf, blk, 1, 1)
            pages, rest = _pages_of(old, fmt)
            pages = jax.lax.dynamic_update_slice_in_dim(
                pages, x, ids[i] % fmt.group, 1)
            x = jnp.concatenate([pages.reshape(rest.shape[0], -1), rest],
                                axis=1).reshape(old.shape)
        return jax.lax.dynamic_update_slice_in_dim(leaf, x, blk, 1)
    return jax.lax.fori_loop(0, ids.shape[0], body, leaf)
