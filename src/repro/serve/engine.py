"""Serving engine: jitted, mesh-aware prefill / decode over the ModelAPI.

Decode-shape inference is where BWQ's weight compression pays off on TPU
(HBM-bandwidth-bound).  The engine extends the same idea to activations
with a *quantized-at-rest* KV cache: ``kv_quant_bits`` of 8 or 4 rebuilds
the model config so the cache itself stores int8 / nibble-packed int4
entries plus per-token scales (models.attention) — each written slot is
rounded exactly once and dequantized in-graph per attention call.  This
replaces the old per-step whole-tree re-quantization, which both re-rounded
already-quantized entries every step (compounding error per token) and
burned O(cache) requant work per decoded token.

When a ``dist.sharding`` mesh is active at construction, parameters are
placed by ``param_pspecs`` and prompt/state tensors by ``batch_pspecs`` /
``cache_pspecs``, so prefill and decode run sharded (batch on the data
axes, KV heads on the model axis) with no API change.  Under
``padded_sharding`` (default) a dim the mesh does not divide is
zero-padded to the next multiple at placement and sliced back to its
true shape inside every jitted entry point — non-dividing vocab /
kv-head dims shard instead of replicating (see ``dist.sharding``).

``backend`` selects how deployed (ServingWeight / BitplaneServingWeight)
matmuls execute inside the jitted prefill/decode: ``dense`` dequantizes
each leaf in-graph and runs plain dots; ``pallas`` streams the deployed
representation through its Pallas kernel (interpret mode auto-detected
off-TPU); ``ref`` is the pure-jnp kernel oracle; ``bitplane`` runs the
paper's plane-sliced precision-aware mapping (deploy with
``to_serving_params(..., layout="bitplane")``) so per-step weight bytes
track each block's live bit count.  The flag is applied as a
trace-time ``models.common.matmul_backend`` context around every jitted
entry point, so the whole serving program is built for one backend and
A/B comparisons (benchmarks/serve_bench.py --backend) are apples-to-apples.

``attn_backend`` does the same for the decode-attention read side:
``gather`` re-materializes each slot's contiguous KV view and
dequantizes in-graph (legacy); ``fused`` runs the Pallas paged-attention
kernel over the stored (quantized) cache — block-table walk and KV
dequant happen inside the kernel, so the decode program never holds a
full-width or f32 KV tensor (graph_lint's kv-* census pins this);
``ref`` is that kernel's jnp oracle.  Applied as a trace-time
``models.attention.paged_attn_backend`` context alongside the matmul
backend.

Two call surfaces:
  * ``generate(batch, max_new)`` — one-shot static-batch decoding (legacy).
  * ``serve(requests)`` — request-level continuous batching through
    :class:`repro.serve.scheduler.Scheduler`; ``page_size`` /
    ``prefill_chunk`` engine fields (or per-call overrides) select the
    paged block-table KV cache and chunked prompt insertion — both
    token-identical to the contiguous monolithic path.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

# Decode donates its state (double-buffering a multi-GB KV cache per tick
# is the thing the graph lint forbids); platforms that cannot honor the
# donation (CPU tests) fall back to copying and would warn every call.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

from ..dist.sharding import (batch_pspecs, cache_pspecs, get_mesh,
                             pad_leaf, param_pspecs, unpad_leaf, use_mesh)
from ..kernels.kv_pages import put_pages, take_pages
from ..models.api import ModelAPI
from ..models.attention import PAGED_ATTN_BACKENDS, paged_attn_backend
from ..models.common import MATMUL_BACKENDS, matmul_backend
from .sampling import SamplingParams, sample_token


def _roundup64(n: int) -> int:
    # round headroom up to limit recompiles across max_new values
    return -(-n // 64) * 64


def _mark_splits(params, specs, mesh):
    """Record on each deployed leaf how ``specs`` placed it over the
    mesh's 'model' axis (``split``: 'col', 'row' or ''), so the kernel
    call splits its work the same way (``models.common._run_split``).

    A split is kept only where every shard holds whole weight blocks of
    an unpadded dim, so a kernel can run on each shard alone.  The
    engine's placement is the one place a split is set: a leaf placed
    any other way keeps '' and its kernel runs on the whole weight."""
    from .deploy import BitplaneServingWeight, ServingWeight
    deployed = (ServingWeight, BitplaneServingWeight)
    m = dict(mesh.shape).get("model", 1)

    def mark(leaf, ps):
        if not isinstance(leaf, deployed):
            return leaf
        packed = isinstance(leaf, ServingWeight)
        payload, pspec = (leaf.w_int, ps.w_int) if packed \
            else (leaf.planes, ps.planes)
        gr, gc = leaf.scale.shape[-2:]
        entries = [e if isinstance(e, tuple) else (e,)
                   for e in tuple(pspec) + (None,) * (payload.ndim
                                                      - len(pspec))]
        split = ""
        if m > 1 and "model" in entries[-1]:
            n = leaf.shape[-1]
            if n == gc * leaf.spec.wb_cols and gc % m == 0:
                split = "col"
        elif m > 1 and "model" in entries[-2]:
            rows = payload.shape[-2]
            per_row = 8 if not packed else (2 if leaf.bits == 4 else 1)
            kp = gr * leaf.spec.wb_rows
            if leaf.shape[-2] == kp == rows * per_row and gr % m == 0 \
                    and rows % m == 0:
                split = "row"
        return dataclasses.replace(leaf, split=split)

    return jax.tree_util.tree_map(
        mark, params, specs, is_leaf=lambda x: isinstance(x, deployed))


@dataclasses.dataclass
class ServeEngine:
    api: ModelAPI
    params: Any
    kv_quant_bits: int = 32       # 8 / 4 select the quantized-at-rest cache
    backend: str = "dense"        # 'dense' | 'pallas' | 'ref' matmul exec
    attn_backend: str = "gather"  # 'gather' | 'fused' | 'ref' decode attn
    page_size: int = 0            # >0: paged KV cache (tokens per page)
    n_pages: Optional[int] = None  # page-pool capacity (None = worst case)
    prefill_chunk: int = 0        # >0: insert prompts in chunks this wide
    overcommit: float = 1.0       # >1: admit past capacity, park victims
    prefix_cache: bool = False    # share full prompt pages by content hash
    donate_state: bool = True     # donate decode state (no double-buffer)
    padded_sharding: bool = True  # pad-place params on non-dividing axes
    validate: bool = True         # contract-check deployed leaves on build
    speculate_planes: int = 0     # >0: self-speculative decode, top-k draft
    draft_gamma: int = 4          # draft tokens proposed per round

    def __post_init__(self):
        cfg = self.api.cfg
        if self.backend not in MATMUL_BACKENDS:
            raise ValueError(f"backend must be one of {MATMUL_BACKENDS}, "
                             f"got {self.backend!r}")
        if self.attn_backend not in PAGED_ATTN_BACKENDS:
            raise ValueError(
                f"attn_backend must be one of {PAGED_ATTN_BACKENDS}, "
                f"got {self.attn_backend!r}")
        if self.backend != "dense" and not self._has_packed_weights():
            hint = ", layout='bitplane'" if self.backend == "bitplane" else ""
            warnings.warn(
                f"backend={self.backend!r} only accelerates deployed packed "
                f"weights (serve.deploy.to_serving_params(...{hint})); this "
                f"param tree has none, so execution is identical to 'dense'",
                stacklevel=2)
        if self.backend == "bitplane":
            from ..analysis.graph_lint import fallback_leaf_paths
            stale = fallback_leaf_paths(self.params, self.backend)
            if stale:
                warnings.warn(
                    f"backend='bitplane' executes only the plane-sliced "
                    f"layout; {len(stale)} packed ServingWeight leaves "
                    f"fall back to the in-graph dense dequant dot "
                    f"(deploy with layout='bitplane'): {stale[:4]}",
                    stacklevel=2)
        if self.validate:
            from ..analysis.contracts import validate_serving_tree
            bad = [f for f in validate_serving_tree(self.params)
                   if f.severity == "error"]
            if bad:
                raise ValueError(
                    "deployed param tree violates the serving contract:\n"
                    + "\n".join(f.format() for f in bad[:8]))
        if self.kv_quant_bits < 32:
            if self.kv_quant_bits not in (4, 8):
                raise ValueError(f"kv_quant_bits must be 4, 8 or >=32, "
                                 f"got {self.kv_quant_bits}")
            if cfg.family == "ssm":
                warnings.warn(
                    f"kv_quant_bits={self.kv_quant_bits} has no effect on "
                    f"family 'ssm': recurrent state has no KV cache and "
                    f"serves at full precision", stacklevel=2)
            cfg = dataclasses.replace(cfg,
                                      kv_cache_bits=self.kv_quant_bits)
            self.api = ModelAPI(cfg)
        self.mesh = get_mesh()
        self._pad_shapes = None   # true leaf shapes when params pad-placed
        self._prefill_j = self._jit(self.api.prefill, "prefill",
                                    static_argnames=("extra_slots",))
        self._prefill_at_j = self._jit(self.api.prefill_at, "prefill_at")
        self._prefill_chunk_j = self._jit(self.api.prefill_chunk_at,
                                          "prefill_chunk_at")
        # decode_step(params, tokens, state, index): the state (arg 2) is
        # consumed and rebuilt every step — donate it so the cache updates
        # in place instead of double-buffering (graph lint enforces this)
        self._decode_j = self._jit(
            self.api.decode_step, "decode_step",
            **({"donate_argnums": (2,)} if self.donate_state else {}))
        self.draft_params = None
        self._verify_j = None
        if self.speculate_planes:
            if self.api.cfg.is_encdec or self.api.cfg.family in (
                    "ssm", "hybrid", "rwkv"):
                raise ValueError(
                    f"speculate_planes needs a purely positional KV cache "
                    f"(rejected drafts roll back by fill level); family "
                    f"{self.api.cfg.family!r} carries recurrent state")
            if self.draft_gamma < 1:
                raise ValueError(f"draft_gamma must be >= 1, "
                                 f"got {self.draft_gamma}")
            from .autotune.speculative import make_draft_params
            # Zero-copy top-k mask view; deliberately NOT BP2-validated
            # (it zeroes low planes) — AT2 is its contract instead.
            self.draft_params = make_draft_params(self.params,
                                                  self.speculate_planes)
            self._verify_j = self._jit(
                self.api.verify_step, "verify_step",
                **({"donate_argnums": (2,)} if self.donate_state else {}))
        if self.mesh is not None:
            self.params = self._place_params(self.params)
            if self.draft_params is not None:
                self.draft_params = self._place_params(self.draft_params)

    def _has_packed_weights(self) -> bool:
        """True if the tree holds leaves this backend can accelerate:
        ``bitplane`` executes only the plane-sliced layout (packed leaves
        fall back to dense); ``pallas``/``ref`` run either wire format."""
        from .deploy import BitplaneServingWeight, ServingWeight
        deployed = (ServingWeight, BitplaneServingWeight)
        want = (BitplaneServingWeight,) if self.backend == "bitplane" \
            else deployed
        return any(isinstance(leaf, want)
                   for leaf in jax.tree_util.tree_leaves(
                       self.params,
                       is_leaf=lambda x: isinstance(x, deployed)))

    def _jit(self, fn, name: str, **jit_kwargs):
        """jit ``fn`` with the engine's matmul + decode-attention backends
        active at trace time — both are part of the traced program, and
        each engine owns its jit cache, so traces never leak across
        backends.  ``name`` names the program: its XLA module is
        ``jit_<name>``, which is how a profile finds it."""
        backend, attn = self.backend, self.attn_backend

        @functools.wraps(fn)
        def run(params, *args, **kwargs):
            params = self._unpad_params(params)
            with matmul_backend(backend), paged_attn_backend(attn):
                return fn(params, *args, **kwargs)
        run.__name__ = run.__qualname__ = name
        return jax.jit(run, **jit_kwargs)

    # ---- sharding helpers -----------------------------------------------
    def _place(self, tree, spec_fn, *args):
        """device_put every leaf per its logical-rule PartitionSpec."""
        with use_mesh(self.mesh):
            specs = spec_fn(tree, *args)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            tree, specs)

    def _place_params(self, tree):
        """Padded param placement: fit specs with padding enabled, zero-pad
        every leaf to its padded shape at the placement boundary, and
        device_put evenly — so a non-dividing vocab/kv-head dim shards on
        the model axis instead of replicating.  True shapes are remembered
        and every jitted entry point slices back (``_unpad_params``)
        before the model ever sees the tree."""
        if not self.padded_sharding:
            with use_mesh(self.mesh):
                specs = param_pspecs(tree)
            return _mark_splits(self._place(tree, param_pspecs), specs,
                                self.mesh)
        with use_mesh(self.mesh):
            specs = param_pspecs(tree, pad=True)
        if self._pad_shapes is None:
            # flat list (tuples are pytrees, so not storable as leaves);
            # draft_params share every leaf shape with params
            self._pad_shapes = [tuple(x.shape)
                                for x in jax.tree_util.tree_leaves(tree)]
        placed = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(
                pad_leaf(x, s, self.mesh), NamedSharding(self.mesh, s)),
            tree, specs)
        return _mark_splits(placed, specs, self.mesh)

    def _unpad_params(self, params):
        """In-graph mask side of padded placement: slice each leaf back to
        its true shape (identity when nothing was padded)."""
        if self._pad_shapes is None:
            return params
        flat, treedef = jax.tree_util.tree_flatten(params)
        return jax.tree_util.tree_unflatten(
            treedef, [unpad_leaf(x, s)
                      for x, s in zip(flat, self._pad_shapes)])

    def _shard_inputs(self, batch):
        return batch if self.mesh is None else self._place(batch,
                                                           batch_pspecs)

    def _shard_state(self, state, n_slots: int):
        # pad=False: the decode state round-trips through the donated step
        # unchanged, so it cannot carry placement padding — an uneven
        # KV-head dim serves replicated here (padded mode covers weights)
        return state if self.mesh is None else self._place(
            state, functools.partial(cache_pspecs, pad=False), n_slots)

    # ---- core ops (scheduler building blocks) ---------------------------
    def prefill(self, batch: Dict[str, jnp.ndarray], extra_slots: int = 0,
                place_state: bool = True) -> tuple:
        """Whole-prompt forward; returns (last-token logits, decode state).

        ``place_state=False`` skips the mesh placement of the returned
        state (for callers that reshape it first, e.g. the scheduler's
        lazy broadcast init)."""
        batch = self._shard_inputs(batch)
        with use_mesh(self.mesh):
            logits, state = self._prefill_j(self.params, batch,
                                            extra_slots=extra_slots)
        if place_state:
            state = self._shard_state(state, batch["tokens"].shape[0])
        return logits, state

    def prefill_at(self, batch: Dict[str, jnp.ndarray], state: Any,
                   slot) -> tuple:
        """Insert a prompt into batch row ``slot`` of a live decode state."""
        batch = self._shard_inputs(batch)
        with use_mesh(self.mesh):
            return self._prefill_at_j(self.params, batch, state, slot)

    def prefill_chunk_at(self, batch: Dict[str, jnp.ndarray], state: Any,
                         slot, start) -> tuple:
        """Insert a prompt chunk at cache position ``start`` of row
        ``slot``; returns (full (1, W, V) chunk logits, updated state)."""
        batch = self._shard_inputs(batch)
        with use_mesh(self.mesh):
            return self._prefill_chunk_j(
                self.params, batch, state,
                jnp.asarray(slot, jnp.int32), jnp.asarray(start, jnp.int32))

    def init_decode_state(self, example: Dict[str, jnp.ndarray],
                          n_slots: int, max_len: int, page_size: int = 0,
                          n_pages: Optional[int] = None) -> Any:
        """Empty (zeroed) decode state for the continuous-batching
        scheduler — paged when ``page_size > 0`` — placed per
        ``cache_pspecs`` under an active mesh."""
        state = self.api.init_decode_state(self.params, example, n_slots,
                                           max_len, page_size=page_size,
                                           n_pages=n_pages)
        return self._shard_state(state, n_slots)

    def set_tables(self, state: Any, tables) -> Any:
        """Push host-side block tables ((n_slots, nb) int32) into every
        paged KV sub-dict of ``state`` (broadcast over each stack dim).
        Allocation is host-owned (scheduler free list); storage is
        device-owned — only this tiny map crosses per change."""
        tables = np.asarray(tables, np.int32)

        def walk(cache):
            if isinstance(cache, dict):
                if "table" in cache:
                    stack = cache["table"].shape[0]
                    t = jnp.asarray(
                        np.broadcast_to(tables[None], (stack, *tables.shape)))
                    if self.mesh is not None:
                        t = jax.device_put(t, NamedSharding(
                            self.mesh, PartitionSpec()))
                    return dict(cache, table=t)
                return {k: walk(v) for k, v in cache.items()}
            return cache
        return dict(state, cache=walk(state["cache"]))

    def decode(self, tokens: jnp.ndarray, state: Any, index) -> tuple:
        """One decode step; ``index`` is a () or per-slot (B,) fill level."""
        if self.mesh is not None:
            put = self._shard_inputs({"tokens": tokens, "index": index})
            tokens, index = put["tokens"], put["index"]
        with use_mesh(self.mesh):
            return self._decode_j(self.params, tokens, state, index)

    def draft_decode(self, tokens: jnp.ndarray, state: Any, index) -> tuple:
        """One decode step with the truncated-mask draft tree.

        Identical shapes/treedef to :meth:`decode` (the draft tree shares
        every payload tensor with the deployed one), so it reuses the same
        compiled decode executable — no second trace, no second weight
        copy.  Draft K/V writes are transient: the verify pass rewrites
        every drafted position at full precision before it can be read
        below the accepted fill level."""
        if self.draft_params is None:
            raise ValueError("engine built without speculate_planes")
        if self.mesh is not None:
            put = self._shard_inputs({"tokens": tokens, "index": index})
            tokens, index = put["tokens"], put["index"]
        with use_mesh(self.mesh):
            return self._decode_j(self.draft_params, tokens, state, index)

    def verify(self, tokens: jnp.ndarray, state: Any, index) -> tuple:
        """Batched W-token verify forward with the full deployed tree.

        ``tokens`` (B, W): each slot's last accepted token followed by its
        draft proposals; returns ((B, W, V) logits, state) with all W
        positions (re)written at full precision."""
        if self._verify_j is None:
            raise ValueError("engine built without speculate_planes")
        if self.mesh is not None:
            put = self._shard_inputs({"tokens": tokens, "index": index})
            tokens, index = put["tokens"], put["index"]
        with use_mesh(self.mesh):
            return self._verify_j(self.params, tokens, state, index)

    def compiled_text(self, step: str, *args) -> str:
        """Optimized HLO of the ``step`` program ('prefill_chunk' or
        'decode') for the arguments :meth:`prefill_chunk_at` /
        :meth:`decode` would pass after the params; a program that
        already ran comes from the jit cache, not a second compile."""
        fn = {"prefill_chunk": self._prefill_chunk_j,
              "decode": self._decode_j}[step]
        with use_mesh(self.mesh):
            return fn.lower(self.params, *args).compile().as_text()

    # ---- preemption / prefix-cache state plumbing ------------------------
    def park_slot(self, state: Any, slot: int, pages) -> Dict[str, Any]:
        """Snapshot everything batch row ``slot`` holds to host memory:
        its pool pages (in ``pages``/block order) from every paged KV
        sub-dict, its row of every non-paged per-slot cache leaf
        (recurrent state), and its encoder buffer row if the family has
        one.  Pure ``np.asarray`` of the stored representation —
        quantized-at-rest payloads and scales cross as raw bytes, no
        dequantization — so :meth:`restore_slot` round-trips
        bit-identically (the PX1/PX3 contracts and the preemption leg of
        the stress suite rely on this)."""
        ids = np.asarray(pages, np.int32)
        rec: Dict[str, Any] = {"pages": {}, "rows": {}, "enc_out": None,
                               "n_pages": len(ids)}

        def walk(cache, path):
            if isinstance(cache, dict):
                if "table" in cache:
                    pool = cache["pages"]
                    for name, leaf in pool.items():
                        rec["pages"][f"{path}.{name}"] = np.asarray(
                            take_pages(leaf, pool.fmt(name), ids))
                    return
                for k, v in cache.items():
                    walk(v, f"{path}.{k}")
                return
            rec["rows"][path] = np.asarray(cache[:, slot])

        walk(state["cache"], "cache")
        if "enc_out" in state:
            rec["enc_out"] = np.asarray(state["enc_out"][slot])
        return rec

    def restore_slot(self, state: Any, slot: int, pages,
                     record: Dict[str, Any]) -> Any:
        """Write a :meth:`park_slot` snapshot back into batch row ``slot``,
        landing the parked pool pages on the freshly allocated ``pages``
        (same count, any ids — the caller rewrites its block-table row to
        match).  The inverse of parking, bit for bit."""
        if len(pages) != record["n_pages"]:
            raise ValueError(f"snapshot holds {record['n_pages']} pages, "
                             f"restore got {len(pages)} page ids")
        ids = jnp.asarray(np.asarray(pages, np.int32))

        def walk(cache, path):
            if isinstance(cache, dict):
                if "table" in cache:
                    pool = cache["pages"]
                    new = {name: (put_pages(leaf, pool.fmt(name), ids,
                                            record["pages"]
                                            [f"{path}.{name}"])
                                  if record["n_pages"] else leaf)
                           for name, leaf in pool.items()}
                    return dict(cache, pages=pool.replace(**new))
                return {k: walk(v, f"{path}.{k}") for k, v in cache.items()}
            return cache.at[:, slot].set(jnp.asarray(record["rows"][path]))

        out = dict(state, cache=walk(state["cache"], "cache"))
        if record.get("enc_out") is not None and "enc_out" in state:
            out["enc_out"] = state["enc_out"].at[slot].set(
                jnp.asarray(record["enc_out"]))
        return out

    def copy_pool_page(self, state: Any, src: int, dst: int) -> Any:
        """Copy pool page ``src`` onto ``dst`` in every paged KV sub-dict
        (payloads and scales alike) — the scheduler's copy-on-write
        primitive for diverging from a shared prefix page."""
        def walk(cache):
            if isinstance(cache, dict):
                if "table" in cache:
                    pool = cache["pages"]
                    return dict(cache, pages=pool.replace(**{
                        name: put_pages(leaf, pool.fmt(name), [dst],
                                        take_pages(leaf, pool.fmt(name),
                                                   [src]))
                        for name, leaf in pool.items()}))
                return {k: walk(v) for k, v in cache.items()}
            return cache
        return dict(state, cache=walk(state["cache"]))

    def prompt_width(self, batch: Dict[str, jnp.ndarray]) -> int:
        """Cache positions a prompt occupies (tokens + VLM vision prefix)."""
        p = batch["tokens"].shape[1]
        if self.api.cfg.family == "vlm":
            p += self.api.cfg.vision_tokens
        return p

    # ---- one-shot API (static batch) ------------------------------------
    def generate(self, batch: Dict[str, jnp.ndarray], max_new: int = 16,
                 greedy: bool = True, key=None, temperature: float = 1.0,
                 top_k: int = 0) -> jnp.ndarray:
        """batch: prompt inputs per the model family. Returns (B, max_new).

        ``greedy`` (or no ``key``) takes per-step argmax; otherwise tokens
        are drawn at ``temperature`` over the ``top_k`` best logits.
        With ``speculate_planes`` set and greedy sampling, decoding runs
        the draft/verify protocol — token-identical output, fewer
        full-precision passes."""
        if self.speculate_planes and (greedy or key is None):
            return self._generate_speculative(batch, max_new)
        logits, state = self.prefill(batch, extra_slots=_roundup64(max_new))
        prompt_len = self.prompt_width(batch)
        sp = SamplingParams(temperature=temperature, top_k=top_k)

        def pick(logits, key):
            if greedy or key is None:
                return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            return sample_token(logits, sp, key)[:, None]

        def split(key):
            return jax.random.split(key) if key is not None else (None, None)

        outs: List[jnp.ndarray] = []
        key, sub = split(key)
        tok = pick(logits, sub)       # first token sampled like the rest
        outs.append(tok[:, 0])
        index = jnp.asarray(prompt_len, jnp.int32)
        for _ in range(max_new - 1):  # max_new-1 steps, like the scheduler
            logits, state = self.decode(tok, state, index)
            key, sub = split(key)
            tok = pick(logits, sub)
            outs.append(tok[:, 0])
            index = index + 1
        return jnp.stack(outs, axis=1)

    def _generate_speculative(self, batch: Dict[str, jnp.ndarray],
                              max_new: int) -> jnp.ndarray:
        """Greedy static-batch decoding via draft/verify rounds.

        Rows accept different draft counts per round, so fill levels are
        per-row (B,) vectors; a row that reaches ``max_new`` simply stops
        taking tokens (its index freezes, later writes overwrite masked
        headroom).  The extra ``draft_gamma + 1`` headroom keeps every
        write inside the cache."""
        from .autotune.speculative import greedy_verify
        gamma = self.draft_gamma
        logits, state = self.prefill(
            batch, extra_slots=_roundup64(max_new + gamma + 1))
        prompt_len = self.prompt_width(batch)
        b = batch["tokens"].shape[0]
        outs: List[List[int]] = [[int(t)] for t in
                                 np.asarray(jnp.argmax(logits, -1))]
        counts = np.ones((b,), dtype=np.int64)
        index = np.full((b,), prompt_len, dtype=np.int64)
        tok = jnp.asarray([[o[-1]] for o in outs], jnp.int32)
        while int(counts.min()) < max_new:
            g = min(gamma, max_new - int(counts.min()) - 1)
            if g < 1:                      # last token: plain decode step
                logits, state = self.decode(
                    tok, state, jnp.asarray(index, jnp.int32))
                nxt = np.asarray(jnp.argmax(logits, -1))
                accepted = [np.asarray([t]) for t in nxt]
            else:
                cur, drafts = tok, []
                for j in range(g):
                    lg, state = self.draft_decode(
                        cur, state, jnp.asarray(index + j, jnp.int32))
                    cur = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
                    drafts.append(cur)
                vtoks = jnp.concatenate([tok] + drafts, axis=1)  # (B, g+1)
                vlogits, state = self.verify(
                    vtoks, state, jnp.asarray(index, jnp.int32))
                accepted, _ = greedy_verify(np.asarray(vtoks[:, 1:]),
                                            np.asarray(vlogits))
            for r in range(b):
                take = min(len(accepted[r]), max_new - int(counts[r]))
                outs[r].extend(int(t) for t in accepted[r][:take])
                counts[r] += take
                index[r] += take
            tok = jnp.asarray([[o[-1]] for o in outs], jnp.int32)
        return jnp.asarray([o[:max_new] for o in outs], jnp.int32)

    # ---- request-level API ----------------------------------------------
    def make_scheduler(self, requests, n_slots: int = 8,
                       max_len: Optional[int] = None,
                       page_size: Optional[int] = None,
                       n_pages: Optional[int] = None,
                       prefill_chunk: Optional[int] = None,
                       overcommit: Optional[float] = None,
                       prefix_cache: Optional[bool] = None):
        """Continuous-batching scheduler sized for ``requests``.

        ``max_len`` (total per-slot cache width) defaults to the widest
        request's prompt plus 64-rounded generation headroom — the same
        rounding ``generate`` uses, so both paths compile identical decode
        shapes.  ``page_size`` / ``n_pages`` / ``prefill_chunk`` /
        ``overcommit`` / ``prefix_cache`` default to the engine's settings
        (0 = contiguous slots / monolithic prefill; 1.0 = reservation-safe
        admission; False = no prompt-page sharing).  The scheduler is the
        stats surface too (``cache_report()``)."""
        from .scheduler import Scheduler
        if max_len is None:
            max_len = max(self.prompt_width(r.inputs) +
                          _roundup64(r.sampling.max_new_tokens)
                          for r in requests)
        return Scheduler(
            self, n_slots=n_slots, max_len=max_len,
            page_size=self.page_size if page_size is None else page_size,
            n_pages=self.n_pages if n_pages is None else n_pages,
            prefill_chunk=(self.prefill_chunk if prefill_chunk is None
                           else prefill_chunk),
            overcommit=self.overcommit if overcommit is None else overcommit,
            prefix_cache=(self.prefix_cache if prefix_cache is None
                          else prefix_cache))

    def serve(self, requests, n_slots: int = 8,
              max_len: Optional[int] = None,
              page_size: Optional[int] = None,
              n_pages: Optional[int] = None,
              prefill_chunk: Optional[int] = None,
              overcommit: Optional[float] = None,
              prefix_cache: Optional[bool] = None):
        """Run ``requests`` through a continuous-batching scheduler (see
        :meth:`make_scheduler`); results come back in submission order."""
        return self.make_scheduler(
            requests, n_slots=n_slots, max_len=max_len,
            page_size=page_size, n_pages=n_pages,
            prefill_chunk=prefill_chunk, overcommit=overcommit,
            prefix_cache=prefix_cache).run(requests)
