"""Uniform Model API over all families (decoder-only, enc-dec).

Gives the launcher / dry-run / tests one surface:
  init, loss, prefill, decode_step, input specs per shape-cell.
Input specs are ShapeDtypeStructs (no allocation) — the dry-run lowers
against them directly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeCell
from . import encdec, transformer
from .attention import is_kv_node


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _write_rows(big, small, slot, batch_dim: int):
    """Write ``small`` into ``big`` at offset ``slot`` along ``batch_dim``
    (zero offsets elsewhere — time axes write from position 0)."""
    starts = [jnp.zeros((), jnp.int32)] * big.ndim
    starts[batch_dim] = slot
    return jax.lax.dynamic_update_slice(big, small.astype(big.dtype),
                                        tuple(starts))


def cache_is_paged(cache) -> bool:
    """True if any KV sub-dict of a decode cache carries a block table."""
    if isinstance(cache, dict):
        return "table" in cache or any(cache_is_paged(v)
                                       for v in cache.values())
    return False


def _row_cache_view(cache, slot, fresh=None):
    """Single-slot view of a decode cache: paged sub-dicts keep the whole
    page pool but narrow the block table to ``slot``'s row; contiguous /
    recurrent leaves (stack, B, ...) are row-sliced on the batch dim.

    ``fresh`` (traced bool) zeroes *recurrent* rows — when the view starts
    a brand-new request (first prompt chunk), the slot's previous
    occupant's rwkv/mamba state must read as the zero init a standalone
    prefill would use.  KV rows have no such hazard (stale positions stay
    masked by the fill level) and pass through untouched."""
    if isinstance(cache, dict):
        if "table" in cache:
            return dict(cache, table=jax.lax.dynamic_slice_in_dim(
                cache["table"], slot, 1, axis=1))
        if is_kv_node(cache):
            return {k: jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=1)
                    for k, v in cache.items()}
        return {k: _row_cache_view(v, slot, fresh) for k, v in cache.items()}
    row = jax.lax.dynamic_slice_in_dim(cache, slot, 1, axis=1)
    if fresh is not None:
        row = jnp.where(fresh, jnp.zeros_like(row), row)
    return row


def _row_cache_unview(big, row, slot):
    """Merge an updated single-slot view back: paged pools were scattered
    into in place (all slots share them) and replace wholesale, with the
    full block table restored; row-sliced leaves write back at ``slot``."""
    if isinstance(big, dict):
        if "table" in big:
            return dict(row, table=big["table"])
        return {k: _row_cache_unview(big[k], row[k], slot) for k in big}
    return _write_rows(big, row, slot, batch_dim=1)


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig

    # ---- parameters ---------------------------------------------------
    def init(self, key) -> Any:
        if self.cfg.is_encdec:
            return encdec.init_encdec(key, self.cfg)
        return transformer.init_lm(key, self.cfg)

    def abstract_params(self) -> Any:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ---- losses / steps ------------------------------------------------
    def loss(self, params, batch) -> tuple:
        if self.cfg.is_encdec:
            return encdec.encdec_loss(params, self.cfg, batch)
        return transformer.loss_fn(params, self.cfg, batch)

    def prefill(self, params, batch, extra_slots: int = 0) -> tuple:
        """Full-sequence forward that also fills the decode cache.

        ``extra_slots`` reserves cache headroom for subsequent decode steps
        (a decode write past the cache end would clamp and corrupt)."""
        cfg = self.cfg
        if cfg.is_encdec:
            b, s = batch["tokens"].shape
            cache = encdec.encdec_init_cache(cfg, b, s + extra_slots)
            logits, cache, enc_out = encdec.encdec_forward(
                params, cfg, batch["frames"], batch["tokens"], cache,
                jnp.asarray(0, jnp.int32))
            return logits[:, -1], {"cache": cache, "enc_out": enc_out}
        b, s = batch["tokens"].shape
        extra = (cfg.vision_tokens if cfg.family == "vlm" else 0) + extra_slots
        cache = transformer.init_cache(cfg, b, s + extra)
        logits, _, cache = transformer.forward(
            params, cfg, batch["tokens"],
            vision_embeds=batch.get("vision_embeds"),
            positions=batch.get("positions"), cache=cache,
            index=jnp.asarray(0, jnp.int32))
        return logits[:, -1], {"cache": cache}

    def decode_step(self, params, tokens, state, index) -> tuple:
        """One decode step.  ``index`` is either the scalar shared fill
        level (train / dry-run paths) or a per-slot (B,) vector of fill
        levels (request-level serving: each slot advances independently)."""
        cfg = self.cfg
        if cfg.is_encdec:
            logits, cache = encdec.encdec_decode_step(
                params, cfg, tokens, state["cache"], index, state["enc_out"])
            return logits, {**state, "cache": cache}
        logits, cache = transformer.decode_step(params, cfg, tokens,
                                                state["cache"], index)
        return logits, {**state, "cache": cache}

    def verify_step(self, params, tokens, state, index) -> tuple:
        """Batched multi-token decode forward (speculative verification).

        ``tokens`` (B, W) are each slot's last accepted token followed by
        its draft proposals, written at fill levels ``index .. index+W-1``
        — the same cache-write machinery chunked prefill uses, so paged
        and contiguous layouts both work.  Returns (B, W, V) logits."""
        if self.cfg.is_encdec:
            raise NotImplementedError(
                "speculative verify is decoder-only (KV rollback is "
                "positional; enc-dec cross attention is out of scope)")
        logits, cache = transformer.verify_step(params, self.cfg, tokens,
                                                state["cache"], index)
        return logits, {**state, "cache": cache}

    def init_decode_state(self, params, batch, n_slots: int, max_len: int,
                          page_size: int = 0,
                          n_pages: Optional[int] = None) -> Any:
        """Empty decode state for ``n_slots`` continuous-batching slots.

        The state *tree* (cache layout per family, enc-dec encoder buffer)
        comes from ``jax.eval_shape`` over this model's own prefill on the
        example ``batch`` — no forward pass runs.  ``page_size > 0`` builds
        the paged layout (global pool of ``n_pages`` pages, default
        ``1 + n_slots * nb`` so worst-case demand plus the trash page
        always fits; allocators may size it tighter) instead of contiguous
        ``max_len``-wide slots.  Prompts are inserted per-request via
        :meth:`prefill_at` / :meth:`prefill_chunk_at`."""
        sub = jax.eval_shape(
            lambda p, b: self.prefill(p, b, extra_slots=0)[1], params, batch)
        if page_size > 0:
            nb = -(-max_len // page_size)
            n_pages = n_pages or (1 + n_slots * nb)
            cache = transformer.paginate_cache_tree(
                sub["cache"], n_slots, n_pages, page_size, nb)
        else:
            cache = transformer.rebatch_cache_tree(sub["cache"], n_slots,
                                                   max_len)
        state = {"cache": cache}
        if "enc_out" in sub:
            eo = sub["enc_out"]
            state["enc_out"] = jnp.zeros((n_slots, *eo.shape[1:]), eo.dtype)
        return state

    def prefill_chunk_at(self, params, batch, state, slot, start) -> tuple:
        """Insert a prompt *chunk* into batch row ``slot`` of a live state.

        ``batch`` carries the chunk's tokens (1, W) — plus ``frames`` /
        ``vision_embeds`` on the first chunk, which must start at
        ``start == 0`` — and ``start`` is the cache position of the chunk's
        first token (VLM text chunks count from ``vision_tokens``).  The
        chunk runs through the family forward against a single-slot view of
        the state, attending over the slot's already-cached prefix, so
        chunk-by-chunk insertion reproduces a monolithic prefill
        bit-for-bit (stale positions past the written prefix stay masked by
        the fill level).  Returns the full (1, W, V) chunk logits — callers
        take the last *real* column when the final chunk is padded — and
        the updated state."""
        cfg = self.cfg
        slot = jnp.asarray(slot, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        row_cache = _row_cache_view(state["cache"], slot, fresh=(start == 0))
        new_state = dict(state)
        if cfg.is_encdec:
            if "frames" in batch:
                logits, row_cache, enc_out = encdec.encdec_forward(
                    params, cfg, batch["frames"], batch["tokens"],
                    row_cache, start)
                new_state["enc_out"] = _write_rows(
                    state["enc_out"], enc_out, slot, batch_dim=0)
            else:
                enc_row = jax.lax.dynamic_slice_in_dim(
                    state["enc_out"], slot, 1, axis=0)
                logits, row_cache = encdec.encdec_decode_tokens(
                    params, cfg, batch["tokens"], row_cache, start, enc_row)
        else:
            positions = None
            if batch.get("vision_embeds") is None:
                pos1 = transformer.decode_positions(
                    start, 1, batch["tokens"].shape[1])
                positions = jnp.stack([pos1] * 3, axis=-1) if cfg.mrope \
                    else pos1
            logits, _, row_cache = transformer.forward(
                params, cfg, batch["tokens"],
                vision_embeds=batch.get("vision_embeds"),
                positions=positions, cache=row_cache, index=start)
        new_state["cache"] = _row_cache_unview(state["cache"], row_cache,
                                               slot)
        return logits, new_state

    def prefill_at(self, params, batch, state, slot) -> tuple:
        """Prefill ``batch`` (nb prompt rows) INTO an existing decode state.

        With a *paged* state this is single-row whole-prompt insertion —
        one :meth:`prefill_chunk_at` call at ``start=0``, writing through
        the slot's block table.  Contiguous states run a standalone prefill
        for the sub-batch and write the resulting cache /
        recurrent-state / encoder rows into batch rows [slot, slot+nb) of
        ``state`` — the continuous-batching insertion primitive (a prompt
        joins a live decode batch without touching the other slots).  Every
        cache leaf is stacked (L, B, ...) so the batch dim is 1;
        ``enc_out`` carries batch at dim 0.  The target cache's time axis
        must be at least the sub-batch's prefill width; stale positions
        past the prompt stay masked by the per-slot fill level.  Returns
        (last-token logits of the inserted rows, updated state)."""
        if cache_is_paged(state["cache"]):
            logits, new_state = self.prefill_chunk_at(params, batch, state,
                                                      slot, 0)
            return logits[:, -1], new_state
        logits, sub = self.prefill(params, batch, extra_slots=0)
        slot = jnp.asarray(slot, jnp.int32)
        new_state = dict(state)
        new_state["cache"] = jax.tree_util.tree_map(
            lambda big, small: _write_rows(big, small, slot, batch_dim=1),
            state["cache"], sub["cache"])
        if "enc_out" in state:
            if sub["enc_out"].shape[1] != state["enc_out"].shape[1]:
                raise ValueError(
                    "enc-dec slot insertion needs the same encoder length "
                    f"as the live batch: {sub['enc_out'].shape[1]} != "
                    f"{state['enc_out'].shape[1]} (cross-attention has no "
                    "per-row length masking)")
            new_state["enc_out"] = _write_rows(
                state["enc_out"], sub["enc_out"], slot, batch_dim=0)
        return logits, new_state

    # ---- abstract input specs per shape cell ----------------------------
    def train_batch_spec(self, cell: ShapeCell) -> Dict[str, Any]:
        cfg = self.cfg
        b, s = cell.global_batch, cell.seq_len
        if cfg.is_encdec:
            return {
                "frames": _sds((b, s, cfg.d_model), jnp.bfloat16),
                "tokens": _sds((b, s), jnp.int32),
                "labels": _sds((b, s), jnp.int32),
            }
        if cfg.family == "vlm":
            tv = cfg.vision_tokens
            st = s - tv
            return {
                "tokens": _sds((b, st), jnp.int32),
                "labels": _sds((b, st), jnp.int32),
                "vision_embeds": _sds((b, tv, cfg.d_model), jnp.bfloat16),
                "positions": _sds((b, s, 3), jnp.int32),
            }
        return {"tokens": _sds((b, s), jnp.int32),
                "labels": _sds((b, s), jnp.int32)}

    def decode_state_spec(self, cell: ShapeCell) -> Dict[str, Any]:
        cfg = self.cfg
        b, s = cell.global_batch, cell.seq_len
        if cfg.is_encdec:
            cache = jax.eval_shape(
                lambda: encdec.encdec_init_cache(cfg, b, s))
            return {"cache": cache,
                    "enc_out": _sds((b, s, cfg.d_model),
                                    jnp.dtype(cfg.dtype))}
        cache = jax.eval_shape(lambda: transformer.init_cache(cfg, b, s))
        return {"cache": cache}

    def decode_token_spec(self, cell: ShapeCell):
        return _sds((cell.global_batch, 1), jnp.int32)


def build(cfg: ModelConfig) -> ModelAPI:
    return ModelAPI(cfg)
