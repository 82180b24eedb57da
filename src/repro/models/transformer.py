"""Decoder-only LM assembly: dense / MoE / SSM / hybrid families.

One scan-over-layers drives training, prefill and decode; the layer body
dispatches on the config family.  Params hold stacked (L, ...) leaves.
``prepare_params`` runs once per step (outside the scan) to cast plain
floats and compose bit-plane tensors; scan-sliceable quantized storage
(FakeQuantTensor, packed ServingWeight) rides the scan untouched and is
consumed per layer by ``qmatmul`` — on the packed serving path the layer
code never sees a dequantized full-precision weight.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..dist.sharding import constraint, shard_params_tree
from ..kernels.kv_pages import make_pool
from .attention import attn_forward, init_attn, is_kv_node
from .common import (act_quant, embed_init, make_weight,
                     prepare_params, qmatmul, rms_norm, softcap)
from .ffn import init_mlp, mlp_forward
from .mla import init_mla, make_latent_cache, mla_forward
from .moe import moe_forward
from .rwkv import init_rwkv6, rwkv6_forward, rwkv6_init_state
from .ssm import init_mamba2, mamba2_forward, mamba2_init_state


# ``jax.named_scope`` names of the model step's parts.  Each op's HLO
# ``op_name`` metadata carries its scope path; the innermost of these
# names is the op's scope (``dist.hlo_analysis.hlo_op_scopes``).  Trace-time
# metadata only: they change no fusion and cost nothing at run time.
SCOPES = (
    "embed",       # token (and vision) embedding
    "kv_slice",    # a layer sliced out of a stacked contiguous cache or
                   # recurrent state (a paged pool is read in place)
    "attn_in",     # pre-attention norm, activation quant, q/k/v, rope
    "kv_write",    # quantize the new K/V and write it into the cache
    "attn_core",   # read the cache and attend (paged kernel or gather)
    "latent_attn",  # MLA decode: latent scores and sum over the cache
    "attn_out",    # output projection, post-norm, residual
    "kv_update",   # a layer's recurrent state written back into the stack
    "mlp",         # pre-MLP norm, activation quant, MLP or experts
    "moe_route",   # router logits, softmax, top-k gates
    "moe_experts",  # the held routed experts' part of the output
    "moe_shared",  # the shared experts
    "head",        # final norm, output head, softcap
)


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def scan_or_loop(body, carry, xs, use_scan: bool, length: int):
    """lax.scan or an unrolled python loop (cfg.scan_layers=False).

    The unrolled form exists for the dry-run's cost *calibration* lowering:
    XLA cost_analysis counts a scan body once, so exact FLOP/byte totals
    are obtained from small unrolled configs and scaled (launch/dryrun.py).
    """
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(length):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and any(l is not None for l in jax.tree_util.tree_leaves(ys[0])) \
            or (ys and ys[0] is not None):
        ys_stacked = jax.tree_util.tree_map(
            lambda *zs: jnp.stack(zs), *ys)
    else:
        ys_stacked = None
    return carry, ys_stacked


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, stack: int,
                dense: bool = False) -> Dict:
    """One stacked parameter set for ``stack`` homogeneous layers;
    ``dense`` gives a MoE model's leading dense layers (an MLP of width
    ``cfg.dense_d_ff``)."""
    qc = cfg.quant
    dt = jnp.float32
    ks = jax.random.split(key, 8)
    d, dh = cfg.d_model, cfg.head_dim
    p: Dict[str, Any] = {}
    if cfg.family in ("dense", "moe", "vlm"):
        p["ln_attn"] = jnp.zeros((stack, d), dt)
        p["ln_mlp"] = jnp.zeros((stack, d), dt)
        if cfg.use_post_norms:
            p["ln_attn_post"] = jnp.zeros((stack, d), dt)
            p["ln_mlp_post"] = jnp.zeros((stack, d), dt)
        if cfg.kv_lora_rank:
            p["attn"] = init_mla(ks[0], cfg, stack, dtype=dt)
        else:
            p["attn"] = {
                "wq": make_weight(ks[0], (stack, d, cfg.n_heads * dh), qc, dtype=dt),
                "wk": make_weight(ks[1], (stack, d, cfg.n_kv_heads * dh), qc, dtype=dt),
                "wv": make_weight(ks[2], (stack, d, cfg.n_kv_heads * dh), qc, dtype=dt),
                "wo": make_weight(ks[3], (stack, cfg.n_heads * dh, d), qc, dtype=dt),
            }
        if cfg.qkv_bias:
            p["attn"]["bq"] = jnp.zeros((stack, cfg.n_heads * dh), dt)
            p["attn"]["bk"] = jnp.zeros((stack, cfg.n_kv_heads * dh), dt)
            p["attn"]["bv"] = jnp.zeros((stack, cfg.n_kv_heads * dh), dt)
        if (cfg.family == "moe" or cfg.n_experts) and not dense:
            e = cfg.held_experts
            p["moe"] = {
                "router_w": jax.random.normal(
                    ks[4], (stack, d, cfg.n_experts), jnp.float32) * 0.02,
                "expert_gate": make_weight(
                    ks[5], (stack, e, d, cfg.d_ff), qc, dtype=dt),
                "expert_up": make_weight(
                    jax.random.fold_in(ks[5], 1),
                    (stack, e, d, cfg.d_ff), qc, dtype=dt),
                "expert_down": make_weight(
                    jax.random.fold_in(ks[5], 2),
                    (stack, e, cfg.d_ff, d), qc, dtype=dt),
            }
            if cfg.n_shared_experts:
                f = cfg.n_shared_experts * cfg.d_ff
                p["moe"]["shared_gate"] = make_weight(
                    ks[6], (stack, d, f), qc, dtype=dt)
                p["moe"]["shared_up"] = make_weight(
                    jax.random.fold_in(ks[6], 1), (stack, d, f), qc, dtype=dt)
                p["moe"]["shared_down"] = make_weight(
                    jax.random.fold_in(ks[6], 2), (stack, f, d), qc, dtype=dt)
        else:
            ff = cfg.dense_d_ff if dense else cfg.d_ff
            if cfg.mlp_kind == "swiglu":
                p["mlp"] = {
                    "w_gate": make_weight(ks[4], (stack, d, ff), qc, dtype=dt),
                    "w_up": make_weight(ks[5], (stack, d, ff), qc, dtype=dt),
                    "w_down": make_weight(ks[6], (stack, ff, d), qc, dtype=dt),
                }
            else:
                p["mlp"] = {
                    "w_in": make_weight(ks[4], (stack, d, ff), qc, dtype=dt),
                    "w_out": make_weight(ks[5], (stack, ff, d), qc, dtype=dt),
                }
        if qc.enabled and qc.act_bits < 32:
            p["beta_attn"] = jnp.full((stack,), qc.pact_init, dt)
            p["beta_mlp"] = jnp.full((stack,), qc.pact_init, dt)
    elif cfg.family == "ssm":        # rwkv6 (token-mix + channel-mix per layer)
        p = init_rwkv6(ks[0], d, cfg.n_heads, qc, stack=stack, d_ff=cfg.d_ff)
    elif cfg.family == "hybrid":     # zamba2 mamba trunk
        p = init_mamba2(ks[0], d, cfg.ssm_state, qc, expand=cfg.ssm_expand,
                        headdim=cfg.ssm_headdim, stack=stack)
        p["ln"] = jnp.zeros((stack, d), dt)
    else:
        raise ValueError(cfg.family)
    return p


def init_lm(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 6)
    d, dt = cfg.d_model, jnp.float32
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab, d, dt),
        "final_norm": jnp.zeros((d,), dt),
        "layers": _init_block(ks[1], cfg,
                              cfg.n_layers - cfg.first_dense_layers),
    }
    if cfg.first_dense_layers:
        params["dense_layers"] = _init_block(ks[3], cfg,
                                             cfg.first_dense_layers,
                                             dense=True)
    if not cfg.tie_embeddings:
        params["lm_head"] = make_weight(
            ks[2], (d, cfg.vocab), cfg.quant, dtype=dt,
            quantize=cfg.quant.quantize_embeddings)
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        # zamba2: ONE shared attention block, invoked every k layers on
        # concat(hidden, original_embedding) (2*d input).
        params["shared_attn"] = init_attn(
            ks[3], d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.quant,
            make_weight, d_model_in=2 * d, dtype=dt)
        params["shared_ln"] = jnp.zeros((2 * d,), dt)
        params["shared_mlp"] = init_mlp(ks[5], d, cfg.d_ff, cfg.quant,
                                        kind=cfg.mlp_kind, dtype=dt)
        params["shared_ln2"] = jnp.zeros((d,), dt)
    if cfg.family == "vlm":
        params["vision_proj"] = make_weight(ks[4], (d, d), cfg.quant, dtype=dt)
    return params


def _index_cache(cache, i):
    """Slice layer i's recurrent state out of stacked (L, ...) leaves."""
    with jax.named_scope("kv_slice"):
        return jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False),
            cache)


def _update_cache(cache, new_layer, i):
    with jax.named_scope("kv_update"):
        return jax.tree_util.tree_map(
            lambda c, nl: jax.lax.dynamic_update_index_in_dim(c, nl, i, 0),
            cache, new_layer)


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def _attn_block(lp, h, positions, cfg: ModelConfig, is_local,
                cache=None, index=None, layer=None):
    """Attention sub-block over layer ``layer`` of the stacked ``cache``
    (None without a cache); returns (h, the stacked cache)."""
    qc = cfg.quant
    with jax.named_scope("attn_in"):
        x = rms_norm(h, lp["ln_attn"])
        x = act_quant(x, lp.get("beta_attn"), qc)
        window = jnp.where(is_local, cfg.sliding_window, 0) if \
            cfg.alt_local_global else (cfg.sliding_window or 0)
    if cfg.kv_lora_rank:
        out, new_cache = mla_forward(lp["attn"], x, positions, cfg,
                                     cache=cache, cache_index=index,
                                     layer=layer)
        with jax.named_scope("attn_out"):
            return h + out, new_cache
    # window as traced value: attention uses dynamic comparison, so pass
    # the array directly (0 disables).
    out, new_cache = attn_forward(
        lp["attn"], x, positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
        d_head=cfg.head_dim, rope_theta=cfg.rope_theta, causal=True,
        window=window, attn_softcap=cfg.attn_softcap, mrope=cfg.mrope,
        cache=cache, cache_index=index, layer=layer)
    with jax.named_scope("attn_out"):
        if cfg.use_post_norms:
            out = rms_norm(out, lp["ln_attn_post"])
        return h + out, new_cache


@jax.named_scope("mlp")
def _mlp_block(lp, h, cfg: ModelConfig):
    qc = cfg.quant
    x = rms_norm(h, lp["ln_mlp"])
    x = act_quant(x, lp.get("beta_mlp"), qc)
    aux = jnp.asarray(0.0, jnp.float32)
    if "moe" in lp:
        out, aux = moe_forward(lp["moe"], x, cfg.top_k,
                               offset=cfg.expert_offset,
                               renorm=cfg.norm_topk_prob)
    else:
        out = mlp_forward(lp["mlp"], x, cfg.mlp_kind)
    if cfg.use_post_norms:
        out = rms_norm(out, lp["ln_mlp_post"])
    return h + out, aux


# ---------------------------------------------------------------------------
# full model walk
# ---------------------------------------------------------------------------

def _walk_dense(mp, cfg, h, positions, cache, index):
    """Scan over homogeneous attention+FFN layers: a MoE model's leading
    dense layers (``dense_layers``) first, then ``layers``; the stacked
    cache holds every layer, in that order."""
    def body(carry, xs):
        # the stacked cache rides in the carry and each layer reads and
        # writes its own pages of it in place (layer ``li``): no layer is
        # sliced out or written back, and scan carries alias buffers, so
        # the KV cache is never duplicated (xs/ys threading would
        # double-buffer multi-GiB caches).
        h, aux, cache_c, li = carry
        lp, loc = xs
        h, cache_c = _attn_block(lp, h, positions, cfg, loc,
                                 cache=cache_c, index=index, layer=li)
        h, aux_l = _mlp_block(lp, h, cfg)
        h = constraint(h, "batch", None, None)
        return (h, aux + aux_l, cache_c, li + 1), None

    if cfg.remat:
        body = jax.checkpoint(body)
    carry = (h, jnp.asarray(0.0, jnp.float32), cache,
             jnp.asarray(0, jnp.int32))
    first = 0
    for name in ("dense_layers", "layers"):
        if name not in mp:
            continue
        n = jax.tree_util.tree_leaves(mp[name])[0].shape[0]
        layer = first + jnp.arange(n)
        is_local = (layer % 2 == 0) if cfg.alt_local_global else \
            jnp.zeros((n,), bool)
        carry, _ = scan_or_loop(body, carry, (mp[name], is_local),
                                cfg.scan_layers, n)
        first += n
    h, aux, new_cache, _ = carry
    return h, aux, new_cache


def _walk_ssm(mp, cfg, h, cache, index):
    def body(carry, lp):
        h, aux, cache_c, li = carry
        layer_state = _index_cache(cache_c, li) if cache_c is not None \
            else None
        h, new_state = rwkv6_forward(lp, h, n_heads=cfg.n_heads,
                                     chunk=cfg.rwkv_chunk, state=layer_state)
        if cache_c is not None:
            cache_c = _update_cache(cache_c, new_state, li)
        h = constraint(h, "batch", None, None)
        return (h, aux, cache_c, li + 1), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (h, aux, new_cache, _), _ = scan_or_loop(
        body, (h, jnp.asarray(0.0, jnp.float32), cache,
               jnp.asarray(0, jnp.int32)), mp["layers"],
        cfg.scan_layers, cfg.n_layers)
    return h, aux, new_cache


def _walk_hybrid(mp, cfg, h, emb0, positions, cache, index):
    """zamba2: mamba trunk + ONE shared attention block every k layers.

    All decode states ride in the scan carries (in-place updates)."""
    period = cfg.hybrid_attn_every
    n = cfg.n_layers
    n_super = n // period if period else 0
    n_main = n_super * period
    shared = mp.get("shared_attn")

    def mamba_body(carry, lp):
        h, aux, mstates, li = carry
        layer_state = _index_cache(mstates, li) if mstates is not None \
            else None
        x = rms_norm(h, lp["ln"])
        out, new_state = mamba2_forward(
            {k: v for k, v in lp.items() if k != "ln"}, x,
            n_state=cfg.ssm_state, headdim=cfg.ssm_headdim,
            chunk=cfg.ssm_chunk, state=layer_state)
        if mstates is not None:
            mstates = _update_cache(mstates, new_state, li)
        h = constraint(h + out, "batch", None, None)
        return (h, aux, mstates, li + 1), None

    if cfg.remat:
        mamba_body = jax.checkpoint(mamba_body)

    cache_mamba = cache["mamba"] if cache is not None else None
    attn_caches = cache["attn"] if cache is not None else None
    layers_main = jax.tree_util.tree_map(
        lambda a: a[:n_main].reshape(n_super, period, *a.shape[1:]),
        mp["layers"])
    layers_tail = jax.tree_util.tree_map(lambda a: a[n_main:], mp["layers"])

    def super_body(carry, xs):
        h, aux, mstates, li, acaches, si = carry
        blk = xs
        (h, aux, mstates, li), _ = jax.lax.scan(
            mamba_body, (h, aux, mstates, li), blk)
        xcat = jnp.concatenate([h, emb0], axis=-1)
        xcat = rms_norm(xcat, mp["shared_ln"])
        out, acaches = attn_forward(
            shared, xcat, positions, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
            rope_theta=cfg.rope_theta, causal=True,
            cache=acaches, cache_index=index, layer=si)
        h = h + out
        h = h + mlp_forward(mp["shared_mlp"],
                            rms_norm(h, mp["shared_ln2"]), cfg.mlp_kind)
        return (h, aux, mstates, li, acaches, si + 1), None

    carry0 = (h, jnp.asarray(0.0, jnp.float32), cache_mamba,
              jnp.asarray(0, jnp.int32), attn_caches,
              jnp.asarray(0, jnp.int32))
    (h, aux, new_cm, li, new_attn, _), _ = scan_or_loop(
        super_body, carry0, layers_main, cfg.scan_layers, n_super)
    if n - n_main:
        (h, aux, new_cm, _), _ = scan_or_loop(
            mamba_body, (h, aux, new_cm, li), layers_tail,
            cfg.scan_layers, n - n_main)
    new_cache = None
    if cache is not None:
        new_cache = {"mamba": new_cm, "attn": new_attn}
    return h, aux, new_cache


def _embed_inputs(mp, cfg: ModelConfig, tokens, vision_embeds, positions):
    h = jnp.take(mp["embed"], tokens, axis=0)
    if cfg.family == "vlm" and vision_embeds is not None:
        v = qmatmul(vision_embeds, mp["vision_proj"])
        h = jnp.concatenate([v.astype(h.dtype), h], axis=1)
    b, s, _ = h.shape
    if positions is None:
        pos1 = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        positions = jnp.stack([pos1] * 3, axis=-1) if cfg.mrope else pos1
    return h.astype(_cdtype(cfg)), positions


def forward(params, cfg: ModelConfig, tokens, *, vision_embeds=None,
            positions=None, cache=None, index=None):
    """Returns (logits, aux, new_cache)."""
    mp = shard_params_tree(prepare_params(params, _cdtype(cfg)))
    with jax.named_scope("embed"):
        h, positions = _embed_inputs(mp, cfg, tokens, vision_embeds,
                                     positions)
        h = constraint(h, "batch", None, None)
    emb0 = h
    if cfg.family in ("dense", "moe", "vlm"):
        h, aux, new_cache = _walk_dense(mp, cfg, h, positions, cache, index)
    elif cfg.family == "ssm":
        h, aux, new_cache = _walk_ssm(mp, cfg, h, cache, index)
    elif cfg.family == "hybrid":
        h, aux, new_cache = _walk_hybrid(mp, cfg, h, emb0, positions, cache,
                                         index)
    else:
        raise ValueError(cfg.family)
    with jax.named_scope("head"):
        h = rms_norm(h, mp["final_norm"])
        head = mp["lm_head"] if "lm_head" in mp else mp["embed"].T
        logits = qmatmul(h, head).astype(jnp.float32)
        logits = softcap(logits, cfg.logit_softcap)
        logits = constraint(logits, "batch", None, "vocab")
    return logits, aux, new_cache


# ---------------------------------------------------------------------------
# caches (decode)
# ---------------------------------------------------------------------------

def make_kv_cache(cfg: ModelConfig, stack: int, batch: int,
                  max_len: int) -> Dict:
    """One stacked K/V cache at ``cfg.kv_cache_bits`` precision.

    <32 bits stores quantized-at-rest entries (int8, or int4 nibble-packed
    along the head dim) plus per-token/head scales; see models.attention."""
    if cfg.kv_lora_rank:
        return make_latent_cache(cfg, stack, batch, max_len, _cdtype(cfg))
    dh, kv = cfg.head_dim, cfg.n_kv_heads
    shape = (stack, batch, max_len, kv, dh)
    bits = cfg.kv_cache_bits
    if bits == 8:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.float32),
                "v_scale": jnp.zeros(shape[:-1], jnp.float32)}
    if bits == 4:
        assert dh % 2 == 0, f"int4 KV cache needs even head_dim, got {dh}"
        pshape = shape[:-1] + (dh // 2,)
        return {"k": jnp.zeros(pshape, jnp.uint8),
                "v": jnp.zeros(pshape, jnp.uint8),
                "k_scale": jnp.zeros(shape[:-1], jnp.float32),
                "v_scale": jnp.zeros(shape[:-1], jnp.float32)}
    dt = _cdtype(cfg)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _walk_cache_tree(cache, kv_fn, leaf_fn):
    """Apply ``kv_fn`` to KV sub-dicts (``attention.is_kv_node``: leaves
    with a time axis) and ``leaf_fn`` to recurrent leaves (no time axis)
    of a stacked decode-cache tree (works on arrays or
    ShapeDtypeStructs)."""
    if isinstance(cache, dict):
        if is_kv_node(cache):
            return kv_fn(cache)
        return {k: _walk_cache_tree(v, kv_fn, leaf_fn)
                for k, v in cache.items()}
    return leaf_fn(cache)


def rebatch_cache_tree(cache, n_slots: int, time_len: int):
    """Zero contiguous decode cache re-sized to ``n_slots`` slots of
    ``time_len`` positions, mirroring ``cache``'s tree/dtypes (which may
    come from ``jax.eval_shape`` — no allocation until here)."""
    return _walk_cache_tree(
        cache,
        lambda node: {n: jnp.zeros((l.shape[0], n_slots, time_len,
                                    *l.shape[3:]), l.dtype)
                      for n, l in node.items()},
        lambda l: jnp.zeros((l.shape[0], n_slots, *l.shape[2:]), l.dtype))


def paginate_cache_tree(cache, n_slots: int, n_pages: int, page_size: int,
                        nb: int):
    """Zero *paged* decode cache mirroring contiguous ``cache``.

    Every KV sub-dict becomes ``{"pages", "table"}``: pool leaves trade the
    per-slot (B, T) layout for ``n_pages`` pages of ``page_size`` tokens in
    the lane-dense storage of ``kernels.kv_pages`` (a
    :class:`~repro.kernels.kv_pages.PagePool`; same storage dtypes, so
    int8/int4-at-rest formats carry over), and the (stack, n_slots, nb)
    block table starts all-trash (page 0 is reserved; a slot's block b
    maps to the pool page holding its tokens [b*page_size,
    (b+1)*page_size)).  Recurrent leaves (no time axis) are plain
    re-batched rows, as in :func:`rebatch_cache_tree`."""
    def paged(node):
        stack = next(iter(node.values())).shape[0]
        return {"pages": make_pool({n: (l.shape[3:], l.dtype)
                                    for n, l in node.items()},
                                   stack, n_pages, page_size),
                "table": jnp.zeros((stack, n_slots, nb), jnp.int32)}
    return _walk_cache_tree(
        cache, paged,
        lambda l: jnp.zeros((l.shape[0], n_slots, *l.shape[2:]), l.dtype))


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    dt = _cdtype(cfg)
    if cfg.family in ("dense", "moe", "vlm"):
        return make_kv_cache(cfg, cfg.n_layers, batch, max_len)
    if cfg.family == "ssm":
        st = rwkv6_init_state(batch, cfg.d_model, cfg.n_heads, dt)
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros((cfg.n_layers, *a.shape), a.dtype), st)
    if cfg.family == "hybrid":
        mst = mamba2_init_state(batch, cfg.d_model, cfg.ssm_state,
                                cfg.ssm_expand, cfg.ssm_headdim, dtype=dt)
        mamba = jax.tree_util.tree_map(
            lambda a: jnp.zeros((cfg.n_layers, *a.shape), a.dtype), mst)
        n_super = cfg.n_layers // cfg.hybrid_attn_every
        return {"mamba": mamba,
                "attn": make_kv_cache(cfg, n_super, batch, max_len)}
    raise ValueError(cfg.family)


def decode_positions(index, batch: int, seq: int = 1) -> jnp.ndarray:
    """(B, S) absolute positions from a scalar or per-slot (B,) index."""
    idx = jnp.asarray(index, jnp.int32)
    base = idx[:, None] if idx.ndim else idx
    return jnp.broadcast_to(base + jnp.arange(seq, dtype=jnp.int32)[None, :],
                            (batch, seq)).astype(jnp.int32)


def decode_step(params, cfg: ModelConfig, tokens, cache, index):
    """One-token step. tokens: (B, 1); index: () int32 current length, or a
    per-slot (B,) vector of lengths (continuous batching)."""
    b = tokens.shape[0]
    pos1 = decode_positions(index, b)
    positions = jnp.stack([pos1] * 3, axis=-1) if cfg.mrope else pos1
    logits, aux, new_cache = forward(params, cfg, tokens,
                                     positions=positions, cache=cache,
                                     index=index)
    return logits[:, -1], new_cache


def verify_step(params, cfg: ModelConfig, tokens, cache, index):
    """W-token decode forward for speculative verification.

    ``tokens``: (B, W) — a slot's last accepted token followed by its
    draft proposals; ``index``: scalar or per-slot (B,) fill levels.
    Returns the full (B, W, V) logits (the verifier needs every
    position's next-token distribution, not just the last) and the
    cache with all W positions (re)written at full precision."""
    b, w = tokens.shape
    pos = decode_positions(index, b, w)
    positions = jnp.stack([pos] * 3, axis=-1) if cfg.mrope else pos
    logits, aux, new_cache = forward(params, cfg, tokens,
                                     positions=positions, cache=cache,
                                     index=index)
    return logits, new_cache


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    """Next-token cross entropy (+ MoE aux).  batch: tokens, labels, [mask]."""
    logits, aux, _ = forward(params, cfg, batch["tokens"],
                             vision_embeds=batch.get("vision_embeds"),
                             positions=batch.get("positions"))
    labels = batch["labels"]
    if cfg.family == "vlm" and batch.get("vision_embeds") is not None:
        logits = logits[:, -labels.shape[1]:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    mask = batch.get("mask")
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
    else:
        denom = float(nll.size)
    ce = jnp.sum(nll) / denom
    return ce + 0.01 * aux, dict(ce=ce, aux=aux)
