"""Plain float32 reference of the served MLA + MoE decoder (DeepSeek-V2).

It follows the configuration file alone and imports nothing of the
program: the weights are drawn here from the run's key the way the
configuration's deploy defines them, and every step is written out in
``jax.numpy`` at ``highest`` matmul precision.  Attention is the
published, expanded form everywhere (the program's decode absorbs
``W_UK`` and ``W_UV``; this file never does).

The deployed model, as the configuration states it:

* weights: as in ``decoder.py`` (each quantized (K, N) slice drawn
  N(0, 1/K), rounded to 255 magnitude levels of its own max |w| and
  halved into an int8 container); a leaf with lead dims (layers[,
  experts]) takes its slices, in row-major order, from
  ``split(fold_in(key, leaf), n_slices)``, ``leaf`` numbering the
  parameter tree's leaves in sorted-key order (``_leaf_order``);
* the embedding N(0, 0.02^2) from ``split(key, 6)[0]`` and the router
  N(0, 0.02^2) of shape (MoE layers, d, routed experts) from
  ``split(split(split(key, 6)[1], 8)[4])``, both served in the compute
  dtype (bf16 in the configuration); norm
  scales are zero (RMSNorm multiplies by 1 + scale); the output head is
  a quantized (d, vocab) leaf;
* layer input x = PACT_8(RMSNorm(h)) (clip 6.0) into attention and FFN;
* MLA: ``q = x W_q`` per head [nope 128 | rope 64]; ``[c | k_pe] =
  x W_kva``, ``c = RMSNorm(c)``; RoPE on the rope parts (yarn
  frequencies, half-split pairs; scale ``192**-0.5 * mscale**2``); the
  cache stores c and k_pe as int8, each with a per-token scale, and
  attention reads them back: ``[k_nope | v] = c W_kvb`` per head,
  ``k = [k_nope | k_pe]``, causal softmax, ``o W_o``;
* layer 0: SwiGLU MLP of the dense width; layers 1..: MoE, softmax over
  every routed expert, top-k gates not renormalized, times the routed
  scaling; only the held experts (``expert_offset ..``) add their part,
  and the shared SwiGLU (width n_shared x expert width) is added whole;
* logits: the final RMSNorm's output times the head.

``fp8=True`` is the control: every matmul operand is scaled to float8
e4m3 (per weight tensor, per activation row) before it is used.
Computed in blocks (one layer, one row and 512 queries at a time, one
expert at a time) so that a 7k-token row fits on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from references.decoder import _bf16, _f8, _quantized

Q_BLOCK = 512


def _leaf_order(moe_layers: int) -> dict:
    """Leaf number of each parameter, in the tree's sorted-key order."""
    attn = ["attn.kv_norm", "attn.wkv_a", "attn.wkv_b", "attn.wo",
            "attn.wq", "beta_attn", "beta_mlp", "ln_attn", "ln_mlp"]
    dense = ["dense_layers." + n for n in attn + [
        "mlp.w_down", "mlp.w_gate", "mlp.w_up"]]
    moe = ["layers." + n for n in attn + [
        "moe.expert_down", "moe.expert_gate", "moe.expert_up",
        "moe.router_w", "moe.shared_down", "moe.shared_gate",
        "moe.shared_up"]]
    names = sorted(dense + ["embed", "final_norm"] + moe + ["lm_head"])
    return {n: i for i, n in enumerate(names)}


class MlaMoe:
    def __init__(self, conf: dict):
        w = conf["program"]["widths"]
        num = conf["numerics"]
        self.L, self.d, self.H = w["n_layers"], w["d_model"], w["n_heads"]
        self.V = w["vocab"]
        self.r, self.dn = w["kv_lora_rank"], w["qk_nope_dim"]
        self.dr, self.dv = w["qk_rope_dim"], w["v_head_dim"]
        self.E, self.k = w["n_experts"], w["top_k"]
        self.held, self.offset = w["n_held_experts"], w["expert_offset"]
        self.ff, self.n_shared = w["d_ff"], w["n_shared_experts"]
        self.dense, self.dense_ff = w["first_dense_layers"], w["dense_d_ff"]
        self.renorm = num["norm_topk_prob"]
        self.routed_scale = num["routed_scaling_factor"]
        self.theta = num["rope_theta"]
        self.yarn = num["rope_scaling"]
        self.eps = num["rms_eps"]
        self.beta = num["pact_clip"]
        self.act_levels = float(2 ** (num["act_bits"] - 1) - 1)
        self.served = _bf16 if num["compute"] == "bfloat16" \
            else (lambda x: x)
        self.leaf = _leaf_order(self.L - self.dense)

    # ---- weights -------------------------------------------------------
    def _slices(self, key, leaf, n):
        return jax.random.split(jax.random.fold_in(key, self.leaf[leaf]), n)

    def _attn_weights(self, key, pre, l, n):
        d, H, r = self.d, self.H, self.r
        qd = self.dn + self.dr
        w = {}
        for name, kn in (("wq", (d, H * qd)), ("wkv_a", (d, r + self.dr)),
                         ("wkv_b", (r, H * (self.dn + self.dv))),
                         ("wo", (H * self.dv, d))):
            w[name] = _quantized(self._slices(key, f"{pre}.attn.{name}",
                                              n)[l], kn)
        return w

    def _mlp_weights(self, key, leaf, l, n, ff):
        d = self.d
        return {"wg": _quantized(self._slices(key, f"{leaf}_gate", n)[l],
                                 (d, ff)),
                "wu": _quantized(self._slices(key, f"{leaf}_up", n)[l],
                                 (d, ff)),
                "wd": _quantized(self._slices(key, f"{leaf}_down", n)[l],
                                 (ff, d))}

    def _embed(self, key):
        k0 = jax.random.split(key, 6)[0]
        return self.served(jax.random.normal(k0, (self.V, self.d)) * 0.02)

    def _router(self, key):
        k = jax.random.split(jax.random.split(key, 6)[1], 8)[4]
        return self.served(jax.random.normal(
            k, (self.L - self.dense, self.d, self.E)) * 0.02)

    # ---- pieces ----------------------------------------------------------
    def _mm(self, eq, a, b, fp8, a_axis=-1, b_axis=None):
        if fp8:
            a, b = _f8(a, a_axis), _f8(b, b_axis)
        return jnp.einsum(eq, a, b)

    def _rms(self, x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.eps)

    def _norm_quant(self, h):
        x = self._rms(h)
        y = 0.5 * (jnp.abs(x + self.beta) - jnp.abs(x - self.beta))
        return jnp.round(y / self.beta * self.act_levels) \
            * (self.beta / self.act_levels)

    def inv_freq(self):
        """Yarn inverse frequencies of the rope dims (DeepSeek-V2's
        ``yarn_find_correction_range`` and linear ramp)."""
        y, dim, base = self.yarn, self.dr, self.theta

        def corr(rot):
            return dim * math.log(y["original_max_position_embeddings"]
                                  / (rot * 2 * math.pi)) \
                / (2 * math.log(base))
        low = max(math.floor(corr(y["beta_fast"])), 0)
        high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
        extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                       0, 1)
        mask = 1.0 - ramp
        inv = extra / y["factor"] * (1 - mask) + extra * mask
        return jnp.asarray(inv, jnp.float32), (low, high)

    def softmax_scale(self):
        y = self.yarn
        m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
        return (self.dn + self.dr) ** -0.5 * m * m

    def _rope(self, x, pos):
        """Half-split rotation of x (T, ..., dr)."""
        inv, _ = self.inv_freq()
        ang = pos.astype(jnp.float32)[:, None] * inv
        shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (self.dr // 2,)
        cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
        half = self.dr // 2
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    @staticmethod
    def _q8(x):
        """int8 storage with one scale per token (the last axis)."""
        s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0,
                        1e-6)
        return jnp.clip(jnp.round(x / s), -127.0, 127.0) * s

    def _attention(self, w, x, fp8):
        t = x.shape[0]
        H, r, dn, dr, dv = self.H, self.r, self.dn, self.dr, self.dv
        pos = jnp.arange(t)
        q = self._mm("td,dn->tn", x, w["wq"], fp8).reshape(t, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], self._rope(q[..., dn:], pos)],
                            -1)
        kva = self._mm("td,dn->tn", x, w["wkv_a"], fp8)
        c = self._q8(self._rms(kva[:, :r]))
        k_pe = self._q8(self._rope(kva[:, r:], pos))
        kv = self._mm("tr,rn->tn", c, w["wkv_b"], fp8).reshape(
            t, H, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_pe[:, None, :], (t, H, dr))], -1)
        v = kv[..., dn:]
        qb = min(Q_BLOCK, t)
        scale = self.softmax_scale()

        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            sc = self._mm("shd,thd->hst", qi, k, fp8) * scale
            qpos = i * qb + jnp.arange(qb)
            sc = jnp.where(qpos[None, :, None] >= pos[None, None, :], sc,
                           -jnp.inf)
            return self._mm("hst,thd->shd", jax.nn.softmax(sc, -1), v, fp8)
        o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, H * dv)
        return self._mm("tn,nd->td", o, w["wo"], fp8)

    def _swiglu(self, w, x, fp8):
        h = jax.nn.silu(self._mm("td,df->tf", x, w["wg"], fp8)) \
            * self._mm("td,df->tf", x, w["wu"], fp8)
        return self._mm("tf,fd->td", h, w["wd"], fp8)

    def _moe(self, key, l, router, x, fp8):
        """Held experts' gated outputs plus the shared experts (layer l
        of the MoE stack)."""
        n = self.L - self.dense
        probs = jax.nn.softmax(self._mm("td,de->te", x, router, fp8), -1)
        g, idx = jax.lax.top_k(probs, self.k)
        if self.renorm:
            g = g / jnp.sum(g, -1, keepdims=True)
        g = g * self.routed_scale
        keys = {m: self._slices(key, f"layers.moe.expert_{m}",
                                n * self.held)
                for m in ("gate", "up", "down")}

        def expert(y, e):
            gate = jnp.sum(jnp.where(idx == self.offset + e, g, 0.0), -1)
            s = l * self.held + e
            w = {"wg": _quantized(keys["gate"][s], (self.d, self.ff)),
                 "wu": _quantized(keys["up"][s], (self.d, self.ff)),
                 "wd": _quantized(keys["down"][s], (self.ff, self.d))}
            return y + gate[:, None] * self._swiglu(w, x, fp8), None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            jnp.arange(self.held))
        shared = self._mlp_weights(key, "layers.moe.shared", l, n,
                                   self.n_shared * self.ff)
        return y + self._swiglu(shared, x, fp8)

    # ---- whole model, one layer at a time ----------------------------------
    @functools.partial(jax.jit, static_argnums=(0, 4))
    def _dense_layer(self, key, h, l, fp8):
        w = self._attn_weights(key, "dense_layers", l, self.dense)
        mlp = self._mlp_weights(key, "dense_layers.mlp.w", l, self.dense,
                                self.dense_ff)

        def one(h):
            h = h + self._attention(w, self._norm_quant(h), fp8)
            return h + self._swiglu(mlp, self._norm_quant(h), fp8)
        return jax.lax.map(one, h)

    @functools.partial(jax.jit, static_argnums=(0, 5))
    def _moe_layer(self, key, h, l, router, fp8):
        w = self._attn_weights(key, "layers", l, self.L - self.dense)

        def one(h):
            h = h + self._attention(w, self._norm_quant(h), fp8)
            return h + self._moe(key, l, router, self._norm_quant(h), fp8)
        return jax.lax.map(one, h)

    @functools.partial(jax.jit, static_argnums=(0, 4))
    def _head(self, key, h, rows, fp8):
        x = self._rms(h.reshape(-1, self.d)[rows])
        head = _quantized(self._slices(key, "lm_head", 1)[0],
                          (self.d, self.V))
        return self._mm("td,dv->tv", x, head, fp8)

    def logits(self, key, tokens: np.ndarray, rows: np.ndarray,
               fp8: bool = False) -> np.ndarray:
        """Logits at flat positions ``rows`` of ``tokens`` (B, T)."""
        with jax.default_matmul_precision("highest"):
            h = jnp.take(self._embed(key), jnp.asarray(tokens), axis=0)
            router = self._router(key)
            for l in range(self.dense):
                h = self._dense_layer(key, h, jnp.asarray(l, jnp.int32),
                                      fp8)
            for l in range(self.L - self.dense):
                h = self._moe_layer(key, h, jnp.asarray(l, jnp.int32),
                                    router[l], fp8)
            return np.asarray(self._head(key, h, jnp.asarray(rows), fp8))
