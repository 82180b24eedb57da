"""Plain float32 reference of the served dense decoder models.

It follows the configuration file alone and imports nothing of the
program: the weights are drawn here from the run's key the way the
configuration's deploy defines them, and every step is written out in
``jax.numpy`` at ``highest`` matmul precision.

The deployed model, as the configuration states it:

* weights: each quantized (K, N) slice is drawn N(0, 1/K) and rounded to
  255 magnitude levels of its own max |w|, then halved into an int8
  container (8-bit blocks exceed int8's 7 magnitude bits by one, so the
  block scale doubles); the slices of a stacked leaf come from
  ``split(fold_in(key, leaf), n_slices)``, where ``leaf`` numbers the
  parameter tree's leaves in sorted-key order;
* embeddings N(0, 0.02^2), stored in bf16, from ``split(key, 6)[0]``;
  norm scales are zero (RMSNorm multiplies by 1 + scale);
* activations into the attention and MLP blocks pass RMSNorm and then
  symmetric PACT quantization to 8 bits with clip 6.0;
* keys (after RoPE) and values are stored as int8 with one scale per
  token and KV head;
* logits are the final RMSNorm's output times the embedding (tied head).

``precision="fp8"`` is the control: every matmul operand is scaled to
float8 e4m3 (per weight tensor, per activation row) before it is used.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn


def _leaf_order():
    layer = sorted(["attn.wk", "attn.wo", "attn.wq", "attn.wv", "beta_attn",
                    "beta_mlp", "ln_attn", "ln_mlp", "mlp.w_down",
                    "mlp.w_gate", "mlp.w_up"])
    return {name: i for i, name in
            enumerate(["embed", "final_norm"] + layer)}


def _quantized(key, kn):
    """One deployed (K, N) slice, dequantized to f32."""
    k = kn[0]
    w = jax.random.normal(key, kn, jnp.float32) * (1.0 / jnp.sqrt(k))
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8)
    q = jnp.clip(jnp.round(jnp.abs(w) / s * 255.0), 0.0, 255.0)
    wq = jnp.clip(jnp.round(jnp.where(w < 0, -1.0, 1.0) * q / 2.0),
                  -128.0, 127.0)
    return wq * (s / 255.0 * 2.0)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _f8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 448.0
    return (x / s).astype(F8).astype(jnp.float32) * s


class Decoder:
    def __init__(self, conf: dict):
        w = conf["program"]["widths"]
        num = conf["numerics"]
        self.L, self.d, self.H = w["n_layers"], w["d_model"], w["n_heads"]
        self.KV, self.dh, self.V = w["n_kv_heads"], w["d_head"], w["vocab"]
        self.ff = w["d_ff"]
        self.theta = num["rope_theta"]
        self.eps = num["rms_eps"]
        self.beta = num["pact_clip"]
        self.act_levels = float(2 ** (num["act_bits"] - 1) - 1)
        self.leaf = _leaf_order()

    # ---- weights -------------------------------------------------------
    def _keys(self, key, leaf, n):
        return jax.random.split(jax.random.fold_in(key, self.leaf[leaf]), n)

    def _dense(self, key, leaf, l, kn):
        return _quantized(self._keys(key, leaf, self.L)[l], kn)

    def _embed(self, key):
        k0 = jax.random.split(key, 6)[0]
        return _bf16(jax.random.normal(k0, (self.V, self.d)) * 0.02)

    # ---- pieces ----------------------------------------------------------
    def _mm(self, eq, a, b, fp8, a_axis=-1, b_axis=None):
        if fp8:
            a, b = _f8(a, a_axis), _f8(b, b_axis)
        return jnp.einsum(eq, a, b)

    def _norm_quant(self, h):
        x = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + self.eps)
        y = 0.5 * (jnp.abs(x + self.beta) - jnp.abs(x - self.beta))
        return jnp.round(y / self.beta * self.act_levels) \
            * (self.beta / self.act_levels)

    def _rope(self, x, pos):
        half = self.dh // 2
        inv = 1.0 / (self.theta ** (jnp.arange(half, dtype=jnp.float32)
                                    / half))
        ang = pos.astype(jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    @staticmethod
    def _kv8(x):
        s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0,
                        1e-6)
        return jnp.clip(jnp.round(x / s), -127.0, 127.0) * s

    def _weights(self, key, l):
        d, H, KV, dh, ff = self.d, self.H, self.KV, self.dh, self.ff
        w = {"wq": self._dense(key, "attn.wq", l, (d, H * dh)),
             "wk": self._dense(key, "attn.wk", l, (d, KV * dh)),
             "wv": self._dense(key, "attn.wv", l, (d, KV * dh)),
             "wo": self._dense(key, "attn.wo", l, (H * dh, d)),
             "wg": self._dense(key, "mlp.w_gate", l, (d, ff)),
             "wu": self._dense(key, "mlp.w_up", l, (d, ff)),
             "wd": self._dense(key, "mlp.w_down", l, (ff, d))}
        return w

    def _attention(self, w, x, fp8):
        t = x.shape[0]
        H, KV, dh = self.H, self.KV, self.dh
        pos = jnp.arange(t)
        q = self._rope(self._mm("td,dn->tn", x, w["wq"], fp8)
                       .reshape(t, H, dh), pos)
        k = self._kv8(self._rope(
            self._mm("td,dn->tn", x, w["wk"], fp8).reshape(t, KV, dh), pos))
        v = self._kv8(self._mm("td,dn->tn", x, w["wv"], fp8)
                      .reshape(t, KV, dh))
        qg = q.reshape(t, KV, H // KV, dh)
        sc = self._mm("skgd,tkd->kgst", qg, k, fp8) / np.sqrt(dh)
        sc = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        o = self._mm("kgst,tkd->skgd", p, v, fp8).reshape(t, H * dh)
        return self._mm("tn,nd->td", o, w["wo"], fp8)

    def _mlp(self, w, x, fp8):
        h = jax.nn.silu(self._mm("td,df->tf", x, w["wg"], fp8)) \
            * self._mm("td,df->tf", x, w["wu"], fp8)
        return self._mm("tf,fd->td", h, w["wd"], fp8)

    # ---- whole model, one layer at a time ----------------------------------
    @functools.partial(jax.jit, static_argnums=(0, 4))
    def _layer(self, key, h, l, fp8):
        w = self._weights(key, l)

        def one(h):
            h = h + self._attention(w, self._norm_quant(h), fp8)
            return h + self._mlp(w, self._norm_quant(h), fp8)
        return jax.lax.map(one, h)

    @functools.partial(jax.jit, static_argnums=(0, 4))
    def _head(self, key, h, rows, fp8):
        x = h.reshape(-1, self.d)[rows]
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return self._mm("td,vd->tv", x, self._embed(key), fp8,
                        b_axis=None)

    def logits(self, key, tokens: np.ndarray, rows: np.ndarray,
               fp8: bool = False) -> np.ndarray:
        """Logits at flat positions ``rows`` of ``tokens`` (B, T)."""
        with jax.default_matmul_precision("highest"):
            h = jnp.take(self._embed(key), jnp.asarray(tokens), axis=0)
            for l in range(self.L):
                h = self._layer(key, h, jnp.asarray(l, jnp.int32), fp8)
            return np.asarray(self._head(key, h, jnp.asarray(rows), fp8))
