"""Rotary position embeddings: standard RoPE and Qwen2-VL M-RoPE."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN context extension of RoPE (arXiv:2309.00071), as DeepSeek-V2
    configures it (``rope_scaling`` of type ``yarn``)."""
    factor: float
    original_max: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_bounds(y: YarnScaling, dim: int, theta: float):
    """(low, high): the rotary pairs below ``low`` keep their original
    frequency, those from ``high`` on are interpolated by ``factor``."""
    def pair(rotations):
        return dim * math.log(y.original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair(y.beta_fast)), 0)
    high = min(math.ceil(pair(y.beta_slow)), dim - 1)
    return low, high


def yarn_inv_freq(y: YarnScaling, dim: int, theta: float) -> jnp.ndarray:
    """(dim//2,) inverse frequencies: ``f_inter * (1 - m) + f_extra * m``,
    ``m = 1 - ramp(low, high)``."""
    low, high = yarn_bounds(y, dim, theta)
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                             / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    m = 1.0 - ramp
    return extra / y.factor * (1.0 - m) + extra * m


def yarn_softmax_scale(y: Optional[YarnScaling], q_dim: int) -> float:
    """Attention's softmax scale: ``q_dim**-0.5`` times the square of
    yarn's ``mscale_all_dim`` correction (cos/sin stay unscaled when
    ``mscale == mscale_all_dim``)."""
    s = q_dim ** -0.5
    if y is not None:
        s *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def rope_angles(positions: jnp.ndarray, d_head: int,
                theta: float = 1e4,
                yarn: Optional[YarnScaling] = None) -> jnp.ndarray:
    """(.., S) int positions -> (.., S, d_head//2) angles."""
    half = d_head // 2
    if yarn is not None:
        inv_freq = yarn_inv_freq(yarn, d_head, theta)
    else:
        inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                    / half))
    return positions.astype(jnp.float32)[..., None] * inv_freq


def apply_rope(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, dh); angles: (B, S, dh//2) -> rotated x."""
    dt = x.dtype
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[..., None, :].astype(jnp.float32)
    sin = jnp.sin(angles)[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1f * cos - x2f * sin,
                            x2f * cos + x1f * sin], axis=-1).astype(dt)


def mrope_angles(positions3: jnp.ndarray, d_head: int, theta: float,
                 sections: Sequence[int] | None = None) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.

    positions3: (B, S, 3) = (temporal, height, width) position ids.  The
    d_head//2 frequency slots are split into ``sections`` (t, h, w); each
    section rotates with its own position stream.  Text tokens carry equal
    (t, h, w) ids, which makes M-RoPE degenerate to standard RoPE for them.
    """
    half = d_head // 2
    if sections is None:
        # Qwen2-VL ratio (16, 24, 24)/64 generalized to any head size.
        hw = 3 * half // 8
        sections = (half - 2 * hw, hw, hw)
    assert sum(sections) == half, (sections, half)
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.asarray(sections), total_repeat_length=half)
    # gather per-frequency-slot positions: (B, S, half)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(sec_id[None, None, :],
                         positions3.shape[:-1] + (half,)).astype(jnp.int32),
        axis=-1)
    return pos * inv_freq
