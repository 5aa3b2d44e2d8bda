"""Rotary position embeddings — standard RoPE, YaRN and Qwen2-VL M-RoPE.

YaRN (arXiv:2309.00071, as DeepSeek-V3 configures it) blends each
frequency between its extrapolated value θ^(-2i/D) and its interpolated
value θ^(-2i/D)/factor by a linear ramp over the dims whose wavelength
lies between ``beta_fast`` and ``beta_slow`` rotations of the original
context, and scales the attention softmax by mscale² (``yarn_softmax_scale``).

M-RoPE (multimodal RoPE, arXiv:2409.12191) splits the rotary half-dim into
three sections (temporal, height, width) and rotates each section with its
own position id.  For pure text all three ids are equal, which reduces
M-RoPE exactly to 1-D RoPE — tested in tests/test_models.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape [d_head//2] (f32)."""
    half = d_head // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor 0.1·mscale·ln(factor) + 1."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(d_head: int, theta: float, factor: float, original_max_pos: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies, shape [d_head//2] (f32)."""
    half = d_head // 2
    extra = 1.0 / theta ** (np.arange(0, d_head, 2, dtype=np.float64) / d_head)
    inter = extra / factor

    def dim_of(rotations):
        return (d_head * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), d_head - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp           # 1: extrapolate (high frequency)
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def cfg_rope_freqs(cfg, d_head: int) -> np.ndarray:
    """A config's inverse frequencies: YaRN where it sets a factor."""
    if cfg.yarn_factor:
        return yarn_freqs(d_head, cfg.rope_theta, cfg.yarn_factor,
                          cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                          cfg.yarn_beta_slow)
    return rope_freqs(d_head, cfg.rope_theta)


def cfg_rope_mscale(cfg) -> float:
    """Factor on cos/sin: mscale(factor, mscale) / mscale(factor, all)."""
    if not cfg.yarn_factor:
        return 1.0
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def yarn_softmax_scale(cfg, qk_head_dim: int) -> float:
    """qk_head_dim^-0.5, times mscale(factor, mscale_all_dim)² under YaRN."""
    scale = qk_head_dim ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, *,
               inv_freq=None, mscale: float = 1.0,
               interleaved: bool = False) -> jnp.ndarray:
    """Rotate x: [..., S, H, D] by per-token positions [..., S] (int32).

    Pairs (i, i + D/2) rotate together.  ``interleaved`` first reorders
    D as (0, 2, 4, …, 1, 3, 5, …), as HF DeepSeek-V3 does, so that pairs
    (2i, 2i+1) rotate and the output holds them in that order.
    ``inv_freq`` overrides θ^(-2i/D) (YaRN); cos and sin are scaled by
    ``mscale``."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta) if inv_freq is None else inv_freq  # [D/2]
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]                   # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xf = x.astype(jnp.float32)
    if interleaved:
        xf = jnp.concatenate([xf[..., 0::2], xf[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float,
                sections: tuple) -> jnp.ndarray:
    """Qwen2-VL M-RoPE.  x: [B, S, H, D]; positions3: [B, S, 3] (t, h, w).

    ``sections`` partitions the half-dim (sum(sections) == D//2); section i
    rotates with positions3[..., i].
    """
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_freqs(d, theta)                          # [D/2]
    # build per-frequency position ids by section
    sec_id = np.concatenate([
        np.full((s,), i, np.int32) for i, s in enumerate(sections)
    ])                                                  # [D/2]
    pos = jnp.take(positions3, jnp.asarray(sec_id), axis=-1)   # [B, S, D/2]
    ang = pos.astype(jnp.float32) * inv                 # [B, S, D/2]
    cos = jnp.cos(ang)[..., None, :]                    # [B, S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
