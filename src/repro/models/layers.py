"""Shared neural-net building blocks (pure functional JAX, dict pytrees).

Conventions:
* params are nested dicts of jnp arrays; every function takes (params, x).
* compute dtype follows the input; normalization statistics in f32.
* init functions take an explicit PRNG key and an ArchConfig-ish scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perturbations as pert
from repro.kernels import ops as kops


def dense_init(key, d_in, d_out, *, bias=False, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def groupnorm_heads(p, x, n_heads, eps=1e-5):
    """GroupNorm with one group per head over the flattened head dim
    (RWKV6's ln_x).  x: [..., H*D]."""
    *lead, hd = x.shape
    d = hd // n_heads
    xf = x.astype(jnp.float32).reshape(*lead, n_heads, d)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, hd)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def embedding_init(key, vocab, d, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
                      ).astype(dtype)}


def embed(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def glu_mlp_init(key, d, d_ff, dtype=jnp.float32):
    """Gated (SwiGLU) MLP — the LM-family feedforward."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d, d_ff, dtype=dtype),
        "up": dense_init(k2, d, d_ff, dtype=dtype),
        "down": dense_init(k3, d_ff, d, dtype=dtype),
    }


def glu_mlp(p, x):
    h = jax.nn.silu(dense(p["gate"], x).astype(jnp.float32)).astype(x.dtype)
    return dense(p["down"], h * dense(p["up"], x))


# --- perturbable primitives (MGD fused probe path) --------------------------
#
# ``pdense`` is the perturbable counterpart of ``dense``: instead of adding a
# materialized θ̃ to W in HBM, the weight matmul is routed through the Pallas
# perturbed-matmul kernels, which regenerate the Rademacher signs in VMEM
# next to the MXU — a probe forward reads W once, the same bytes as
# inference.  An antithetic central pair (signs == (+1, −1)) uses the
# single-pass pair kernel, reading W once per *pair*.  Non-matrix leaves
# (biases, norm scales) fall back to a materialized θ̃ — they are O(d), not
# O(d²), so the HBM cost is negligible.
#
# All perturbable ops take/return a TUPLE of activation streams, one per
# probe sign (1 for a forward probe, 2 for a central pair), plus the leaf-id
# subtree (``repro.core.utils.leaf_id_tree``) that anchors every leaf to the
# global hash the host generator uses.  ``layer`` (traced, from a
# stacked-layer scan) selects the row-major slice of stacked leaves via a
# seed shift — see perturbations.shifted_leaf_seed.  ``probe=None`` is the
# plain forward: one stream, no perturbation — so a layer written once in
# stream form is both the model's forward and its probe.


def _stream_offset(layer, nelem):
    """Element offset of layer ``layer``'s slice in a stacked leaf (traced
    uint32; wraparound matches the generator's uint32 iota)."""
    return (jnp.asarray(layer, jnp.uint32)
            * jnp.uint32(int(nelem) & 0xFFFFFFFF))


def pleaf(leaf, leaf_id, probe, *, layer=None):
    """Per-stream perturbed values of a non-matmul leaf (or its layer
    slice): tuple of leaf + sign_i·θ̃, float order identical to the
    materializing optimizer path."""
    if probe is None:
        return (leaf,)
    offset = 0 if layer is None else _stream_offset(layer, leaf.size)
    theta = probe.leaf_theta(leaf.shape, leaf.dtype, leaf_id, offset=offset)
    return tuple(pert.apply_signed(leaf, theta, s) for s in probe.ctx.signs)


def pdense(p, xs, ids, probe, *, layer=None):
    """Perturbable dense: xs (tuple of per-sign streams) @ (W ± θ̃) + (b ± θ̃_b).

    W's perturbation is generated in-kernel (never materialized); the bias
    falls back to a materialized θ̃.  ``ids`` is the leaf-id subtree aligned
    with ``p``; ``layer`` the stacked-bank slice index (or None).
    """
    if probe is None:
        return tuple(dense(p, x) for x in xs)
    ctx = probe.ctx
    w = p["w"]
    lseed = probe.lseed(ids["w"])
    if layer is not None:
        lseed = pert.shifted_leaf_seed(
            lseed, _stream_offset(layer, w.shape[-2] * w.shape[-1]))
    if ctx.is_pair:
        ys = kops.perturbed_matmul_pair(
            xs[0], xs[1], w, lseed, dtheta=ctx.dtheta, impl=ctx.impl)
    else:
        ys = tuple(
            kops.perturbed_matmul(
                x, w, lseed, dtheta=ctx.dtheta, sign=s, impl=ctx.impl)
            for x, s in zip(xs, ctx.signs))
    if "b" in p:
        bs = pleaf(p["b"], ids["b"], probe, layer=layer)
        ys = tuple(y + b for y, b in zip(ys, bs))
    return tuple(ys)


def prmsnorm(p, xs, ids, probe, *, layer=None, eps=1e-5):
    """Per-stream rmsnorm with the scale leaf perturbed (materialized)."""
    scales = pleaf(p["scale"], ids["scale"], probe, layer=layer)
    return tuple(rmsnorm({"scale": sc}, x, eps)
                 for sc, x in zip(scales, xs))


@jax.named_scope("repro.model.mlp")
def pglu_mlp(p, xs, ids, probe, layer=None):
    """Perturbable SwiGLU MLP over the streams ``xs``."""
    gs = pdense(p["gate"], xs, ids["gate"], probe, layer=layer)
    us = pdense(p["up"], xs, ids["up"], probe, layer=layer)
    hs = tuple(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
               for g, u, x in zip(gs, us, xs))
    return pdense(p["down"], hs, ids["down"], probe, layer=layer)


# --- convolutions for the paper-scale CNNs ---------------------------------


def conv2d_init(key, kh, kw, c_in, c_out, dtype=jnp.float32):
    scale = 1.0 / np.sqrt(kh * kw * c_in)
    return {
        "w": (jax.random.normal(key, (kh, kw, c_in, c_out), jnp.float32)
              * scale).astype(dtype),
        "b": jnp.zeros((c_out,), dtype),
    }


def conv2d(p, x, *, stride=1, padding="SAME"):
    """x: [B, H, W, C] NHWC."""
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + p["b"]


def maxpool2(x):
    """2×2 max-pool, stride 2. x: [B, H, W, C]."""
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )
