"""Pure-jnp oracles for the Pallas kernels.

Bit-exactness contract: these use the *same* murmur3 counter hash via
``repro.core.perturbations``, with the same row-major linear indexing, so
the Pallas kernels (interpret or TPU) must match them exactly on the sign
pattern and to float tolerance on the accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.perturbations import rademacher_signs


def leaf_signs(lseed, shape):
    """±1 f32 signs for a whole leaf of ``shape`` (row-major indexing)."""
    n = 1
    for s in shape:
        n *= s
    idx = jax.lax.iota(jnp.uint32, n)
    return rademacher_signs(lseed, idx).reshape(shape)


def perturbed_matmul_ref(x, w, lseed, *, dtheta, sign=1.0, out_dtype=None):
    """y = x @ (W + sign·Δθ·signs) — materializes θ̃ (the thing the Pallas
    kernel avoids); used as the correctness oracle."""
    signs = leaf_signs(jnp.asarray(lseed, jnp.uint32), w.shape)
    wp = w.astype(jnp.float32) + (sign * dtheta) * signs
    y = x.astype(jnp.float32) @ wp
    return y.astype(out_dtype or x.dtype)


def perturbed_matmul_pair_ref(xp, xm, w, lseed, *, dtheta, out_dtype=None):
    """(xp @ (W+θ̃), xm @ (W−θ̃)) — two materialized matmuls sharing θ̃."""
    yp = perturbed_matmul_ref(xp, w, lseed, dtheta=dtheta, sign=1.0,
                              out_dtype=out_dtype)
    ym = perturbed_matmul_ref(xm, w, lseed, dtheta=dtheta, sign=-1.0,
                              out_dtype=out_dtype)
    return yp, ym


def mla_attention_ref(q, kv, k_rope, *, scale):
    """Causal MLA attention in f32 with every score materialized:
    ``ops.mla_flash_attention``'s operands and result."""
    f32 = jnp.float32
    b, h, s, dq = q.shape
    dr = k_rope.shape[-1]
    kv = kv.reshape(b, s, h, -1).astype(f32)
    k = jnp.concatenate([kv[..., :dq - dr], jnp.broadcast_to(
        k_rope[:, :, None].astype(f32), (b, s, h, dr))], axis=-1)
    hi = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("bhqd,bkhd->bhqk", q.astype(f32), k,
                        precision=hi) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dq - dr:], precision=hi)
    return out.reshape(b, s, -1).astype(q.dtype)


def mgd_update_ref(w, lseeds, coefs, *, eta, dtheta):
    """W − (η/Δθ)·Σ_j coefs[j]·signs_j — materializes every window sign."""
    acc = jnp.zeros(w.shape, jnp.float32)
    for j in range(lseeds.shape[0]):
        acc = acc + coefs[j] * leaf_signs(lseeds[j], w.shape)
    return (w.astype(jnp.float32) - (eta / dtheta) * acc).astype(w.dtype)


def mgd_update_window_ref(w, lseeds, coefs, *, alpha, dtheta):
    """Sequential-axpy window update, association identical to the kernel:
    W ← W + α·((Δθ·sign_j)·coefs[j]) for j = 0..J−1 in order."""
    w32 = w.astype(jnp.float32)
    for j in range(lseeds.shape[0]):
        sgn = leaf_signs(lseeds[j], w.shape)
        w32 = w32 + alpha * ((dtheta * sgn) * coefs[j])
    return w32.astype(w.dtype)
