"""Central-pair MGD with counter-hashed Rademacher perturbations, written
out plainly (arXiv:2303.03986, Algorithm 1 in central-difference form).

At step n the probe perturbs every parameter i by ±Δθ·s_i, where the
sign s_i is the top bit of a murmur3 hash of (probe seed, n, leaf id,
linear index i), reads the two costs c± = C(θ ± Δθ·s), and applies

    θ ← θ − η · C̃ · s / Δθ,     C̃ = (c⁺ − c⁻) / 2,

rounding once to the parameters' dtype.  The hash is part of the
algorithm's definition (it is how a chip regenerates its perturbation
instead of storing it), so it is restated here bit for bit.

Over a mesh of k pods (the paper's multiplexing across hardware), pod i
draws its own perturbation from its own probe seed (``pod_seed``) on its
own rows and reads its own C̃_i; every pod then applies all k:

    θ ← θ − η · Σ_i (C̃_i / k) · s_i / Δθ,

summed in pod order in float32 and rounded once.  With k = 1 this is the
single-probe step above.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = np.uint32(0x9E3779B9)
M1 = np.uint32(0x85EBCA6B)
M2 = np.uint32(0xC2B2AE35)


def fmix32(x):
    """murmur3's 32-bit finalizer."""
    x = x ^ (x >> np.uint32(16))
    x = x * M1
    x = x ^ (x >> np.uint32(13))
    x = x * M2
    return x ^ (x >> np.uint32(16))


def leaf_seed(probe_seed, step, leaf_id):
    """uint32 seed of one leaf at one step."""
    s = jnp.uint32(probe_seed) * GOLDEN + jnp.uint32(leaf_id)
    s = fmix32(s)
    s = s + jnp.asarray(step, jnp.uint32) * M1
    return fmix32(s)


def signs(lseed, index):
    """±1 (float32) at the uint32 linear indices ``index`` of a leaf."""
    h = fmix32(index.astype(jnp.uint32) * GOLDEN + lseed)
    return 1.0 - 2.0 * (h >> np.uint32(31)).astype(jnp.float32)


def slice_signs(lseed, shape, offset):
    """Signs of a row-major slice of a leaf that starts at element
    ``offset`` (uint32, may be traced)."""
    n = int(np.prod(shape))
    idx = jax.lax.iota(jnp.uint32, n) + jnp.asarray(offset, jnp.uint32)
    return signs(lseed, idx).reshape(shape)


def pod_seed(probe_seed: int, pod: int) -> int:
    """Probe seed of pod ``pod``: (seed + pod·0x9E3779B9) mod 2**32, so pod
    0 keeps the run's seed."""
    return (int(probe_seed) + int(pod) * int(GOLDEN)) % 2 ** 32


@jax.jit
def _update_leaf(leaf, lseeds, coefs):
    """leaf + Σ_i coefs[i]·s_i in float32, in pod order, rounded once to
    leaf.dtype."""
    idx = jax.lax.iota(jnp.uint32, leaf.size).reshape(leaf.shape)
    acc = leaf.astype(jnp.float32)
    for i in range(coefs.shape[0]):
        acc = acc + coefs[i] * signs(lseeds[i], idx)
    return acc.astype(leaf.dtype)


def update(params, leaf_ids, probe_seeds, step, c_tildes, *, eta, dtheta):
    """One MGD update of every leaf from each pod's probe seed and C̃ (host
    floats, one per pod)."""
    k = len(c_tildes)
    coefs = jnp.asarray([-eta * c / dtheta / k for c in c_tildes],
                        jnp.float32)
    return {path: _update_leaf(
        leaf, jnp.stack([leaf_seed(s, step, leaf_ids[path])
                         for s in probe_seeds]), coefs)
        for path, leaf in params.items()}
