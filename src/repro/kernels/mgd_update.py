"""Pallas TPU kernel: fused scalar-replay MGD parameter update.

Applies the τ_θ-window update of the scalar-replay mode in one pass over W:

    W ← W − (η/Δθ) · Σ_j  c̃_j · sign(h(idx, lseed_j))

The per-window-step leaf seeds (lseed_j) and cost scalars (c̃_j) live in SMEM
(scalar-prefetch); the J sign regenerations happen in VMEM against the
already-resident W tile.  HBM traffic is therefore read-W + write-W — the
same bytes as a plain SGD update, independent of the window length J — which
is the memory-roofline form of the paper's "no per-parameter gradient memory"
claim for τ_θ > τ_p hardware.

Grid: (K/bk, N/bn); the J-loop is an in-register fori_loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .perturbed_matmul import _index_signs, _tile_index


def _kernel(lseeds_ref, coefs_ref, w_ref, o_ref, *,
            scale, bk, bn, n_cols, window):
    i = pl.program_id(0)
    j = pl.program_id(1)
    idx_g = _tile_index(i * bk, j * bn, bk, bn, n_cols)

    def body(t, acc):
        sgn = _index_signs(idx_g, lseeds_ref[t])
        return acc + coefs_ref[t] * sgn

    acc = jax.lax.fori_loop(
        0, window, body, jnp.zeros((bk, bn), jnp.float32)
    )
    o_ref[...] = (w_ref[...].astype(jnp.float32) - scale * acc).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("eta", "dtheta", "bk", "bn", "interpret",
                              "n_cols")
)
def mgd_update(
    w: jnp.ndarray,        # [K, N] parameter matrix
    lseeds: jnp.ndarray,   # [J] uint32 — leaf_seed per window step
    coefs: jnp.ndarray,    # [J] f32   — C̃ scalar per window step
    *,
    eta: float,
    dtheta: float,
    bk: int = 256,
    bn: int = 256,
    interpret: bool = False,
    n_cols: int | None = None,
) -> jnp.ndarray:
    """W − (η/Δθ)·Σ_j coefs[j]·signs_j, fused; returns the updated W.

    ``n_cols`` overrides the sign-indexing row stride (the unpadded N) when
    W arrives zero-padded on its last dim — see perturbed_matmul.
    """
    kdim, n = w.shape
    bk, bn = min(bk, kdim), min(bn, n)
    assert kdim % bk == 0 and n % bn == 0, (w.shape, bk, bn)
    window = lseeds.shape[0]
    assert coefs.shape == (window,)

    kernel = functools.partial(
        _kernel, scale=float(eta) / float(dtheta),
        bk=bk, bn=bn, n_cols=n_cols or n, window=window,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(kdim // bk, n // bn),
            in_specs=[pl.BlockSpec((bk, bn), lambda i, j, *_: (i, j))],
            out_specs=pl.BlockSpec((bk, bn), lambda i, j, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((kdim, n), w.dtype),
        interpret=interpret,
    )(jnp.asarray(lseeds, jnp.uint32), jnp.asarray(coefs, jnp.float32), w)


# ---------------------------------------------------------------------------
# Exact-order window update (the optimizer's fused path)
# ---------------------------------------------------------------------------
#
# The kernel above computes sum-then-subtract, which is the natural fused
# form but NOT the floating-point order of the reference optimizer
# (core/mgd.py applies the window sequentially:
#     W ← W + a_j·θ̃_j,  θ̃_j = Δθ·sign_j,  one axpy per window step).
# ``mgd_update_window`` reproduces that exact association —
#     W ← W + α·((Δθ·sign_j)·coef_j)   for j = 0..J−1, in order —
# so the fused optimizer path is bit-identical (f32) to the materializing
# path while still paying only read-W + write-W in HBM traffic.


def _window_kernel(lseeds_ref, terms_ref, w_ref, o_ref, *,
                   bk, bn, n_cols, window):
    i = pl.program_id(0)
    j = pl.program_id(1)
    idx_g = _tile_index(i * bk, j * bn, bk, bn, n_cols)

    def body(t, w32):
        # sign-LAST: sgn = ±1 makes the multiply feeding the add exact,
        # so FMA contraction of mul+add cannot move the result off the
        # reference optimizer's two-rounding chain
        return w32 + _index_signs(idx_g, lseeds_ref[t]) * terms_ref[t]

    w32 = jax.lax.fori_loop(
        0, window, body, w_ref[...].astype(jnp.float32)
    )
    o_ref[...] = w32.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("alpha", "dtheta", "bk", "bn", "interpret",
                              "n_cols")
)
def mgd_update_window(
    w: jnp.ndarray,        # [K, N] parameter matrix
    lseeds: jnp.ndarray,   # [J] uint32 — leaf_seed per window step
    coefs: jnp.ndarray,    # [J] f32   — per-step scalar coefficient
    *,
    alpha: float,
    dtheta: float,
    bk: int = 256,
    bn: int = 256,
    interpret: bool = False,
    n_cols: int | None = None,
) -> jnp.ndarray:
    """W + α·Σ_j (Δθ·sign_j)·coefs[j], applied sequentially in j.

    Bit-exact (f32) fused form of the optimizer's per-step axpy chain; the
    coefficients carry whatever scalar the caller's order requires
    (C̃/Δθ² for τ_θ=1 with α=−η; −η·C̃/Δθ² for replay with α=1).
    """
    kdim, n = w.shape
    bk, bn = min(bk, kdim), min(bn, n)
    assert kdim % bk == 0 and n % bn == 0, (w.shape, bk, bn)
    window = lseeds.shape[0]
    assert coefs.shape == (window,)
    # association mirrors tree_scale→tree_axpy: α·((Δθ·sgn)·coef) =
    # sgn·(α·(Δθ·coef)) exactly (sgn = ±1 commutes through both
    # roundings).  The J scalars are computed here, outside the kernel
    # (Mosaic cannot lower optimization_barrier); the barriers keep XLA
    # from merging the α and Δθ constants into one factor.
    pin = jax.lax.optimization_barrier
    terms = pin(jnp.float32(alpha) * pin(
        jnp.float32(dtheta) * jnp.asarray(coefs, jnp.float32)))

    kernel = functools.partial(
        _window_kernel, bk=bk, bn=bn, n_cols=n_cols or n, window=window,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(kdim // bk, n // bn),
            in_specs=[pl.BlockSpec((bk, bn), lambda i, j, *_: (i, j))],
            out_specs=pl.BlockSpec((bk, bn), lambda i, j, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((kdim, n), w.dtype),
        interpret=interpret,
    )(jnp.asarray(lseeds, jnp.uint32), terms, w)
