"""Pallas TPU kernel: matmul with in-kernel Rademacher perturbation.

Computes  y = x @ (W + σ·Δθ·sign(h(idx, lseed)))  without ever materializing
the perturbation θ̃ in HBM: the ±1 signs are regenerated inside VMEM from the
same murmur3 counter hash the host uses (``repro.core.perturbations``), tile
by tile, while the W tile is already resident for the MXU matmul.

This is the TPU adaptation of the paper's "perturbation generated locally at
the parameter" (an LFSR per synapse in hardware): the synapse-local noise
source becomes a hash of the weight's linear index, evaluated next to the
compute unit.  Memory-roofline effect: an MGD probe step reads W exactly
once per matmul, the same HBM bytes as inference — versus 2× for an
implementation that materializes θ+θ̃ (measured in EXPERIMENTS.md §Perf).

σ ∈ {+1, −1} selects the antithetic probe for central differences.

Grid: (M/bm, N/bn, K/bk), K innermost; f32 accumulation in VMEM scratch.
Tile defaults are MXU-aligned (128×128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# numpy scalars: static constants, never captured as traced values
_GOLDEN = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)


def _fmix32(x):
    """murmur3 finalizer — must stay bit-identical to perturbations._fmix32."""
    x = x ^ (x >> np.uint32(16))
    x = x * _M1
    x = x ^ (x >> np.uint32(13))
    x = x * _M2
    x = x ^ (x >> np.uint32(16))
    return x


def _tile_index(k0, n0, bk, bn, n_cols):
    """uint32 linear indices of the W tile whose top-left element is
    (k0, n0): W[r, c] flattens row-major to r*N + c — identical to the
    ``lax.iota`` indexing of the host-side generator.  ``n_cols`` is the
    UNPADDED row stride (see perturbed_matmul's docstring)."""
    # k0/n0 are traced (program_id·tile) — convert via astype, not np.uint32
    rows = (jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0)
            + jnp.asarray(k0, jnp.int32).astype(jnp.uint32))
    cols = (jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1)
            + jnp.asarray(n0, jnp.int32).astype(jnp.uint32))
    return rows * np.uint32(n_cols) + cols


def _index_signs(idx, lseed):
    """±1 f32 Rademacher signs for linear indices ``idx`` under ``lseed``
    — the ONE in-kernel copy of the host hash (perturbations.rademacher_
    signs); every kernel that regenerates θ̃ must go through here."""
    h = _fmix32(idx * _GOLDEN + lseed)
    # the top bit is the int32 sign bit; Mosaic has no uint32 → f32 cast,
    # and exact ±1 is the same value the host's 1 − 2·bit gives
    neg = jax.lax.bitcast_convert_type(h, jnp.int32) < 0
    return jnp.where(neg, jnp.float32(-1.0), jnp.float32(1.0))


def _tile_signs(lseed, k0, n0, bk, bn, n_cols):
    """±1 f32 signs for the W tile whose top-left element is (k0, n0)."""
    return _index_signs(_tile_index(k0, n0, bk, bn, n_cols), lseed)


def _kernel(lseed_ref, x_ref, w_ref, o_ref, acc_ref, *,
            dtheta, sign, bk, bn, n_cols, k_tiles):
    k = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lseed = lseed_ref[0]
    signs = _tile_signs(lseed, k * bk, j * bn, bk, bn, n_cols)
    w = w_ref[...].astype(jnp.float32) + (sign * dtheta) * signs
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == k_tiles - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("dtheta", "sign", "bm", "bn", "bk", "out_dtype",
                     "interpret", "n_cols"),
)
def perturbed_matmul(
    x: jnp.ndarray,            # [M, K]
    w: jnp.ndarray,            # [K, N]
    lseed: jnp.ndarray,        # uint32 scalar — leaf_seed(seed, step, leaf_id)
    *,
    dtheta: float,
    sign: float = 1.0,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
    n_cols: int | None = None,
) -> jnp.ndarray:
    """y = x @ (W + sign·Δθ·rademacher(lseed)) with fused sign generation.

    ``n_cols`` overrides the row stride used for sign indexing — pass the
    *unpadded* N when W has been zero-padded on its last dim so the signs of
    the real elements keep their original linear indices (padded rows/cols
    feed only discarded outputs or zero x columns, so their garbage signs
    are harmless).
    """
    m, kdim = x.shape
    k2, n = w.shape
    assert kdim == k2, (x.shape, w.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (
        f"shapes ({m},{kdim})x({kdim},{n}) not divisible by tile "
        f"({bm},{bn},{bk}); pad upstream")
    out_dtype = out_dtype or x.dtype
    k_tiles = kdim // bk

    grid = (m // bm, n // bn, k_tiles)
    kernel = functools.partial(
        _kernel, dtheta=float(dtheta), sign=float(sign),
        bk=bk, bn=bn, n_cols=n_cols or n, k_tiles=k_tiles,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(jnp.asarray(lseed, jnp.uint32).reshape(1), x, w)


# ---------------------------------------------------------------------------
# Antithetic pair: y± = x± @ (W ± Δθ·signs), one HBM read of W per pair
# ---------------------------------------------------------------------------


def _pair_kernel(lseed_ref, xp_ref, xm_ref, w_ref, op_ref, om_ref,
                 accp_ref, accm_ref, *, dtheta, bk, bn, n_cols, k_tiles):
    k = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        accp_ref[...] = jnp.zeros_like(accp_ref)
        accm_ref[...] = jnp.zeros_like(accm_ref)

    lseed = lseed_ref[0]
    signs = _tile_signs(lseed, k * bk, j * bn, bk, bn, n_cols)
    w = w_ref[...].astype(jnp.float32)
    theta = dtheta * signs
    dn = (((1,), (0,)), ((), ()))
    accp_ref[...] += jax.lax.dot_general(
        xp_ref[...].astype(jnp.float32), w + theta, dn,
        preferred_element_type=jnp.float32)
    accm_ref[...] += jax.lax.dot_general(
        xm_ref[...].astype(jnp.float32), w + (-dtheta) * signs, dn,
        preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _():
        op_ref[...] = accp_ref[...].astype(op_ref.dtype)
        om_ref[...] = accm_ref[...].astype(om_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("dtheta", "bm", "bn", "bk", "out_dtype", "interpret",
                     "n_cols"),
)
def perturbed_matmul_pair(
    xp: jnp.ndarray,           # [M, K] activation stream of the +θ̃ probe
    xm: jnp.ndarray,           # [M, K] activation stream of the −θ̃ probe
    w: jnp.ndarray,            # [K, N]
    lseed: jnp.ndarray,        # uint32 scalar
    *,
    dtheta: float,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
    n_cols: int | None = None,
):
    """(xp @ (W+θ̃), xm @ (W−θ̃)) in ONE grid pass over W.

    The central-difference probe pair of MGD shares one HBM read of each W
    tile: the tile is loaded for the MXU once, the ±Δθ sign pattern is
    regenerated in VMEM, and both antithetic products accumulate in separate
    scratch.  Per probe *pair* the weight-read traffic is therefore 1× the
    inference bytes (vs 2× for two independent fused calls and ~4× for the
    materializing baseline — see EXPERIMENTS.md §Perf).

    ``xp`` and ``xm`` are the two activation streams (identical at the input
    layer, diverging after the first perturbed layer).
    """
    m, kdim = xp.shape
    assert xm.shape == xp.shape, (xp.shape, xm.shape)
    k2, n = w.shape
    assert kdim == k2, (xp.shape, w.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (
        f"shapes ({m},{kdim})x({kdim},{n}) not divisible by tile "
        f"({bm},{bn},{bk}); pad upstream")
    out_dtype = out_dtype or xp.dtype
    k_tiles = kdim // bk

    grid = (m // bm, n // bn, k_tiles)
    kernel = functools.partial(
        _pair_kernel, dtheta=float(dtheta),
        bk=bk, bn=bn, n_cols=n_cols or n, k_tiles=k_tiles,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k)),
                pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
                pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
            ],
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                            pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((m, n), out_dtype),
                   jax.ShapeDtypeStruct((m, n), out_dtype)],
        interpret=interpret,
    )(jnp.asarray(lseed, jnp.uint32).reshape(1), xp, xm, w)
