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

Grid: (⌈M/bm⌉, ⌈N/bn⌉, K/bk), K innermost; f32 accumulation in VMEM
scratch.  ``pick_tiles`` sizes the blocks from the call's shapes and a VMEM
budget: each W tile is read, and its signs hashed, once per row block.
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
    leaf's row stride N: a ragged last block's columns past N get indices
    that belong to other elements, but they feed only dropped outputs."""
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


VMEM_LIMIT = 64 * 2 ** 20
"""Scoped VMEM the probe kernels ask Mosaic for: above its default (16 MiB
on v5e), within every TPU core's VMEM (64 MiB on v5p and v7x, 128 MiB on
v5e and v6e)."""
VMEM_BUDGET = 56 * 2 ** 20
"""What ``pick_tiles`` lets one grid step take by ``vmem_bytes``; the rest
of ``VMEM_LIMIT`` is left to Mosaic's own scratch.  It admits (2048, 512,
1024) at bf16, the fastest tiles a TPU v5e sweep found at 2048 rows."""
_LANE = 128
_SUB = 16           # row alignment: bf16's sublane tile, a multiple of f32's
_BM_MAX = 2048      # rows per block
_BK_MAX = 512       # contraction depth per block
_BN_MAX = 2048      # output columns per block


def vmem_bytes(bm, bk, bn, itemsize):
    """VMEM one grid step of the pair kernel holds: the double-buffered
    x± [bm, bk], W [bk, bn] and y± [bm, bn] blocks, both f32 accumulators
    [bm, bn], and the f32 W tile temporaries (W, signs, W + θ̃, W − θ̃).
    The single-probe kernel holds less on the same tiles."""
    blocks = 2 * itemsize * (2 * bm * bk + bk * bn + 2 * bm * bn)
    return blocks + 4 * (2 * bm * bn + 4 * bk * bn)


def _rows(m, cap):
    """Row block: all of M up to ``cap``, else the fewest ``_SUB``-aligned
    blocks of at most ``cap``, split evenly (the last may be ragged)."""
    if m <= cap:
        return m
    units = pl.cdiv(m, _SUB)
    return _SUB * pl.cdiv(units, pl.cdiv(units, cap // _SUB))


def _cols(n, cap):
    """Column block: all of N up to ``cap``, else the multiple of 128 up
    to ``cap`` that minimises the blocks' padded width plus one lane tile
    per block (a grid step's fixed cost), the wider on a tie.  A ragged
    last block is sized by this, not by a pad of W."""
    if n <= cap:
        return n
    units = pl.cdiv(n, _LANE)
    best = min(range(cap // _LANE, 0, -1),
               key=lambda b: pl.cdiv(units, b) * (b + 1))
    return _LANE * best


def k_block(k):
    """bk for a contraction depth K: all of K up to ``_BK_MAX``, else the
    largest multiple of 128 up to it that divides K; None where there is
    none, and the caller must zero-pad K (a ragged K block would add
    garbage to every output)."""
    if k <= _BK_MAX:
        return k
    return max((b for b in range(_LANE, _BK_MAX + 1, _LANE) if k % b == 0),
               default=None)


def pick_tiles(m, k, n, itemsize):
    """(bm, bk, bn) for an [m, k] @ [k, n] probe kernel call.

    Rows first: the fewer row blocks, the fewer times each W tile is read
    from HBM and its signs hashed; up to ``_BM_MAX`` rows that is once.  bk
    is ``k_block(k)``.  bn is as wide as ``VMEM_BUDGET`` then allows, its
    cap halved down to one lane tile before the row cap is halved.  Dims
    under the caps keep whole-dim tiles.
    """
    bk = k_block(k)
    assert bk, f"K={k} needs zero-padding to a multiple of {_LANE}"
    bm_cap = _BM_MAX
    while True:
        bm = _rows(m, bm_cap)
        bn_cap = _BN_MAX
        while bn_cap >= _LANE:
            bn = _cols(n, bn_cap)
            if vmem_bytes(bm, bk, bn, itemsize) <= VMEM_BUDGET:
                return bm, bk, bn
            bn_cap //= 2
        assert bm_cap > _SUB, (m, k, n, itemsize)
        bm_cap //= 2


def _tiles(x, w):
    """``pick_tiles`` for ``x @ w``, at the wider of their dtypes."""
    return pick_tiles(x.shape[0], x.shape[1], w.shape[1],
                      max(x.dtype.itemsize, w.dtype.itemsize))


def _probe_call(kernel, xs, w, lseed, *, bm, bn, bk, out_dtype, interpret,
                name):
    """One pallas_call over a (⌈M/bm⌉, ⌈N/bn⌉, K/bk) grid: the x streams'
    blocks (i, k), W's (k, j), one f32 accumulator and one output per
    stream.  Ragged last M and N blocks read past the arrays, and only
    outputs past them see what was read; their writeback is masked."""
    m, kdim = xs[0].shape
    n = w.shape[1]
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), kdim // bk)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[x_spec] * len(xs) + [w_spec],
            out_specs=[o_spec] * len(xs),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] * len(xs),
        ),
        out_shape=[jax.ShapeDtypeStruct((m, n), out_dtype)] * len(xs),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(jnp.asarray(lseed, jnp.uint32).reshape(1), *xs, w)


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
    jax.jit, static_argnames=("dtheta", "sign", "out_dtype", "interpret"))
def perturbed_matmul(
    x: jnp.ndarray,            # [M, K]
    w: jnp.ndarray,            # [K, N]
    lseed: jnp.ndarray,        # uint32 scalar — leaf_seed(seed, step, leaf_id)
    *,
    dtheta: float,
    sign: float = 1.0,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = x @ (W + sign·Δθ·rademacher(lseed)) with fused sign generation.

    The tiles are the pair kernel's (``pick_tiles``), so one probe of a
    pair and the single-probe call sum in the same order.
    """
    kdim, n = w.shape
    assert x.shape[1] == kdim, (x.shape, w.shape)
    bm, bk, bn = _tiles(x, w)
    kernel = functools.partial(
        _kernel, dtheta=float(dtheta), sign=float(sign),
        bk=bk, bn=bn, n_cols=n, k_tiles=kdim // bk,
    )
    (y,) = _probe_call(kernel, [x], w, lseed, bm=bm, bn=bn, bk=bk,
                       out_dtype=out_dtype or x.dtype, interpret=interpret,
                       name="perturbed_matmul")
    return y


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
    jax.jit, static_argnames=("dtheta", "out_dtype", "interpret"))
def perturbed_matmul_pair(
    xp: jnp.ndarray,           # [M, K] activation stream of the +θ̃ probe
    xm: jnp.ndarray,           # [M, K] activation stream of the −θ̃ probe
    w: jnp.ndarray,            # [K, N]
    lseed: jnp.ndarray,        # uint32 scalar
    *,
    dtheta: float,
    out_dtype=None,
    interpret: bool = False,
):
    """(xp @ (W+θ̃), xm @ (W−θ̃)) in ONE grid pass over W.

    The central-difference probe pair of MGD shares one HBM read of each W
    tile per row block: the tile is loaded for the MXU once, the ±Δθ sign
    pattern is regenerated in VMEM, and both antithetic products accumulate
    in separate scratch.  With all M rows in one block (``pick_tiles``),
    the weight-read traffic per probe *pair* is 1× the inference bytes (vs
    2× for two independent fused calls and ~4× for the materializing
    baseline — see EXPERIMENTS.md §Perf).

    ``xp`` and ``xm`` are the two activation streams (identical at the input
    layer, diverging after the first perturbed layer).
    """
    assert xm.shape == xp.shape, (xp.shape, xm.shape)
    kdim, n = w.shape
    assert xp.shape[1] == kdim, (xp.shape, w.shape)
    bm, bk, bn = _tiles(xp, w)
    kernel = functools.partial(
        _pair_kernel, dtheta=float(dtheta),
        bk=bk, bn=bn, n_cols=n, k_tiles=kdim // bk,
    )
    return tuple(_probe_call(
        kernel, [xp, xm], w, lseed, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype or xp.dtype, interpret=interpret,
        name="perturbed_matmul_pair"))
