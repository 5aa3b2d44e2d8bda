"""Pallas TPU kernel: grouped probe products over a bank of held experts.

    y_s[rows of expert e] = x_s[rows of expert e] @ (W[e] + σ_s·Δθ·signs_e)

for each probe stream s (σ = +1 / −1 for the central pair), where W is a
stacked expert bank [E, K, N] and each stream's rows are laid out by
expert (``group_layout``): expert e's rows start at a row block of their
own, so every row block belongs to one expert.  The two streams route
differently (the router is perturbed too), so each has its own layout;
both are passed in scalar prefetch.  Each grid step reads W[e]'s tile
once for both streams and regenerates its signs in VMEM from the hash the
host generator uses, at the expert's offset e·K·N within the bank: the
pattern is the generator's view of the bank leaf.

Grid: (E, blocks, ⌈N/bn⌉, K/bk), K innermost.  ``blocks`` is the most
row blocks one expert can need (every token of a stream routed to it);
steps past an expert's last block in both streams do no work, and their
block indices repeat the last ones, so they move no data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import perturbations as pert
from .perturbed_matmul import VMEM_LIMIT, _GOLDEN, _tile_signs, pick_tiles

_ROW_ALIGN = 128


def row_block(rows_per_expert: float) -> int:
    """Rows per block for an expected load per expert: 1.5× the mean,
    rounded up to 128 and held within [128, 512], so a typical expert is
    one block and its W tile is read once per probe pair."""
    want = int(np.ceil(1.5 * rows_per_expert / _ROW_ALIGN)) * _ROW_ALIGN
    return int(min(max(want, _ROW_ALIGN), 4 * _ROW_ALIGN))


def layout_blocks(rows: int, n_experts: int, bm: int) -> int:
    """Row blocks of a stream's laid-out buffer that holds ``rows`` routed
    rows however they split over ``n_experts`` (each expert's group starts
    on a block of its own)."""
    return -(-rows // bm) + n_experts


def group_layout(sizes, bm: int):
    """Layout of each stream's rows by expert.  ``sizes`` [S, E] int32:
    rows of stream s routed to expert e.  Returns int32 [S, E] arrays:
    ``start`` (first block of expert e), ``nblk`` (its blocks) and
    ``last`` (the stream's last block at or before expert e, 0 before any;
    where inactive grid steps point, so they move and write nothing)."""
    nblk = (sizes + (bm - 1)) // bm
    start = jnp.cumsum(nblk, axis=1) - nblk
    end = start + nblk - 1
    last = jax.lax.cummax(jnp.where(nblk > 0, end, 0), axis=1)
    return start.astype(jnp.int32), nblk.astype(jnp.int32), \
        last.astype(jnp.int32)


def _grouped_kernel(lseed_ref, meta_ref, *refs, n_streams, n_experts,
                    signs_of, dtheta, bk, bn, n_cols, k_tiles, w_elems):
    s_n = n_streams
    x_refs, w_ref = refs[:s_n], refs[s_n]
    o_refs, acc_refs = refs[s_n + 1:2 * s_n + 1], refs[2 * s_n + 1:]
    e, i, j, k = (pl.program_id(a) for a in range(4))
    active = [i < meta_ref[(s_n + s) * n_experts + e] for s in range(s_n)]
    any_active = functools.reduce(jnp.logical_or, active)

    @pl.when(any_active)
    def _():
        lseed = (lseed_ref[0] + jnp.asarray(e, jnp.int32).astype(jnp.uint32)
                 * np.uint32(w_elems & 0xFFFFFFFF) * _GOLDEN)
        signs = _tile_signs(lseed, k * bk, j * bn, bk, bn, n_cols)
        w = w_ref[...].astype(jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        for s in range(s_n):
            @pl.when(active[s] & (k == 0))
            def _():
                acc_refs[s][...] = jnp.zeros_like(acc_refs[s])

            @pl.when(active[s])
            def _():
                acc_refs[s][...] += jax.lax.dot_general(
                    x_refs[s][...].astype(jnp.float32),
                    w + (signs_of[s] * dtheta) * signs, dn,
                    preferred_element_type=jnp.float32)

            @pl.when(active[s] & (k == k_tiles - 1))
            def _():
                o_refs[s][...] = acc_refs[s][...].astype(o_refs[s].dtype)


@functools.partial(
    jax.jit, static_argnames=("signs", "dtheta", "bm", "blocks",
                              "interpret", "name"))
def perturbed_grouped_matmul(xs, w, lseed, start, nblk, last, *, signs,
                             dtheta, bm, blocks, interpret=False,
                             name="perturbed_grouped_matmul_pair"):
    """Each stream's laid-out rows [R, K] times its expert's W[e] ± Δθ·s.

    ``xs``: one [R, K] buffer per stream (``signs`` gives each stream's
    σ), R a multiple of ``bm``; ``w``: [E, K, N]; ``start``/``nblk``/
    ``last``: [S, E] int32 from ``group_layout``; ``blocks``: the grid's
    row blocks per expert.  Rows of blocks that no expert owns are left
    unwritten."""
    n_e, kdim, n = w.shape
    s_n = len(xs)
    _, bk, bn = pick_tiles(bm, kdim, n, max(xs[0].dtype.itemsize,
                                            w.dtype.itemsize))
    assert kdim % bk == 0, (kdim, bk)
    kt, jt = kdim // bk, pl.cdiv(n, bn)
    meta = jnp.concatenate([start.reshape(-1), nblk.reshape(-1),
                            last.reshape(-1)]).astype(jnp.int32)

    def x_map(s):
        def f(e, i, j, k, _, m):
            act = i < m[(s_n + s) * n_e + e]
            row = jnp.where(act, m[s * n_e + e] + i, m[(2 * s_n + s) * n_e + e])
            return row, jnp.where(act, k, kt - 1)
        return f

    def o_map(s):
        def f(e, i, j, k, _, m):
            act = i < m[(s_n + s) * n_e + e]
            row = jnp.where(act, m[s * n_e + e] + i, m[(2 * s_n + s) * n_e + e])
            return row, jnp.where(act, j, jt - 1)
        return f

    def w_map(e, i, j, k, _, m):
        act = functools.reduce(jnp.logical_or, [
            i < m[(s_n + s) * n_e + e] for s in range(s_n)])
        return e, jnp.where(act, k, kt - 1), jnp.where(act, j, jt - 1)

    kernel = functools.partial(
        _grouped_kernel, n_streams=s_n, n_experts=n_e,
        signs_of=tuple(float(x) for x in signs), dtheta=float(dtheta),
        bk=bk, bn=bn, n_cols=n, k_tiles=kt, w_elems=kdim * n)
    rows = xs[0].shape[0]
    return tuple(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_e, blocks, jt, kt),
            in_specs=[pl.BlockSpec((bm, bk), x_map(s)) for s in range(s_n)]
            + [pl.BlockSpec((pl.Squeezed(), bk, bn), w_map)],
            out_specs=[pl.BlockSpec((bm, bn), o_map(s)) for s in range(s_n)],
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] * s_n,
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, n), xs[0].dtype)] * s_n,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(jnp.asarray(lseed, jnp.uint32).reshape(1), meta, *xs, w))


def grouped_matmul_ref(xs, w, lseed, start, nblk, *, signs, dtheta, bm):
    """Plain form of ``perturbed_grouped_matmul``: block by block, the
    block's expert's W[e] + σ·Δθ·signs_e materialized at the bank offset
    e·K·N (the host generator's indices); blocks no expert owns give 0.
    ``lseed`` None is the unperturbed product."""
    n_e, kdim, n = w.shape
    rows = xs[0].shape[0]
    nb = rows // bm
    b = jnp.arange(nb, dtype=jnp.int32)
    outs = []
    for s, x in enumerate(xs):
        owner = (b[:, None] >= start[s][None, :]) & (
            b[:, None] < (start[s] + nblk[s])[None, :])       # [nb, E]
        expert = jnp.argmax(owner, axis=1)
        used = jnp.any(owner, axis=1)

        def one(args, x=x, sign=signs[s]):
            xb, e, u = args

            def product():
                we = jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
                if lseed is None:
                    return xb @ we
                idx = (jax.lax.iota(jnp.uint32, kdim * n)
                       + e.astype(jnp.uint32) * np.uint32(kdim * n))
                sg = pert.rademacher_signs(
                    jnp.asarray(lseed, jnp.uint32), idx).reshape(kdim, n)
                wp = we.astype(jnp.float32) + (sign * dtheta) * sg
                return (xb.astype(jnp.float32) @ wp).astype(xb.dtype)

            return jax.lax.cond(u, product,
                                lambda: jnp.zeros((bm, n), x.dtype))

        y = jax.lax.map(one, (x.reshape(nb, bm, kdim), expert, used))
        outs.append(y.reshape(rows, n))
    return tuple(outs)
