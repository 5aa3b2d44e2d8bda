"""Pallas TPU kernel: causal multi-head latent attention in one pass.

Expand-form MLA (``models/mla.py``, DeepSeek-V3) gives every query head
its own key head: keys are each head's ``k_nope`` beside a rope key that
all heads share, queries are ``q_nope`` beside ``q_rope``, and the value
width may differ from the query/key width (V3: 128 + 64 against 128).
Blocked causal attention in XLA writes each [bq, bk] score block to HBM
and reads it back for the mask, the max, the exp and the sum, and again
for p·v.  Here a block's scores live only in VMEM: the online softmax
keeps m, l and the output accumulator in f32 scratch across the key
blocks of one query block.

Grid: (batch, head, query block, key block), the key blocks innermost.
A key block wholly above the diagonal is skipped (``pl.when``), and its
index map is clamped to the query block's last needed key block, so the
pipeline fetches nothing for it.  Only blocks the diagonal cuts build a
mask.

Operands are read where the model writes them: ``kv`` is ``wkv_b``'s
output [B, S, H·(dn + dv)] (each head's k_nope then v), ``k_rope`` the
shared [B, S, dr] key, and the output goes out as [B, S, H·dv], the
input of ``wo``.  Only q comes heads-major, [B, H, S, dn + dr], since the
rope has to be applied to it first.

Arithmetic: q·kᵀ = q_nope·k_nopeᵀ + q_rope·k_ropeᵀ and p·v run on the MXU
with operands in the inputs' dtype (p is cast to it) and f32
accumulation; the scores are multiplied by ``scale`` in f32; m, l and acc
update as ``models.attention``'s online softmax does, and the output is
acc / l.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .perturbed_matmul import VMEM_BUDGET, VMEM_LIMIT

NEG_INF = -1e30     # as models.attention's: masked rows stay NaN-free
_LANE = 128         # m and l are kept lane-replicated, [bq, 128]
_BQ_MAX = 1024      # query rows per block
_BK_MAX = 512       # keys per block


def vmem_bytes(bq, bk, dq, dkv, dr, dv, itemsize):
    """VMEM one grid step holds: the double-buffered q [bq, dq], kv
    [bk, dkv], k_rope [bk, dr] and output [bq, dv] blocks, the f32 m, l
    and acc scratch, and four f32 [bq, bk] temporaries (scores, p, the
    mask's row and column indices)."""
    blocks = 2 * itemsize * (bq * dq + bk * (dkv + dr) + bq * dv)
    return blocks + 4 * bq * (2 * _LANE + dv) + 4 * 4 * bq * bk


def _pow2_at_least(n):
    return 1 << max(n - 1, 0).bit_length()


def pick_blocks(s, dq, dkv, dr, dv, itemsize):
    """(bq, bk) for a sequence of ``s``: powers of two up to the caps (the
    smallest one that holds the whole sequence where that is less), the
    larger halved until ``vmem_bytes`` fits ``VMEM_BUDGET``."""
    bq = max(16, min(_BQ_MAX, _pow2_at_least(s)))
    bk = max(16, min(_BK_MAX, _pow2_at_least(s)))
    while vmem_bytes(bq, bk, dq, dkv, dr, dv, itemsize) > VMEM_BUDGET:
        if bq >= bk:
            bq //= 2
        else:
            bk //= 2
    return bq, bk


def _last_block(i, bq, bk):
    """The last key block query block ``i`` needs (causal)."""
    return (i * bq + bq - 1) // bk


def _lanes(x, n):
    """A lane-replicated [rows, 128] statistic at width ``n``."""
    if n % _LANE == 0:
        return jnp.tile(x, (1, n // _LANE))
    return x[:, :1]


def _kernel(q_ref, kv_ref, kr_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, bq, bk, dn, nk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def visit(masked):
        q = q_ref[0, 0]
        kv = kv_ref[0]
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q[:, :dn], kv[:, :dn], nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q[:, dn:], kr_ref[0], nt,
                                   preferred_element_type=jnp.float32))
        s = s * scale
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bk))
        corr = jnp.exp(m_prev - m_next)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        v = kv[:, dn:]
        acc_ref[...] = acc_ref[...] * _lanes(corr, v.shape[1]) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    below = j * bk + bk - 1 <= i * bq        # wholly below the diagonal
    needed = j <= _last_block(i, bq, bk)
    pl.when(below)(lambda: visit(False))
    pl.when(needed & jnp.logical_not(below))(lambda: visit(True))

    @pl.when(j == nk - 1)
    def _():
        dv = acc_ref.shape[1]
        o_ref[0] = (acc_ref[...] / _lanes(l_ref[...], dv)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "blocks", "interpret"))
def mla_flash_attention(q, kv, k_rope, *, scale, blocks=None,
                        interpret=False):
    """Causal attention of q [B, H, S, dn + dr] (heads-major, rope applied)
    against kv [B, S, H·(dn + dv)] (each head's k_nope then v) and the
    shared rope key k_rope [B, S, dr]: softmax(scale·q·kᵀ)·v as
    [B, S, H·dv] in q's dtype.

    ``blocks`` (bq, bk) overrides ``pick_blocks``.  A sequence that is not
    a multiple of the blocks is end-padded: padded keys lie after every
    real query, so the causal mask hides them, and padded query rows are
    dropped.
    """
    b, h, s, dq = q.shape
    dr = k_rope.shape[-1]
    dkv = kv.shape[-1] // h
    dn = dq - dr
    dv = dkv - dn
    assert kv.shape == (b, s, h * dkv) and k_rope.shape == (b, s, dr), (
        q.shape, kv.shape, k_rope.shape)
    bq, bk = blocks or pick_blocks(s, dq, dkv, dr, dv, q.dtype.itemsize)
    pad = -s % max(bq, bk)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv, k_rope = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                      for x in (kv, k_rope))
    nq, nk = (s + pad) // bq, (s + pad) // bk

    def key_block(bi, hi, i, j):
        return bi, jnp.minimum(j, _last_block(i, bq, bk)), hi

    kernel = functools.partial(_kernel, scale=float(scale), bq=bq, bk=bk,
                               dn=dn, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dq), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec((1, bk, dkv), key_block),
            pl.BlockSpec((1, bk, dr), lambda bi, hi, i, j: key_block(
                bi, 0, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda bi, hi, i, j: (bi, i, hi)),
        out_shape=jax.ShapeDtypeStruct((b, s + pad, h * dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, _LANE), jnp.float32),
                        pltpu.VMEM((bq, _LANE), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="mla_flash_attention",
    )(q, kv, k_rope)
    return out[:, :s] if pad else out
