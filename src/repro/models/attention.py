"""Blockwise (flash-style) causal attention in pure JAX.

Grouped-query attention is computed natively in grouped layout — KV heads
are never materialized at Q-head multiplicity, so GQA's KV memory saving is
real, not cosmetic.

Two exact implementations:

* ``masked``   — scan over Q blocks × all KV blocks with causal masking.
  Simple; wastes ~2× FLOPs on fully-masked upper-triangle blocks.
* ``balanced`` — pairs Q block i with Q block n−1−i so every scan step does
  a constant (n+1) KV-block visits with no masked-block waste.  ~2× fewer
  attention FLOPs at long sequence; bit-compatible with ``masked`` (tested).

Both use online-softmax accumulation in f32, O(S·block) memory.
``decode_attention`` handles the single-token KV-cache path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


def _block_scores(qb, kb, scale):
    """qb: [B, bq, KVH, G, D], kb: [B, bk, KVH, D] → [B, KVH, G, bq, bk] f32."""
    return jnp.einsum(
        "bqhgd,bkhd->bhgqk",
        qb.astype(jnp.float32), kb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale


def _block_values(p, vb):
    """p: [B, KVH, G, bq, bk] f32, vb: [B, bk, KVH, D] → [B, bq, KVH, G, D]."""
    return jnp.einsum(
        "bhgqk,bkhd->bqhgd", p, vb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def _online_update(carry, qb, kb, vb, mask, scale):
    """One online-softmax accumulation step (all f32)."""
    m, l, acc = carry
    s = _block_scores(qb, kb, scale)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * _to_bqhgd(corr)[..., None] + _block_values(p, vb)
    return m_new, l_new, acc_new


def _to_bqhgd(x):
    """[B, KVH, G, bq] → [B, bq, KVH, G] (align stats with value layout)."""
    return jnp.transpose(x, (0, 3, 1, 2))


def _finish(m, l, acc, dtype):
    out = acc / _to_bqhgd(l)[..., None]
    return out.astype(dtype)


def chunked_causal_attention(
    q: jnp.ndarray,   # [B, S, H, D]
    k: jnp.ndarray,   # [B, S, KVH, D]
    v: jnp.ndarray,   # [B, S, KVH, D]
    *,
    q_block: int = 512,
    kv_block: int = 512,
    impl: str = "masked",
    scale=None,
) -> jnp.ndarray:
    """Exact causal attention, O(S·block) memory.  Returns [B, S, H, Dv].

    V's head dim may differ from Q/K's (MLA uses 192/128).  ``scale``
    multiplies the scores (default 1/√D).
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    dv = v.shape[-1]
    g = h // kvh
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    q_block = min(q_block, s)
    kv_block = min(kv_block, s)
    if s % q_block or s % kv_block:
        # end-padding is exact under the causal mask: padded keys sit at
        # positions after every real query; padded query rows are dropped.
        blk = max(q_block, kv_block)
        pad = blk - s % blk
        padded = [jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                  for x in (q, k, v)]
        out = chunked_causal_attention(
            *padded, q_block=q_block, kv_block=kv_block, impl=impl,
            scale=scale)
        return out[:, :s]
    qg = q.reshape(b, s, kvh, g, d)

    if impl == "balanced" and (s // q_block) % 2 == 0:
        return _balanced(qg, k, v, q_block, scale).reshape(b, s, h, dv)
    # balanced pairs blocks; an odd count runs masked (the same values)
    assert impl in ("masked", "balanced"), impl
    nq, nk = s // q_block, s // kv_block

    qs = qg.reshape(b, nq, q_block, kvh, g, d)
    ks = k.reshape(b, nk, kv_block, kvh, d)
    vs = v.reshape(b, nk, kv_block, kvh, dv)

    def per_q_block(_, iq):
        qb = qs[:, iq]
        qpos = iq * q_block + jnp.arange(q_block)

        def inner(carry, jk):
            j, kb, vb = jk
            kpos = j * kv_block + jnp.arange(kv_block)
            mask = kpos[None, :] <= qpos[:, None]            # [bq, bk]
            mask = mask[None, None, None]                    # [1,1,1,bq,bk]
            return _online_update(carry, qb, kb, vb, mask, scale), None

        init = (
            jnp.full((b, kvh, g, q_block), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, q_block), jnp.float32),
            jnp.zeros((b, q_block, kvh, g, dv), jnp.float32),
        )
        xs = (jnp.arange(nk), jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0))
        (m, l, acc), _ = jax.lax.scan(inner, init, xs)
        return None, _finish(m, l, acc, q.dtype)

    _, outs = jax.lax.scan(per_q_block, None, jnp.arange(nq))
    # outs: [nq, B, bq, KVH, G, Dv] → [B, S, H, Dv]
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, dv)


def _balanced(qg, k, v, blk, scale):
    """Load-balanced exact causal attention (q_block == kv_block == blk).

    Q block i pairs with Q block n−1−i; each pair visits exactly n+1 KV
    blocks, so there are no masked-out block matmuls and the total block
    count is n(n+1)/2 + n/2 ≈ half of the masked implementation's n².
    """
    b, s, kvh, g, d = qg.shape
    dv = v.shape[-1]
    n = s // blk
    assert n % 2 == 0, f"balanced impl needs an even number of blocks, got {n}"
    ks = jnp.moveaxis(k.reshape(b, n, blk, kvh, d), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, n, blk, kvh, dv), 1, 0)
    qs = jnp.moveaxis(qg.reshape(b, n, blk, kvh, g, d), 1, 0)

    def per_pair(_, p):
        i_lo, i_hi = p, n - 1 - p
        q_lo, q_hi = qs[i_lo], qs[i_hi]

        def inner(carry, t):
            (m, l, acc) = carry
            use_lo = t <= p
            iq = jnp.where(use_lo, i_lo, i_hi)
            j = jnp.where(use_lo, t, t - (p + 1))
            qb = jnp.where(use_lo, q_lo, q_hi)
            kb = jax.lax.dynamic_index_in_dim(ks, j, 0, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vs, j, 0, keepdims=False)
            qpos = iq * blk + jnp.arange(blk)
            kpos = j * blk + jnp.arange(blk)
            mask = (kpos[None, :] <= qpos[:, None])[None, None, None]
            half = jnp.where(use_lo, 0, 1)
            sel = lambda c: jax.lax.dynamic_index_in_dim(c, half, 0, keepdims=False)
            upd = _online_update(
                (sel(m), sel(l), sel(acc)), qb, kb, vb, mask, scale)
            put = lambda c, u: jax.lax.dynamic_update_index_in_dim(
                c, u, half, 0)
            return (put(m, upd[0]), put(l, upd[1]), put(acc, upd[2])), None

        init = (
            jnp.full((2, b, kvh, g, blk), NEG_INF, jnp.float32),
            jnp.zeros((2, b, kvh, g, blk), jnp.float32),
            jnp.zeros((2, b, blk, kvh, g, dv), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(inner, init, jnp.arange(n + 1))
        out = jax.vmap(lambda mm, ll, aa: _finish(mm, ll, aa, qg.dtype))(m, l, acc)
        return None, out   # [2, B, blk, KVH, G, D]

    _, outs = jax.lax.scan(per_pair, None, jnp.arange(n // 2))
    # outs: [n/2, 2, B, blk, kvh, g, d]; pair p wrote blocks (p, n-1-p)
    order = np.empty((n,), np.int32)
    for p in range(n // 2):
        order[p] = p * 2          # position of block p in flattened outs
        order[n - 1 - p] = p * 2 + 1
    flat = outs.reshape(n, b, blk, kvh, g, dv)
    flat = jnp.take(flat, jnp.asarray(order), axis=0)
    return jnp.moveaxis(flat, 0, 1).reshape(b, s, kvh, g, dv)


def decode_attention(
    q1: jnp.ndarray,       # [B, 1, H, D] — the new token's query
    k_cache: jnp.ndarray,  # [B, S_max, KVH, D]
    v_cache: jnp.ndarray,  # [B, S_max, KVH, D]
    length,                # int32 — valid cache length (new token included)
) -> jnp.ndarray:
    """Single-token attention against the cache.  Returns [B, 1, H, Dv]."""
    b, _, h, d = q1.shape
    kvh = k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = h // kvh
    scale = 1.0 / np.sqrt(d)
    qg = q1.reshape(b, kvh, g, d)
    s = jnp.einsum("bhgd,bshd->bhgs",
                   qg.astype(jnp.float32), k_cache.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k_cache.shape[1])
    s = jnp.where(pos[None, None, None, :] < length, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, dv).astype(q1.dtype)
