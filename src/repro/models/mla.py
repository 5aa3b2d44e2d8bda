"""Multi-head Latent Attention (DeepSeek-V3, arXiv:2412.19437).

Queries and KV are low-rank compressed; only the compressed latent c_kv
(kv_lora_rank) plus the shared decoupled-RoPE key k_rope are cached.  Two
execution forms:

* expand form (train / prefill): decompress K/V per position and run
  causal attention through the Pallas kernel
  ``kernels.flash_attention`` — matmul-friendly at full sequence length.
* absorbed form (decode): fold W_UK into the query and W_UV into the
  output so attention runs directly against the compressed cache —
  per-step FLOPs O(H·(r + d_rope)) per cached token instead of
  O(H·(d_nope + d_rope)), and cache bytes per token are
  (kv_lora_rank + d_rope) instead of 2·H·d_head (~ 18× smaller for V3).

Equivalence of the two forms is asserted in tests/test_models.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.utils import leaf_id_tree
from repro.kernels import ops as kops
from .attention import chunked_causal_attention, NEG_INF
from .layers import dense_init, pdense, prmsnorm, rmsnorm_init
from .rope import apply_rope, cfg_rope_freqs, cfg_rope_mscale, \
    yarn_softmax_scale


def mla_init(key, cfg, dtype):
    """cfg needs: d_model, n_heads, q_lora_rank, kv_lora_rank,
    qk_nope_head_dim, qk_rope_head_dim, v_head_dim."""
    ks = jax.random.split(key, 8)
    h = cfg.n_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {
        "wkv_a": dense_init(ks[0], cfg.d_model,
                            cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                            dtype=dtype),
        "kv_norm": rmsnorm_init(cfg.kv_lora_rank, dtype),
        "wkv_b": dense_init(ks[1], cfg.kv_lora_rank,
                            h * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                            dtype=dtype),
        "wo": dense_init(ks[2], h * cfg.v_head_dim, cfg.d_model, dtype=dtype),
    }
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[3], cfg.d_model, cfg.q_lora_rank, dtype=dtype)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, dtype)
        p["wq_b"] = dense_init(ks[4], cfg.q_lora_rank, h * qd, dtype=dtype)
    else:
        p["wq"] = dense_init(ks[3], cfg.d_model, h * qd, dtype=dtype)
    return p


def mla_rope(x, positions, cfg):
    """DeepSeek-V3's rope on the decoupled rope dims: interleaved pairs,
    YaRN frequencies and cos/sin scale where the config sets them."""
    return apply_rope(x, positions, cfg.rope_theta,
                      inv_freq=cfg_rope_freqs(cfg, x.shape[-1]),
                      mscale=cfg_rope_mscale(cfg), interleaved=True)


def softmax_scale(cfg) -> float:
    return yarn_softmax_scale(
        cfg, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _queries(p, xs, cfg, ids, probe, layer):
    """Per-stream (q_nope [B,S,H,dn], q_rope [B,S,H,dr]), unrotated."""
    b, s, _ = xs[0].shape
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        qa = pdense(p["wq_a"], xs, ids["wq_a"], probe, layer=layer)
        qa = prmsnorm(p["q_norm"], qa, ids["q_norm"], probe, layer=layer,
                      eps=cfg.norm_eps)
        qs = pdense(p["wq_b"], qa, ids["wq_b"], probe, layer=layer)
    else:
        qs = pdense(p["wq"], xs, ids["wq"], probe, layer=layer)
    return tuple(jnp.split(q.reshape(b, s, cfg.n_heads, qd),
                           [cfg.qk_nope_head_dim], axis=-1) for q in qs)


def _kv_latent(p, xs, cfg, positions, ids, probe, layer):
    """Per-stream (c_kv [B,S,r] normalized, k_rope [B,S,1,dr] rotated)."""
    ckvs = pdense(p["wkv_a"], xs, ids["wkv_a"], probe, layer=layer)
    cs, krs = zip(*(jnp.split(c, [cfg.kv_lora_rank], axis=-1) for c in ckvs))
    cs = prmsnorm(p["kv_norm"], cs, ids["kv_norm"], probe, layer=layer,
                  eps=cfg.norm_eps)
    krs = tuple(mla_rope(kr[:, :, None, :], positions, cfg) for kr in krs)
    return cs, krs


def _attend_xla(q, kv, k_rope, scale):
    """``_attend`` through ``chunked_causal_attention`` (XLA): the path
    its backward differentiates."""
    b, h, s, dq = q.shape
    dr = k_rope.shape[-1]
    kv = kv.reshape(b, s, h, -1)
    k = jnp.concatenate([kv[..., :dq - dr], jnp.broadcast_to(
        k_rope[:, :, None], (b, s, h, dr))], axis=-1)
    out = chunked_causal_attention(jnp.swapaxes(q, 1, 2), k,
                                   kv[..., dq - dr:], scale=scale)
    return out.reshape(b, s, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, kv, k_rope, scale):
    """Causal attention of heads-major q [B,H,S,dn+dr] against ``wkv_b``'s
    output kv [B,S,H·(dn+dv)] and the shared rope key [B,S,dr], through
    the Pallas kernel (``kernels.ops.mla_flash_attention``) →
    [B,S,H·dv].  Differentiated through ``_attend_xla``."""
    return kops.mla_flash_attention(q, kv, k_rope, scale=scale)


def _attend_fwd(q, kv, k_rope, scale):
    return _attend(q, kv, k_rope, scale), (q, kv, k_rope)


def _attend_bwd(scale, res, g):
    return jax.vjp(functools.partial(_attend_xla, scale=scale), *res)[1](g)


_attend.defvjp(_attend_fwd, _attend_bwd)


def mla_streams(p, xs, positions, cfg, ids, probe, layer=None):
    """Expand-form causal MLA over full sequences, one result per stream
    (``layers.pdense``'s stream convention; ``probe=None`` is the plain
    forward).  Returns (ys [B,S,d_model] per stream, caches (c_kv, k_rope)
    per stream)."""
    qs = _queries(p, xs, cfg, ids, probe, layer)
    cs, krs = _kv_latent(p, xs, cfg, positions, ids, probe, layer)
    kvs = pdense(p["wkv_b"], cs, ids["wkv_b"], probe, layer=layer)
    outs = []
    for (q_nope, q_rope), kv, k_rope in zip(qs, kvs, krs):
        q = jnp.concatenate([q_nope, mla_rope(q_rope, positions, cfg)], -1)
        outs.append(_attend(jnp.swapaxes(q, 1, 2), kv, k_rope[:, :, 0],
                            softmax_scale(cfg)))
    ys = pdense(p["wo"], tuple(outs), ids["wo"], probe, layer=layer)
    return ys, tuple((c, kr[:, :, 0, :]) for c, kr in zip(cs, krs))


def mla_attention(p, x, positions, cfg):
    """Expand-form causal MLA over a full sequence: the one-stream, plain
    case of ``mla_streams``.

    Returns (y [B,S,d_model], cache = (c_kv, k_rope squeezed)).
    """
    (y,), (cache,) = mla_streams(p, (x,), positions, cfg, leaf_id_tree(p),
                                 None)
    return y, cache


def _absorb_weights(p, cfg):
    """Split wkv_b into per-head W_UK [r,H,dn] and W_UV [r,H,dv]."""
    h = cfg.n_heads
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    wkv_b = p["wkv_b"]["w"].reshape(cfg.kv_lora_rank, h, dn + dv)
    return wkv_b[..., :dn], wkv_b[..., dn:]


def mla_decode(p, x1, cache, length, cfg):
    """Absorbed-form single-token decode.

    x1: [B, 1, d_model]; cache = (c_kv [B,Smax,r], k_rope [B,Smax,dr]),
    already containing this token's entries at position length−1.
    """
    b = x1.shape[0]
    c_cache, r_cache = cache
    pos = jnp.full((b, 1), length - 1, jnp.int32)

    ids = leaf_id_tree(p)
    ((q_nope, q_rope),) = _queries(p, (x1,), cfg, ids, None, None)
    q_rope = mla_rope(q_rope, pos, cfg)                     # [B,1,H,dr]
    w_uk, w_uv = _absorb_weights(p, cfg)

    # fold W_UK into the query: q_eff [B,H,r]
    q_eff = jnp.einsum("bhd,rhd->bhr",
                       q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scores = (
        jnp.einsum("bhr,bsr->bhs", q_eff,
                   c_cache.astype(jnp.float32))
        + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                     r_cache.astype(jnp.float32))
    ) * softmax_scale(cfg)
    idx = jnp.arange(c_cache.shape[1])
    scores = jnp.where(idx[None, None, :] < length, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs, c_cache.astype(jnp.float32))
    attn = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(jnp.float32))
    (y,) = pdense(p["wo"], (attn.reshape(b, 1, -1).astype(x1.dtype),),
                  ids["wo"], None)
    return y


def mla_cache_update(p, x1, cache, length, cfg):
    """Compute this token's (c_kv, k_rope) and write them at length−1."""
    b = x1.shape[0]
    pos = jnp.full((b, 1), length - 1, jnp.int32)
    (c_kv,), (k_rope,) = _kv_latent(p, (x1,), cfg, pos, leaf_id_tree(p),
                                    None, None)
    c_cache, r_cache = cache
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        c_cache, c_kv.astype(c_cache.dtype), length - 1, 1)
    r_cache = jax.lax.dynamic_update_slice_in_dim(
        r_cache, k_rope[:, :, 0, :].astype(r_cache.dtype), length - 1, 1)
    return c_cache, r_cache
