"""Decoder assembly for all 10 assigned architectures.

One set of entry points, family-dispatched:

    model_init(cfg, key)                  → params (stacked-layer pytree)
    model_forward(params, cfg, batch)     → logits          (train/prefill)
    model_loss(params, cfg, batch)        → scalar xent     (MGD's loss_fn)
    init_cache(cfg, batch, max_len)       → decode cache/state
    model_prefill(params, cfg, batch, max_len) → (logits, cache)
    model_decode(params, cfg, tokens, cache)   → (logits, cache)

Layers are stacked on a leading L dim and driven by ``lax.scan`` — one
layer's HLO regardless of depth (compile-time and GSPMD-friendliness at
88-layer scale).  Activation sharding uses logical axis names translated
against whatever mesh is active (repro.distributed.sharding).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import perturbations as pert
from repro.core.utils import leaf_id_tree, tree_add, tree_axpy
from repro.distributed.sharding import shard
from .attention import chunked_causal_attention, decode_attention
from .config import ArchConfig
from .layers import (dense, dense_init, embed, embedding_init, glu_mlp,
                     glu_mlp_init, pdense, pglu_mlp, pleaf, prmsnorm, rmsnorm,
                     rmsnorm_init)
from .mamba2 import (mamba2_block, mamba2_block_init, mamba2_block_step,
                     mamba2_state_init)
from .mla import (mla_attention, mla_cache_update, mla_decode, mla_init,
                  mla_streams)
from .moe import moe_apply, moe_init, moe_streams
from .rope import apply_mrope, apply_rope
from .rwkv6 import (rwkv6_block, rwkv6_block_init, rwkv6_block_step,
                    rwkv6_state_init)

# ---------------------------------------------------------------------------
# GQA attention sub-layer
# ---------------------------------------------------------------------------


def attn_init(key, cfg: ArchConfig, dtype):
    h, kvh, dh, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], d, kvh * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], d, kvh * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], h * dh, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(dh, dtype)
        p["k_norm"] = rmsnorm_init(dh, dtype)
    return p


def _rope(cfg, x, positions):
    if cfg.mrope_sections is not None and positions.ndim == 3:
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


def _qkv(p, x, positions, cfg):
    b, s, d = x.shape
    h, kvh, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = dense(p["wq"], x).reshape(b, s, h, dh)
    k = dense(p["wk"], x).reshape(b, s, kvh, dh)
    v = dense(p["wv"], x).reshape(b, s, kvh, dh)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)
    return q, k, v


def attn_apply(p, x, positions, cfg: ArchConfig):
    """Full-sequence causal attention.  Returns (y, (k, v) for caching)."""
    b, s, d = x.shape
    q, k, v = _qkv(p, x, positions, cfg)
    q = shard(q, "batch", None, "model", None)
    k = shard(k, "batch", None, "model", None)
    v = shard(v, "batch", None, "model", None)
    y = chunked_causal_attention(
        q, k, v, q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block,
        impl=cfg.attn_impl)
    y = dense(p["wo"], y.reshape(b, s, -1))
    return y, (k, v)


def attn_decode_step(p, x1, positions, kcache, vcache, length, cfg):
    """x1: [B,1,d].  Caches [B,Smax,KVH,dh]; entry written at length−1."""
    b = x1.shape[0]
    q, k, v = _qkv(p, x1, positions, cfg)
    kcache = jax.lax.dynamic_update_slice_in_dim(
        kcache, k.astype(kcache.dtype), length - 1, 1)
    vcache = jax.lax.dynamic_update_slice_in_dim(
        vcache, v.astype(vcache.dtype), length - 1, 1)
    y = decode_attention(q, kcache, vcache, length)
    y = dense(p["wo"], y.reshape(b, 1, -1))
    return y, kcache, vcache


# ---------------------------------------------------------------------------
# One decoder layer (dense / moe / mla variants)
# ---------------------------------------------------------------------------


def block_init(key, cfg: ArchConfig, dtype, dense: bool = False):
    """One layer's parameters; ``dense`` marks a leading dense layer of
    an MoE model (its MLP is ``d_ff_dense`` wide)."""
    ka, km = jax.random.split(key)
    p = {"ln1": rmsnorm_init(cfg.d_model, dtype),
         "ln2": rmsnorm_init(cfg.d_model, dtype)}
    if cfg.use_mla:
        p["attn"] = mla_init(ka, cfg, dtype)
    else:
        p["attn"] = attn_init(ka, cfg, dtype)
    if cfg.n_experts and not dense:
        p["moe"] = moe_init(km, cfg, dtype)
    else:
        p["mlp"] = glu_mlp_init(
            km, cfg.d_model, (cfg.d_ff_dense or cfg.d_ff) if dense
            else cfg.d_ff, dtype)
    return p


def _mlp_part(p, x, cfg):
    if "mlp" in p:
        return glu_mlp(p["mlp"], x)
    if cfg.moe_dropless:
        (y,), _ = moe_streams(p["moe"], (x,), cfg, leaf_id_tree(p["moe"]),
                              None)
        return y
    return moe_apply(p["moe"], x, cfg, group_size=cfg.moe_group_size,
                     capacity_factor=cfg.moe_capacity_factor)


def block_apply(p, x, positions, cfg: ArchConfig):
    """Pre-norm residual block.  Returns (x', kv-cache payload)."""
    seq_ax = "sp" if cfg.seq_parallel else None
    xn = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        att, cache = mla_attention(p["attn"], xn, positions, cfg)
    else:
        att, cache = attn_apply(p["attn"], xn, positions, cfg)
    x = x + att
    x = shard(x, "batch", seq_ax, None)
    y = _mlp_part(p, rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    x = x + y
    return shard(x, "batch", seq_ax, None), cache


def block_decode(p, x1, positions, layer_cache, length, cfg: ArchConfig):
    xn = rmsnorm(p["ln1"], x1, cfg.norm_eps)
    if cfg.use_mla:
        cache = mla_cache_update(p["attn"], xn, layer_cache, length, cfg)
        att = mla_decode(p["attn"], xn, cache, length, cfg)
    else:
        kc, vc = layer_cache
        att, kc, vc = attn_decode_step(
            p["attn"], xn, positions, kc, vc, length, cfg)
        cache = (kc, vc)
    x1 = x1 + att
    y = _mlp_part(p, rmsnorm(p["ln2"], x1, cfg.norm_eps), cfg)
    return x1 + y, cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed_init(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    n_tables = max(cfg.n_codebooks, 1)
    p = {"tok": embedding_init(k1, cfg.vocab * n_tables, cfg.d_model, dtype),
         "ln_f": rmsnorm_init(cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        head_out = cfg.vocab * n_tables
        p["head"] = dense_init(k2, cfg.d_model, head_out, dtype=dtype)
    return p


def _embed_tokens(p, cfg: ArchConfig, batch):
    """Tokens or precomputed (stub-frontend) embeddings → [B,S,d]."""
    if "embeds" in batch:
        x = batch["embeds"]
    elif cfg.n_codebooks:
        # musicgen: tokens [B, nq, S]; codebook i uses table slice i
        toks = batch["tokens"]
        b, nq, s = toks.shape
        offs = (jnp.arange(nq, dtype=toks.dtype) * cfg.vocab)[None, :, None]
        x = embed(p["tok"], toks + offs).sum(axis=1)
    else:
        x = embed(p["tok"], batch["tokens"])
    return shard(x, "batch", "sp" if cfg.seq_parallel else None, None)


def _logits(p, cfg: ArchConfig, x):
    if cfg.tie_embeddings:
        logits = x @ p["tok"]["table"].T
    else:
        logits = dense(p["head"], x)
    logits = shard(logits, "batch", None, "model")
    if cfg.n_codebooks:
        b, s, _ = logits.shape
        logits = logits.reshape(b, s, cfg.n_codebooks, cfg.vocab)
    return logits


def _positions(cfg: ArchConfig, batch, s, b):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[..., None], (b, s, 3))
    return pos


# ---------------------------------------------------------------------------
# Model: init / forward / loss
# ---------------------------------------------------------------------------


def _layer_keys(key, n):
    return jax.random.split(key, n)


def model_init(cfg: ArchConfig, key):
    dtype = cfg.jdtype
    k_emb, k_layers, k_shared = jax.random.split(key, 3)
    params: Dict[str, Any] = {"embed": _embed_init(k_emb, cfg, dtype)}
    if cfg.n_dense_layers:
        init_dense = functools.partial(block_init, cfg=cfg, dtype=dtype,
                                       dense=True)
        params["dense_layers"] = jax.vmap(init_dense)(
            _layer_keys(k_shared, cfg.n_dense_layers))
    if cfg.family == "ssm":
        init_one = functools.partial(rwkv6_block_init, cfg=cfg, dtype=dtype)
        params["layers"] = jax.vmap(init_one)(_layer_keys(k_layers, cfg.n_layers))
    elif cfg.family == "hybrid":
        n_mamba, n_shared_calls = _hybrid_plan(cfg)
        init_one = functools.partial(mamba2_block_init, cfg=cfg, dtype=dtype)
        params["layers"] = jax.vmap(init_one)(_layer_keys(k_layers, n_mamba))
        params["shared_attn"] = block_init(k_shared, cfg, dtype)
    else:
        init_one = functools.partial(block_init, cfg=cfg, dtype=dtype)
        params["layers"] = jax.vmap(init_one)(
            _layer_keys(k_layers, cfg.n_moe_layers))
    return params


def _stack_keys(params):
    """Keys of the decoder's layer stacks in order: leading dense layers,
    then the rest."""
    return [k for k in ("dense_layers", "layers") if k in params]


def _stacks(params):
    return [params[k] for k in _stack_keys(params)]


def _split_layers(tree, params):
    """Split a tree stacked over all layers as ``_stacks`` orders them."""
    n = [jax.tree_util.tree_leaves(st)[0].shape[0] for st in _stacks(params)]
    if len(n) == 1:
        return [tree]
    return [jax.tree_util.tree_map(lambda a: a[:n[0]], tree),
            jax.tree_util.tree_map(lambda a: a[n[0]:], tree)]


def _concat_layers(trees):
    if len(trees) == 1:
        return trees[0]
    return jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *trees)


def _hybrid_plan(cfg: ArchConfig):
    """zamba2: n_layers counts mamba blocks + shared-attn invocations.
    With attn_every = k: groups of (k mamba + 1 shared attn)."""
    k = cfg.attn_every
    group = k + 1
    n_groups = cfg.n_layers // group
    n_mamba = n_groups * k
    return n_mamba, n_groups


def model_forward(params, cfg: ArchConfig, batch, *, return_state=False,
                  state=None):
    """Full-sequence forward → logits [B,S,V].  For ssm/hybrid, optionally
    returns the recurrent state (prefill path)."""
    x = _embed_tokens(params["embed"], cfg, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, batch, s, b)

    if cfg.family == "ssm":
        if state is None:
            state = jax.vmap(
                lambda _: rwkv6_state_init(cfg, b), axis_size=cfg.n_layers,
                out_axes=0)(jnp.arange(cfg.n_layers))

        def body(x, layer):
            lp, st = layer
            x, st = rwkv6_block(lp, x, st, cfg, chunk=cfg.la_chunk)
            return x, st

        x, new_state = jax.lax.scan(body, x, (params["layers"], state))
    elif cfg.family == "hybrid":
        n_mamba, n_groups = _hybrid_plan(cfg)
        k = cfg.attn_every
        if state is None:
            state = {
                "mamba": jax.vmap(
                    lambda _: mamba2_state_init(cfg, b), axis_size=n_mamba,
                    out_axes=0)(jnp.arange(n_mamba)),
                "attn_kv": None,
            }
        lp_grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(n_groups, k, *a.shape[1:]), params["layers"])
        st_grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(n_groups, k, *a.shape[1:]), state["mamba"])

        def body(x, layer):
            lps, sts = layer

            def inner(x, one):
                lp, st = one
                x, st = mamba2_block(lp, x, st, cfg, chunk=cfg.la_chunk)
                return x, st

            x, new_sts = jax.lax.scan(inner, x, (lps, sts))
            x, kv = block_apply(params["shared_attn"], x, positions, cfg)
            return x, (new_sts, kv)

        x, (new_m, kvs) = jax.lax.scan(body, x, (lp_grouped, st_grouped))
        new_state = {
            "mamba": jax.tree_util.tree_map(
                lambda a: a.reshape(n_mamba, *a.shape[2:]), new_m),
            "attn_kv": kvs,
        }
    else:
        def body(x, lp):
            x, kv = block_apply(lp, x, positions, cfg)
            return x, kv

        kvs = []
        for stack in _stacks(params):
            x, kv = jax.lax.scan(body, x, stack)
            kvs.append(kv)
        new_state = _concat_layers(kvs)

    x = rmsnorm(params["embed"]["ln_f"], x, cfg.norm_eps)
    logits = _logits(params["embed"], cfg, x)
    if return_state:
        return logits, new_state
    return logits


def _loss_from_logits(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, labels[..., None].clip(0), axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def model_loss(params, cfg: ArchConfig, batch):
    """Token-mean softmax cross-entropy — MGD's scalar cost."""
    return _loss_from_logits(model_forward(params, cfg, batch),
                             batch["labels"])


# ---------------------------------------------------------------------------
# Fused probe path (MGD): forward under θ ± θ̃ without materializing θ̃
# ---------------------------------------------------------------------------
#
# The weight matmuls — GQA or MLA projections, MLPs, the head: the
# HBM-dominant leaves — route through the Pallas perturbed-matmul kernels
# (sign generation in VMEM; the antithetic central pair reads each W tile
# ONCE), and the dropless expert layer's held banks through the grouped
# kernel (``moe.moe_streams``).  Norm scales, biases, the f32 router and
# the embedding table fall back to materialized θ̃ (O(d) or gather-bound).
# Stacked-layer banks are addressed through the per-layer seed shift, so the
# in-kernel sign pattern is bit-identical to the host generator's view of
# the stacked leaf.


def _pqkv(p, xs, positions, cfg, ids, probe, layer):
    b, s, _ = xs[0].shape
    h, kvh, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    qs = tuple(q.reshape(b, s, h, dh)
               for q in pdense(p["wq"], xs, ids["wq"], probe, layer=layer))
    ks = tuple(k.reshape(b, s, kvh, dh)
               for k in pdense(p["wk"], xs, ids["wk"], probe, layer=layer))
    vs = tuple(v.reshape(b, s, kvh, dh)
               for v in pdense(p["wv"], xs, ids["wv"], probe, layer=layer))
    if cfg.qk_norm:
        qs = prmsnorm(p["q_norm"], qs, ids["q_norm"], probe, layer=layer,
                      eps=cfg.norm_eps)
        ks = prmsnorm(p["k_norm"], ks, ids["k_norm"], probe, layer=layer,
                      eps=cfg.norm_eps)
    qs = tuple(_rope(cfg, q, positions) for q in qs)
    ks = tuple(_rope(cfg, k, positions) for k in ks)
    return qs, ks, vs


@jax.named_scope("repro.model.attn")
def _pattn_apply(p, xs, positions, cfg: ArchConfig, ids, probe, layer):
    b, s, _ = xs[0].shape
    qs, ks, vs = _pqkv(p, xs, positions, cfg, ids, probe, layer)
    ys = []
    for q, k, v in zip(qs, ks, vs):
        q = shard(q, "batch", None, "model", None)
        k = shard(k, "batch", None, "model", None)
        v = shard(v, "batch", None, "model", None)
        y = chunked_causal_attention(
            q, k, v, q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block,
            impl=cfg.attn_impl)
        ys.append(y.reshape(b, s, -1))
    return pdense(p["wo"], tuple(ys), ids["wo"], probe, layer=layer)


@jax.named_scope("repro.model.attn")
def _pmla_apply(p, xs, positions, cfg: ArchConfig, ids, probe, layer):
    ys, _ = mla_streams(p, xs, positions, cfg, ids, probe, layer)
    return ys


@jax.named_scope("repro.model.block")
def _pblock_apply(p, xs, positions, cfg: ArchConfig, ids, probe, layer):
    """One layer over the probe streams → (streams, rows each stream
    routed to each held expert, or None without experts)."""
    seq_ax = "sp" if cfg.seq_parallel else None
    xn = prmsnorm(p["ln1"], xs, ids["ln1"], probe, layer=layer,
                  eps=cfg.norm_eps)
    attn = _pmla_apply if cfg.use_mla else _pattn_apply
    att = attn(p["attn"], xn, positions, cfg, ids["attn"], probe, layer)
    xs = tuple(x + a for x, a in zip(xs, att))
    xs = tuple(shard(x, "batch", seq_ax, None) for x in xs)
    xn = prmsnorm(p["ln2"], xs, ids["ln2"], probe, layer=layer,
                  eps=cfg.norm_eps)
    rows = None
    if "mlp" in p:
        ys = pglu_mlp(p["mlp"], xn, ids["mlp"], probe, layer)
    else:
        ys, rows = moe_streams(p["moe"], xn, cfg, ids["moe"], probe, layer)
    xs = tuple(x + y for x, y in zip(xs, ys))
    return tuple(shard(x, "batch", seq_ax, None) for x in xs), rows


def supports_fused_probe(cfg: ArchConfig) -> bool:
    """Dense GQA decoders (incl. vlm/audio stub frontends), MLA, and the
    dropless expert layer have the fully fused probe path; capacity MoE,
    SSM and hybrid fall back to materializing."""
    return (cfg.family in ("dense", "vlm", "audio", "moe")
            and (not cfg.n_experts or cfg.moe_dropless))


def model_forward_perturbed(params, cfg: ArchConfig, batch, probe):
    """Per-sign perturbed logits, θ̃ fused into the weight matmuls.

    Returns a tuple of logits arrays, one per ``probe.ctx.signs`` entry.
    """
    return _forward_perturbed(params, cfg, batch, probe)[0]


def _forward_perturbed(params, cfg: ArchConfig, batch, probe):
    """(per-sign logits, aux): aux holds ``moe_rows`` [MoE layers,
    signs, held experts], the rows each stream routed to each held
    expert, for a model with the dropless expert layer."""
    assert supports_fused_probe(cfg), cfg.family
    ids = leaf_id_tree(params)
    emb, eids = params["embed"], ids["embed"]
    with jax.named_scope("repro.model.embed"):
        tables = pleaf(emb["tok"]["table"], eids["tok"]["table"], probe)
        if "embeds" in batch:
            xs = tuple(batch["embeds"] for _ in probe.ctx.signs)
        elif cfg.n_codebooks:
            toks = batch["tokens"]
            _, nq, _ = toks.shape
            offs = (jnp.arange(nq, dtype=toks.dtype)
                    * cfg.vocab)[None, :, None]
            xs = tuple(jnp.take(t, toks + offs, axis=0).sum(axis=1)
                       for t in tables)
        else:
            xs = tuple(jnp.take(t, batch["tokens"], axis=0) for t in tables)
    xs = tuple(shard(x, "batch", "sp" if cfg.seq_parallel else None, None)
               for x in xs)
    b, s, _ = xs[0].shape
    positions = _positions(cfg, batch, s, b)

    aux = {}
    with jax.named_scope("repro.model.layers"):
        for key in _stack_keys(params):
            def body(carry, layer_in, key=key):
                lp, l = layer_in
                return _pblock_apply(lp, carry, positions, cfg, ids[key],
                                     probe, l)

            n = jax.tree_util.tree_leaves(params[key])[0].shape[0]
            xs, rows = jax.lax.scan(body, xs, (params[key], jnp.arange(n)))
            if rows is not None:
                aux["moe_rows"] = rows
    xs = prmsnorm(emb["ln_f"], xs, eids["ln_f"], probe, eps=cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = tuple(x @ t.T for x, t in zip(xs, tables))
    else:
        logits = pdense(emb["head"], xs, eids["head"], probe)
    logits = tuple(shard(l, "batch", None, "model") for l in logits)
    if cfg.n_codebooks:
        logits = tuple(l.reshape(b, s, cfg.n_codebooks, cfg.vocab)
                       for l in logits)
    return logits, aux


def model_probe_costs(params, cfg: ArchConfig, batch, probe):
    """probe_fn for ``MGDConfig(fused=True)``: [n_signs] xent costs, and
    beside them, for a model with the dropless expert layer, the counter
    ``{"moe_rows": …}`` (``_forward_perturbed``) as ``(costs, aux)``.

    Fused for the families ``supports_fused_probe`` names; others
    materialize θ̃ per sign with the exact float order of the unfused
    optimizer path.
    """
    if supports_fused_probe(cfg):
        logits, aux = _forward_perturbed(params, cfg, batch, probe)
        with jax.named_scope("repro.model.loss"):
            costs = jnp.stack(
                [_loss_from_logits(l, batch["labels"]) for l in logits])
        return (costs, aux) if aux else costs
    theta = pert.generate(
        params, ptype="rademacher", step=probe.step, seed=probe.seed,
        dtheta=probe.ctx.dtheta, tau_p=probe.ctx.tau_p)
    costs = []
    for s in probe.ctx.signs:
        p_s = tree_add(params, theta) if s == 1.0 else tree_axpy(
            s, theta, params)
        costs.append(model_loss(p_s, cfg, batch))
    return jnp.stack(costs)


def make_transformer_probe_fn(cfg: ArchConfig):
    """Bind ``cfg`` → probe_fn(params, batch, probe) for build_mgd_step."""

    def probe_fn(params, batch, probe):
        return model_probe_costs(params, cfg, batch, probe)

    return probe_fn


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch_size: int, max_len: int):
    dtype = cfg.jdtype
    if cfg.family == "ssm":
        st = jax.vmap(lambda _: rwkv6_state_init(cfg, batch_size),
                      axis_size=cfg.n_layers, out_axes=0)(
            jnp.arange(cfg.n_layers))
        return {"state": st, "length": jnp.zeros((), jnp.int32)}
    if cfg.family == "hybrid":
        n_mamba, n_groups = _hybrid_plan(cfg)
        st = jax.vmap(lambda _: mamba2_state_init(cfg, batch_size),
                      axis_size=n_mamba, out_axes=0)(jnp.arange(n_mamba))
        kvh, dh = cfg.kv_heads, cfg.head_dim
        kv = jnp.zeros((n_groups, batch_size, max_len, kvh, dh), dtype)
        kv = shard(kv, None, "batch", "kvseq", None, None)
        return {"state": st, "k": kv, "v": kv,
                "length": jnp.zeros((), jnp.int32)}
    if cfg.use_mla:
        c = jnp.zeros((cfg.n_layers, batch_size, max_len, cfg.kv_lora_rank),
                      dtype)
        r = jnp.zeros((cfg.n_layers, batch_size, max_len,
                       cfg.qk_rope_head_dim), dtype)
        return {"c_kv": shard(c, None, "batch", "kvseq", None),
                "k_rope": shard(r, None, "batch", "kvseq", None),
                "length": jnp.zeros((), jnp.int32)}
    kvh, dh = cfg.kv_heads, cfg.head_dim
    kv = jnp.zeros((cfg.n_layers, batch_size, max_len, kvh, dh), dtype)
    kv = shard(kv, None, "batch", "kvseq", None, None)
    return {"k": kv, "v": kv, "length": jnp.zeros((), jnp.int32)}


def model_prefill(params, cfg: ArchConfig, batch, max_len: int):
    """Run the prompt; returns (full-seq logits, ready-to-decode cache)."""
    b = (batch["tokens"].shape[0] if "tokens" in batch
         else batch["embeds"].shape[0])
    s = (batch["tokens"].shape[-1] if "tokens" in batch
         else batch["embeds"].shape[1])
    logits, st = model_forward(params, cfg, batch, return_state=True)
    length = jnp.asarray(s, jnp.int32)
    if cfg.family == "ssm":
        return logits, {"state": st, "length": length}
    if cfg.family == "hybrid":
        cache = init_cache(cfg, b, max_len)
        kvs = st["attn_kv"]  # ([G,B,S,kvh,dh], [G,B,S,kvh,dh])
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], kvs[0].astype(cache["k"].dtype), 0, 2)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], kvs[1].astype(cache["v"].dtype), 0, 2)
        return logits, {"state": st["mamba"], "k": k, "v": v,
                        "length": length}
    cache = init_cache(cfg, b, max_len)
    if cfg.use_mla:
        c = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], st[0].astype(cache["c_kv"].dtype), 0, 2)
        r = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], st[1].astype(cache["k_rope"].dtype), 0, 2)
        return logits, {"c_kv": c, "k_rope": r, "length": length}
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], st[0].astype(cache["k"].dtype), 0, 2)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], st[1].astype(cache["v"].dtype), 0, 2)
    return logits, {"k": k, "v": v, "length": length}


def model_decode(params, cfg: ArchConfig, tokens, cache, embeds=None):
    """One decode step.  tokens: [B] int32 (or embeds [B,1,d] for stub
    frontends).  Returns (logits [B,V...], new cache)."""
    if embeds is not None:
        x1 = embeds
    elif cfg.n_codebooks:
        offs = (jnp.arange(cfg.n_codebooks, dtype=tokens.dtype)
                * cfg.vocab)[None, :]
        x1 = embed(params["embed"]["tok"], tokens + offs).sum(axis=1)[:, None, :]
    else:
        x1 = embed(params["embed"]["tok"], tokens)[:, None, :]
    b = x1.shape[0]
    length = cache["length"] + 1
    pos = jnp.full((b, 1), length - 1, jnp.int32)
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[..., None], (b, 1, 3))

    if cfg.family == "ssm":
        def body(x, layer):
            lp, st = layer
            y, st = rwkv6_block_step(lp, x[:, 0, :], st, cfg)
            return y[:, None, :], st

        x1, new_state = jax.lax.scan(body, x1, (params["layers"],
                                                cache["state"]))
        new_cache = {"state": new_state, "length": length}
    elif cfg.family == "hybrid":
        n_mamba, n_groups = _hybrid_plan(cfg)
        k = cfg.attn_every
        lp_g = jax.tree_util.tree_map(
            lambda a: a.reshape(n_groups, k, *a.shape[1:]), params["layers"])
        st_g = jax.tree_util.tree_map(
            lambda a: a.reshape(n_groups, k, *a.shape[1:]), cache["state"])

        def body(x, layer):
            lps, sts, kc, vc = layer

            def inner(x, one):
                lp, st = one
                y, st = mamba2_block_step(lp, x[:, 0, :], st, cfg)
                return y[:, None, :], st

            x, new_sts = jax.lax.scan(inner, x, (lps, sts))
            x, (kc, vc) = block_decode(
                params["shared_attn"], x, pos, (kc, vc), length, cfg)
            return x, (new_sts, kc, vc)

        x1, (new_m, kc, vc) = jax.lax.scan(
            body, x1, (lp_g, st_g, cache["k"], cache["v"]))
        new_cache = {
            "state": jax.tree_util.tree_map(
                lambda a: a.reshape(n_mamba, *a.shape[2:]), new_m),
            "k": kc, "v": vc, "length": length,
        }
    else:
        names = ("c_kv", "k_rope") if cfg.use_mla else ("k", "v")

        def body(x, layer):
            lp, c0, c1 = layer
            x, (c0, c1) = block_decode(lp, x, pos, (c0, c1), length, cfg)
            return x, (c0, c1)

        outs = []
        for stack, (c0, c1) in zip(
                _stacks(params),
                _split_layers((cache[names[0]], cache[names[1]]), params)):
            x1, out = jax.lax.scan(body, x1, (stack, c0, c1))
            outs.append(out)
        c0, c1 = _concat_layers(outs)
        new_cache = {names[0]: c0, names[1]: c1, "length": length}

    x1 = rmsnorm(params["embed"]["ln_f"], x1, cfg.norm_eps)
    logits = _logits(params["embed"], cfg, x1)[:, 0]
    return logits, new_cache
