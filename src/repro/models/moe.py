"""Mixture-of-Experts layers.

Two dispatches share one router (``route``):

* capacity (``moe_apply``; mesh-TF/GSPMD style): tokens are split into
  groups of ``group_size``; each group routes its tokens to top-k experts
  under a per-group capacity C = ceil(group·k/E·cf), and tokens over it
  are dropped (combine weight 0).  The dispatch/combine tensors are
  [G, Sg, E, C]; GSPMD shards them cleanly (experts → "expert", groups →
  batch).
* dropless chip share (``moe_streams``; DeepSeek-V3, arXiv:2412.19437
  §3.4.1 expert parallelism): the layer holds experts [e0, e0 + n) of E,
  routes every token over all E, and computes its own experts' part of
  the result for every (token, slot) routed to them — none is dropped —
  through grouped products over the held bank
  (``kernels.perturbed_grouped_matmul``), plus the shared expert.  On one
  chip of the deployment, the absent experts' part is the other chips'.

Routing is softmax top-k, or DeepSeek-V3's published rule (``noaux_tc``):
sigmoid scores; the score-correction bias (a buffer, zero here) added for
the choice only; groups ranked by the sum of their top-2 biased scores
and the ``topk_groups`` best kept; top-k experts among the kept groups;
weights the unbiased scores of the chosen experts, normalized and scaled
by ``routed_scale``.  The router is a parameter like any other: MGD
perturbs it and trains it from the same scalar cost, and no balance loss
is added.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perturbations as pert
from repro.distributed.sharding import shard
from repro.kernels import ops as kops
from repro.kernels.grouped_matmul import group_layout, layout_blocks, \
    row_block
from .layers import dense, dense_init, glu_mlp, glu_mlp_init, pglu_mlp, pleaf


def moe_init(key, cfg, dtype):
    """cfg needs: d_model, d_ff (expert inner), n_experts, n_shared_experts."""
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    held = range(cfg.expert_first, cfg.expert_first + cfg.n_held)

    def bank(k, d_in, d_out):
        """Expert i's matrix drawn from fold_in(k, i): a share holds the
        same experts the whole layer would."""
        return jnp.stack([
            jax.random.normal(jax.random.fold_in(k, i), (d_in, d_out),
                              jnp.float32) * (1.0 / np.sqrt(d_in))
            for i in held]).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, e, dtype=jnp.float32),  # f32 routing
        "gate": bank(ks[1], d, f),
        "up": bank(ks[2], d, f),
        "down": bank(ks[3], f, d),
    }
    if cfg.n_shared_experts:
        p["shared"] = glu_mlp_init(ks[4], d, f * cfg.n_shared_experts, dtype)
    return p


def route(logits, cfg, bias=None):
    """Router logits [..., E] (f32) → (weights [..., k] f32, experts
    [..., k] int32): softmax top-k renormalized, or the published
    DeepSeek-V3 rule for ``router_score == "sigmoid"`` (module
    docstring).  ``bias`` is the score-correction buffer (None: zero)."""
    k = cfg.n_experts_active
    if cfg.router_score == "softmax":
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return w / (jnp.sum(w, -1, keepdims=True) + 1e-9), idx
    assert cfg.router_score == "sigmoid", cfg.router_score
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias
    *lead, e = choice.shape
    g = cfg.n_groups
    grouped = choice.reshape(*lead, g, e // g)
    group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_scores, cfg.topk_groups)
    keep = jnp.any(jax.nn.one_hot(kept, g, dtype=jnp.bool_), axis=-2)
    keep = jnp.repeat(keep, e // g, axis=-1)
    _, idx = jax.lax.top_k(jnp.where(keep, choice, -jnp.inf), k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.routed_scale, idx


def capacity(group_size: int, top_k: int, n_experts: int,
             factor: float = 1.25, multiple: int = 4) -> int:
    c = math.ceil(group_size * top_k / n_experts * factor)
    return max(multiple, ((c + multiple - 1) // multiple) * multiple)


def moe_apply(p, x, cfg, *, group_size: int = 256,
              capacity_factor: float = 1.25):
    """x: [B, S, d] → [B, S, d]."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_active
    t = b * s
    gs = min(group_size, t)
    assert t % gs == 0, (t, gs)
    g = t // gs
    c = capacity(gs, k, e, capacity_factor)

    xg = x.reshape(g, gs, d)
    logits = dense(p["router"], xg.astype(jnp.float32))      # [G,Sg,E] f32
    gate_vals, expert_idx = route(logits, cfg)               # [G,Sg,K]

    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # [G,Sg,K,E]
    # position of each (token,k) routing within its expert, in (s,k) order
    flat = onehot.reshape(g, gs * k, e)
    pos = (jnp.cumsum(flat, axis=1) - 1.0) * flat               # [G,Sg*K,E]
    pos = pos.reshape(g, gs, k, e)
    keep = (pos < c) & (onehot > 0)
    slot = jax.nn.one_hot(jnp.sum(pos * onehot, -1), c, dtype=jnp.float32)
    # combine[g,s,e,c] = Σ_k gate·onehot·keep·slot
    combine = jnp.einsum("gske,gskc->gsec",
                         onehot * keep * gate_vals[..., None], slot)
    dispatch = (combine > 0).astype(x.dtype)

    # expert tensors: E → EP axis, groups → DP axes (keeps the dispatch
    # working set sharded both ways; the token all-to-all happens here)
    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, x.reshape(g, gs, d))
    expert_in = shard(expert_in, "expert", "batch", None, None)
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", expert_in, p["gate"])
                    .astype(jnp.float32)).astype(x.dtype)
    h = h * jnp.einsum("egcd,edf->egcf", expert_in, p["up"])
    h = shard(h, "expert", "batch", None, None)
    expert_out = jnp.einsum("egcf,efd->egcd", h, p["down"])
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(x.dtype), expert_out)
    y = y.reshape(b, s, d)

    if "shared" in p:
        y = y + glu_mlp(p["shared"], x)
    return y


def moe_apply_dense_ref(p, x, cfg):
    """O(E·T) dense reference — every expert sees every token; used as the
    dispatch-correctness oracle in tests (no capacity drops)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_active
    logits = dense(p["router"], x.astype(jnp.float32))
    gate_vals, expert_idx = route(logits, cfg)
    dense_w = jnp.sum(
        jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
        * gate_vals[..., None], axis=-2)                       # [B,S,E]

    def one_expert(i):
        h = jax.nn.silu((x @ p["gate"][i]).astype(jnp.float32)
                        ).astype(x.dtype)
        h = h * (x @ p["up"][i])
        return h @ p["down"][i]

    outs = jax.lax.map(one_expert, jnp.arange(e))              # [E,B,S,d]
    y = jnp.einsum("bse,ebsd->bsd", dense_w.astype(x.dtype), outs)
    if "shared" in p:
        y = y + glu_mlp(p["shared"], x)
    return y


# ---------------------------------------------------------------------------
# Dropless chip share: held experts [e0, e0 + n), grouped products
# ---------------------------------------------------------------------------


PASS_LOAD = 4
"""Rows one pass of the expert products takes: this many times the held
experts' expected share of a stream's (token, slot) pairs.  Routing that
sends more takes more passes; none is dropped."""


def share_sizes(cfg, tokens: int):
    """Static sizes of the dropless layer at ``tokens`` tokens: rows per
    block, rows per pass (``PASS_LOAD``), row blocks of a pass's laid-out
    buffer, and the most blocks one expert can take in a pass."""
    k, n = cfg.n_experts_active, cfg.n_held
    bm = row_block(tokens * k / cfg.n_experts)
    most = tokens * min(k, n)              # a token picks an expert once
    rows = min(most, math.ceil(PASS_LOAD * tokens * k * n / cfg.n_experts))
    return bm, rows, layout_blocks(rows, n, bm), -(-min(tokens, rows) // bm)


def _grouped(bank, xs, ids_w, probe, layer, lay, bm, blocks):
    """Per-stream laid-out rows times their experts' bank matrices (± θ̃
    in-kernel under a probe; plain products without one)."""
    start, nblk, last = lay
    if probe is None:
        return kops.perturbed_grouped_matmul(
            xs, bank, None, start, nblk, last, signs=(1.0,) * len(xs),
            dtheta=0.0, bm=bm, blocks=blocks, impl="ref")
    lseed = probe.lseed(ids_w)
    if layer is not None:
        lseed = pert.shifted_leaf_seed(
            lseed, jnp.asarray(layer, jnp.uint32) * jnp.uint32(
                int(np.prod(bank.shape)) & 0xFFFFFFFF))
    return kops.perturbed_grouped_matmul(
        xs, bank, lseed, start, nblk, last, signs=probe.ctx.signs,
        dtheta=probe.ctx.dtheta, bm=bm, blocks=blocks, impl=probe.ctx.impl)


def moe_streams(p, xs, cfg, ids, probe, layer=None):
    """Dropless expert layer over the chip's held experts, one result per
    stream (``layers.pdense``'s stream convention; ``probe=None`` is the
    plain forward).  Returns (ys [B,S,d] per stream, rows [S, n] int32:
    the (token, slot) pairs each stream routed to each held expert).

    Each stream's pairs that chose a held expert are ordered by expert
    (then token, then slot) and taken in passes of at most ``rows`` pairs:
    a pass lays its pairs out by expert, gathers their tokens' rows, runs
    the three grouped products and adds each pair's weighted result to
    its token.  The usual routing takes one pass."""
    b, s, d = xs[0].shape
    t, k, n, e0 = b * s, cfg.n_experts_active, cfg.n_held, cfg.expert_first
    bm, rows, blocks_total, blocks = share_sizes(cfg, t)
    buf_rows = bm * blocks_total
    flat = tuple(x.reshape(t, d) for x in xs)
    with jax.named_scope("repro.model.moe.route"):
        routers = pleaf(p["router"]["w"], ids["router"]["w"], probe,
                        layer=layer)
        # e_score_correction_bias: a buffer, not a parameter (zero here)
        bias = jnp.zeros((cfg.n_experts,), jnp.float32)
        routes = [route(jnp.matmul(x.astype(jnp.float32), r,
                                   precision=jax.lax.Precision.HIGHEST),
                        cfg, bias) for x, r in zip(flat, routers)]
    with jax.named_scope("repro.model.moe.dispatch"):
        experts, order = [], []     # per pair: held expert (n: none), place
        for _, idx in routes:
            local = idx - e0                                # [T, k]
            held = (local >= 0) & (local < n)
            oh = jax.nn.one_hot(jnp.where(held, local, n), n + 1,
                                dtype=jnp.int32)[..., :n].reshape(t * k, n)
            rank = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=1)
            experts.append(jnp.where(held, local, n))
            order.append(rank.reshape(t, k))
        sizes = jnp.stack([jnp.bincount(e.reshape(-1), length=n + 1)[:n]
                           for e in experts]).astype(jnp.int32)   # [S, n]
        first = jnp.cumsum(sizes, axis=1) - sizes   # place of expert's 1st
        ends = first + sizes
        place = [jnp.where(e < n, jnp.take(first[i], jnp.minimum(e, n - 1))
                           + r, -1) for i, (e, r) in enumerate(
                               zip(experts, order))]           # [T, k]
        passes = -(-jnp.max(jnp.sum(sizes, axis=1)) // rows)
    tok = jnp.arange(t * k, dtype=jnp.int32) // k

    def one_pass(i, ys):
        lo = i * rows
        with jax.named_scope("repro.model.moe.dispatch"):
            lay = group_layout(jnp.clip(ends, lo, lo + rows)
                               - jnp.clip(first, lo, lo + rows), bm)
            bufs, dests = [], []
            for si, (e, q, x) in enumerate(zip(experts, place, flat)):
                inside = (q >= lo) & (q < lo + rows)
                ec = jnp.minimum(e, n - 1)
                dest = jnp.where(
                    inside, jnp.take(lay[0][si], ec) * bm + q
                    - jnp.maximum(jnp.take(first[si], ec), lo), buf_rows)
                src = jnp.full((buf_rows,), t, jnp.int32).at[
                    dest.reshape(-1)].set(tok, mode="drop")
                bufs.append(jnp.concatenate(
                    [x, jnp.zeros((1, d), x.dtype)])[src])
                dests.append(dest)
        with jax.named_scope("repro.model.moe.experts"):
            gs = _grouped(p["gate"], bufs, ids["gate"], probe, layer, lay,
                          bm, blocks)
            us = _grouped(p["up"], bufs, ids["up"], probe, layer, lay, bm,
                          blocks)
            hs = [jax.nn.silu(g_.astype(jnp.float32)).astype(g_.dtype) * u_
                  for g_, u_ in zip(gs, us)]
            outs = _grouped(p["down"], hs, ids["down"], probe, layer, lay,
                            bm, blocks)
        with jax.named_scope("repro.model.moe.dispatch"):
            new = []
            for (w, _), dest, o, y in zip(routes, dests, outs, ys):
                for slot in range(k):
                    pos = dest[:, slot]
                    row = jnp.take(o, jnp.minimum(pos, buf_rows - 1), axis=0)
                    y = y + jnp.where((pos < buf_rows)[:, None],
                                      w[:, slot, None]
                                      * row.astype(jnp.float32), 0.0)
                new.append(y)
        return tuple(new)

    ys = jax.lax.fori_loop(0, passes, one_pass, tuple(
        jnp.zeros((t, d), jnp.float32) for _ in xs))
    ys = tuple(y.astype(x.dtype).reshape(b, s, d) for y, x in zip(ys, xs))
    if "shared" in p:
        shared = pglu_mlp(p["shared"], xs, ids["shared"], probe, layer)
        ys = tuple(y + sh for y, sh in zip(ys, shared))
    return ys, sizes
