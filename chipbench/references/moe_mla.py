"""Plain float32 reference of DeepSeek-V3's decoder on one chip's share of
the experts, as published (HF ``DeepseekV3ForCausalLM``, config.json and
arXiv:2412.19437), trained by central-pair MGD (``mgd.py``).

One decoder layer (``DeepseekV3DecoderLayer``):

    h  = x + o(MLA(rms(x)))
    x' = h + MLP(rms(h))            first ``first_k_dense_replace`` layers
    x' = h + shared(rms(h)) + Σ_{held e} w_e · expert_e(rms(h))    the rest

MLA (``DeepseekV3Attention``): q = q_b(rms(q_a(x))) split per head into
q_nope [128] and q_pe [64]; kv_a(x) = [c (512), k_pe (64)]; k_nope and v
from kv_b(rms(c)) per head; q_pe and the one shared k_pe rotated by YaRN
rope in HF's layout (each head's rope dims reordered (0, 2, 4, …, 1, 3,
…) then rotated by halves, cos/sin from the YaRN frequencies times
mscale(factor, mscale)/mscale(factor, mscale_all_dim)); causal softmax of
[q_nope, q_pe]·[k_nope, k_pe] × q_head_dim^-½ · mscale(factor,
mscale_all_dim)², times v.  Experts and MLPs are SwiGLU.

Routing (``MoEGate``, ``noaux_tc``) over all E published experts: router
logits in float32; scores σ(logits); the choice adds the score-correction
bias; groups of E/n_group ranked by the sum of their two best biased
scores, the ``topk_group`` best kept; the top-k experts among the kept
groups; weights the unbiased scores of those, normalized to sum 1 and
times ``routed_scaling_factor``.  This chip holds experts
[first_held_expert, +n_routed_experts) and adds only their part; each is
computed on the rows of the tokens that chose it, gathered.

Every product runs in float32 at ``Precision.HIGHEST``; parameters are
stored in the configuration's dtype and perturbed in float32.
``compute`` swaps the operands of every product to a lower precision for
the control (float8 e4m3).  Random weights follow the program's scheme,
drawn here on their own: normal(0, 1/fan_in) projections (expert i of a
bank from fold_in(key, i)), normal(0, 0.02²) embedding, unit norm scales,
the router in float32.

Departures from the published model: random weights; bf16 weights, as in
DeepSeek's own bf16 conversion, not the fp8 block-scaled release; no MTP
module; the score-correction bias at zero (a buffer training sets); the
depth, held experts and vocabulary of the configuration.

``planted`` turns this reference into a program with a known fault, for
the test that each fault fails the compared numbers
(``chipbench/tests/test_moe_mla.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import mgd

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("capacity", "softmax", "shared_signs", "expert_off")
_planted = {"fault": None}


@contextlib.contextmanager
def planted(fault):
    """Within the block, the reference runs with ``fault`` (one of
    ``FAULTS``) as a program with that bug would:

    * ``capacity``: routing drops the pairs over a per-expert capacity of
      ⌈128·k/E·1.25⌉ (rounded up to 4) in each group of 128 tokens;
    * ``softmax``: softmax top-k routing instead of the published rule;
    * ``shared_signs``: every held expert probes with expert 0's signs
      (the per-expert offset missing), the update unchanged;
    * ``expert_off``: held expert e computes with expert e+1's weights.
    """
    assert fault in FAULTS, fault
    _planted["fault"] = fault
    try:
        yield
    finally:
        _planted["fault"] = None


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    ff: int             # dense layers' MLP
    expert_ff: int
    shared: int         # shared experts
    experts: int        # published routed experts (router width)
    first: int          # first held expert
    held: int
    top_k: int
    groups: int
    top_groups: int
    scale: float
    vocab: int
    dense: int          # leading dense layers
    layers: int         # all layers
    eps: float
    theta: float
    yarn: tuple         # (factor, original, beta_fast, beta_slow, mscale, all)
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        y = c["rope_scaling"]
        return cls(
            d=c["hidden_size"], heads=c["num_attention_heads"],
            q_lora=c["q_lora_rank"], kv_lora=c["kv_lora_rank"],
            nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
            v=c["v_head_dim"], ff=c["intermediate_size"],
            expert_ff=c["moe_intermediate_size"], shared=c["n_shared_experts"],
            experts=c["n_routed_experts_published"],
            first=c["first_held_expert"], held=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"], groups=c["n_group"],
            top_groups=c["topk_group"],
            scale=float(c["routed_scaling_factor"]), vocab=c["vocab_held"],
            dense=c["first_k_dense_replace"], layers=c["num_hidden_layers"],
            eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
            yarn=(float(y["factor"]), int(y["original_max_position_embeddings"]),
                  float(y["beta_fast"]), float(y["beta_slow"]),
                  float(y["mscale"]), float(y["mscale_all_dim"])),
            dtype=c["torch_dtype"])

    @property
    def moe(self) -> int:
        return self.layers - self.dense


def _attn_leaves(stack, n, a):
    qd = a.nope + a.rope
    return [(f"{stack}/attn/kv_norm/scale", (n, a.kv_lora), None),
            (f"{stack}/attn/q_norm/scale", (n, a.q_lora), None),
            (f"{stack}/attn/wkv_a/w", (n, a.d, a.kv_lora + a.rope), a.d),
            (f"{stack}/attn/wkv_b/w", (n, a.kv_lora, a.heads * (a.nope + a.v)),
             a.kv_lora),
            (f"{stack}/attn/wo/w", (n, a.heads * a.v, a.d), a.heads * a.v),
            (f"{stack}/attn/wq_a/w", (n, a.d, a.q_lora), a.d),
            (f"{stack}/attn/wq_b/w", (n, a.q_lora, a.heads * qd), a.q_lora),
            (f"{stack}/ln1/scale", (n, a.d), None),
            (f"{stack}/ln2/scale", (n, a.d), None)]


def leaves(a: Arch):
    """(path, shape, fan_in or None) of every parameter, in the order the
    leaf ids count them (paths sorted)."""
    L, D, f, sf = a.moe, a.dense, a.expert_ff, a.expert_ff * a.shared
    out = []
    if D:
        out += _attn_leaves("dense_layers", D, a) + [
            ("dense_layers/mlp/down/w", (D, a.ff, a.d), a.ff),
            ("dense_layers/mlp/gate/w", (D, a.d, a.ff), a.d),
            ("dense_layers/mlp/up/w", (D, a.d, a.ff), a.d)]
    out += [("embed/head/w", (a.d, a.vocab), a.d),
            ("embed/ln_f/scale", (a.d,), None),
            ("embed/tok/table", (a.vocab, a.d), None)]
    out += _attn_leaves("layers", L, a) + [
        ("layers/moe/down", (L, a.held, f, a.d), f),
        ("layers/moe/gate", (L, a.held, a.d, f), a.d),
        ("layers/moe/router/w", (L, a.d, a.experts), a.d),
        ("layers/moe/shared/down/w", (L, sf, a.d), sf),
        ("layers/moe/shared/gate/w", (L, a.d, sf), a.d),
        ("layers/moe/shared/up/w", (L, a.d, sf), a.d),
        ("layers/moe/up", (L, a.held, a.d, f), a.d)]
    return out


def leaf_ids(a: Arch):
    return {path: i for i, (path, _, _) in enumerate(leaves(a))}


# which split of a layer's key draws each projection: (part, splits, index)
_ATTN = {"wkv_a": 0, "wkv_b": 1, "wo": 2, "wq_a": 3, "wq_b": 4}
_MLP = {"gate": 0, "up": 1, "down": 2}
_MOE = {"router": 0, "gate": 1, "up": 2, "down": 3, "shared": 4}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init_leaf(a: Arch, path: str, key):
    dtype = jnp.dtype(a.dtype)
    shape, fan_in = next((s, f) for p, s, f in leaves(a) if p == path)
    if path.endswith("/scale"):
        return jnp.ones(shape, dtype)
    k_emb, k_layers, k_dense = jax.random.split(key, 3)
    if path.startswith("embed/"):
        k_tok, k_head = jax.random.split(k_emb)
        if path == "embed/tok/table":
            return (jax.random.normal(k_tok, shape, jnp.float32) * 0.02
                    ).astype(dtype)
        return (jax.random.normal(k_head, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)
    parts = path.split("/")
    stack, part = parts[0], parts[1]
    scale = 1.0 / np.sqrt(fan_in)

    def one(key):
        k_attn, k_mlp = jax.random.split(key)
        if part == "attn":
            k = jax.random.split(k_attn, 8)[_ATTN[parts[2]]]
        elif part == "mlp":
            k = jax.random.split(k_mlp, 3)[_MLP[parts[2]]]
        else:
            k = jax.random.split(k_mlp, 5)[_MOE[parts[2]]]
            if parts[2] == "shared":
                k = jax.random.split(k, 3)[_MLP[parts[3]]]
            elif parts[2] != "router":       # an expert bank
                return jnp.stack([
                    jax.random.normal(jax.random.fold_in(k, i), shape[2:],
                                      jnp.float32) * scale
                    for i in range(a.first, a.first + a.held)]).astype(dtype)
        w = jax.random.normal(k, shape[1:], jnp.float32) * scale
        return w if parts[2] == "router" else w.astype(dtype)

    n = a.dense if stack == "dense_layers" else a.moe
    return jax.vmap(one)(jax.random.split(
        k_dense if stack == "dense_layers" else k_layers, n))


def init_leaf(a: Arch, path: str, seed: int):
    return _init_leaf(a, path, jax.random.PRNGKey(seed))


def init(a: Arch, seed: int):
    """Every parameter from ``seed``, leaf by leaf, on the default device."""
    return {path: init_leaf(a, path, seed) for path, _, _ in leaves(a)}


# --- forward ----------------------------------------------------------------


def _mm(x, w, compute):
    if compute == "float32":
        return jnp.matmul(x, w, precision=HIGHEST)
    cd = jnp.dtype(compute)
    return jnp.matmul(x.astype(cd), w.astype(cd),
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn(a: Arch, s: int):
    """cos, sin [S, rope] of HF's DeepseekV3YarnRotaryEmbedding."""
    factor, original, fast, slow, msc, msc_all = a.yarn
    dim = a.rope
    extra = 1.0 / a.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (factor * a.theta ** (np.arange(0, dim, 2,
                                                  dtype=np.float64) / dim))

    def corr(rot):
        return dim * math.log(original / (rot * 2 * math.pi)) / (
            2 * math.log(a.theta))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                    dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = (inter * (1 - mask) + extra * mask).astype(np.float32)
    freqs = np.outer(np.arange(s, dtype=np.float64), inv.astype(np.float64))
    emb = np.concatenate([freqs, freqs], -1)
    m = _mscale(factor, msc) / _mscale(factor, msc_all)
    return (jnp.asarray(np.cos(emb) * m, jnp.float32),
            jnp.asarray(np.sin(emb) * m, jnp.float32))


def _rotate(x, cos, sin):
    """HF apply_rotary_pos_emb on x [B, S, H, D]: reorder D as (0, 2, …,
    1, 3, …), then x·cos + rotate_half(x)·sin."""
    b, s, h, d = x.shape
    x = x.reshape(b, s, h, d // 2, 2).swapaxes(-1, -2).reshape(b, s, h, d)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def _softmax_scale(a: Arch):
    factor, _, _, _, _, msc_all = a.yarn
    scale = (a.nope + a.rope) ** -0.5
    if msc_all:
        scale *= _mscale(factor, msc_all) ** 2
    return scale


def _attention(a, q, k, v, compute, block=256, group=4):
    """Causal softmax attention, one block of queries at a time.
    q, k: [B, S, H, Dq]; v: [B, S, H, Dv] → [B, S, H·Dv].  The query
    blocks go in groups of ``group``, each against the keys up to its
    group's end: the keys after it are masked for every query of the
    group, so they are left out and not computed."""
    b, s, h, _ = q.shape
    block = min(block, s)
    scale = _softmax_scale(a)
    outs = []
    for g0 in range(0, s, block * group):
        g1 = min(g0 + block * group, s)
        kg, vg = k[:, :g1], v[:, :g1]

        def one(i, g0=g0, g1=g1, kg=kg, vg=vg):
            start = g0 + i * block
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, 1)
            if compute == "float32":
                sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kg, precision=HIGHEST)
            else:
                cd = jnp.dtype(compute)
                sc = jnp.einsum("bqhd,bkhd->bhqk", qb.astype(cd),
                                kg.astype(cd),
                                preferred_element_type=jnp.float32)
            sc = sc * scale
            causal = (jnp.arange(g1)[None, :]
                      <= (start + jnp.arange(block))[:, None])
            p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            if compute == "float32":
                return jnp.einsum("bhqk,bkhd->bqhd", p, vg, precision=HIGHEST)
            cd = jnp.dtype(compute)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(cd), vg.astype(cd),
                              preferred_element_type=jnp.float32)

        o = jax.lax.map(one, jnp.arange((g1 - g0) // block))
        outs.append(jnp.moveaxis(o, 0, 1).reshape(b, g1 - g0, -1))
    return jnp.concatenate(outs, 1)


def _perturbed(leaf, lseed, sigma, dtheta, layer=None):
    """float32 value of a leaf (or of its slice for ``layer``) under θ ± Δθ·s."""
    if layer is None:
        w, offset = leaf, 0
    else:
        w = jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
        offset = jnp.asarray(layer, jnp.uint32) * jnp.uint32(w.size)
    return (w.astype(jnp.float32)
            + sigma * dtheta * mgd.slice_signs(lseed, w.shape, offset))


def _bank(leaf, lseed, sigma, dtheta, layer, fault):
    """A held expert bank's slice [n, K, N] of ``layer`` under θ ± Δθ·s,
    with the planted faults that touch it."""
    w = jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    if fault == "shared_signs":
        s = mgd.slice_signs(lseed, w.shape[1:],
                            jnp.asarray(layer, jnp.uint32) * jnp.uint32(w.size))
        s = jnp.broadcast_to(s, w.shape)
    else:
        s = mgd.slice_signs(lseed, w.shape,
                            jnp.asarray(layer, jnp.uint32) * jnp.uint32(w.size))
    w = w.astype(jnp.float32) + sigma * dtheta * s
    if fault == "expert_off":
        w = jnp.roll(w, -1, axis=0)
    return w


def _mlp(h, gate, up, down, compute):
    return _mm(jax.nn.silu(_mm(h, gate, compute)) * _mm(h, up, compute),
               down, compute)


def route(a: Arch, logits, fault=None):
    """(weights [T, k], experts [T, k]) of the published rule, or of the
    planted fault's."""
    if fault == "softmax":
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), a.top_k)
        return w / jnp.sum(w, -1, keepdims=True), idx
    scores = jax.nn.sigmoid(logits)
    choice = scores + jnp.zeros((a.experts,), jnp.float32)   # bias: zero
    t = choice.shape[0]
    group = jnp.sum(jax.lax.top_k(
        choice.reshape(t, a.groups, -1), 2)[0], -1)          # [T, groups]
    _, kept = jax.lax.top_k(group, a.top_groups)
    mask = jnp.zeros((t, a.groups), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    mask = jnp.repeat(mask, a.experts // a.groups, axis=1)
    _, idx = jax.lax.top_k(jnp.where(mask, choice, -jnp.inf), a.top_k)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * a.scale
    if fault == "capacity":
        w = w * _capacity_keep(a, idx)
    return w, idx


def _capacity_keep(a: Arch, idx, group=128, factor=1.25):
    """1 for the (token, slot) pairs a capacity router keeps: per group of
    ``group`` tokens, each expert takes its first ⌈group·k/E·factor⌉ pairs
    (rounded up to 4) in (token, slot) order."""
    t, k = idx.shape
    group = min(group, t)
    cap = math.ceil(group * k / a.experts * factor)
    cap = max(4, -(-cap // 4) * 4)
    oh = jax.nn.one_hot(idx.reshape(t // group, group * k), a.experts)
    pos = jnp.cumsum(oh, axis=1) - 1.0
    keep = jnp.sum(oh * (pos < cap), -1)
    return keep.reshape(t, k)


def _experts(a, h, w, idx, banks, compute):
    """Σ over held experts of weight · expert(rows that chose it), each
    computed on its gathered rows and added back to their tokens."""
    t = h.shape[0]
    y = jnp.zeros_like(h)
    for e in range(a.held):
        chose = idx == a.first + e                            # [T, k]
        weight = jnp.sum(jnp.where(chose, w, 0.0), -1)        # [T]
        (rows,) = jnp.nonzero(jnp.any(chose, -1), size=t, fill_value=t)
        hz = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])
        out = _mlp(hz[rows], banks["gate"][e], banks["up"][e],
                   banks["down"][e], compute)
        wz = jnp.concatenate([weight, jnp.zeros((1,), weight.dtype)])
        y = y.at[rows].add(wz[rows][:, None] * out, mode="drop")
    return y


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _layer(a, sigma, dtheta, compute, stack, fault, x, p, lseeds, layer):
    """(x after the layer, its experts [T, k] or None for a dense one)."""
    def w(name):
        key = f"{stack}/{name}"
        return _perturbed(p[key], lseeds[key], sigma, dtheta, layer)

    b, s, _ = x.shape
    hh, qd = a.heads, a.nope + a.rope
    h = _rms(x, w("ln1/scale"), a.eps)
    q = _mm(_rms(_mm(h, w("attn/wq_a/w"), compute), w("attn/q_norm/scale"),
                 a.eps), w("attn/wq_b/w"), compute).reshape(b, s, hh, qd)
    ckv = _mm(h, w("attn/wkv_a/w"), compute)
    c, k_pe = ckv[..., :a.kv_lora], ckv[..., a.kv_lora:]
    kv = _mm(_rms(c, w("attn/kv_norm/scale"), a.eps), w("attn/wkv_b/w"),
             compute).reshape(b, s, hh, a.nope + a.v)
    cos, sin = _yarn(a, s)
    q = jnp.concatenate([q[..., :a.nope], _rotate(q[..., a.nope:], cos, sin)],
                        -1)
    k_pe = _rotate(k_pe[:, :, None, :], cos, sin)
    k = jnp.concatenate([kv[..., :a.nope],
                         jnp.broadcast_to(k_pe, (b, s, hh, a.rope))], -1)
    att = _attention(a, q, k, kv[..., a.nope:], compute)
    x = x + _mm(att, w("attn/wo/w"), compute)
    h = _rms(x, w("ln2/scale"), a.eps)
    if stack == "dense_layers":
        return x + _mlp(h, w("mlp/gate/w"), w("mlp/up/w"), w("mlp/down/w"),
                        compute), None
    hf = h.reshape(b * s, -1)
    logits = _mm(hf, w("moe/router/w"), compute)
    wts, idx = route(a, logits, fault)
    banks = {n: _bank(p[f"layers/moe/{n}"], lseeds[f"layers/moe/{n}"], sigma,
                      dtheta, layer, fault) for n in ("gate", "up", "down")}
    routed = _experts(a, hf, wts, idx, banks, compute).reshape(b, s, -1)
    shared = _mlp(h, w("moe/shared/gate/w"), w("moe/shared/up/w"),
                  w("moe/shared/down/w"), compute)
    return x + shared + routed, idx


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _embed(a, sigma, dtheta, compute, table, lseed, tokens):
    rows = table[tokens].astype(jnp.float32)
    idx = (tokens.astype(jnp.uint32)[..., None] * jnp.uint32(a.d)
           + jax.lax.iota(jnp.uint32, a.d))
    return rows + sigma * dtheta * mgd.signs(lseed, idx)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _head_cost(a, sigma, dtheta, compute, token_frac, x, ln_f, head,
               lseed_ln, lseed_head, labels):
    x = _rms(x, _perturbed(ln_f, lseed_ln, sigma, dtheta), a.eps)
    logits = _mm(x, _perturbed(head, lseed_head, sigma, dtheta), compute)
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
    nll = nll.reshape(-1)
    return jnp.mean(nll[:int(nll.size * token_frac)])


def _forward(a, sigma, dtheta, compute, params, lseeds, tokens):
    """Hidden state after the last layer, and each MoE layer's experts."""
    x = _embed(a, sigma, dtheta, compute, params["embed/tok/table"],
               lseeds["embed/tok/table"], tokens)
    chosen = []
    for stack, n in (("dense_layers", a.dense), ("layers", a.moe)):
        p = {k: v for k, v in params.items() if k.startswith(stack + "/")}
        s = {k: lseeds[k] for k in p}
        for layer in range(n):
            x, idx = _layer(a, sigma, dtheta, compute, stack,
                            _planted["fault"], x, p, s, jnp.int32(layer))
            if idx is not None:
                chosen.append(idx)
    return x, chosen


def routes(a: Arch, params, tokens):
    """Experts [T, k] each MoE layer chooses for ``tokens`` under the
    unperturbed parameters."""
    lseeds = {p: jnp.uint32(0) for p in leaf_ids(a)}
    return _forward(a, 0.0, 0.0, "float32", params, lseeds, tokens)[1]


def costs(a: Arch, params, tokens, labels, probe_seed, step, *, dtheta,
          compute="float32", token_frac=1.0):
    """(c⁺, c⁻) of one pod's rows at step ``step``, as device scalars on
    the device that holds ``params``.

    ``token_frac`` < 1 takes the mean over the first share of the tokens
    only (a planted fault for the checks' upper readings)."""
    ids = leaf_ids(a)
    lseeds = {p: mgd.leaf_seed(probe_seed, step, i) for p, i in ids.items()}
    out = []
    for sigma in (1.0, -1.0):
        x, _ = _forward(a, sigma, dtheta, compute, params, lseeds, tokens)
        out.append(_head_cost(
            a, sigma, dtheta, compute, float(token_frac), x,
            params["embed/ln_f/scale"], params["embed/head/w"],
            lseeds["embed/ln_f/scale"], lseeds["embed/head/w"], labels))
    return tuple(out)
