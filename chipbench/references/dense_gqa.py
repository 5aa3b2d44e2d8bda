"""Plain float32 reference of a dense GQA decoder, as published for
Qwen3 (``qwen3``: per-head RMSNorm on q and k) and Mistral-Nemo
(``mistral``), trained by central-pair MGD.

One decoder layer (HF ``Qwen3DecoderLayer`` / ``MistralDecoderLayer``):

    h = x + o( attn( rope(qk_norm(q(rms(x)))), rope(qk_norm(k(rms(x)))), v(rms(x)) ) )
    x' = h + down( silu(gate(rms(h))) · up(rms(h)) )

with causal softmax attention over grouped KV heads (query head j reads
KV head j // (heads / kv_heads)), rotary embeddings on the two halves of
each head, and an untied output head over the final RMSNorm.  The cost
is the token-mean cross-entropy of the next token.

Every product runs in float32 at ``Precision.HIGHEST``; parameters are
stored in the configuration's dtype (bfloat16), as the trained model
keeps them, and perturbed in float32.  ``compute`` swaps the operands of
every product to a lower precision for the control (float8 e4m3).

Random weights from a seed follow one fixed scheme: normal(0, 1/fan_in)
projections, normal(0, 0.02²) embedding, unit norm scales, drawn with
``jax.random`` from one key per leaf, stacked over layers.  Departures
from the published models: random weights, the depth listed in the
configuration, and no attention or MLP bias (none is published).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import mgd

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    eps: float
    theta: float
    qk_norm: bool
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   layers=c["num_hidden_layers"], eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]),
                   qk_norm=c["model_type"] == "qwen3",
                   dtype=c["torch_dtype"])


def leaves(a: Arch):
    """(path, shape, fan_in or None) of every parameter, in the order the
    leaf ids count them (paths sorted)."""
    L, d, hd, kvd = a.layers, a.d, a.heads * a.head_dim, a.kv_heads * a.head_dim
    out = [("embed/head/w", (d, a.vocab), d),
           ("embed/ln_f/scale", (d,), None),
           ("embed/tok/table", (a.vocab, d), None)]
    if a.qk_norm:
        out += [("layers/attn/k_norm/scale", (L, a.head_dim), None),
                ("layers/attn/q_norm/scale", (L, a.head_dim), None)]
    out += [("layers/attn/wk/w", (L, d, kvd), d),
            ("layers/attn/wo/w", (L, hd, d), hd),
            ("layers/attn/wq/w", (L, d, hd), d),
            ("layers/attn/wv/w", (L, d, kvd), d),
            ("layers/ln1/scale", (L, d), None),
            ("layers/ln2/scale", (L, d), None),
            ("layers/mlp/down/w", (L, a.ff, d), a.ff),
            ("layers/mlp/gate/w", (L, d, a.ff), d),
            ("layers/mlp/up/w", (L, d, a.ff), d)]
    return out


def leaf_ids(a: Arch):
    return {path: i for i, (path, _, _) in enumerate(leaves(a))}


# which split of a layer's key draws each projection
_LAYER_KEY = {"wq": ("attn", 0), "wk": ("attn", 1), "wv": ("attn", 2),
              "wo": ("attn", 3), "gate": ("mlp", 0), "up": ("mlp", 1),
              "down": ("mlp", 2)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init_leaf(a: Arch, path: str, key):
    dtype = jnp.dtype(a.dtype)
    shape, fan_in = next((s, f) for p, s, f in leaves(a) if p == path)
    if path.endswith("/scale"):
        return jnp.ones(shape, dtype)
    k_emb, k_layers, _ = jax.random.split(key, 3)
    k_tok, k_head = jax.random.split(k_emb)
    if path == "embed/tok/table":
        return (jax.random.normal(k_tok, shape, jnp.float32) * 0.02
                ).astype(dtype)
    if path == "embed/head/w":
        return (jax.random.normal(k_head, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)
    part, idx = _LAYER_KEY[path.split("/")[-2]]

    def one(key):
        k_attn, k_mlp = jax.random.split(key)
        key = jax.random.split(k_attn if part == "attn" else k_mlp,
                               4 if part == "attn" else 3)[idx]
        return (jax.random.normal(key, shape[1:], jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    return jax.vmap(one)(jax.random.split(k_layers, a.layers))


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


def _rope(x, theta):
    """x: [B, S, H, D]; rotate the pairs (i, i + D/2) by position · θ^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d // 2, dtype=np.float64) / (d // 2))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, compute):
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, dh)
    if compute == "float32":
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=HIGHEST)
    else:
        cd = jnp.dtype(compute)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(cd), k.astype(cd),
                        preferred_element_type=jnp.float32)
    sc = sc / np.sqrt(dh)
    causal = np.tril(np.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    if compute == "float32":
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)
    else:
        cd = jnp.dtype(compute)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(cd), v.astype(cd),
                       preferred_element_type=jnp.float32)
    return o.reshape(b, s, h * dh)


def _perturbed(leaf, lseed, sigma, dtheta, layer=None):
    """float32 value of a leaf (or of its slice for ``layer``) under θ ± Δθ·s."""
    if layer is None:
        w, offset = leaf, 0
    else:
        w = jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
        offset = jnp.asarray(layer, jnp.uint32) * jnp.uint32(w.size)
    return (w.astype(jnp.float32)
            + sigma * dtheta * mgd.slice_signs(lseed, w.shape, offset))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _embed(a, sigma, dtheta, compute, table, lseed, tokens):
    rows = table[tokens].astype(jnp.float32)
    idx = (tokens.astype(jnp.uint32)[..., None] * jnp.uint32(a.d)
           + jax.lax.iota(jnp.uint32, a.d))
    return rows + sigma * dtheta * mgd.signs(lseed, idx)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _layer(a, sigma, dtheta, compute, x, p, lseeds, layer):
    def w(name):
        return _perturbed(p[name], lseeds[name], sigma, dtheta, layer)

    b, s, _ = x.shape
    h = _rms(x, w("layers/ln1/scale"), a.eps)
    q = _mm(h, w("layers/attn/wq/w"), compute).reshape(b, s, a.heads, -1)
    k = _mm(h, w("layers/attn/wk/w"), compute).reshape(b, s, a.kv_heads, -1)
    v = _mm(h, w("layers/attn/wv/w"), compute).reshape(b, s, a.kv_heads, -1)
    if a.qk_norm:
        q = _rms(q, w("layers/attn/q_norm/scale"), a.eps)
        k = _rms(k, w("layers/attn/k_norm/scale"), a.eps)
    att = _attention(_rope(q, a.theta), _rope(k, a.theta), v, compute)
    x = x + _mm(att, w("layers/attn/wo/w"), compute)
    h = _rms(x, w("layers/ln2/scale"), a.eps)
    g = _mm(h, w("layers/mlp/gate/w"), compute)
    u = _mm(h, w("layers/mlp/up/w"), compute)
    return x + _mm(jax.nn.silu(g) * u, w("layers/mlp/down/w"), compute)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _head_cost(a, sigma, dtheta, compute, token_frac, x, ln_f, head,
               lseed_ln, lseed_head, labels):
    x = _rms(x, _perturbed(ln_f, lseed_ln, sigma, dtheta), a.eps)
    logits = _mm(x, _perturbed(head, lseed_head, sigma, dtheta), compute)
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
    nll = nll.reshape(-1)
    return jnp.mean(nll[:int(nll.size * token_frac)])


def costs(a: Arch, params, tokens, labels, probe_seed, step, *, dtheta,
          compute="float32", token_frac=1.0):
    """(c⁺, c⁻) of one pod's rows at step ``step``, as device scalars on
    the device that holds ``params``.

    ``token_frac`` < 1 takes the mean over the first share of the tokens
    only (a planted fault for the checks' upper readings)."""
    ids = leaf_ids(a)
    lseeds = {p: mgd.leaf_seed(probe_seed, step, i) for p, i in ids.items()}
    layer_p = {p: v for p, v in params.items() if p.startswith("layers/")}
    layer_s = {p: lseeds[p] for p in layer_p}
    out = []
    for sigma in (1.0, -1.0):
        x = _embed(a, sigma, dtheta, compute, params["embed/tok/table"],
                   lseeds["embed/tok/table"], tokens)
        for layer in range(a.layers):
            x = _layer(a, sigma, dtheta, compute, x, layer_p, layer_s,
                       jnp.int32(layer))
        out.append(_head_cost(
            a, sigma, dtheta, compute, float(token_frac), x,
            params["embed/ln_f/scale"], params["embed/head/w"],
            lseeds["embed/ln_f/scale"], lseeds["embed/head/w"], labels))
    return tuple(out)
