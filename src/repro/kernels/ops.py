"""jit'd dispatch wrappers for the MGD Pallas kernels.

``impl`` selection:
* "pallas"    — compiled Pallas (TPU target)
* "interpret" — Pallas interpret mode (CPU-correctness path; default when no
  TPU backend is present)
* "ref"       — pure-jnp oracle (always available, materializes θ̃)

Any (M, K, N) works.  The probe kernels pick their tiles from the shapes
and take ragged last M and N blocks, so neither x nor W is padded there;
only a K that no multiple of 128 divides and that is too deep for one
block is zero-padded.  The update wrappers zero-pad non-tile-aligned
shapes.  Padding is sign-safe on every dim because the kernels index
signs with the *unpadded* row stride (``n_cols``): real elements keep
their original row-major linear indices; padded rows multiply zero x
columns and padded columns feed only outputs that are sliced away.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from .flash_attention import (
    mla_flash_attention as _mla_flash_attention_pallas)
from .grouped_matmul import grouped_matmul_ref as _grouped_matmul_ref
from .grouped_matmul import (
    perturbed_grouped_matmul as _perturbed_grouped_matmul_pallas)
from .mgd_update import mgd_update as _mgd_update_pallas
from .mgd_update import mgd_update_window as _mgd_update_window_pallas
from .perturbed_matmul import k_block
from .perturbed_matmul import perturbed_matmul as _perturbed_matmul_pallas
from .perturbed_matmul import (
    perturbed_matmul_pair as _perturbed_matmul_pair_pallas)


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def _prep():
    """Device scope of a wrapper's reshapes, pads and output slices around
    its ``pallas_call`` (metadata only: the compiled ops do not change)."""
    return jax.named_scope("repro.kernels.prep")


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _tile(dim: int, cap: int) -> int:
    """Tile size for ``dim``: the whole dim when it fits under ``cap``,
    else the cap itself (the operand is zero-padded to a multiple)."""
    return dim if dim <= cap else cap


def _flatten_lead(x):
    return x.reshape(-1, x.shape[-1]), x.shape[:-1]


def _pad_k(xs, w):
    """Zero-pad K to a multiple of 128 where the probe kernels have no
    block for it (``k_block``): zero x columns meet the padded W rows, so
    those rows' signs add nothing."""
    if k_block(w.shape[0]):
        return xs, w
    return [_pad_to(x, 128, 1) for x in xs], _pad_to(w, 128, 0)


def perturbed_matmul(x, w, lseed, *, dtheta, sign=1.0, impl=None,
                     out_dtype=None):
    """y = x @ (W + sign·Δθ·rademacher(lseed)); θ̃ fused in-kernel.

    Leading batch dims of ``x`` are flattened into M.  Tiles are picked
    from the shapes (``perturbed_matmul.pick_tiles``).
    """
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.perturbed_matmul_ref(
            x, w, lseed, dtheta=dtheta, sign=sign, out_dtype=out_dtype)

    n = w.shape[1]
    with _prep():
        x2, lead = _flatten_lead(x)
        (x2,), wk = _pad_k([x2], w)
    y = _perturbed_matmul_pallas(
        x2, wk, lseed, dtheta=dtheta, sign=sign,
        out_dtype=out_dtype or x.dtype, interpret=(impl == "interpret"),
    )
    with _prep():
        return y.reshape(*lead, n)


def perturbed_matmul_pair(xp, xm, w, lseed, *, dtheta, impl=None,
                          out_dtype=None):
    """(xp @ (W+θ̃), xm @ (W−θ̃)) with ONE pass over W (antithetic probe pair).

    ``xp``/``xm`` are the +/− probe activation streams (same shape); leading
    batch dims are flattened into M.  Tiles are picked from the shapes
    (``perturbed_matmul.pick_tiles``).
    """
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.perturbed_matmul_pair_ref(
            xp, xm, w, lseed, dtheta=dtheta, out_dtype=out_dtype)

    n = w.shape[1]
    with _prep():
        xp2, lead = _flatten_lead(xp)
        xm2, _ = _flatten_lead(xm)
        (xp2, xm2), wk = _pad_k([xp2, xm2], w)
    yp, ym = _perturbed_matmul_pair_pallas(
        xp2, xm2, wk, lseed, dtheta=dtheta, out_dtype=out_dtype or xp.dtype,
        interpret=(impl == "interpret"),
    )
    with _prep():
        return yp.reshape(*lead, n), ym.reshape(*lead, n)


def perturbed_grouped_matmul(xs, w, lseed, start, nblk, last, *, signs,
                             dtheta, bm, blocks, impl=None):
    """Each stream's expert-laid-out rows [R, K] times its expert's
    W[e] ± Δθ·signs_e (``grouped_matmul.perturbed_grouped_matmul``); the
    streams of a central pair share one read of each W tile.  K must
    have a block (``perturbed_matmul.k_block``).  ``lseed`` None
    (``impl="ref"`` only) is the plain product."""
    impl = impl or default_impl()
    if impl == "ref":
        return _grouped_matmul_ref(xs, w, lseed, start, nblk, signs=signs,
                                   dtheta=dtheta, bm=bm)
    pair = tuple(signs) == (1.0, -1.0)
    return _perturbed_grouped_matmul_pallas(
        tuple(xs), w, lseed, start, nblk, last, signs=tuple(signs),
        dtheta=dtheta, bm=bm, blocks=blocks,
        interpret=(impl == "interpret"),
        name="perturbed_grouped_matmul_pair" if pair
        else "perturbed_grouped_matmul")


def mla_flash_attention(q, kv, k_rope, *, scale, impl=None, blocks=None):
    """Causal MLA attention, q [B, H, S, dn + dr] heads-major with the rope
    applied, kv [B, S, H·(dn + dv)] as ``wkv_b`` writes it, the shared
    rope key k_rope [B, S, dr] → [B, S, H·dv]
    (``flash_attention.mla_flash_attention``)."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.mla_attention_ref(q, kv, k_rope, scale=scale)
    return _mla_flash_attention_pallas(q, kv, k_rope, scale=scale,
                                       blocks=blocks,
                                       interpret=(impl == "interpret"))


def _as_matrix(w):
    """View an ndim≥2 leaf as [prod(lead), last] — row-major flattening, so
    the linear sign indices are unchanged."""
    assert w.ndim >= 2, w.shape
    return w.reshape(-1, w.shape[-1])


def mgd_update(w, lseeds, coefs, *, eta, dtheta, impl=None, bk=256, bn=256):
    """Fused scalar-replay window update for one weight matrix."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.mgd_update_ref(w, lseeds, coefs, eta=eta, dtheta=dtheta)
    kdim, n = w.shape
    bk_eff = _tile(kdim, bk)
    bn_eff = _tile(n, bn)
    with _prep():
        wp = _pad_to(_pad_to(w, bk_eff, 0), bn_eff, 1)
    out = _mgd_update_pallas(
        wp, lseeds, coefs, eta=eta, dtheta=dtheta,
        bk=bk_eff, bn=bn_eff, n_cols=n,
        interpret=(impl == "interpret"),
    )
    with _prep():
        return out[:kdim, :n]


def mgd_update_window(w, lseeds, coefs, *, alpha, dtheta, impl=None,
                      bk=256, bn=256):
    """Sequential-axpy window update W ← W + α·((Δθ·sign_j)·coefs[j]) —
    bit-exact (f32) fused form of the optimizer's per-step update chain.

    Accepts any ndim ≥ 2 leaf (stacked [L, d_in, d_out] banks, conv
    kernels); the leaf is viewed row-major as a matrix, which preserves the
    host generator's linear sign indices exactly.
    """
    shape = w.shape
    w2 = _as_matrix(w)
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.mgd_update_window_ref(
            w2, lseeds, coefs, alpha=alpha, dtheta=dtheta).reshape(shape)
    kdim, n = w2.shape
    bk_eff = _tile(kdim, bk)
    bn_eff = _tile(n, bn)
    with _prep():
        wp = _pad_to(_pad_to(w2, bk_eff, 0), bn_eff, 1)
    out = _mgd_update_window_pallas(
        wp, lseeds, coefs, alpha=alpha, dtheta=dtheta,
        bk=bk_eff, bn=bn_eff, n_cols=n,
        interpret=(impl == "interpret"),
    )
    with _prep():
        return out[:kdim, :n].reshape(shape)
