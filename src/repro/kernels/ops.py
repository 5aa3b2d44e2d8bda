"""jit'd dispatch wrappers for the MGD Pallas kernels.

``impl`` selection:
* "pallas"    — compiled Pallas (TPU target)
* "interpret" — Pallas interpret mode (CPU-correctness path; default when no
  TPU backend is present)
* "ref"       — pure-jnp oracle (always available, materializes θ̃)

The wrappers zero-pad non-tile-aligned shapes, so any (M, K, N) works.
Padding is sign-safe on every dim because the kernels index signs with the
*unpadded* row stride (``n_cols``): real elements keep their original
row-major linear indices; padded rows multiply zero x columns and padded
columns feed only outputs that are sliced away.  (The previous strategy —
largest divisor ≤ cap — silently degraded to 1-wide tiles for prime dims,
e.g. K=257 → bk=1, a catastrophic grid.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from .mgd_update import mgd_update as _mgd_update_pallas
from .mgd_update import mgd_update_window as _mgd_update_window_pallas
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
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= s
    return x.reshape(m, x.shape[-1]), lead, m


def perturbed_matmul(x, w, lseed, *, dtheta, sign=1.0, impl=None,
                     bm=128, bn=128, bk=128, out_dtype=None):
    """y = x @ (W + sign·Δθ·rademacher(lseed)); θ̃ fused in-kernel.

    Leading batch dims of ``x`` are flattened into M.  Arbitrary shapes are
    zero-padded to tile multiples; sign indexing stays anchored to the
    unpadded W (see module docstring).
    """
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.perturbed_matmul_ref(
            x, w, lseed, dtheta=dtheta, sign=sign, out_dtype=out_dtype)

    kdim, n = w.shape
    with _prep():
        x2, lead, m = _flatten_lead(x)
        bm_eff = min(bm, max(8, m))
        bk_eff = _tile(kdim, bk)
        bn_eff = _tile(n, bn)
        x2p = _pad_to(_pad_to(x2, bm_eff, 0), bk_eff, 1)
        wp = _pad_to(_pad_to(w, bk_eff, 0), bn_eff, 1)
    y = _perturbed_matmul_pallas(
        x2p, wp, lseed, dtheta=dtheta, sign=sign,
        bm=min(bm_eff, x2p.shape[0]), bn=bn_eff, bk=bk_eff,
        out_dtype=out_dtype or x.dtype, n_cols=n,
        interpret=(impl == "interpret"),
    )
    with _prep():
        return y[:m, :n].reshape(*lead, n)


def perturbed_matmul_pair(xp, xm, w, lseed, *, dtheta, impl=None,
                          bm=128, bn=128, bk=128, out_dtype=None):
    """(xp @ (W+θ̃), xm @ (W−θ̃)) with ONE pass over W (antithetic probe pair).

    ``xp``/``xm`` are the +/− probe activation streams (same shape); leading
    batch dims are flattened into M.
    """
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.perturbed_matmul_pair_ref(
            xp, xm, w, lseed, dtheta=dtheta, out_dtype=out_dtype)

    kdim, n = w.shape
    with _prep():
        xp2, lead, m = _flatten_lead(xp)
        xm2, _, _ = _flatten_lead(xm)
        bm_eff = min(bm, max(8, m))
        bk_eff = _tile(kdim, bk)
        bn_eff = _tile(n, bn)
        xp2 = _pad_to(_pad_to(xp2, bm_eff, 0), bk_eff, 1)
        xm2 = _pad_to(_pad_to(xm2, bm_eff, 0), bk_eff, 1)
        wp = _pad_to(_pad_to(w, bk_eff, 0), bn_eff, 1)
    yp, ym = _perturbed_matmul_pair_pallas(
        xp2, xm2, wp, lseed, dtheta=dtheta,
        bm=min(bm_eff, xp2.shape[0]), bn=bn_eff, bk=bk_eff,
        out_dtype=out_dtype or xp.dtype, n_cols=n,
        interpret=(impl == "interpret"),
    )
    with _prep():
        return (yp[:m, :n].reshape(*lead, n), ym[:m, :n].reshape(*lead, n))


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
