"""Pallas kernel correctness: interpret-mode vs pure-jnp oracle, swept over
shapes and dtypes, plus bit-exactness against the host perturbation
generator (the contract that lets the kernel regenerate θ̃ in VMEM)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import perturbations as pert
from repro.kernels import ops, ref

SHAPES_MM = [
    (64, 128, 256), (16, 48, 80), (1, 256, 256), (130, 384, 96),
    (8, 8, 8), (256, 512, 128),
]


@pytest.mark.parametrize("m,k,n", SHAPES_MM)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_perturbed_matmul_matches_ref(m, k, n, dtype):
    kx = jax.random.PRNGKey(0)
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype) * 0.1
    lseed = pert.leaf_seed(7, 3, 2)
    y_ref = ref.perturbed_matmul_ref(x, w, lseed, dtheta=0.01)
    y_pal = ops.perturbed_matmul(x, w, lseed, dtheta=0.01, impl="interpret")
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    err = float(jnp.max(jnp.abs(
        y_ref.astype(jnp.float32) - y_pal.astype(jnp.float32))))
    assert err < tol, (m, k, n, dtype, err)


@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_antithetic_probe_sign(sign):
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    ls = pert.leaf_seed(1, 5, 0)
    a = ref.perturbed_matmul_ref(x, w, ls, dtheta=0.05, sign=sign)
    b = ops.perturbed_matmul(x, w, ls, dtheta=0.05, sign=sign,
                             impl="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_kernel_signs_match_host_generator():
    """The in-kernel hash must reproduce perturbations.generate exactly —
    this is what makes regeneration (not storage) of θ̃ sound."""
    x = jnp.eye(96, dtype=jnp.float32)          # identity: y = W + Δθ·signs
    w = jnp.zeros((96, 128), jnp.float32)
    step, seed = 11, 42
    th = pert.generate({"w": w}, ptype="rademacher", step=step, seed=seed,
                       dtheta=1.0)["w"]
    lseed = pert.leaf_seed(seed, step, 0)
    y = ops.perturbed_matmul(x, w, lseed, dtheta=1.0, impl="interpret")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(th))


@pytest.mark.parametrize("k,n,j", [(128, 256, 4), (96, 80, 7), (256, 512, 1),
                                   (8, 8, 3)])
def test_mgd_update_matches_ref(k, n, j):
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32)
    lseeds = jnp.array([pert.leaf_seed(7, t, 0) for t in range(j)],
                       jnp.uint32)
    coefs = jax.random.normal(jax.random.PRNGKey(2), (j,), jnp.float32)
    u_ref = ref.mgd_update_ref(w, lseeds, coefs, eta=0.1, dtheta=0.01)
    u_pal = ops.mgd_update(w, lseeds, coefs, eta=0.1, dtheta=0.01,
                           impl="interpret")
    np.testing.assert_allclose(np.asarray(u_ref), np.asarray(u_pal),
                               rtol=1e-4, atol=1e-3)


def test_mgd_update_equals_sequential_sgd_steps():
    """One fused window update == applying each scalar step separately."""
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 64), jnp.float32)
    steps = [5, 6, 7]
    lseeds = jnp.array([pert.leaf_seed(0, t, 0) for t in steps], jnp.uint32)
    coefs = jnp.array([0.3, -0.2, 0.05], jnp.float32)
    fused = ops.mgd_update(w, lseeds, coefs, eta=0.01, dtheta=0.1,
                           impl="interpret")
    w_seq = w
    for t, c in zip(steps, coefs):
        th = pert.generate({"w": w}, ptype="rademacher", step=t, seed=0,
                           dtheta=0.1)["w"]
        w_seq = w_seq - 0.01 * float(c) * th / (0.1 * 0.1)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(w_seq),
                               rtol=1e-4, atol=1e-4)


def test_batched_leading_dims():
    """ops wrapper flattens leading batch dims."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    ls = pert.leaf_seed(0, 0, 0)
    y = ops.perturbed_matmul(x, w, ls, dtheta=0.01, impl="interpret")
    assert y.shape == (2, 5, 32)
    y_ref = ref.perturbed_matmul_ref(x.reshape(10, 64), w, ls, dtheta=0.01)
    np.testing.assert_allclose(np.asarray(y.reshape(10, 32)),
                               np.asarray(y_ref), rtol=1e-4, atol=1e-4)


# --- tile picking ------------------------------------------------------------

pm = importlib.import_module("repro.kernels.perturbed_matmul")


def _weight_matmuls(name):
    """(K, N) of every weight product of one forward of ``name``."""
    c = get_config(name)
    q, kv = c.n_heads * c.d_head, c.n_kv_heads * c.d_head
    return [(c.d_model, q), (c.d_model, kv), (q, c.d_model),
            (c.d_model, c.d_ff), (c.d_ff, c.d_model), (c.d_model, c.vocab)]


CELL_SHAPES = sorted({(m, k, n) for m in (2048, 128, 8)
                      for name in ("qwen3-14b", "mistral-nemo-12b")
                      for k, n in _weight_matmuls(name)})


@pytest.mark.parametrize("m,k,n", CELL_SHAPES)
def test_pick_tiles_at_model_widths(m, k, n):
    """Tiles of the probe kernels at the benchmark models' widths (train
    rows 2048, online 128, decode-sized 8): K is never split raggedly nor
    padded, W is not padded along N, one grid step fits the VMEM budget,
    and each W tile is read and hashed at most 4 times at 2048 rows."""
    bm, bk, bn = pm.pick_tiles(m, k, n, 2)
    assert k % bk == 0 and bk % 128 == 0
    assert bn % 128 == 0 or bn == n
    assert bm % 16 == 0 or bm == m
    assert bn >= 512 or bn == n
    assert pm.vmem_bytes(bm, bk, bn, 2) <= pm.VMEM_BUDGET <= pm.VMEM_LIMIT
    w = jax.ShapeDtypeStruct((k, n), jnp.bfloat16)
    assert ops._pad_k([], w)[1] is w
    if m == 2048:
        assert -(-m // bm) <= 4


@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (64, 128, 128), (5, 127, 96),
                                   (128, 64, 10)])
def test_pick_tiles_keeps_small_dims_whole(m, k, n):
    """The paper-scale MLPs' dims (≤ 128) keep whole-dim tiles: one grid
    step, as before tiles were picked."""
    assert pm.pick_tiles(m, k, n, 4) == (m, k, n)


@pytest.mark.parametrize("m,k,n", [(2050, 256, 128 * 17),
                                   (2071, 1024, 128 * 19)])
def test_pair_ragged_last_blocks_match_ref(m, k, n):
    """Past 2048 rows and 2048 columns the picked tiles leave a ragged last
    row block and column block: the kernel reads past x and W there and
    must drop what it computed from that on writeback, and the columns of
    the second block must take their signs at the leaf's row stride."""
    bm, bk, bn = pm.pick_tiles(m, k, n, 4)
    assert m % bm and n % bn and n > bn
    xp = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    xm = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n), jnp.float32) * 0.1
    ls = pert.leaf_seed(4, 9, 1)
    yp, ym = ops.perturbed_matmul_pair(xp, xm, w, ls, dtheta=0.05,
                                       impl="interpret")
    rp, rm = ref.perturbed_matmul_pair_ref(xp, xm, w, ls, dtheta=0.05)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(rp),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ym), np.asarray(rm),
                               rtol=1e-4, atol=1e-4)
