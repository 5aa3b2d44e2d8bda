"""The main path compiles for a TPU v5e at qwen3-14b widths — no chip needed.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``v5e:2x2``), not attached, and refuses what the chip would
refuse: unsupported casts and primitives inside Pallas kernels, tiles that
do not fit VMEM, programs that do not fit HBM.  Interpret mode sees none of
these.  Each test compiles for the first chip of the topology and checks
that the Pallas kernels are in the program (``tpu_custom_call``).

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and the test workers each
import every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro.configs import get_config
from repro.kernels import ops
from repro.models import make_transformer_probe_fn, model_init, model_loss

V5E_HBM_BYTES = 16 * 2 ** 30
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else it logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                   # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    """First chip of the topology, with the persistent compilation cache
    off: a program compiled for a described chip is written to the cache
    but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KERNELS = {
    "perturbed_matmul_pair": lambda s, n: (
        lambda xp, xm, w, l: ops.perturbed_matmul_pair(
            xp, xm, w, l, dtheta=2 ** -6, impl="pallas"),
        [s((2048, 5120), BF16), s((2048, 5120), BF16), s((5120, n), BF16),
         s((), jnp.uint32)]),
    "perturbed_matmul": lambda s, n: (
        lambda x, w, l: ops.perturbed_matmul(
            x, w, l, dtheta=2 ** -6, sign=-1.0, impl="pallas"),
        [s((2048, 5120), BF16), s((5120, n), BF16), s((), jnp.uint32)]),
    "mgd_update_window": lambda s, n: (
        lambda w, l, c: ops.mgd_update_window(
            w, l, c, alpha=-2e-4, dtheta=2 ** -6, impl="pallas"),
        [s((5120, n), BF16), s((1,), jnp.uint32), s((1,), jnp.float32)]),
    "mgd_update": lambda s, n: (
        lambda w, l, c: ops.mgd_update(
            w, l, c, eta=2e-4, dtheta=2 ** -6, impl="pallas"),
        [s((5120, n), BF16), s((4,), jnp.uint32), s((4,), jnp.float32)]),
}


@pytest.mark.parametrize("n", [5120, 17408], ids=["attn_5120", "mlp_17408"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_at_qwen3_14b_widths(one_chip, kernel, n):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, args = KERNELS[kernel](shape, n)
    _compile(fn, *args)


def test_window_update_compiles_on_stacked_norm_leaf(one_chip):
    """A stacked [40, 128] q/k-norm bank goes through the window kernel
    as a matrix (every ndim ≥ 2 leaf does)."""
    _compile(lambda w, l, c: ops.mgd_update_window(
        w, l, c, alpha=-2e-4, dtheta=2 ** -6, impl="pallas"),
        jax.ShapeDtypeStruct((40, 128), BF16, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip))


def test_fused_mgd_step_compiles_and_fits_one_chip(one_chip):
    """One whole fused central-pair MGD step of qwen3-14b cut to 2 layers,
    batch 4×512: compiles, holds the kernels, and fits a chip's HBM."""
    cfg = get_config("qwen3-14b").replace(n_layers=2)

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    shapes = jax.eval_shape(lambda: model_init(cfg, jax.random.PRNGKey(0)))
    drv = repro.driver(
        "discrete",
        repro.DriverConfig(fused=True, mode="central", kernel_impl="pallas",
                           dtheta=2 ** -6, eta=2e-4),
        lambda p, b: model_loss(p, cfg, b),
        probe_fn=make_transformer_probe_fn(cfg))
    params = jax.tree_util.tree_map(on_chip, shapes)
    state = jax.tree_util.tree_map(on_chip, jax.eval_shape(drv.init, shapes))
    tokens = jax.ShapeDtypeStruct((4, 512), jnp.int32, sharding=one_chip)
    compiled = _compile(drv.step, params, state,
                        {"tokens": tokens, "labels": tokens})
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
