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
    "perturbed_matmul_pair": lambda s, m, n: (
        lambda xp, xm, w, l: ops.perturbed_matmul_pair(
            xp, xm, w, l, dtheta=2 ** -6, impl="pallas"),
        [s((m, 5120), BF16), s((m, 5120), BF16), s((5120, n), BF16),
         s((), jnp.uint32)]),
    "perturbed_matmul": lambda s, m, n: (
        lambda x, w, l: ops.perturbed_matmul(
            x, w, l, dtheta=2 ** -6, sign=-1.0, impl="pallas"),
        [s((m, 5120), BF16), s((5120, n), BF16), s((), jnp.uint32)]),
    "mgd_update_window": lambda s, m, n: (
        lambda w, l, c: ops.mgd_update_window(
            w, l, c, alpha=-2e-4, dtheta=2 ** -6, impl="pallas"),
        [s((5120, n), BF16), s((1,), jnp.uint32), s((1,), jnp.float32)]),
    "mgd_update": lambda s, m, n: (
        lambda w, l, c: ops.mgd_update(
            w, l, c, eta=2e-4, dtheta=2 ** -6, impl="pallas"),
        [s((5120, n), BF16), s((4,), jnp.uint32), s((4,), jnp.float32)]),
}


@pytest.mark.parametrize(
    "m,n", [(2048, 5120), (2048, 17408), (2048, 151936), (128, 131072),
            (8, 5120)],
    ids=["attn_5120", "mlp_17408", "head_151936_m2048", "head_131072_m128",
         "attn_5120_m8"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_at_qwen3_14b_widths(one_chip, kernel, m, n):
    """Each kernel at a weight's width, the probe kernels at m rows of
    tokens: the train cell's 2048 (qwen3-14b's head too), the online
    cell's 128 at mistral-nemo-12b's head, and a decode-sized 8.  The
    probe kernels' picked tiles must fit the chip's VMEM."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, args = KERNELS[kernel](shape, m, n)
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


PROGRAM_SCOPES = ("repro.model.embed", "repro.model.layers",
                  "repro.model.block", "repro.model.attn", "repro.model.mlp",
                  "repro.model.loss", "repro.kernels.prep")


def test_fused_step_names_its_kernels_and_program_scopes(one_chip):
    """The compiled fused step keeps the custom calls' names that a
    profiler trace shows and the benchmark matches, and carries every
    program scope in its ops' metadata (a one-layer qwen3-14b: its vocab
    pads the update kernel's tiles, so prep ops exist)."""
    import re

    cfg = get_config("qwen3-14b").replace(n_layers=1)

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
    text = _compile(drv.step, params, state,
                    {"tokens": tokens, "labels": tokens}).as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+?)(?:\.\d+)? = .*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert set(calls) == {"perturbed_matmul_pair", "mgd_update_window"}
    scopes = set(re.findall(r"repro\.[a-z]+\.[a-z]+",
                            " ".join(re.findall(r'op_name="([^"]*)"', text))))
    assert scopes == set(PROGRAM_SCOPES)


# --- DeepSeek-V3 widths, and the dense path's calls held as they were -----

DSV3_PAIR_SHAPES = [(7168, 1536), (1536, 24576), (7168, 576), (512, 32768),
                    (16384, 7168), (7168, 18432), (18432, 7168),
                    (7168, 16160)]


@pytest.mark.parametrize("k,n", DSV3_PAIR_SHAPES,
                         ids=[f"{k}x{n}" for k, n in DSV3_PAIR_SHAPES])
def test_pair_kernel_compiles_at_deepseek_v3_widths(one_chip, k, n):
    """The pair kernel at each DeepSeek-V3 weight it runs (MLA's five
    projections, the dense MLP, the head), 8192 rows."""
    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    _compile(lambda xp, xm, w, l: ops.perturbed_matmul_pair(
        xp, xm, w, l, dtheta=2 ** -6, impl="pallas"),
        s((8192, k), BF16), s((8192, k), BF16), s((k, n), BF16),
        s((), jnp.uint32))


@pytest.mark.parametrize("k,n", [(7168, 2048), (2048, 7168)],
                         ids=["gate_up", "down"])
def test_grouped_kernel_compiles_at_deepseek_v3_widths(one_chip, k, n):
    """The grouped probe-pair kernel over 8 held experts at 8192 tokens:
    the dropless layer's pass buffers and blocks (``moe.share_sizes``)."""
    from repro.configs import get_config
    from repro.models.moe import share_sizes

    cfg = get_config("deepseek-v3-671b").replace(experts_held=8)
    bm, _, blocks_total, blocks = share_sizes(cfg, 8192)

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    meta = s((2, 8), jnp.int32)
    text = _compile(lambda xp, xm, w, l, a, b, c: ops.perturbed_grouped_matmul(
        (xp, xm), w, l, a, b, c, signs=(1.0, -1.0), dtheta=2 ** -6, bm=bm,
        blocks=blocks, impl="pallas"),
        s((bm * blocks_total, k), BF16), s((bm * blocks_total, k), BF16),
        s((8, k, n), BF16), s((), jnp.uint32), meta, meta, meta).as_text()
    assert "perturbed_grouped_matmul_pair" in text


@pytest.mark.parametrize("s", [8192, 8000], ids=["8192", "8000_padded"])
def test_mla_flash_attention_compiles_at_deepseek_v3_widths(one_chip, s):
    """The MLA kernel at one probe stream of the DeepSeek-V3 cell: 128
    heads, q/k 128 + 64 against v 128, the sequence at its picked blocks
    (and a length that is no multiple of them)."""
    def sd(dims):
        return jax.ShapeDtypeStruct(dims, BF16, sharding=one_chip)

    text = _compile(lambda q, kv, kr: ops.mla_flash_attention(
        q, kv, kr, scale=0.1353, impl="pallas"),
        sd((1, 128, s, 192)), sd((1, s, 128 * 256)), sd((1, s, 64))).as_text()
    assert "mla_flash_attention" in text


def _pallas_calls(jaxpr, out):
    """(name, grid, block shapes) of every pallas_call in a jaxpr."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            gm = e.params["grid_mapping"]
            out.append((str(e.params["name"]), tuple(gm.grid), tuple(
                tuple(b.block_size for b in m.block_shape)
                for m in gm.block_mappings)))
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, out)
    return out


_PAIR_BLOCKS = ((2048, 512), (2048, 512), (512, 1024), (2048, 1024),
                (2048, 1024))
_UPD = ((256, 256), (256, 256))
# the dense fused step's Pallas calls at 2 layers, batch 4×512, as the
# dense path picks them: (name, grid, blocks) → count.  A change made for
# another architecture must leave them as they are.
DENSE_CALLS = {
    "qwen3-14b": {
        ("mgd_update_window", (1, 1), ((2, 128), (2, 128))): 2,
        ("mgd_update_window", (1, 20), ((2, 256), (2, 256))): 2,
        ("mgd_update_window", (20, 594), _UPD): 1,
        ("mgd_update_window", (40, 4), _UPD): 2,
        ("mgd_update_window", (40, 20), _UPD): 2,
        ("mgd_update_window", (40, 68), _UPD): 2,
        ("mgd_update_window", (136, 20), _UPD): 1,
        ("mgd_update_window", (594, 20), _UPD): 1,
        ("perturbed_matmul_pair", (1, 1, 10), _PAIR_BLOCKS): 2,
        ("perturbed_matmul_pair", (1, 5, 10), _PAIR_BLOCKS): 2,
        ("perturbed_matmul_pair", (1, 5, 34), _PAIR_BLOCKS): 1,
        ("perturbed_matmul_pair", (1, 17, 10), _PAIR_BLOCKS): 2,
        ("perturbed_matmul_pair", (1, 149, 10), _PAIR_BLOCKS): 1},
    "mistral-nemo-12b": {
        ("mgd_update_window", (1, 20), ((2, 256), (2, 256))): 2,
        ("mgd_update_window", (20, 512), _UPD): 1,
        ("mgd_update_window", (32, 20), _UPD): 1,
        ("mgd_update_window", (40, 4), _UPD): 2,
        ("mgd_update_window", (40, 16), _UPD): 1,
        ("mgd_update_window", (40, 56), _UPD): 2,
        ("mgd_update_window", (112, 20), _UPD): 1,
        ("mgd_update_window", (512, 20), _UPD): 1,
        ("perturbed_matmul_pair", (1, 1, 10), _PAIR_BLOCKS): 2,
        ("perturbed_matmul_pair", (1, 4, 10), _PAIR_BLOCKS): 1,
        ("perturbed_matmul_pair", (1, 5, 8), _PAIR_BLOCKS): 1,
        ("perturbed_matmul_pair", (1, 5, 28), _PAIR_BLOCKS): 1,
        ("perturbed_matmul_pair", (1, 14, 10), _PAIR_BLOCKS): 2,
        ("perturbed_matmul_pair", (1, 128, 10), _PAIR_BLOCKS): 1},
}
DENSE_LEAVES = {
    "qwen3-14b": [
        "embed/head/w", "embed/ln_f/scale", "embed/tok/table",
        "layers/attn/k_norm/scale", "layers/attn/q_norm/scale",
        "layers/attn/wk/w", "layers/attn/wo/w", "layers/attn/wq/w",
        "layers/attn/wv/w", "layers/ln1/scale", "layers/ln2/scale",
        "layers/mlp/down/w", "layers/mlp/gate/w", "layers/mlp/up/w"],
    "mistral-nemo-12b": [
        "embed/head/w", "embed/ln_f/scale", "embed/tok/table",
        "layers/attn/wk/w", "layers/attn/wo/w", "layers/attn/wq/w",
        "layers/attn/wv/w", "layers/ln1/scale", "layers/ln2/scale",
        "layers/mlp/down/w", "layers/mlp/gate/w", "layers/mlp/up/w"],
}


@pytest.mark.parametrize("arch", sorted(DENSE_CALLS))
def test_dense_step_keeps_its_pallas_calls_and_tiles(arch):
    """The benchmarked dense configs' fused step traces to the Pallas
    calls, grids and blocks written in ``DENSE_CALLS``."""
    from collections import Counter

    cfg = get_config(arch).replace(n_layers=2)
    shapes = jax.eval_shape(lambda: model_init(cfg, jax.random.PRNGKey(0)))
    drv = repro.driver(
        "discrete",
        repro.DriverConfig(fused=True, mode="central", kernel_impl="pallas",
                           dtheta=2 ** -6, eta=2e-4),
        lambda p, b: model_loss(p, cfg, b),
        probe_fn=make_transformer_probe_fn(cfg))
    tokens = jax.ShapeDtypeStruct((4, 512), jnp.int32)
    jaxpr = jax.make_jaxpr(drv.step)(shapes, jax.eval_shape(drv.init, shapes),
                                     {"tokens": tokens, "labels": tokens})
    assert Counter(_pallas_calls(jaxpr.jaxpr, [])) == DENSE_CALLS[arch]


@pytest.mark.parametrize("arch", sorted(DENSE_LEAVES))
def test_dense_leaf_ids_unchanged(arch):
    """Leaf ids (the perturbation hash's) of the dense configs: the paths
    of ``DENSE_LEAVES`` in that order (a stack added for other models
    must not shift them)."""
    from repro.core.utils import leaf_id_tree

    shapes = jax.eval_shape(lambda: model_init(
        get_config(arch).replace(n_layers=2), jax.random.PRNGKey(0)))
    ids = jax.tree_util.tree_flatten_with_path(leaf_id_tree(shapes))[0]
    assert [("/".join(k.key for k in p), i) for p, i in ids] == [
        (p, i) for i, p in enumerate(DENSE_LEAVES[arch])]
