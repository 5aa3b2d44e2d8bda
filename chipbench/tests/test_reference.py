"""The plain reference draws the same weights as the program and
computes the same costs at a small float32 size on the CPU, and the
float8 control put in the program's place fails the check."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import check, textgen
from chipbench.references import dense_gqa as R
from chipbench.references import mgd
from chipbench.tests.tiny import tiny_cell

repro = pytest.importorskip("repro")
from repro.core import perturbations as pert  # noqa: E402
from repro.models import make_transformer_probe_fn, model_init  # noqa: E402


@pytest.mark.parametrize("config", ["qwen3-14b", "mistral-nemo-12b"])
def test_init_and_costs_match_the_program(config):
    from chipbench.run import arch_config
    cell = tiny_cell(config)
    cfg = arch_config(cell.config)
    arch = R.Arch.from_config(cell.config)
    seed = 2 ** 31 - 5
    params = jax.jit(model_init, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    ref = R.init(arch, seed)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert ["/".join(k.key for k in p) for p, _ in flat] == list(ref)
    for path, leaf in flat:
        assert bool(jnp.all(leaf == ref["/".join(k.key for k in path)]))
    b = textgen.batch(7, 3, 2, 32, arch.vocab)
    ctx = pert.ProbeCtx(signs=(1.0, -1.0), dtheta=2 ** -6, impl="ref")
    with jax.default_matmul_precision("highest"):
        got = make_transformer_probe_fn(cfg)(
            params, b, pert.Probe(jnp.int32(3), jnp.uint32(12345), ctx))
    want = R.costs(arch, ref, b["tokens"], b["labels"], 12345, 3,
                   dtheta=2 ** -6)
    for g, w in zip(got, want):
        assert abs(float(g) - float(w)) < 1e-4


def test_signs_match_the_program_hash():
    idx = jnp.arange(1000, dtype=jnp.uint32)
    ls = mgd.leaf_seed(77, 5, 3)
    assert int(ls) == int(pert.leaf_seed(77, 5, 3))
    assert bool(jnp.all(mgd.signs(ls, idx) == pert.rademacher_signs(ls, idx)))


def test_control_fails_the_check():
    cell = tiny_cell("qwen3-14b")
    seeds = {"init": 5, "data": 6, "mgd": 7}
    ref = check.follow(cell, seeds)
    ctl = check.follow(cell, seeds, compute="float8_e4m3fn")
    nums = check.numbers(cell, ctl, ref)
    assert not check.passed(nums), nums
    same = check.numbers(cell, ref, ref)
    assert check.passed(same) and all(v["value"] == 0 for v in same.values())
