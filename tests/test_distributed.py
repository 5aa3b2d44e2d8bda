"""Distribution substrate: logical sharding translation (in-process) and
mesh-dependent behaviour (subprocess with virtual devices — the main test
process must keep the single real CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str, n_devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# --- in-process: logical translation is pure metadata ----------------------


class FakeMesh:
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 16, "model": 16}


def test_logical_spec_translation():
    spec = shd.logical_spec((256, 4096), ["batch", None], FakeMesh())
    assert spec == P(("pod", "data"), None)


def test_logical_spec_drops_nondivisible():
    # batch 1 can't shard anywhere; kvseq picks up data×model
    spec = shd.logical_spec((1, 524288), ["batch", "kvseq"], FakeMesh())
    assert spec == P(None, ("data", "model"))


def test_logical_spec_dedups_axes():
    # batch eats pod+data; kvseq then only gets model
    spec = shd.logical_spec((128, 32768), ["batch", "kvseq"], FakeMesh())
    assert spec == P(("pod", "data"), "model")


def test_logical_spec_partial_axis_drop():
    # dim 8 divides data(16)? no → drop to pod(2)? 8 % 2 == 0 → ("pod",)
    spec = shd.logical_spec((8,), ["batch"], FakeMesh())
    assert spec == P("pod")


def test_param_specs_right_alignment():
    rules = [(r"w$", ("fsdp", "model"))]
    tree = {"layers": {"w": jax.ShapeDtypeStruct((28, 4096, 1024),
                                                 jax.numpy.float32)}}
    specs = shd.param_specs(tree, rules, FakeMesh())
    assert specs["layers"]["w"] == P(None, "data", "model")


# --- subprocess: actual multi-device semantics ------------------------------


def test_probe_parallel_converges():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("pod", "data"))
        from repro.core.mgd import MGDConfig
        from repro.core.probe_parallel import build_probe_parallel_step
        target = jnp.array([1.0, -2.0, 3.0, 0.5])
        def loss(p, batch):
            pred = batch["x"] @ p["w"]
            return jnp.mean((pred - batch["x"] @ target)**2)
        params = {"w": jnp.zeros(4)}
        cfg = MGDConfig(mode="central", dtheta=1e-3, eta=0.1)
        step_fn = build_probe_parallel_step(loss, cfg, mesh)
        key = jax.random.PRNGKey(0)
        p = params
        for i in range(2000):
            x = jax.random.normal(jax.random.fold_in(key, i), (8, 4))
            p, m = step_fn(p, i, {"x": x})
        err = float(jnp.max(jnp.abs(p["w"] - target)))
        print("ERR", err)
        assert err < 0.05, err
    """, n_devices=4)
    assert "ERR" in out


def test_pipeline_forward_exact():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        from repro.distributed.pipeline import pipeline_forward
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (4, 8, 8)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        stage = lambda w, x: jnp.tanh(x @ w)
        y = pipeline_forward(stage, ws, x, mesh=mesh, axis="pod",
                             microbatches=4)
        ref = x
        for i in range(4):
            ref = jnp.tanh(ref @ ws[i])
        err = float(jnp.max(jnp.abs(y - ref)))
        print("ERR", err)
        assert err < 1e-5, err
    """, n_devices=4)
    assert "ERR" in out


def test_sharded_mgd_step_runs_on_mesh():
    """A small dense model's MGD step executes (not just compiles) on an
    8-device (2,4) mesh with the production sharding rules."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, functools
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.configs import get_smoke_config
        from repro.core import MGDConfig, build_mgd_step, mgd_init
        from repro.distributed import sharding as shd
        from repro.launch import specs
        from repro.models import model_init, model_loss
        cfg = get_smoke_config("qwen3-14b").replace(
            d_model=64, n_heads=4, n_kv_heads=4, d_head=16, vocab=128)
        mgd_cfg = MGDConfig(dtheta=1e-2, eta=0.1)
        with shd.use_mesh(mesh):
            params = model_init(cfg, jax.random.PRNGKey(0))
            shardings = specs.param_shardings(cfg, mesh)
            params = jax.device_put(params, shardings)
            loss_fn = lambda p, b: model_loss(p, cfg, b)
            step = jax.jit(build_mgd_step(loss_fn, mgd_cfg))
            state = mgd_init(params, mgd_cfg)
            toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                      cfg.vocab)
            batch = {"tokens": toks, "labels": toks}
            costs = []
            for i in range(30):
                params, state, m = step(params, state, batch)
                costs.append(float(m["cost"]))
        print("COSTS", costs[0], costs[-1])
        assert costs[-1] == costs[-1]  # no NaN
    """, n_devices=8)
    assert "COSTS" in out


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint on a (2,4) mesh, restore onto (4,2) and (1-device) —
    elastic scaling."""
    out = _run_subprocess(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.training import checkpoint as ckpt
        params = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        mesh1 = make_mesh((2, 4), ("data", "model"))
        sh1 = {{"w": NamedSharding(mesh1, P("data", "model"))}}
        p1 = jax.device_put(params, sh1)
        ckpt.save(r"{tmp_path}", 3, p1)
        mesh2 = make_mesh((4, 2), ("data", "model"))
        sh2 = {{"w": NamedSharding(mesh2, P("model", "data"))}}
        p2, _, step = ckpt.restore(r"{tmp_path}", params, shardings=sh2)
        np.testing.assert_array_equal(np.asarray(p2["w"]),
                                      np.asarray(params["w"]))
        p3, _, _ = ckpt.restore(r"{tmp_path}", params)   # single device
        np.testing.assert_array_equal(np.asarray(p3["w"]),
                                      np.asarray(params["w"]))
        print("ELASTIC OK", step)
    """, n_devices=8)
    assert "ELASTIC OK" in out
