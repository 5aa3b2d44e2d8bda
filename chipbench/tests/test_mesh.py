"""The probe-parallel mesh cell on four virtual CPU devices: a sound run
reads ``correct``; runs with the mesh broken underneath do not.  Also:
a mix without ``pods`` (or with ``pods`` 1) follows the one-chip
reference as before, the reference's planted missing-pod fault fails the
check, and the mesh readers count per chip."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import bench, check, context, counts
from chipbench import trace as tr
from chipbench.tests.tiny import tiny_cell

MESH = "qwen3-14b.mesh4-train-4x512"

# Each variant plants one fault in the program (or none) and runs the
# whole cell through run_cell, kernels interpreted.
SCRIPT = r"""
import contextlib, json, sys
import jax
import jax.numpy as jnp
import repro.core.probe_parallel as pp
from repro.models import make_transformer_probe_fn
from chipbench import calibrate, check, run
from chipbench.programs.dense_gqa import arch_config
from chipbench.tests.tiny import tiny_cell

run.use_compile_cache = lambda: None
cell = tiny_cell("qwen3-14b", "qwen3-14b.mesh4-train-4x512")
PODS = check.pods(cell.traffic)


@contextlib.contextmanager
def patched(obj, name, make):
    real = getattr(obj, name)
    setattr(obj, name, make(real))
    try:
        yield
    finally:
        setattr(obj, name, real)


def drop_last_pod(real):
    def gather(x, axis_name, **kw):
        return real(x, axis_name, **kw).at[-1].set(0.0)
    return gather


def own_pod_only(real):
    def gather(x, axis_name, **kw):
        return jnp.zeros((PODS,), x.dtype).at[
            jax.lax.axis_index(axis_name)].set(x)
    return gather


def reversed_seeds(real):
    return lambda seed, k: real(seed, jnp.asarray(PODS - 1, jnp.int32) - k)


def frozen(real):
    def build(*a, **k):
        step = real(*a, **k)

        def step_fn(params, n, batch):
            return params, step(params, n, batch)[1]
        return step_fn
    return build


def half_batch():
    full = make_transformer_probe_fn(arch_config(cell.config))

    def probe(params, batch, p):
        rows = batch["tokens"].shape[0] // 2
        return full(params, jax.tree_util.tree_map(lambda x: x[:rows], batch),
                    p)
    return probe


VARIANTS = {
    "sound": (contextlib.nullcontext, None),
    "pod_dropped": (lambda: patched(jax.lax, "all_gather", drop_last_pod),
                    None),
    "no_exchange": (lambda: patched(jax.lax, "all_gather", own_pod_only),
                    None),
    "seeds_swapped": (lambda: patched(pp, "pod_seed", reversed_seeds), None),
    "state_unchanged": (lambda: patched(pp, "build_probe_parallel_step",
                                        frozen), None),
    "half_batch": (contextlib.nullcontext, half_batch),
}
out = {}
for name, (ctx, probe) in VARIANTS.items():
    with ctx():
        res = run.run_cell(cell, 2 ** 31 + 17, 0.2, False, jax.devices()[:PODS],
                           kernel_impl="interpret",
                           probe_fn=probe() if probe else None)
    out[name] = {"correct": res["correct"], "checks": res["checks"],
                 "count": res["device"]["count"]}
out["calibrate"] = calibrate.calibrate(
    cell, [5], {5}, jax.devices()[:PODS], kernel_impl="interpret",
    log=lambda *a: None)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    pytest.importorskip("repro")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(bench.ROOT),
                                           str(bench.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=bench.ROOT,
                       env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_mesh_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert r["count"] == 4


@pytest.mark.parametrize("fault,number", [
    ("pod_dropped", "grad_gap_nats"),
    ("no_exchange", "grad_gap_nats"),
    ("seeds_swapped", "grad_gap_nats"),
    ("state_unchanged", "change_gap"),
    ("half_batch", "grad_gap_nats"),
])
def test_broken_mesh_is_not_correct(runs, fault, number):
    r = runs[fault]
    assert not r["correct"], r["checks"]
    c = r["checks"][number]
    assert c["value"] > c["limit"], r["checks"]


def test_calibrate_reads_the_mesh_and_its_faults(runs):
    cal = runs["calibrate"]
    limits = bench.load_json("limits", MESH)
    assert all(v <= limits[k] for k, v in cal["sound"]["5"].items()
               if limits[k] is not None)
    assert set(cal["faults"]) == {"control_fp8", "half_batch", "missing_pod"}
    for fault in ("control_fp8", "missing_pod"):
        assert cal["faults"][fault]["5"]["grad_gap_nats"] > \
            limits["grad_gap_nats"], cal["faults"]


def test_the_loss_is_pod_0s():
    """The reference's other pods' costs lie far from the one the check
    compares: the pod is not a free choice."""
    pytest.importorskip("repro")
    from chipbench.references import dense_gqa as R
    from chipbench.references import mgd
    from chipbench import textgen
    cell = tiny_cell("qwen3-14b", MESH)
    t, arch = cell.traffic, R.Arch.from_config(cell.config)
    params = R.init(arch, 5)
    b = textgen.batch(6, 0, t["batch"], t["seq_len"], arch.vocab)
    rows = t["batch"] // check.pods(t)
    costs = [sum(float(c) for c in R.costs(
        arch, params, b["tokens"][i * rows:(i + 1) * rows],
        b["labels"][i * rows:(i + 1) * rows], mgd.pod_seed(7, i), 0,
        dtheta=t["dtheta"])) / 2 for i in range(check.pods(t))]
    ref = check.follow(cell, {"init": 5, "data": 6, "mgd": 7})
    assert ref.cost[0] == costs[check.REPORTED_POD] == costs[0]
    assert min(abs(c - costs[0]) for c in costs[1:]) > 1e-3


# check.follow of the qwen3-14b tiny train cell (seeds 5, 6, 7) before
# the reference learnt pods
BEFORE = {
    "cost": {0: 5.330980539321899, 1: 5.258593559265137,
             2: 5.315952777862549, 3: 5.276394844055176},
    "applied": [-0.1793066239950367, -0.09617083958716328,
                -0.03331450853233991, 0.020178403371755332],
    "change": {
        "embed/head/w": 0.23975741863250732,
        "embed/ln_f/scale": 0.021521160379052162,
        "embed/tok/table": 0.2391192466020584,
        "layers/attn/k_norm/scale": 0.01507802214473486,
        "layers/attn/q_norm/scale": 0.01640128716826439,
        "layers/attn/wk/w": 0.16989675164222717,
        "layers/attn/wo/w": 0.23925700783729553,
        "layers/attn/wq/w": 0.23988041281700134,
        "layers/attn/wv/w": 0.17118287086486816,
        "layers/ln1/scale": 0.029245398938655853,
        "layers/ln2/scale": 0.03059369884431362,
        "layers/mlp/down/w": 0.33837655186653137,
        "layers/mlp/gate/w": 0.3380180597305298,
        "layers/mlp/up/w": 0.3403709828853607},
}


def test_one_pod_follows_the_reference_as_before():
    pytest.importorskip("repro")
    seeds = {"init": 5, "data": 6, "mgd": 7}
    cell = tiny_cell("qwen3-14b")
    assert "pods" not in cell.traffic and check.pods(cell.traffic) == 1
    ref = check.follow(cell, seeds)
    assert (ref.cost, ref.applied, ref.change) == (
        BEFORE["cost"], BEFORE["applied"], BEFORE["change"])
    cell.traffic["pods"] = 1
    one = check.follow(cell, seeds)
    assert (one.cost, one.applied, one.change) == (
        ref.cost, ref.applied, ref.change)


def test_missing_pod_fault_fails_the_check():
    """calibrate.py's planted fault: the reference put in the program's
    place with one pod's C̃ left out of the update."""
    pytest.importorskip("repro")
    cell = tiny_cell("qwen3-14b", MESH)
    seeds = {"init": 5, "data": 6, "mgd": 7}
    ref = check.follow(cell, seeds)
    assert len(ref.applied) == check.check_steps(cell.traffic) * 4
    same = check.numbers(cell, ref, ref)
    assert check.passed(same) and all(v["value"] == 0 for v in same.values())
    bad = check.follow(cell, seeds, drop_pod=1)
    nums = check.numbers(cell, bad, ref)
    assert not check.passed(nums), nums
    assert nums["grad_gap_nats"]["value"] > nums["grad_gap_nats"]["limit"]


def _ctx(events, chips=4, steps=8, traffic=None):
    cfg = bench.load_json("configs", "qwen3-14b")
    t = traffic or bench.load_json("traffic", "mesh4-train-4x512")
    trace = tr.Trace({i: sorted(events(i), key=lambda e: e.start)
                      for i in range(chips)}, [])
    return context.Context(trace, 0.0, 10.0, steps, chips, cfg, t,
                           197e12, 819e9)


def _ev(name, a, b):
    return tr.Event(a, b, name, f"%{name}.1 = f32[4] {name}()")


def test_gather_reader_counts_collectives_per_chip():
    read = bench.metric_reader("mesh.gather_ms_per_step")
    ctx = _ctx(lambda i: [_ev("all-reduce-start", 1.0, 1.0 + 0.001 * (i + 1)),
                          _ev("all-reduce-done", 2.0, 2.002),
                          _ev("all-gather", 3.0, 3.004),
                          _ev("perturbed_matmul_pair", 4.0, 5.0)])
    # per chip 0.001·(i + 1) + 0.002 + 0.004 s; mean over 4 chips 8.5 ms
    assert read(ctx) == pytest.approx(8.5 / 8)
    assert read(_ctx(lambda i: [_ev("fusion", 1.0, 2.0)])) is None


def test_mesh_pair_roofline_counts_each_chips_rows():
    """Each chip's pair kernel takes its pod's 4×512 rows; counted at the
    whole batch, the share would read four times as high."""
    read = bench.metric_reader("perturbed_matmul_pair_roofline.mesh")
    cfg = bench.load_json("configs", "qwen3-14b")
    calls = counts.pair_calls(cfg, 4 * 512)
    least = sum(s for s, _ in counts.roofline_seconds(calls, 197e12, 819e9))
    steps = 8
    per_call = least * steps / len(calls)
    ctx = _ctx(lambda i: [_ev("perturbed_matmul_pair", 0.1 + 2 * per_call * j,
                              0.1 + 2 * per_call * j + 2 * per_call)
                          for j in range(len(calls))], steps=steps)
    assert read(ctx) == pytest.approx(50.0)
    assert bench.metric_reader("step.mfu.mesh")(ctx) == pytest.approx(
        100.0 * counts.model_flops(cfg, 16, 512) * steps / (10.0 * 4 * 197e12))
