"""The one-driver API: registry construction, f32 trajectory parity with
the legacy entry points, train_mgd generality, and deprecation hygiene.

Load-bearing contracts:
* ``repro.driver(name, cfg, loss_fn, ...)`` constructs all three
  algorithms behind the uniform ``(init, step)`` pair with standardized
  ``aux`` (cost / c_tilde / grad_norm_proxy).
* Registry-built drivers are bit-identical (f32) to the raw
  ``build_*_step`` constructors — discrete (incl. fused + explicit
  NoisyPlant), analog, and probe-parallel.
* ``train_mgd`` drives ANY driver, checkpoints the full state pytree
  generically, and resumes Algorithm 2 onto the uninterrupted
  trajectory through a ``QuantizedPlant(write_tau=...)``.
* The retired PR 3 shims (``make_*_step``) raise with the registry
  one-liner; ambiguous config mixes are rejected with actionable errors.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import DriverConfig, MGDDriver, driver, make_epoch, state_step
from repro.core import (AnalogMGDConfig, MGDConfig, analog_init,
                        build_analog_step, build_mgd_step, mgd_init, mse)
from repro.data import tasks
from repro.hardware import IdealPlant, NoisyPlant, QuantizedPlant
from repro.models.simple import make_mlp_probe_fn, mlp_apply, mlp_init

X, Y = tasks.xor_dataset()
BATCH = {"x": X, "y": Y}


def _loss(p, b):
    return mse(mlp_apply(p, b["x"]), b["y"])


def _params(seed=0):
    return mlp_init(jax.random.PRNGKey(seed), (2, 2, 1))


def _rollout(step_fn, params, state, steps=24):
    step = jax.jit(step_fn)
    cts = []
    for _ in range(steps):
        params, state, m = step(params, state, BATCH)
        cts.append(np.asarray(m["c_tilde"]))
    return params, state, np.array(cts)


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# Parity: registry-built drivers == legacy entry points, bit for bit
# ---------------------------------------------------------------------------


DISCRETE_CFGS = [
    MGDConfig(dtheta=1e-2, eta=1.0, seed=3),
    MGDConfig(dtheta=1e-2, eta=0.5, mode="central", seed=3),
    MGDConfig(dtheta=1e-2, eta=0.5, tau_theta=4, replay=True, seed=1),
    MGDConfig(dtheta=1e-2, eta=0.25, tau_theta=3, momentum=0.9, probes=2,
              seed=2),
]


@pytest.mark.parametrize("cfg", DISCRETE_CFGS,
                         ids=["forward", "central", "replay", "momentum"])
def test_discrete_driver_matches_raw_build(cfg):
    p0 = _params()
    raw_step = build_mgd_step(_loss, cfg)
    p_a, s_a, ct_a = _rollout(raw_step, p0, mgd_init(p0, cfg))

    drv = repro.driver("discrete", cfg, _loss)
    p_b, s_b, ct_b = _rollout(drv.step, p0, drv.init(p0))
    np.testing.assert_array_equal(ct_a, ct_b)
    _assert_trees_equal(p_a, p_b)
    _assert_trees_equal(s_a, s_b)


def test_discrete_fused_driver_matches_raw_build():
    cfg = MGDConfig(dtheta=1e-2, eta=0.5, mode="central", fused=True,
                    kernel_impl="interpret", seed=2)
    probe_fn = make_mlp_probe_fn()
    p0 = _params()
    raw_step = build_mgd_step(_loss, cfg, probe_fn=probe_fn)
    p_a, _, ct_a = _rollout(raw_step, p0, mgd_init(p0, cfg))

    drv = driver("discrete", cfg, _loss, probe_fn=probe_fn)
    p_b, _, ct_b = _rollout(drv.step, p0, drv.init(p0))
    np.testing.assert_array_equal(ct_a, ct_b)
    _assert_trees_equal(p_a, p_b)


def test_discrete_noisy_plant_driver_matches_raw_build():
    cfg = MGDConfig(dtheta=1e-2, eta=1.0, seed=5)
    plant = NoisyPlant(_loss, cost_noise=1e-3, write_noise=0.01,
                       dtheta=1e-2, seed=5)
    p0 = _params()
    raw_step = build_mgd_step(None, cfg, plant=plant)
    p_a, _, ct_a = _rollout(raw_step, p0, mgd_init(p0, cfg))

    drv = driver("discrete", cfg, plant=plant)
    p_b, _, ct_b = _rollout(drv.step, p0, drv.init(p0))
    np.testing.assert_array_equal(ct_a, ct_b)
    _assert_trees_equal(p_a, p_b)


def test_analog_driver_matches_raw_build():
    cfg = AnalogMGDConfig(dtheta=1e-2, eta=1e-3)
    p0 = _params()
    raw_step = build_analog_step(_loss, cfg)
    p_a, s_a, ct_a = _rollout(raw_step, p0, analog_init(p0, cfg), 50)

    drv = repro.driver("analog", cfg, _loss)
    p_b, s_b, ct_b = _rollout(drv.step, p0, drv.init(p0), 50)
    np.testing.assert_array_equal(ct_a, ct_b)
    _assert_trees_equal(p_a, p_b)
    _assert_trees_equal(s_a, s_b)


def test_probe_parallel_driver_matches_raw_build():
    from jax.sharding import Mesh
    from repro.core.probe_parallel import build_probe_parallel_step
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("pod",))
    cfg = MGDConfig(dtheta=1e-2, eta=1.0, mode="central", seed=1)
    p0 = _params()
    batch = {"x": X[None], "y": Y[None]}      # [pods, ...] shard layout

    raw = build_probe_parallel_step(_loss, cfg, mesh)
    drv = driver("probe_parallel", cfg, _loss, mesh=mesh)
    p_a, p_b = p0, p0
    s_b = drv.init(p0)
    for i in range(6):
        p_a, m_a = raw(p_a, i, batch)
        p_b, s_b, m_b = drv.step(p_b, s_b, batch)
        np.testing.assert_array_equal(np.asarray(m_a["c_tilde_mean"]),
                                      np.asarray(m_b["c_tilde"]))
    assert int(s_b.step) == 6
    _assert_trees_equal(p_a, p_b)


# ---------------------------------------------------------------------------
# The uniform contract
# ---------------------------------------------------------------------------


def test_driver_config_resolves_per_algorithm_defaults():
    d = driver("discrete", DriverConfig(), _loss)
    a = driver("analog", DriverConfig(), _loss)
    assert (d.config.ptype, d.config.dtheta, d.config.eta) == \
        ("rademacher", 1e-3, 1e-2)
    assert (a.config.ptype, a.config.dtheta, a.config.eta) == \
        ("sinusoidal", 1e-2, 1e-3)
    assert isinstance(d, MGDDriver) and isinstance(a, MGDDriver)


@pytest.mark.parametrize("algorithm", ["discrete", "analog"])
def test_standardized_aux_keys(algorithm):
    drv = driver(algorithm, DriverConfig(dtheta=1e-2, eta=0.1), _loss)
    p = _params()
    _, s, aux = jax.jit(drv.step)(p, drv.init(p), BATCH)
    for key in ("cost", "c_tilde", "grad_norm_proxy"):
        assert key in aux, key
    np.testing.assert_allclose(
        np.asarray(aux["grad_norm_proxy"]),
        abs(np.asarray(aux["c_tilde"])) / 1e-2, rtol=1e-6)
    assert int(state_step(s)) == 1


def test_make_epoch_matches_stepwise():
    cfg = DriverConfig(dtheta=1e-2, eta=1.0, seed=4)
    drv = driver("discrete", cfg, _loss)
    p0 = _params()
    run = make_epoch(drv, 12, lambda i: BATCH)
    p_scan, s_scan, _ = run(p0, drv.init(p0))
    assert int(state_step(s_scan)) == 12
    # scanned vs python-loop stepping: same trajectory up to rounding.
    # The programs are compiled separately, and XLA fuses them
    # differently: inside the scan, where step n's parameters are only
    # carried, it fuses the C0 forward of step n+1 (sigmoid into the 2×1
    # dot) unlike the per-step program, so C0 — hence C̃ — can differ by
    # one ulp from step 1 on.  A C̃ error of one ulp of C (C < 0.5 here)
    # moves every parameter by η·ulp/Δθ, so after 12 steps the two walks
    # stay within 12·η·spacing(0.5)/Δθ ≈ 7.2e-5 (measured 1.8e-5) — far
    # below one step's move, η·|C̃|/Δθ ≈ 0.07.
    p_py, s_py = p0, drv.init(p0)
    step = jax.jit(drv.step)
    for _ in range(12):
        p_py, s_py, _ = step(p_py, s_py, BATCH)
    atol = 12 * cfg.eta * float(np.spacing(np.float32(0.5))) / cfg.dtheta
    for a, b in zip(jax.tree_util.tree_leaves(p_scan),
                    jax.tree_util.tree_leaves(p_py)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


# ---------------------------------------------------------------------------
# train_mgd consumes any driver; generic full-state checkpointing
# ---------------------------------------------------------------------------


def test_train_mgd_drives_algorithm2_with_checkpoint_resume(tmp_path):
    """Acceptance: Algorithm 2 through a QuantizedPlant(write_tau=...)
    end to end, resume == uninterrupted (generic full-state ckpt)."""
    from repro.training.train_loop import train_mgd

    def plant():
        return QuantizedPlant(_loss, bits=12, w_clip=8.0, write_tau=4.0)

    cfg = DriverConfig(dtheta=1e-2, eta=5e-3, tau_theta=5.0, tau_hp=50.0,
                       seed=1)
    p0 = _params(3)
    sample_fn = lambda i: BATCH                        # noqa: E731

    cont = train_mgd(None, p0, cfg, sample_fn, 40, algorithm="analog",
                     plant=plant(), chunk=10, log=None)
    assert type(cont.state).__name__ == "AnalogMGDState"

    train_mgd(None, p0, cfg, sample_fn, 20, algorithm="analog",
              plant=plant(), chunk=10, log=None,
              checkpoint_dir=str(tmp_path), checkpoint_every=10)
    res = train_mgd(None, p0, cfg, sample_fn, 40, algorithm="analog",
                    plant=plant(), chunk=10, log=None,
                    checkpoint_dir=str(tmp_path))
    assert res.steps_done == 40
    _assert_trees_equal(cont.params, res.params)
    # the analog filter memories resumed exactly too (full state pytree)
    _assert_trees_equal(cont.state, res.state)


def test_train_mgd_accepts_prebuilt_driver():
    from repro.training.train_loop import train_mgd
    drv = driver("discrete", DriverConfig(dtheta=1e-2, eta=1.0), _loss)
    res = train_mgd(None, _params(), drv, lambda i: BATCH, 20, chunk=10,
                    log=None)
    assert res.steps_done == 20
    with pytest.raises(ValueError, match="pre-built"):
        train_mgd(_loss, _params(), drv, lambda i: BATCH, 10, log=None)


@pytest.mark.parametrize("chunk", [10, 1])
def test_train_mgd_donates_its_own_weights_not_the_callers(chunk):
    """A program of several steps donates the weights it is handed, so it
    holds one copy of them; a program of one step donates nothing (its
    update writes fresh buffers).  The caller's arrays stay readable and
    unchanged either way."""
    from repro.training.train_loop import train_mgd
    p0 = _params(4)
    before = jax.tree_util.tree_map(np.asarray, p0)
    handed = []

    def keep(p):
        handed.append(p)
        return {}

    drv = driver("discrete", DriverConfig(dtheta=1e-2, eta=1.0), _loss)
    res = train_mgd(None, p0, drv, lambda i: BATCH, 3 * chunk,
                    loop=repro.TrainLoopConfig(chunk=chunk, eval_fn=keep,
                                               eval_every=chunk, log=None))
    _assert_trees_equal(p0, before)
    assert len(handed) == 3
    assert all(leaf.is_deleted() == (chunk > 1) for p in handed[:-1]
               for leaf in jax.tree_util.tree_leaves(p))
    _assert_trees_equal(res.params, handed[-1])


def test_train_mgd_discrete_unchanged_by_redesign(tmp_path):
    """The historical call shape (loss_fn + MGDConfig) still trains and
    still resumes from its own checkpoints."""
    from repro.training.train_loop import train_mgd
    cfg = MGDConfig(dtheta=1e-2, eta=0.5, tau_theta=4, momentum=0.9, seed=2)
    p0 = _params(3)
    cont = train_mgd(_loss, p0, cfg, lambda i: BATCH, 30, chunk=10, log=None)
    train_mgd(_loss, p0, cfg, lambda i: BATCH, 10, chunk=10, log=None,
              checkpoint_dir=str(tmp_path), checkpoint_every=10)
    res = train_mgd(_loss, p0, cfg, lambda i: BATCH, 30, chunk=10, log=None,
                    checkpoint_dir=str(tmp_path))
    _assert_trees_equal(cont.params, res.params)
    _assert_trees_equal(cont.state.g, res.state.g)


# ---------------------------------------------------------------------------
# Retired-shim hygiene + ambiguous-mix rejection
# ---------------------------------------------------------------------------


def test_retired_shims_raise_with_registry_pointer():
    """The PR 3 deprecation shims graduated from warn to raise; the
    message carries the registry one-liner."""
    from repro.core import make_analog_step, make_mgd_step
    from repro.core.probe_parallel import make_probe_parallel_step
    for shim, algo in [(make_mgd_step, "discrete"),
                       (make_analog_step, "analog"),
                       (make_probe_parallel_step, "probe_parallel")]:
        with pytest.raises(RuntimeError, match="repro.driver") as e:
            shim(_loss, MGDConfig())
        assert algo in str(e.value)


@pytest.mark.parametrize("build,match", [
    (lambda: driver("nope", DriverConfig(), _loss), "unknown algorithm"),
    (lambda: driver("analog", DriverConfig(probes=4), _loss),
     "discrete-section"),
    (lambda: driver("analog", DriverConfig(momentum=0.9), _loss),
     "discrete-section"),
    (lambda: driver("analog", DriverConfig(fused=True), _loss),
     "discrete-section"),
    (lambda: driver("discrete", DriverConfig(dt=0.1), _loss),
     "analog-section"),
    (lambda: driver("discrete", DriverConfig(tau_hp=5.0), _loss),
     "analog-section"),
    (lambda: driver("discrete", DriverConfig(tau_theta=2.5), _loss),
     "integer"),
    (lambda: driver("probe_parallel", DriverConfig(mode="central"), _loss),
     "mesh"),
    (lambda: driver("analog", MGDConfig(), _loss), "discrete Algorithm 1"),
    (lambda: driver("discrete", AnalogMGDConfig(), _loss), "Algorithm 2"),
])
def test_ambiguous_mixes_rejected(build, match):
    with pytest.raises((ValueError, TypeError), match=match):
        build()


def test_probe_parallel_rejects_forward_mode_and_probes():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("pod",))
    with pytest.raises(ValueError, match="central"):
        driver("probe_parallel", DriverConfig(), _loss, mesh=mesh)
    with pytest.raises(ValueError, match="probes"):
        driver("probe_parallel", DriverConfig(mode="central", probes=4),
               _loss, mesh=mesh)


# ---------------------------------------------------------------------------
# ADC cost readout (mixed-precision readout satellite)
# ---------------------------------------------------------------------------


def test_adc_rounds_cost_to_grid():
    plant = QuantizedPlant(_loss, bits=12, adc_bits=6, adc_range=1.0)
    c = plant.read_cost(_params(), BATCH, step=0)
    code = float(c) / plant.adc_lsb
    assert abs(code - round(code)) < 1e-4
    # the pair readout converts each half independently
    theta = jax.tree_util.tree_map(lambda x: 0.01 * jnp.ones_like(x),
                                   _params())
    cp, cm = plant.read_cost_pair(_params(), theta, BATCH, step=0)
    for v in (cp, cm):
        code = float(v) / plant.adc_lsb
        assert abs(code - round(code)) < 1e-4


def test_adc_floors_small_c_tilde_stochastic_recovers():
    """Sub-LSB cost differences vanish under deterministic rounding but
    survive (in expectation) under stochastic rounding."""
    det = QuantizedPlant(_loss, bits=12, adc_bits=4, adc_range=1.0)
    c1 = det.read_cost(_params(), BATCH, step=0, tag=0)
    c2 = det.read_cost(jax.tree_util.tree_map(
        lambda x: x + 1e-4, _params()), BATCH, step=0, tag=1)
    assert float(c1) == float(c2)     # Δcost ≪ LSB: identical codes

    sto = QuantizedPlant(_loss, bits=12, adc_bits=4, adc_range=1.0,
                         adc_mode="stochastic", seed=0)
    reads = [float(sto.read_cost(_params(), BATCH, step=s, tag=0))
             for s in range(400)]
    exact = float(_loss(_params(), BATCH))
    assert len({round(r / sto.adc_lsb) for r in reads}) >= 2  # dithers
    assert abs(np.mean(reads) - exact) < sto.adc_lsb / 4      # unbiased


def test_adc_validation():
    with pytest.raises(ValueError, match="adc_mode"):
        QuantizedPlant(_loss, adc_bits=8, adc_mode="truncate")
    with pytest.raises(ValueError, match="ADC"):
        QuantizedPlant(_loss, adc_bits=0)
