"""Drift/aging device contracts.

Load-bearing:
* ``DriftingPlant`` transitions are keyed on the step counter — same
  seed + same step range ⇒ the identical drifted weights after a
  restart, for both the OU-walk and decay-toward-rest modes.
* Every algorithm (discrete, analog, probe_parallel_external) trains
  THROUGH a drifting device with bit-exact checkpoint/resume: a resumed
  run is the uninterrupted run.
* A farm of chips with HETEROGENEOUS drift rates keeps the per-chip
  aging distinguishable across the resume (drift is part of the device,
  keyed on its seed, not of the training state).
* ``train_mgd``'s scheduled-recalibration hook rewrites the device from
  the shadow params on a schedule that is a pure function of the global
  step (resume-safe), and the rewrite lands through the plant.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import DriverConfig
from repro.core import AnalogMGDConfig, mse
from repro.data import tasks
from repro.hardware import (DriftingAnalogChip, DriftingPlant, ExternalPlant,
                            IdealPlant, NoisyPlant, SimulatedAnalogChip,
                            simulated_chip_farm)
from repro.models.simple import mlp_apply, mlp_init
from repro.training.train_loop import train_mgd

X, Y = tasks.xor_dataset()
BATCH = {"x": X, "y": Y}


def _loss(params, batch):
    return mse(mlp_apply(params, batch["x"]), batch["y"])


def _params(seed=0, sizes=(2, 2, 1)):
    return mlp_init(jax.random.PRNGKey(seed), sizes)


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# The drift transition itself
# ---------------------------------------------------------------------------


def test_walk_replay_deterministic_across_rebuild():
    """Same seed + same step range ⇒ identical drifted weights, from a
    freshly constructed plant (nothing lives in the instance)."""
    p = _params()

    def walk():
        plant = DriftingPlant(IdealPlant(_loss), mode="walk",
                              drift_rate=0.05, seed=7)
        out = p
        for step in range(4, 12):
            out = plant.drift(out, step)
        return out

    _assert_trees_equal(walk(), walk())


def test_walk_steps_draw_distinct_kicks():
    p = _params()
    plant = DriftingPlant(IdealPlant(_loss), mode="walk", drift_rate=0.05,
                          seed=7)
    a = plant.drift(p, 3)
    b = plant.drift(p, 4)
    assert not np.allclose(np.asarray(jax.tree_util.tree_leaves(a)[1]),
                           np.asarray(jax.tree_util.tree_leaves(b)[1]))


def test_decay_relaxes_toward_rest_exactly():
    """Pure decay (no diffusion) is the closed-form exponential toward
    rest — n transitions contract the distance by exp(−n/τ)."""
    p = _params()
    tau, rest, n = 5.0, 0.25, 10
    plant = DriftingPlant(IdealPlant(_loss), mode="decay", drift_tau=tau,
                          rest=rest, seed=0)
    aged = plant.age(p, 0, n)
    factor = np.exp(-n / tau)
    for la, lb in zip(jax.tree_util.tree_leaves(p),
                      jax.tree_util.tree_leaves(aged)):
        np.testing.assert_allclose(
            np.asarray(lb), rest + factor * (np.asarray(la) - rest),
            rtol=1e-5)


def test_age_matches_unrolled_drift():
    """``age`` is the fori_loop of ``drift`` — equal to the eager unroll
    up to XLA's FMA contraction of the decay blend (the jitted training
    path itself is bit-stable; the resume tests below pin that)."""
    p = _params()
    plant = DriftingPlant(IdealPlant(_loss), mode="walk", drift_rate=0.02,
                          drift_tau=50.0, seed=3)
    unrolled = p
    for step in range(5, 9):
        unrolled = plant.drift(unrolled, step)
    for la, lb in zip(jax.tree_util.tree_leaves(plant.age(p, 5, 4)),
                      jax.tree_util.tree_leaves(unrolled)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-8)


def test_write_lands_through_inner_then_drifts():
    """Composition order: the inner device's write imperfections apply
    first, then one aging transition of what landed."""
    p = _params()
    inner = NoisyPlant(_loss, write_noise=0.5, dtheta=1e-2, seed=9)
    plant = DriftingPlant(inner, mode="walk", drift_rate=0.05, seed=9)
    landed = plant.write_params(p, step=6)
    _assert_trees_equal(landed,
                        plant.drift(inner.write_params(p, step=6), 6))


def test_drift_meta_fields():
    plant = DriftingPlant(IdealPlant(_loss), mode="walk", drift_rate=0.01,
                          drift_tau=30.0, rest=0.5)
    assert plant.meta.drift_mode == "walk"
    assert plant.meta.drift_rate == 0.01
    assert plant.meta.drift_tau == 30.0
    assert plant.meta.drift_rest == 0.5
    assert not plant.meta.external


@pytest.mark.parametrize("build,match", [
    (lambda: DriftingPlant(IdealPlant(_loss), mode="brownian",
                           drift_rate=0.1), "walk' or 'decay"),
    (lambda: DriftingPlant(IdealPlant(_loss), mode="walk"), "drift_rate"),
    (lambda: DriftingPlant(IdealPlant(_loss), mode="decay"), "drift_tau"),
    (lambda: DriftingPlant(_loss, mode="walk", drift_rate=0.1),
     "repro.hardware.Plant"),
    (lambda: DriftingPlant(ExternalPlant(SimulatedAnalogChip((2, 2, 1))),
                           mode="walk", drift_rate=0.1),
     "DriftingAnalogChip"),
])
def test_drifting_plant_validation(build, match):
    with pytest.raises((ValueError, TypeError), match=match):
        build()


# ---------------------------------------------------------------------------
# Training through a drifting device, with bit-exact resume
# ---------------------------------------------------------------------------


def _drift_plant(rate=0.01, seed=5):
    return DriftingPlant(IdealPlant(_loss), mode="walk", drift_rate=rate,
                         seed=seed)


def test_discrete_resume_bit_exact_through_drift(tmp_path):
    cfg = DriverConfig(dtheta=1e-2, eta=0.5, mode="central", seed=1)
    p0 = _params(2)
    sample_fn = lambda i: BATCH                        # noqa: E731

    cont = train_mgd(_loss, p0, cfg, sample_fn, 16, plant=_drift_plant(),
                     chunk=4, log=None)
    train_mgd(_loss, p0, cfg, sample_fn, 8, plant=_drift_plant(),
              chunk=4, log=None, checkpoint_dir=str(tmp_path),
              checkpoint_every=8)
    res = train_mgd(_loss, p0, cfg, sample_fn, 16, plant=_drift_plant(),
                    chunk=4, log=None, checkpoint_dir=str(tmp_path))
    assert res.steps_done == 16
    _assert_trees_equal(cont.params, res.params)
    _assert_trees_equal(cont.state, res.state)


def test_analog_resume_bit_exact_through_drift(tmp_path):
    cfg = AnalogMGDConfig(dtheta=1e-2, eta=1e-3, seed=2)
    p0 = _params(3)
    sample_fn = lambda i: BATCH                        # noqa: E731

    cont = train_mgd(_loss, p0, cfg, sample_fn, 16,
                     plant=_drift_plant(rate=0.005), chunk=4, log=None)
    train_mgd(_loss, p0, cfg, sample_fn, 8, plant=_drift_plant(rate=0.005),
              chunk=4, log=None, checkpoint_dir=str(tmp_path),
              checkpoint_every=8)
    res = train_mgd(_loss, p0, cfg, sample_fn, 16,
                    plant=_drift_plant(rate=0.005), chunk=4, log=None,
                    checkpoint_dir=str(tmp_path))
    _assert_trees_equal(cont.params, res.params)
    _assert_trees_equal(cont.state, res.state)


def test_probe_averaged_retrim_deterministic():
    """The drift benchmark's re-trim configuration (central, probes=4)
    walks the same f32 trajectory on every fresh run."""
    cfg = DriverConfig(dtheta=1e-2, eta=0.8, mode="central", probes=4,
                       seed=0)

    def run():
        mgd = repro.driver("discrete", cfg, _loss, plant=_drift_plant())
        p, s = _params(1), mgd.init(_params(1))
        for _ in range(8):
            p, s, m = mgd.step(p, s, BATCH)
        return p

    _assert_trees_equal(run(), run())


# ---------------------------------------------------------------------------
# Drifting chips behind the host boundary
# ---------------------------------------------------------------------------


def test_drifting_chip_hold_aging_replays():
    """A held chip (write once, read later) ages deterministically: the
    aged readout is a pure function of (seed, write step, read step)."""
    def build():
        chip = DriftingAnalogChip((2, 2, 1), seed=4, sigma_a=0.1,
                                  sigma_theta=0.0, sigma_c=0.0,
                                  drift_rate=0.05)
        chip.set_params(_params(), step=0)
        return chip

    a, b = build(), build()
    assert a.measure_cost(BATCH, step=20, tag=0) \
        == b.measure_cost(BATCH, step=20, tag=0)
    # aging changed the readout; repeating the same read does not
    assert a.measure_cost(BATCH, step=20, tag=0) \
        != a.measure_cost(BATCH, step=0, tag=0)
    assert a.measure_cost(BATCH, step=20, tag=0) \
        == a.measure_cost(BATCH, step=20, tag=0)


def test_drifting_chip_stepless_write_reads_unaged():
    chip = DriftingAnalogChip((2, 2, 1), seed=4, sigma_a=0.0,
                              sigma_theta=0.0, sigma_c=0.0, drift_rate=0.5)
    stable = SimulatedAnalogChip((2, 2, 1), seed=4, sigma_a=0.0,
                                 sigma_theta=0.0, sigma_c=0.0)
    chip.set_params(_params())          # bench-harness write, no step
    stable.set_params(_params())
    assert chip.measure_cost(BATCH, step=30, tag=0) \
        == stable.measure_cost(BATCH, step=30, tag=0)


def test_external_plant_forwards_write_step():
    """ExternalPlant timestamps persistent writes on step-capable
    devices, so training through the boundary ages deterministically —
    and the aging is NOT a no-op: every read sees at least the
    write-settle transition, so a drifting chip's trajectory departs
    from the stable chip's."""
    def run(drift_rate):
        if drift_rate:
            chip = DriftingAnalogChip((2, 2, 1), seed=1, sigma_a=0.1,
                                      sigma_theta=0.0, sigma_c=1e-3,
                                      drift_rate=drift_rate)
        else:
            chip = SimulatedAnalogChip((2, 2, 1), seed=1, sigma_a=0.1,
                                       sigma_theta=0.0, sigma_c=1e-3)
        plant = ExternalPlant(chip)
        cfg = DriverConfig(dtheta=1e-2, eta=0.2, mode="central", seed=0)
        mgd = repro.driver("discrete", cfg, plant=plant)
        p, s = _params(), mgd.init(_params())
        for _ in range(6):
            p, s, m = mgd.step(p, s, BATCH)
            jax.block_until_ready(p)
        return p, chip

    (p_a, chip_a), (p_b, chip_b) = run(0.05), run(0.05)
    _assert_trees_equal(p_a, p_b)
    assert chip_a.measure_cost(BATCH, step=6, tag=0) \
        == chip_b.measure_cost(BATCH, step=6, tag=0)
    p_stable, _ = run(0.0)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(p_a),
                        jax.tree_util.tree_leaves(p_stable)))


def test_farm_heterogeneous_drift_resume_distinguishable(tmp_path):
    """Two chips with different drift rates, trained through the farm
    driver with a checkpoint/resume in the middle: the trainer's
    trajectory is bit-exact, and the per-chip aging stays distinct and
    replay-identical chip by chip."""
    def farm():
        return simulated_chip_farm(2, (2, 2, 1), base_seed=1, sigma_a=0.1,
                                   sigma_theta=0.0, sigma_c=0.0,
                                   drift_rates=(0.0, 0.05))

    cfg = DriverConfig(dtheta=1e-2, eta=0.5, mode="central", seed=4)
    p0 = _params(2)
    sample_fn = lambda i: BATCH                        # noqa: E731

    farm_cont = farm()
    cont = train_mgd(None, p0, cfg, sample_fn, 12,
                     algorithm="probe_parallel_external", plant=farm_cont,
                     chunk=4, log=None)
    train_mgd(None, p0, cfg, sample_fn, 8,
              algorithm="probe_parallel_external", plant=farm(),
              chunk=4, log=None, checkpoint_dir=str(tmp_path),
              checkpoint_every=8)
    farm_res = farm()
    res = train_mgd(None, p0, cfg, sample_fn, 12,
                    algorithm="probe_parallel_external", plant=farm_res,
                    chunk=4, log=None, checkpoint_dir=str(tmp_path))
    _assert_trees_equal(cont.params, res.params)
    _assert_trees_equal(cont.state, res.state)

    # chip-by-chip: the resumed farm's devices read identically to the
    # uninterrupted farm's (same stored weights, same aging)...
    for i in range(2):
        assert farm_cont.devices[i].measure_cost(BATCH, step=12, tag=0) \
            == farm_res.devices[i].measure_cost(BATCH, step=12, tag=0)
    # ...the stable chip reads the same however long it is held, while
    # the drifting chip keeps aging — the rates stay distinguishable
    assert farm_res.devices[0].measure_cost(BATCH, step=12, tag=0) \
        == farm_res.devices[0].measure_cost(BATCH, step=40, tag=0)
    assert farm_res.devices[1].measure_cost(BATCH, step=12, tag=0) \
        != farm_res.devices[1].measure_cost(BATCH, step=40, tag=0)


# ---------------------------------------------------------------------------
# The scheduled-recalibration hook
# ---------------------------------------------------------------------------


def test_recal_hook_rewrites_from_shadow():
    """η = 0 + recal: the device state after the run is exactly the
    shadow pushed through the plant's write path at the last boundary,
    then drifted by the remaining steps — computed by hand here."""
    plant = _drift_plant(rate=0.1, seed=8)
    cfg = DriverConfig(dtheta=1e-2, eta=0.0, mode="central", seed=0)
    p0 = _params(0)
    res = train_mgd(_loss, p0, cfg, lambda i: BATCH, 5, plant=plant,
                    chunk=2, log=None, recal_every=4)

    # steps 0..3 drift the device, then the done=4 boundary rewrites it
    # from the shadow (the initial p0) through the plant, then step 4's
    # η=0 training write drifts once more.  The recalibration write runs
    # op by op, like the first line here; step 4's drift runs inside the
    # jitted training program, so it is computed under jit here too:
    # there XLA folds drift_rate into the normal sampler's √2 constant,
    # a product that op-by-op dispatch rounds separately (1 ulp apart).
    expected = plant.write_params(p0, step=4)
    expected = jax.jit(plant.drift)(expected, jnp.int32(4))
    _assert_trees_equal(res.params, expected)


def test_recal_pulls_aged_device_back():
    """With recalibration the device stays near the shadow; without it
    the walk wanders away."""
    cfg = DriverConfig(dtheta=1e-2, eta=0.0, mode="central", seed=0)
    p0 = _params(0)

    def dist(params):
        return float(sum(
            np.sum((np.asarray(a) - np.asarray(b)) ** 2)
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(p0))))

    free = train_mgd(_loss, p0, cfg, lambda i: BATCH, 40,
                     plant=_drift_plant(rate=0.05, seed=8), chunk=10,
                     log=None)
    recal = train_mgd(_loss, p0, cfg, lambda i: BATCH, 40,
                      plant=_drift_plant(rate=0.05, seed=8), chunk=10,
                      log=None, recal_every=5)
    assert dist(recal.params) < dist(free.params)


def test_recal_resume_bit_exact(tmp_path):
    """Recalibration boundaries are a pure function of the global step:
    a resumed recal run is the uninterrupted one."""
    cfg = DriverConfig(dtheta=1e-2, eta=0.3, mode="central", seed=3)
    p0 = _params(1)
    kw = dict(chunk=2, log=None, recal_every=4, recal_params=_params(9))

    cont = train_mgd(_loss, p0, cfg, lambda i: BATCH, 12,
                     **kw, plant=_drift_plant(rate=0.02))
    # checkpoint OFF the recal boundary: a run ENDING on one stops before
    # its recal (no rewrite after the final step), so its device state is
    # legitimately not the mid-run state a longer run has there
    train_mgd(_loss, p0, cfg, lambda i: BATCH, 6,
              **kw, plant=_drift_plant(rate=0.02),
              checkpoint_dir=str(tmp_path), checkpoint_every=6)
    res = train_mgd(_loss, p0, cfg, lambda i: BATCH, 12,
                    **kw, plant=_drift_plant(rate=0.02),
                    checkpoint_dir=str(tmp_path))
    _assert_trees_equal(cont.params, res.params)
    _assert_trees_equal(cont.state, res.state)


def test_recal_validation():
    with pytest.raises(ValueError, match="recal_every"):
        train_mgd(_loss, _params(), DriverConfig(), lambda i: BATCH, 4,
                  recal_every=-1, log=None)
