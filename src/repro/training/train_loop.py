"""Training drivers: MGD (the paper) and backprop+SGD (the baseline).

``train_mgd`` consumes any ``repro.api.MGDDriver`` — discrete Algorithm 1
(incl. the fused Pallas path), continuous Algorithm 2, or probe-parallel
— or any config the registry resolves (``DriverConfig``, ``MGDConfig``,
``AnalogMGDConfig``).  Both loops share the same loss_fn / sampler
interfaces so every comparison in benchmarks/ runs the algorithms on
identical models and data.  The MGD loop scans ``chunk`` iterations per
device program (τ_x handled inside the scan via index-seeded samplers),
checkpoints periodically, and resumes deterministically — the
perturbation sequence is a pure function of the global step and
checkpoints carry the driver's FULL state pytree (whatever the algorithm
keeps: G accumulator, momentum, replay window, filter memories), so a
resumed run is the uninterrupted run.  The loop drives any
``repro.hardware.Plant``: pure-JAX devices scan ``chunk`` steps per
program; external plants (``ExternalPlant``, ``ChipFarm`` — ordered host
callbacks cannot ride lax.scan) fall back to per-step dispatch with the
same sampler/checkpoint semantics.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.api.driver import (MGDDriver, driver as build_driver, state_step,
                              warn_deprecated)
from repro.core import MGDState
from repro.optim import sgd_init, sgd_step
from . import checkpoint as ckpt


@dataclasses.dataclass
class TrainResult:
    params: Any
    state: Any
    history: list          # list of (step, metric dict)
    steps_done: int


@dataclasses.dataclass
class TrainLoopConfig:
    """Every loop-level knob of ``train_mgd``, in one place.

    ``train_mgd`` historically grew a dozen keyword arguments (chunking,
    eval cadence, checkpointing, resume, recalibration, device plumbing);
    this dataclass is the consolidated surface —

        repro.train(loss_fn, params, cfg, sample_fn, steps,
                    loop=TrainLoopConfig(chunk=50, checkpoint_dir=d,
                                         checkpoint_every=100))

    The flat keyword spelling is still accepted (it builds this config
    internally, so the two paths are the SAME code — f32-bit-identical
    trajectories, pinned in tests/test_online_serving.py) but emits a
    single-fire ``PendingDeprecationWarning``.
    """

    algorithm: Optional[str] = None    # registry name for a DriverConfig
    chunk: int = 100                   # steps per device program
    eval_fn: Optional[Callable] = None     # eval_fn(params) -> dict
    eval_every: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    resume: bool = True
    log: Optional[Callable] = print
    probe_fn: Optional[Callable] = None    # fused probe path (cfg.fused)
    plant: Any = None                  # hardware.Plant (None → implicit)
    mesh: Any = None                   # probe-parallel probe mesh
    recal_every: int = 0               # scheduled full-rewrite period
    recal_params: Any = None           # shadow params (None → initial)

    def replace(self, **kw) -> "TrainLoopConfig":
        return dataclasses.replace(self, **kw)


_LOOP_FIELDS = tuple(f.name for f in dataclasses.fields(TrainLoopConfig))


def resolve_driver(loss_fn, cfg, *, probe_fn=None, plant=None, mesh=None,
                   algorithm: Optional[str] = None) -> MGDDriver:
    """Resolve ``cfg`` to an ``MGDDriver``: pass one through, or build it
    from a config (legacy configs pick their algorithm; ``DriverConfig``
    defaults to discrete unless ``algorithm`` says otherwise)."""
    if isinstance(cfg, MGDDriver):
        if loss_fn is not None or probe_fn is not None or plant is not None \
                or mesh is not None:
            raise ValueError(
                "got a pre-built MGDDriver AND loss_fn/probe_fn/plant/mesh "
                "— those belong to repro.driver(...) at construction time")
        return cfg
    if algorithm is None:
        from repro.core import AnalogMGDConfig
        algorithm = "analog" if isinstance(cfg, AnalogMGDConfig) \
            else "discrete"
    return build_driver(algorithm, cfg, loss_fn, probe_fn=probe_fn,
                        plant=plant, mesh=mesh)


# the historical private name, kept for callers inside the repo's history
_as_driver = resolve_driver


def _ckpt_tree(params, state):
    """Checkpoint payload: params + the driver's FULL state pytree (None
    entries vanish from the flattened tree, so the structure is a pure
    function of the driver config).  Dropping optimizer buffers on resume
    would silently diverge a resumed run mid-τ_θ-window."""
    return {"params": params, "state": state}


def _recalibrate(drv, params, shadow, step):
    """Scheduled recalibration: commit the trainer's shadow parameters to
    the device, replacing whatever drifted state is stored there.  The
    rewrite lands through the plant's write path — DAC grid, write noise,
    and one drift transition all apply (a recalibration write is still a
    write on an aging device).  With no explicit plant the device is the
    implicit ideal one and the rewrite is the shadow itself."""
    plant = drv.plant
    shadow = jax.tree_util.tree_map(jnp.asarray, shadow)
    if plant is None:
        return shadow
    return plant.write_params(shadow, step=jnp.asarray(step, jnp.int32),
                              prev=params)


def _restore_any(checkpoint_dir, params, state, log):
    """Restore the newest checkpoint into (params, state), falling back
    through the historical layouts: full-state → PR-2 buffers-only
    (discrete) → params-only (buffers reset)."""
    try:
        tree, _, start = ckpt.restore(checkpoint_dir,
                                      _ckpt_tree(params, state))
        return tree["params"], tree["state"], start
    except AssertionError:
        pass
    if isinstance(state, MGDState):
        try:    # PR-2 layout: {"params", "opt": {g, replay_c, m}} + extra
            tree, extra, start = ckpt.restore(
                checkpoint_dir,
                {"params": params, "opt": {"g": state.g,
                                           "replay_c": state.replay_c,
                                           "m": state.m}})
            state = state._replace(
                g=tree["opt"]["g"], replay_c=tree["opt"]["replay_c"],
                m=tree["opt"]["m"], step=jnp.asarray(start, jnp.int32),
                c0=jnp.asarray(extra.get("c0", 0.0), jnp.float32),
                metric_cost=jnp.asarray(extra.get("metric_cost", 0.0),
                                        jnp.float32))
            return tree["params"], state, start
        except AssertionError:
            pass
    # params-only legacy checkpoint
    params, extra, start = ckpt.restore(checkpoint_dir, params)
    if log:
        log("[mgd] legacy checkpoint: optimizer buffers reset")
    from repro.api.driver import replace_step
    state = replace_step(state, start)
    if isinstance(state, MGDState):
        state = state._replace(
            c0=jnp.asarray(extra.get("c0", 0.0), jnp.float32),
            metric_cost=jnp.asarray(extra.get("metric_cost", 0.0),
                                    jnp.float32))
    return params, state, start


def train_mgd(
    loss_fn: Optional[Callable],
    params,
    cfg,                          # MGDDriver | DriverConfig | legacy config
    sample_fn: Callable,          # sample_fn(sample_index) -> batch
    num_steps: int,
    *,
    loop: Optional[TrainLoopConfig] = None,
    **flat,                       # legacy flat spelling of TrainLoopConfig
) -> TrainResult:
    """Run any MGD driver for ``num_steps`` iterations (τ_p ticks).

    Loop-level knobs (chunking, eval cadence, checkpoint/resume,
    scheduled recalibration, device plumbing) live in ``loop=``, a
    ``TrainLoopConfig``.  The historical flat keywords (``chunk=``,
    ``eval_fn=``, ``checkpoint_dir=``, ``plant=``, ...) are still
    accepted — they build the same config, so the flat and ``loop=``
    paths are f32-bit-identical — but the flat spelling emits a
    single-fire ``PendingDeprecationWarning``; new code should pass
    ``loop=TrainLoopConfig(...)`` (or call ``repro.train``).

    ``loop.recal_every`` turns on scheduled recalibration — the
    lab-bench mitigation for drifting/aging devices that MGD's online
    feedback is measured against (``benchmarks/drift_aging.py``): every
    ``recal_every`` completed steps the loop rewrites the device from the
    trainer's shadow parameters (``recal_params``, defaulting to the
    initial ``params`` — the last full calibration) through the plant's
    write path.  Boundaries are a pure function of the global step, so
    checkpoint/resume replays the identical recalibration schedule.
    """
    if flat:
        unknown = sorted(set(flat) - set(_LOOP_FIELDS))
        if unknown:
            raise TypeError(f"train_mgd got unexpected keyword arguments "
                            f"{unknown}; loop-level knobs are the fields "
                            f"of TrainLoopConfig: {sorted(_LOOP_FIELDS)}")
        if loop is not None:
            raise ValueError(
                f"got loop=TrainLoopConfig(...) AND the flat keywords "
                f"{sorted(flat)} — set every loop knob in one place")
        warn_deprecated(
            "train_mgd's flat loop keywords",
            "train_mgd(..., loop=TrainLoopConfig(...))",
            category=PendingDeprecationWarning)
        loop = TrainLoopConfig(**flat)
    elif loop is None:
        loop = TrainLoopConfig()
    if loop.recal_every < 0:
        raise ValueError(
            f"recal_every must be >= 0, got {loop.recal_every}")
    (chunk, eval_fn, eval_every, checkpoint_dir, checkpoint_every, log,
     recal_every, recal_params) = (
        loop.chunk, loop.eval_fn, loop.eval_every, loop.checkpoint_dir,
        loop.checkpoint_every, loop.log, loop.recal_every,
        loop.recal_params)
    # shadow captured from the caller's arguments BEFORE any resume
    # restore — the factory calibration, identical across restarts.  Held
    # only when recalibration will read it: otherwise it would pin a
    # second copy of the initial weights in device memory for the run.
    shadow = None
    if recal_every:
        shadow = recal_params if recal_params is not None else params
    drv = resolve_driver(loss_fn, cfg, probe_fn=loop.probe_fn,
                         plant=loop.plant, mesh=loop.mesh,
                         algorithm=loop.algorithm)
    state = drv.init(params)
    start_step = 0
    if checkpoint_dir and loop.resume \
            and ckpt.latest_step(checkpoint_dir) is not None:
        params, state, start_step = _restore_any(
            checkpoint_dir, params, state, log)
        if log:
            log(f"[mgd] resumed from step {start_step}")

    def body(carry, _):
        p, s = carry
        batch = sample_fn(state_step(s) // drv.tau_x)
        p, s, m = drv.step(p, s, batch)
        return (p, s), m

    # External plants (ordered host callbacks — ExternalPlant, ChipFarm)
    # cannot ride lax.scan on all jax versions; drive them step-by-step
    # with the same τ_x sampler semantics.  Checkpoint/resume is identical
    # either way: the state pytree carries the step counter and the
    # device noise is counter-keyed, so a resumed farm run replays the
    # uninterrupted trajectory.
    external = bool(getattr(getattr(drv.plant, "meta", None),
                            "external", False))
    if external:
        step_jit = jax.jit(drv.step)

        def make_runner(n):
            def run(p, s):
                m = {}
                for _ in range(n):
                    batch = sample_fn(int(state_step(s)) // drv.tau_x)
                    p, s, m = step_jit(p, s, batch)
                return p, s, m
            return run
    else:
        def make_runner(n):
            @jax.jit
            def run(p, s):
                (p, s), ms = jax.lax.scan(body, (p, s), None, length=n)
                return p, s, jax.tree_util.tree_map(lambda x: x[-1], ms)
            return run

    # double-buffered farms (ChipFarm(pipeline=True)) leave parameter
    # writes in flight between steps; state-dependent boundaries —
    # checkpoints, evals, recalibration — must not run with writes
    # pending, so the loop fences the plant first.  A no-op for every
    # other plant (and values are unaffected either way: device noise is
    # counter-keyed, so the fence changes WHEN writes land, never what
    # the chips read — resume stays bit-exact through a pipelined
    # boundary).
    plant_fence = getattr(drv.plant, "fence", None)
    fence = plant_fence if callable(plant_fence) else (lambda: None)

    runners = {}
    history = []
    done = start_step
    t0 = time.time()
    while done < num_steps:
        n = min(chunk, num_steps - done)
        if recal_every:
            # stop each device program at the next recalibration boundary
            n = min(n, recal_every - done % recal_every)
        if n not in runners:
            runners[n] = make_runner(n)
        params, state, metrics = runners[n](params, state)
        done += n
        rec = {k: float(v) for k, v in metrics.items()}
        if eval_fn and eval_every and (done % eval_every < chunk):
            fence()
            rec.update({k: float(v) for k, v in eval_fn(params).items()})
        history.append((done, rec))
        if log:
            msg = " ".join(f"{k}={v:.4g}" for k, v in rec.items())
            log(f"[mgd] step {done}/{num_steps} {msg} "
                f"({(time.time()-t0):.1f}s)")
        if recal_every and done % recal_every == 0 and done < num_steps:
            fence()
            params = _recalibrate(drv, params, shadow, done)
            if log:
                log(f"[mgd] step {done}: scheduled recalibration "
                    f"(full rewrite from shadow params)")
        if checkpoint_dir and checkpoint_every and done % checkpoint_every == 0:
            fence()
            ckpt.save(checkpoint_dir, done, _ckpt_tree(params, state),
                      extra={"algo": drv.algorithm,
                             "seed": int(getattr(drv.config, "seed", 0))})
    fence()
    # fault-tolerant plants (ExternalPlant/ChipFarm with a FaultPolicy)
    # expose a telemetry summary — surface it once so a run that survived
    # faults says so instead of looking clean
    fault_summary = getattr(drv.plant, "fault_summary", None)
    if log and callable(fault_summary):
        summary = fault_summary()
        if summary.get("events"):
            log(f"[mgd] fault-tolerance summary: {summary}")
    return TrainResult(params, state, history, done)


def train_backprop(
    loss_fn: Callable,
    params,
    sample_fn: Callable,
    num_steps: int,
    *,
    eta: float,
    momentum: float = 0.0,
    chunk: int = 100,
    eval_fn: Optional[Callable] = None,
    eval_every: int = 0,
    log: Optional[Callable] = print,
) -> TrainResult:
    """The paper's comparison baseline: backprop + plain SGD."""
    opt_state = sgd_init(params, momentum)
    grad_fn = jax.grad(loss_fn)

    def body(carry, i):
        p, o = carry
        batch = sample_fn(i)
        g = grad_fn(p, batch)
        p, o = sgd_step(p, g, o, eta=eta, momentum=momentum)
        return (p, o), loss_fn(p, batch)

    @jax.jit
    def run_chunk(p, o, i0):
        (p, o), losses = jax.lax.scan(
            body, (p, o), i0 + jnp.arange(chunk))
        return p, o, losses[-1]

    history = []
    done = 0
    while done < num_steps:
        params, opt_state, loss = run_chunk(
            params, opt_state, jnp.asarray(done, jnp.int32))
        done += chunk
        rec = {"cost": float(loss)}
        if eval_fn and eval_every and (done % eval_every < chunk):
            rec.update({k: float(v) for k, v in eval_fn(params).items()})
        history.append((done, rec))
        if log:
            msg = " ".join(f"{k}={v:.4g}" for k, v in rec.items())
            log(f"[bp ] step {done}/{num_steps} {msg}")
    return TrainResult(params, opt_state, history, done)


def classification_accuracy(apply_fn, params, x, y_onehot):
    """Fraction of argmax matches — the paper's accuracy metric."""
    pred = apply_fn(params, x)
    return jnp.mean(
        (jnp.argmax(pred, -1) == jnp.argmax(y_onehot, -1)).astype(jnp.float32))
