"""Multiplexed gradient descent — discrete algorithm (paper Algorithm 1).

Construct this algorithm through the driver registry::

    mgd = repro.driver("discrete", repro.DriverConfig(...), loss_fn,
                       plant=..., probe_fn=...)
    state = mgd.init(params)
    params, state, aux = mgd.step(params, state, batch)

``repro.driver`` (see ``repro.api.driver``) builds the discrete,
continuous, and probe-parallel algorithms behind one optax-style
``(init, step)`` contract (the retired ``make_mgd_step`` shim now
raises).  This module keeps the discrete algorithm's implementation:
``MGDConfig``, ``MGDState``, ``mgd_init``, and the step factory
``build_mgd_step``.

The MGD step is *model-free*: it consumes only a scalar cost oracle — a
``repro.hardware.Plant`` (ideal, noisy, quantized, or an external chip),
or equivalently a plain ``loss_fn(params, batch) -> cost`` wrapped into
the implicit in-process plant — plus the three time constants
(τ_p, τ_θ, τ_x) and a perturbation family.  One MGD iteration is:

    1. (re)generate the perturbation θ̃ for this step            [τ_p]
    2. refresh the baseline cost C₀ if the sample or params
       changed (forward mode), or probe ±θ̃ (central mode)       [τ_x]
    3. C̃ ← C(θ+θ̃) − C₀        (the only feedback — ONE SCALAR)
    4. e ← C̃·θ̃/Δθ²;  G ← G + e   (local homodyne accumulation)
    5. every τ_θ: θ ← θ − ηG;  G ← 0                            [τ_θ]

Everything is implemented with ``lax`` control flow so the whole step jits,
lowers, and GSPMD-partitions; under pjit the only gradient-path collective
is the psum XLA inserts for the scalar cost reduction.

Paper-faithful mode is ``mode="forward"`` with ``replay=False`` and
``probes=1``.  Beyond-paper extensions (recorded separately in
EXPERIMENTS.md §Perf):

* ``mode="central"``  — antithetic probe C(θ+θ̃)−C(θ−θ̃): O(Δθ²) bias and no
  C₀ refresh pass (same 2-forward budget as forward mode at τ_x=1).
* ``replay=True``     — scalar-replay memory: instead of the O(P) gradient
  accumulator G the paper requires when τ_θ > τ_p, store only the τ_θ-window
  of C̃ scalars and regenerate θ̃ at update time.  O(1) optimizer memory.
* ``probes=k``        — k independent perturbation vectors per step,
  averaged.  Variance ∝ 1/k; at pod scale the probe axis maps onto the mesh
  (see ``probe_parallel``) with only k scalars crossing the interconnect.
* ``momentum``        — classical heavy-ball on G (the paper notes MGD "is
  capable of implementing" momentum; we provide it).
"""
from __future__ import annotations

import copy
import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import perturbations as pert
from .utils import (
    leaf_meta,
    tree_add,
    tree_axpy,
    tree_scale,
    tree_select,
    tree_size,
    tree_zeros_like,
)

Pytree = Any


# ---------------------------------------------------------------------------
# Config / state
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MGDConfig:
    """Static configuration of the MGD optimizer (hashable → jit-static).

    Attributes mirror the paper's Table 1 plus framework extensions.
    """

    # perturbation family: rademacher | walsh | sequential | sinusoidal
    ptype: str = "rademacher"
    dtheta: float = 1e-3          # Δθ, perturbation amplitude
    eta: float = 1e-2             # η, learning rate
    tau_p: int = 1                # perturbation time constant
    tau_theta: int = 1            # parameter-update (gradient-integration) time
    tau_x: int = 1                # input-sample change time (driver-enforced)
    mode: str = "forward"         # forward (paper) | central (beyond-paper)
    replay: bool = False          # scalar-replay O(1)-memory updates
    probes: int = 1               # probe-averaging count
    probe_impl: str = "map"       # map (sequential) | vmap (parallel/shardable)
    momentum: float = 0.0         # heavy-ball coefficient on G
    seed: int = 0
    # hardware noise emulation (paper §3.5).  These fields describe the
    # IMPLICIT device (they build a hardware.NoisyPlant internally); when
    # an explicit plant is passed to build_mgd_step they must stay 0 — the
    # plant owns all imperfections.
    cost_noise: float = 0.0       # σ_C  — gaussian noise added to every cost read
    update_noise: float = 0.0     # σ_θ  — update noise, std σ_θ·Δθ (see hardware.plants)
    # bounded-staleness feedback: the update at step n may consume C̃ from
    # step n-d (straggler tolerance; 0 = synchronous paper behaviour)
    staleness: int = 0
    # fused probe execution: probes evaluate through a model-provided
    # probe_fn that routes weight matmuls through the Pallas
    # perturbed-matmul kernels (θ̃ generated in VMEM, never in HBM), and
    # the update regenerates θ̃ inside kernels.mgd_update_window for every
    # ndim≥2 leaf.  Bit-identical (f32) cost/parameter trajectories to the
    # materializing path; ~¼ the weight HBM reads per central probe pair
    # (EXPERIMENTS.md §Perf).
    fused: bool = False
    kernel_impl: Optional[str] = None   # pallas | interpret | ref | None=auto

    def __post_init__(self):
        if self.ptype not in pert.PERTURBATION_TYPES:
            raise ValueError(f"unknown perturbation type {self.ptype!r}")
        if self.mode not in ("forward", "central"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.replay and self.ptype == "sinusoidal" and self.tau_theta > 256:
            # replay regenerates θ̃ for each window step — fine for codes,
            # wasteful for long analog windows.
            raise ValueError("replay mode with sinusoidal ptype and large "
                             "tau_theta: use the analog algorithm instead")
        if self.staleness and not self.replay:
            raise ValueError("bounded-staleness feedback requires replay mode "
                             "(the C̃ window is what absorbs the delay)")
        if self.fused:
            if self.ptype != "rademacher":
                raise ValueError("fused path regenerates signs in-kernel — "
                                 "rademacher only")
            if self.probes != 1:
                raise ValueError("fused path supports probes=1 (probe "
                                 "parallelism composes at the mesh level)")
            if self.momentum or self.update_noise:
                raise ValueError("fused path has no materialized update "
                                 "direction — momentum/update_noise need "
                                 "the unfused optimizer")
            if self.tau_theta > 1 and not self.replay:
                raise ValueError("fused path with tau_theta > 1 requires "
                                 "replay=True (the O(P) gradient accumulator "
                                 "is exactly what fusion eliminates)")


class MGDState(NamedTuple):
    """Carried optimizer state.  Structure is fixed per MGDConfig."""

    step: jnp.ndarray                 # int32 global iteration counter n
    c0: jnp.ndarray                   # f32 baseline cost C₀ (forward mode)
    g: Optional[Pytree]               # gradient accumulator (None in replay)
    replay_c: Optional[jnp.ndarray]   # f32[tau_theta + staleness] C̃ window
    m: Optional[Pytree]               # momentum buffer (None if momentum==0)
    metric_cost: jnp.ndarray          # f32 last unperturbed-ish cost (telemetry)


def mgd_init(params: Pytree, cfg: MGDConfig) -> MGDState:
    """Fresh optimizer state for ``params`` under ``cfg``.

    Works with concrete arrays *or* ShapeDtypeStructs (dry-run safe) —
    buffers are created with ``jnp.zeros`` from shape/dtype only.
    τ_θ = 1 needs no gradient accumulator at all (the update consumes the
    error signal immediately — paper §4.2's "only a single additional
    memory element is required"); at deepseek scale the f32 G buffer would
    be 10.5 GiB/device, so this is a fits-in-HBM matter, not a nicety.
    """
    g = (None if (cfg.replay or cfg.tau_theta == 1)
         else tree_zeros_like(params, jnp.float32))
    window = cfg.tau_theta + cfg.staleness
    replay_c = jnp.zeros((window,), jnp.float32) if cfg.replay else None
    m = tree_zeros_like(params, jnp.float32) if cfg.momentum else None
    return MGDState(
        step=jnp.zeros((), jnp.int32),
        c0=jnp.zeros((), jnp.float32),
        g=g,
        replay_c=replay_c,
        m=m,
        metric_cost=jnp.zeros((), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Plant resolution (the device the optimizer drives)
# ---------------------------------------------------------------------------


def _resolve_plant(loss_fn, cfg, *, probe_fn=None, plant=None):
    """The device behind this optimizer run.

    With ``plant=None`` the historical in-process behaviour is rebuilt
    from the config: ``cost_noise``/``update_noise`` become a
    ``NoisyPlant`` with the exact historical key derivation, σ = 0 an
    ``IdealPlant`` — bit-identical (f32) either way.  An explicit plant
    owns ALL hardware imperfections, so the config noise fields must be
    zero (anything else would double-count the noise).
    """
    # runtime import: repro.hardware.base imports core.utils, so a
    # module-level import here would be circular.
    from repro.hardware.base import Plant
    from repro.hardware.plants import plant_from_config

    if plant is None:
        if loss_fn is None:
            raise ValueError("need a loss_fn (or an explicit plant)")
        return plant_from_config(loss_fn, cfg, probe_fn=probe_fn)
    if not isinstance(plant, Plant):
        raise TypeError(f"plant must be a repro.hardware.Plant, "
                        f"got {type(plant).__name__}")
    if getattr(cfg, "cost_noise", 0.0) or getattr(cfg, "update_noise", 0.0):
        raise ValueError(
            "cfg.cost_noise/update_noise describe the implicit device; "
            "with an explicit plant the plant owns all imperfections — "
            "set the config fields to 0")
    if probe_fn is not None and plant.probe_fn is not probe_fn:
        if plant.probe_fn is not None:
            raise ValueError("both the plant and build_mgd_step were given "
                             "a probe_fn — they disagree; set it in one "
                             "place")
        # shallow copy so a plant shared across optimizers never inherits
        # another model's perturbed-apply
        plant = copy.copy(plant)
        plant.probe_fn = probe_fn
    return plant


# ---------------------------------------------------------------------------
# The step factory
# ---------------------------------------------------------------------------


def _probe_seed(cfg: MGDConfig, probe) -> jnp.ndarray:
    # distinct, deterministic seed per probe; probe 0 == cfg.seed so
    # probes=1 is bit-identical to the unprobed path.  uint32 arithmetic —
    # ``probe`` may be a traced int under lax.map/vmap.
    return (jnp.uint32(cfg.seed)
            + jnp.asarray(probe, jnp.uint32) * jnp.uint32(0x9E3779B9))


def build_mgd_step(
    loss_fn: Optional[Callable[[Pytree, Any], jnp.ndarray]],
    cfg: MGDConfig,
    total_params: Optional[int] = None,
    *,
    probe_fn: Optional[Callable] = None,
    plant=None,
):
    """Build the jittable MGD iteration (the registry's discrete builder).

    ``loss_fn(params, batch) -> scalar cost`` is the ONLY model interface —
    MGD never sees the network topology (model-free, paper §1).  All cost
    reads and parameter writes go through a ``repro.hardware.Plant``; pass
    one explicitly to train against a noisy/quantized/external device, or
    pass none to get the implicit in-process device (``IdealPlant``, or
    ``NoisyPlant`` when the config's σ_C/σ_θ fields are set) — bit-identical
    (f32) to the historical inlined path.  With an explicit plant,
    ``loss_fn`` may be ``None``: the plant is the only cost oracle.

    With ``cfg.fused=True`` the model additionally provides
    ``probe_fn(params, batch, probe: perturbations.Probe) -> [n_signs]``
    costs under θ ± θ̃, or ``(costs, aux)`` where the model counts
    something beside them (``aux`` joins the step's metrics) — the
    perturbed-apply interface (e.g.
    ``models.simple.make_mlp_probe_fn`` or
    ``models.make_transformer_probe_fn``) that routes weight matmuls
    through the Pallas kernels so θ̃ never exists in HBM.  The fused path
    produces bit-identical (f32) C̃/parameter trajectories to the
    materializing path, and reaches the kernels through
    ``plant.apply_perturbed`` so hardware models compose with it.

    Returns ``step_fn(params, state, batch) -> (params, state, metrics)``.
    The caller controls τ_x by switching ``batch`` every τ_x calls (the data
    pipeline does this); everything else is internal.
    """
    plant = _resolve_plant(loss_fn, cfg, probe_fn=probe_fn, plant=plant)
    if cfg.fused and not plant.supports_fused:
        raise ValueError("cfg.fused=True needs a probe_fn (the model's "
                         "perturbed-apply interface) on the plant")
    if plant.meta.external:
        # Ordered host callbacks cannot live inside lax.cond: forward
        # mode's C₀ refresh and every windowed update (replay or
        # accumulator select) are conds, and the τ_θ>1 accumulator path
        # additionally computes a write per step that tree_select then
        # discards — on a physical device that write already happened.
        # The cond-free step is central τ_θ=1 (the chip-in-the-loop
        # configuration); temporal windows belong on the host loop.
        if cfg.mode != "central" or cfg.tau_theta != 1 or cfg.replay:
            raise ValueError("external plants need mode='central', "
                             "tau_theta=1, replay=False — the only "
                             "cond-free step an ordered host callback "
                             "can ride (see hardware/external.py)")

    def perturbation(params, step, probe=0):
        return pert.generate(
            params,
            ptype=cfg.ptype,
            step=step,
            seed=_probe_seed(cfg, probe),
            dtheta=cfg.dtheta,
            tau_p=cfg.tau_p,
            total=total_params,
        )

    inv_d2 = 1.0 / (cfg.dtheta * cfg.dtheta)

    # Rounding pin for the scalar homodyne coefficients (C̃/Δθ² and the
    # replay a_j).  XLA's simplifier is free to re-merge constant factors
    # (Δθ, 1/Δθ², η) across these products — legal per-program, but it
    # rounds differently in different programs, which would break the
    # fused-vs-materialized bit-equality contract.  Pinning the coefficient
    # value at its definition keeps every program on the written
    # association.
    _pin = jax.lax.optimization_barrier

    def probe_once(params, state, batch, probe):
        """One perturbation probe → (C̃, θ̃, c0, cost_metric)."""
        n = state.step
        theta_t = perturbation(params, n, probe)
        if cfg.mode == "central":
            c_plus, c_minus = plant.read_cost_pair(
                params, theta_t, batch, step=n, tag=2 * probe)
            # barrier: pin C̃'s own rounding before the ·1/Δθ² scaling —
            # XLA otherwise folds 0.5·inv_d2 into one constant in SOME
            # programs, breaking fused-vs-materialized bit-equality.
            c_tilde = jax.lax.optimization_barrier(0.5 * (c_plus - c_minus))
            return c_tilde, theta_t, state.c0, 0.5 * (c_plus + c_minus)
        # forward mode (paper Algorithm 1): refresh C₀ when the sample
        # changed (n % τ_x == 0) or params were updated (n % τ_θ == 0).
        need_c0 = jnp.logical_or(n % cfg.tau_x == 0, n % cfg.tau_theta == 0)
        c0 = jax.lax.cond(
            need_c0,
            lambda: plant.read_cost(params, batch, step=n,
                                    tag=2 * probe).astype(jnp.float32),
            lambda: state.c0,
        )
        c_pert = plant.read_cost(tree_add(params, theta_t), batch,
                                 step=n, tag=2 * probe + 1)
        return c_pert - c0, theta_t, c0, c0

    def accumulate(params, state, batch):
        """All probes → averaged error signal contribution + scalars."""
        if cfg.probes == 1:
            c_tilde, theta_t, c0, cm = probe_once(params, state, batch, 0)
            e = tree_scale(theta_t, _pin(c_tilde * inv_d2))
            return e, c_tilde, c0, cm

        def one(probe):
            c_tilde, theta_t, c0, cm = probe_once(params, state, batch, probe)
            e = tree_scale(theta_t, _pin(c_tilde * inv_d2))
            return e, c_tilde, c0, cm

        ids = jnp.arange(cfg.probes)
        if cfg.probe_impl == "vmap":
            es, cts, c0s, cms = jax.vmap(one)(ids)
        else:
            es, cts, c0s, cms = jax.lax.map(one, ids)
        e = tree_scale(jax.tree_util.tree_map(lambda x: jnp.sum(x, 0), es),
                       1.0 / cfg.probes)
        return e, jnp.mean(cts), c0s.reshape(-1)[0], jnp.mean(cms)

    def apply_update(params, state, g_step):
        """θ ← θ − η·G (Eq. 4) with optional momentum; the write lands
        through the plant (write noise / DAC quantization / slow-write
        lag happen there — identity for the ideal device)."""
        n = state.step
        m = state.m
        if cfg.momentum:
            m = tree_axpy(1.0, g_step, tree_scale(state.m, cfg.momentum))
            direction = m
        else:
            direction = g_step
        new_params = plant.write_params(
            tree_axpy(-cfg.eta, direction, params), step=n, prev=params)
        return new_params, m

    # ----- fused probe + update paths (cfg.fused) --------------------------
    #
    # The probe evaluates through probe_fn (kernels regenerate θ̃ in VMEM);
    # the update regenerates θ̃ inside kernels.mgd_update_window for every
    # ndim≥2 leaf (read-W + write-W HBM traffic, window-length independent)
    # and materializes only the O(d) leaves.  Every float op mirrors the
    # materializing path's association exactly — see mgd_update_window.

    def _probe(n, signs):
        ctx = pert.ProbeCtx(signs=signs, dtheta=cfg.dtheta, tau_p=cfg.tau_p,
                            impl=cfg.kernel_impl)
        return pert.Probe(n, _probe_seed(cfg, 0), ctx)

    def probe_once_fused(params, state, batch):
        """Fused probe → (C̃, c0, cost_metric, aux); no θ̃ pytree exists.
        ``aux`` is what the model's probe_fn counts beside the costs."""
        n = state.step
        if cfg.mode == "central":
            costs, aux = plant.apply_perturbed(
                params, batch, _probe(n, (1.0, -1.0)), step=n, tags=(0, 1))
            c_plus, c_minus = costs[0], costs[1]
            # same rounding barrier as the materialized probe_once
            c_tilde = jax.lax.optimization_barrier(0.5 * (c_plus - c_minus))
            return c_tilde, state.c0, 0.5 * (c_plus + c_minus), aux
        need_c0 = jnp.logical_or(n % cfg.tau_x == 0, n % cfg.tau_theta == 0)
        c0 = jax.lax.cond(
            need_c0,
            lambda: plant.read_cost(params, batch, step=n,
                                    tag=0).astype(jnp.float32),
            lambda: state.c0,
        )
        costs, aux = plant.apply_perturbed(
            params, batch, _probe(n, (1.0,)), step=n, tags=(1,))
        return costs[0] - c0, c0, c0, aux

    def _fused_leaf_updates(params, lseeds_of, coefs, alpha, small_update):
        """Shared leaf walk: ndim≥2 leaves through mgd_update_window,
        small leaves through ``small_update(leaf, lid)``."""
        from repro.kernels import ops as kops
        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for (lid, _, _), leaf in zip(leaf_meta(params), leaves):
            if leaf.ndim >= 2:
                out.append(kops.mgd_update_window(
                    leaf, lseeds_of(lid), coefs, alpha=alpha,
                    dtheta=cfg.dtheta, impl=cfg.kernel_impl))
            else:
                out.append(small_update(leaf, lid))
        return jax.tree_util.tree_unflatten(treedef, out)

    def fused_update_tau1(params, n, c_tilde):
        """θ ← θ − η·C̃·θ̃/Δθ² with θ̃ regenerated in-kernel (τ_θ = 1)."""
        seed = _probe_seed(cfg, 0)
        s = _pin(c_tilde * inv_d2)     # mirrors tree_scale's f32 scalar

        def small(leaf, lid):
            # sign-LAST form of leaf + (−η)·(θ̃·s): bit-identical (the ±1
            # sign commutes exactly through both roundings) and immune to
            # mul+add FMA contraction — see the sign_exact_update note in
            # the materializing step below and kernels/mgd_update.py.
            signs = pert.rademacher_leaf(
                leaf.shape, jnp.float32, lid, step=n, seed=seed,
                dtheta=1.0, tau_p=cfg.tau_p)
            t = _pin(jnp.float32(-cfg.eta)
                     * _pin(jnp.float32(cfg.dtheta) * s))
            return (leaf.astype(jnp.float32) + signs * t).astype(leaf.dtype)

        def lseeds_of(lid):
            return pert.leaf_seed(seed, n // jnp.int32(cfg.tau_p), lid)[None]

        return _fused_leaf_updates(params, lseeds_of, s[None], -cfg.eta,
                                   small)

    def fused_replay_update(params, state, replay_c):
        """Scalar-replay window update through the fused kernel: the J sign
        regenerations happen against the already-resident W tile, so HBM
        traffic is read-W + write-W regardless of τ_θ."""
        n = state.step
        seed = _probe_seed(cfg, 0)
        window = replay_c.shape[0]
        j = jnp.arange(cfg.tau_theta, dtype=jnp.int32)
        steps = n - (cfg.tau_theta - 1) - cfg.staleness + j       # [J]
        coefs = _pin(jnp.float32(-cfg.eta * inv_d2)
                     * replay_c[steps % window])

        def small(leaf, lid):
            def body(jj, lf):
                theta = pert.rademacher_leaf(
                    lf.shape, lf.dtype, lid, step=steps[jj], seed=seed,
                    dtheta=cfg.dtheta, tau_p=cfg.tau_p)
                return (lf.astype(jnp.float32)
                        + coefs[jj] * theta.astype(jnp.float32)
                        ).astype(lf.dtype)
            return jax.lax.fori_loop(0, cfg.tau_theta, body, leaf)

        def lseeds_of(lid):
            return pert.leaf_seed(seed, steps // jnp.int32(cfg.tau_p), lid)

        return _fused_leaf_updates(params, lseeds_of, coefs, 1.0, small)

    def step_fn_fused(params, state: MGDState, batch):
        n = state.step
        c_tilde, c0, cost_metric, aux = probe_once_fused(
            params, state, batch)
        do_update = (n + 1) % cfg.tau_theta == 0
        metrics = {"cost": cost_metric, "c_tilde": c_tilde,
                   "updated": do_update.astype(jnp.float32), **aux}
        if cfg.replay:
            window = state.replay_c.shape[0]
            replay_c = state.replay_c.at[n % window].set(c_tilde)
            new_params = jax.lax.cond(
                do_update,
                lambda: plant.write_params(
                    fused_replay_update(params, state, replay_c),
                    step=n, prev=params),
                lambda: params,
            )
            new_state = state._replace(
                step=n + 1, c0=c0, replay_c=replay_c, metric_cost=cost_metric
            )
            return new_params, new_state, metrics
        # tau_theta == 1 (enforced in __post_init__): update every step
        new_params = plant.write_params(
            fused_update_tau1(params, n, c_tilde), step=n, prev=params)
        new_state = MGDState(
            step=n + 1, c0=c0, g=None, replay_c=None, m=None,
            metric_cost=cost_metric,
        )
        return new_params, new_state, metrics

    # ----- replay-mode update: regenerate θ̃ for the τ_θ window ------------
    def replay_update(params, state, replay_c):
        """θ −= η Σ_j C̃_j · θ̃_j / Δθ²  over the last τ_θ steps, with the
        perturbations regenerated (never stored).  With staleness d>0 the
        newest d scalars are excluded — they arrive next window."""
        n = state.step

        window = replay_c.shape[0]

        def body(j, p):
            # j-th step of the window, oldest first; the buffer slot for
            # step s is s % window (ring buffer).
            s = n - (cfg.tau_theta - 1) - cfg.staleness + j
            theta_j = perturbation(params, s)
            coef = replay_c[s % window]
            return tree_axpy(_pin(-cfg.eta * inv_d2 * coef), theta_j, p)

        return jax.lax.fori_loop(0, cfg.tau_theta, body, params)

    if cfg.fused:
        return step_fn_fused

    # τ_θ = 1 rademacher updates take a contraction-immune form: the CPU
    # backend may contract θ̃·s into the following add (one rounding instead
    # of two) once the η = 1 multiply folds to a negation — and HLO
    # optimization barriers are stripped before fusion, so no pin survives
    # to block it.  θ − η·(C̃·θ̃/Δθ²) is rewritten as θ + sgn·t with the
    # scalar t = (−η)·(Δθ·s) pinned at each rounding: sgn·t is an EXACT
    # multiply (sgn = ±1), so FMA contraction cannot change the result,
    # and the value is bit-identical (f32) to the written two-step
    # association — and to the fused kernel's w + α·((Δθ·sgn)·s).
    sign_exact_update = (cfg.tau_theta == 1 and cfg.probes == 1
                         and not cfg.momentum and not cfg.replay
                         and cfg.ptype == "rademacher")

    def step_fn(params, state: MGDState, batch):
        n = state.step
        if sign_exact_update and all(
                leaf.dtype == jnp.float32
                for leaf in jax.tree_util.tree_leaves(params)):
            c_tilde, _, c0, cost_metric = probe_once(params, state, batch, 0)
            s = _pin(c_tilde * inv_d2)
            t = _pin(jnp.float32(-cfg.eta)
                     * _pin(jnp.float32(cfg.dtheta) * s))
            signs = pert.generate_signs_only(
                params, step=n, seed=_probe_seed(cfg, 0), tau_p=cfg.tau_p)
            new_params = plant.write_params(
                jax.tree_util.tree_map(lambda p, g_: p + g_ * t,
                                       params, signs),
                step=n, prev=params)
            new_state = MGDState(
                step=n + 1, c0=c0, g=None, replay_c=None, m=None,
                metric_cost=cost_metric,
            )
            metrics = {"cost": cost_metric, "c_tilde": c_tilde,
                       "updated": jnp.float32(1.0)}
            return new_params, new_state, metrics
        e, c_tilde, c0, cost_metric = accumulate(params, state, batch)
        do_update = (n + 1) % cfg.tau_theta == 0

        if cfg.replay:
            window = state.replay_c.shape[0]
            replay_c = state.replay_c.at[n % window].set(c_tilde)
            new_params = jax.lax.cond(
                do_update,
                lambda: plant.write_params(
                    replay_update(params, state, replay_c),
                    step=n, prev=params),
                lambda: params,
            )
            new_state = state._replace(
                step=n + 1, c0=c0, replay_c=replay_c, metric_cost=cost_metric
            )
            metrics = {"cost": cost_metric, "c_tilde": c_tilde,
                       "updated": do_update.astype(jnp.float32)}
            return new_params, new_state, metrics

        if cfg.tau_theta == 1:
            # no accumulator: θ ← θ − η·e directly (update every step);
            # at deepseek scale an f32 G buffer is 10.5 GiB/device.
            new_params, new_m = apply_update(params, state, e)
            new_g = None
        else:
            g = tree_add(state.g, e)
            updated_params, new_m = apply_update(params, state, g)
            new_params = tree_select(do_update, updated_params, params)
            new_g = tree_select(do_update, tree_zeros_like(g), g)
        if cfg.momentum:
            new_m = tree_select(do_update, new_m, state.m)
        else:
            new_m = None
        new_state = MGDState(
            step=n + 1, c0=c0, g=new_g, replay_c=None, m=new_m,
            metric_cost=cost_metric,
        )
        metrics = {"cost": cost_metric, "c_tilde": c_tilde,
                   "updated": do_update.astype(jnp.float32)}
        return new_params, new_state, metrics

    return step_fn


# ---------------------------------------------------------------------------
# Legacy entry point (deprecated shim over the registry)
# ---------------------------------------------------------------------------


def make_mgd_step(*args, **kwargs):
    """RETIRED (PR 3 deprecation shim, removed PR 10)."""
    raise RuntimeError(
        "make_mgd_step was retired; build the algorithm through the "
        "registry: repro.driver('discrete', cfg, loss_fn, ...).step "
        "(bit-identical f32 trajectory, plus the standardized "
        "grad_norm_proxy aux key)")


# ---------------------------------------------------------------------------
# Multi-step driver (τ_x semantics + lax.scan over iterations)
# ---------------------------------------------------------------------------


def make_mgd_epoch(
    loss_fn, cfg: MGDConfig, steps_per_call: int,
    sample_fn: Callable[[jnp.ndarray], Any],
    *,
    probe_fn: Optional[Callable] = None,
    plant=None,
):
    """Scan ``steps_per_call`` MGD iterations inside one jitted call.

    ``sample_fn(sample_index) -> batch`` implements τ_x: iteration n uses
    sample index n // τ_x.  Used by the training loop and benchmarks to
    amortize dispatch overhead (one device program per chunk of steps).
    Note external plants (ordered host callbacks) cannot live under
    ``lax.scan``'s cond-free requirement on all jax versions — drive them
    step-by-step via the driver's ``step`` instead.  The generic
    equivalent for any driver is ``repro.api.make_epoch``.
    """
    step_fn = build_mgd_step(loss_fn, cfg, probe_fn=probe_fn, plant=plant)

    def body(carry, _):
        params, state = carry
        batch = sample_fn(state.step // cfg.tau_x)
        params, state, metrics = step_fn(params, state, batch)
        return (params, state), metrics

    @jax.jit
    def run(params, state):
        (params, state), metrics = jax.lax.scan(
            body, (params, state), None, length=steps_per_call
        )
        return params, state, metrics

    return run
