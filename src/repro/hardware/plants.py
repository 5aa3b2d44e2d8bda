"""In-process hardware models: noisy and quantized plants.

``NoisyPlant`` absorbs the imperfection logic the paper studies in §3.5
(Figs 8–10) that used to be inlined across ``core/mgd.py`` (σ_C, σ_θ) and
``core/noise.py`` (σ_a defects live in the device's loss/probe functions;
see ``hardware.devices`` for per-device-seed samplers):

* σ_C cost-readout noise — one gaussian per scalar read, keyed on
  (device seed, step, tag).
* σ_θ persistent-write noise — θ lands as θ + N(0, σ_θ·Δθ) per element,
  keyed on (device seed + 77, leaf index, step).

Both key derivations reproduce the historical ``MGDConfig.cost_noise`` /
``update_noise`` paths of the DISCRETE driver bit-for-bit, so σ = 0 is
bit-identical (f32) to ``IdealPlant`` and cfg-built Algorithm-1 plants
replay old trajectories exactly.  The continuous driver's σ_C stream was
re-keyed onto the same (seed, tag, step) scheme in this refactor — old
``AnalogMGDConfig(cost_noise>0)`` runs draw a different (statistically
identical) noise sequence; σ = 0 analog runs are unchanged.

``QuantizedPlant`` expresses the scenario the paper motivates but the
repo previously could not: persistent weight writes go through a
limited-bit DAC (clip to ±w_clip, round to 2^bits − 1 levels) and an
optional slow-write lag — each write only moves the stored value a
fraction 1 − e^{−1/τ_w} toward the commanded target.  Probe
perturbations bypass the DAC by default (the paper's picture of a
dedicated perturbation line / LFSR at each synapse); set
``quantize_probes=True`` to model probes that must also round-trip the
DAC (Δθ below the LSB then becomes invisible and training stalls — see
benchmarks/hardware_plants.py).

The dual imperfection — the cost READOUT through a k-bit ADC — is
``adc_bits``/``adc_mode``: every ``read_cost``/``read_cost_pair`` scalar
is clipped to [0, adc_range] and rounded to the ADC grid,
deterministically (``"round"``) or with counter-keyed stochastic
rounding (``"stochastic"``, unbiased: E[q] = C).  Because MGD's only
feedback is C̃ = C(θ+θ̃) − C₀, an ADC LSB above the typical |C̃| floors
the error signal at 0 and training stalls — the Δθ·|∇C| signal floor the
paper's Fig. 8 implies, mapped onto ADC bits (stochastic rounding
recovers the signal in expectation at the cost of readout variance; see
benchmarks/hardware_plants.py and EXPERIMENTS.md §Hardware).

``DriftingPlant`` is the time-VARYING device the follow-up scaling study
(Oripov et al. 2025) flags as the open deployment question: the stored
weights move *between* writes — an Ornstein–Uhlenbeck random walk
(``mode="walk"``: per-step gaussian kicks, optionally mean-reverting)
or a relaxation toward a rest state (``mode="decay"``: analog memory
leakage).  One drift transition lands after every committed write event,
keyed on the optimizer's step counter — the same determinism contract as
``NoisyPlant``/``SimulatedAnalogChip``, so checkpoint/resume replays the
identical device trajectory.  MGD's continuous zero-order feedback then
re-trims the aging device online; ``benchmarks/drift_aging.py`` measures
the drift rate at which that feedback, scheduled recalibration, and no
mitigation each collapse.
"""
from __future__ import annotations

import copy
import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .base import IdealPlant, Plant, PlantMeta


def _gauss_noise(seed, step, tag, shape=()):
    """Standard-normal noise from a counter-based key — no threaded PRNG
    state, so checkpoint/restart replays the identical hardware noise."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), tag)
    key = jax.random.fold_in(key, step)
    return jax.random.normal(key, shape, jnp.float32)


class NoisyPlant(Plant):
    """Device with gaussian readout noise and noisy persistent writes."""

    def __init__(self, loss_fn: Callable, *,
                 cost_noise: float = 0.0,
                 write_noise: float = 0.0,
                 dtheta: float = 1e-3,
                 seed: int = 0,
                 probe_fn: Optional[Callable] = None,
                 meta: Optional[PlantMeta] = None):
        self.loss_fn = loss_fn
        self.cost_noise = float(cost_noise)
        self.write_noise = float(write_noise)
        self.dtheta = float(dtheta)
        self.seed = int(seed)
        self.probe_fn = probe_fn
        self.meta = meta or PlantMeta(
            name="noisy", cost_noise=self.cost_noise,
            write_noise=self.write_noise)

    def _noisy(self, cost, step, tag):
        if self.cost_noise:
            cost = cost + self.cost_noise * _gauss_noise(self.seed, step, tag)
        return cost

    def read_cost(self, params, batch, *, step, tag: int = 0):
        return self._noisy(self.loss_fn(params, batch), step, tag)

    def write_params(self, params, *, step, prev=None):
        if not self.write_noise:
            return params
        # σ_θ in units of Δθ (paper §3.5 / Fig. 9): each element lands as
        # θ + N(0, σ_θ·Δθ), leaf keys counted from 1 (historical layout).
        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for i, x in enumerate(leaves, start=1):
            k = jax.random.fold_in(jax.random.PRNGKey(self.seed + 77), i)
            k = jax.random.fold_in(k, step)
            out.append(x + self.write_noise * self.dtheta * jax.random.normal(
                k, x.shape, jnp.float32).astype(x.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def apply_perturbed(self, params, batch, probe, *, step, tags):
        costs, aux = super().apply_perturbed(params, batch, probe, step=step,
                                             tags=tags)
        if self.cost_noise:
            noise = jnp.stack([_gauss_noise(self.seed, step, t)
                               for t in tags])
            costs = costs + self.cost_noise * noise
        return costs, aux


class QuantizedPlant(Plant):
    """Device whose persistent weight memory sits behind a limited-bit DAC
    with an optional first-order slow-write lag, and (optionally) whose
    cost readout passes a limited-bit ADC."""

    def __init__(self, loss_fn: Callable, *,
                 bits: int = 8,
                 w_clip: float = 2.0,
                 write_tau: float = 0.0,
                 quantize_probes: bool = False,
                 adc_bits: Optional[int] = None,
                 adc_mode: str = "round",
                 adc_range: float = 1.0,
                 seed: int = 0,
                 probe_fn: Optional[Callable] = None,
                 meta: Optional[PlantMeta] = None):
        if bits < 1:
            raise ValueError(f"weight DAC needs >= 1 bit, got {bits}")
        if adc_bits is not None and adc_bits < 1:
            raise ValueError(f"cost ADC needs >= 1 bit, got {adc_bits}")
        if adc_mode not in ("round", "stochastic"):
            raise ValueError(f"adc_mode must be 'round' or 'stochastic', "
                             f"got {adc_mode!r}")
        self.loss_fn = loss_fn
        self.bits = int(bits)
        self.w_clip = float(w_clip)
        self.write_tau = float(write_tau)
        self.quantize_probes = bool(quantize_probes)
        self.adc_bits = None if adc_bits is None else int(adc_bits)
        self.adc_mode = adc_mode
        self.adc_range = float(adc_range)
        self.seed = int(seed)
        self.probe_fn = probe_fn
        self.meta = meta or PlantMeta(name=f"dac{bits}", weight_bits=self.bits,
                                      adc_bits=self.adc_bits)

    @property
    def lsb(self) -> float:
        return 2.0 * self.w_clip / (2 ** self.bits - 1)

    @property
    def adc_lsb(self) -> float:
        if self.adc_bits is None:
            raise ValueError("plant has no cost ADC (adc_bits=None)")
        return self.adc_range / (2 ** self.adc_bits - 1)

    def _quantize_leaf(self, x):
        scale = jnp.float32(self.lsb)
        q = jnp.round((jnp.clip(x, -self.w_clip, self.w_clip)
                       + self.w_clip) / scale)
        return (q * scale - self.w_clip).astype(x.dtype)

    def quantize(self, params):
        return jax.tree_util.tree_map(self._quantize_leaf, params)

    def write_params(self, params, *, step, prev=None):
        target = params
        if self.write_tau and prev is not None:
            # slow write: the memory cell only slews a fraction of the
            # commanded step per write event (first-order lag, τ_w in
            # units of write events).
            alpha = 1.0 - math.exp(-1.0 / self.write_tau)
            target = jax.tree_util.tree_map(
                lambda p, t: (p.astype(jnp.float32)
                              + alpha * (t.astype(jnp.float32)
                                         - p.astype(jnp.float32))
                              ).astype(t.dtype),
                prev, target)
        return self.quantize(target)

    def _adc(self, cost, step, tag):
        """k-bit cost readout: clip to [0, adc_range], land on the ADC
        grid.  Stochastic mode rounds up with probability equal to the
        fractional code (unbiased), counter-keyed on (seed, step, tag) so
        checkpoint/restart replays the identical readout stream."""
        if self.adc_bits is None:
            return cost
        scale = jnp.float32(self.adc_lsb)
        code = jnp.clip(cost.astype(jnp.float32), 0.0, self.adc_range) / scale
        if self.adc_mode == "stochastic":
            key = jax.random.fold_in(jax.random.PRNGKey(self.seed + 131), tag)
            key = jax.random.fold_in(key, step)
            code = jnp.floor(code + jax.random.uniform(key, (), jnp.float32))
        else:
            code = jnp.round(code)
        return code * scale

    def read_cost(self, params, batch, *, step, tag: int = 0):
        if self.quantize_probes:
            params = self.quantize(params)
        return self._adc(self.loss_fn(params, batch), step, tag)

    # read_cost_pair needs no override: the base class issues two
    # read_cost calls with consecutive tags, so each half of the
    # antithetic pair round-trips the ADC independently (two physical
    # conversions), exactly like hardware.

    def apply_perturbed(self, params, batch, probe, *, step, tags):
        # persistent params are already on the DAC grid (write_params);
        # the probe line bypasses the DAC unless quantize_probes, which
        # the fused kernels cannot express (θ̃ is generated in-kernel).
        if self.quantize_probes:
            raise NotImplementedError(
                "quantize_probes=True has no fused kernel path")
        costs, aux = super().apply_perturbed(params, batch, probe, step=step,
                                             tags=tags)
        if self.adc_bits is not None:
            costs = jnp.stack([self._adc(costs[i], step, t)
                               for i, t in enumerate(tags)])
        return costs, aux


class DriftingPlant(Plant):
    """Device whose stored weights age BETWEEN writes (drift/aging model).

    Wraps any in-process plant (composition: DAC quantization, write
    noise, ADC readout all keep applying through ``inner``).  After every
    committed write event the landed weights take one drift transition

        θ ← rest + a·(θ − rest) + σ_d·ξ(seed, leaf, step)

    with ``a = exp(−1/drift_tau)`` (``a = 1`` when ``drift_tau = 0``):

    * ``mode="walk"`` — Ornstein–Uhlenbeck random walk: per-step gaussian
      kicks of std ``drift_rate`` (σ_d), optionally mean-reverting toward
      ``rest`` when ``drift_tau`` is set.  ``drift_tau = 0`` is the pure
      random walk (free diffusion of the stored values).
    * ``mode="decay"`` — relaxation toward ``rest`` with time constant
      ``drift_tau`` write events (analog memory leakage / state decay);
      ``drift_rate`` may ride along as diffusion on top.

    The kick is keyed on (device seed, leaf index, step counter) — never
    on threaded RNG state — so a checkpointed/restarted run replays the
    IDENTICAL device trajectory (the same contract as ``NoisyPlant`` and
    ``SimulatedAnalogChip``).  Because the optimizer carries the landed
    tree, the walk accumulates naturally across steps, and MGD's online
    feedback measures cost at the drifted weights and re-trims from
    wherever the device actually is.  ``drift``/``age`` expose the bare
    transition so benchmarks can age a device with NO optimizer writes
    (the no-mitigation / scheduled-recalibration baselines in
    ``benchmarks/drift_aging.py``).

    External plants are rejected: their true weights live behind the host
    boundary, so drifting the trainer-side belief would age the wrong
    copy — use ``hardware.devices.DriftingAnalogChip`` behind
    ``ExternalPlant``/``ChipFarm`` for the chip-in-the-loop version.
    """

    def __init__(self, inner: Plant, *, mode: str = "walk",
                 drift_rate: float = 0.0, drift_tau: float = 0.0,
                 rest: float = 0.0, seed: int = 0,
                 meta: Optional[PlantMeta] = None):
        if not isinstance(inner, Plant):
            raise TypeError(f"inner must be a repro.hardware.Plant, got "
                            f"{type(inner).__name__}")
        if inner.meta.external:
            raise ValueError(
                "DriftingPlant cannot wrap an external plant — the device's "
                "stored weights live behind the host boundary; put the drift "
                "IN the device (hardware.devices.DriftingAnalogChip) instead")
        if mode not in ("walk", "decay"):
            raise ValueError(f"drift mode must be 'walk' or 'decay', "
                             f"got {mode!r}")
        if mode == "walk" and drift_rate <= 0.0:
            raise ValueError("mode='walk' needs drift_rate > 0 (σ_d, the "
                             "per-step random-walk std)")
        if mode == "decay" and drift_tau <= 0.0:
            raise ValueError("mode='decay' needs drift_tau > 0 (the "
                             "relaxation time constant, in write events)")
        self.inner = inner
        self.mode = mode
        self.drift_rate = float(drift_rate)
        self.drift_tau = float(drift_tau)
        self.rest = float(rest)
        self.seed = int(seed)
        self.probe_fn = inner.probe_fn
        self.meta = meta or dataclasses.replace(
            inner.meta, name=f"drifting-{inner.meta.name}", drift_mode=mode,
            drift_rate=self.drift_rate, drift_tau=self.drift_tau,
            drift_rest=self.rest)

    # -- the aging transition (public: benchmarks age devices write-free) ----
    def drift(self, params, step):
        """One drift transition of the stored weights, keyed on ``step``."""
        a = math.exp(-1.0 / self.drift_tau) if self.drift_tau else 1.0
        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for i, x in enumerate(leaves, start=1):
            y = x.astype(jnp.float32)
            if self.drift_tau:
                y = self.rest + a * (y - self.rest)
            if self.drift_rate:
                k = jax.random.fold_in(
                    jax.random.PRNGKey(self.seed + 313), i)
                k = jax.random.fold_in(k, step)
                y = y + self.drift_rate * jax.random.normal(
                    k, x.shape, jnp.float32)
            out.append(y.astype(x.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def age(self, params, start_step, n_steps: int):
        """``n_steps`` drift transitions with NO writes (a held device):
        steps ``start_step .. start_step + n_steps − 1``.  Jit/scan-safe."""
        return jax.lax.fori_loop(
            0, n_steps, lambda j, p: self.drift(p, start_step + j), params)

    # -- plant protocol: reads delegate (the carried tree IS the drifted
    # device state); writes land through the inner device, then age once --
    def write_params(self, params, *, step, prev=None):
        return self.drift(
            self.inner.write_params(params, step=step, prev=prev), step)

    def read_cost(self, params, batch, *, step, tag: int = 0):
        return self.inner.read_cost(params, batch, step=step, tag=tag)

    def read_cost_pair(self, params, theta, batch, *, step, tag: int = 0):
        return self.inner.read_cost_pair(params, theta, batch,
                                         step=step, tag=tag)

    def apply_perturbed(self, params, batch, probe, *, step, tags):
        inner = self.inner
        if self.probe_fn is not None and inner.probe_fn is not self.probe_fn:
            # a probe_fn attached to the wrapper (driver resolution) rides
            # down so the inner device's imperfections still apply
            inner = copy.copy(inner)
            inner.probe_fn = self.probe_fn
        return inner.apply_perturbed(params, batch, probe,
                                     step=step, tags=tags)


def plant_from_config(loss_fn, cfg, *, probe_fn=None) -> Plant:
    """The implicit device of an ``MGDConfig``: its historical
    ``cost_noise``/``update_noise`` fields become a ``NoisyPlant`` with
    the exact historical key derivation (σ = 0 → ``IdealPlant``)."""
    if getattr(cfg, "cost_noise", 0.0) or getattr(cfg, "update_noise", 0.0):
        return NoisyPlant(
            loss_fn,
            cost_noise=cfg.cost_noise,
            write_noise=getattr(cfg, "update_noise", 0.0),
            dtheta=cfg.dtheta,
            seed=cfg.seed,
            probe_fn=probe_fn,
        )
    return IdealPlant(loss_fn, probe_fn=probe_fn)
