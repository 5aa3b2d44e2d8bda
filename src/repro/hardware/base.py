"""The hardware plant abstraction — MGD's view of the device it trains.

The paper's central premise is that the optimizer treats the network as an
opaque *plant*: it may (1) write parameters, (2) present an input, and
(3) read back ONE scalar cost.  Everything else — activation defects,
write noise, DAC quantization, readout noise, even whether the "device"
is a JAX function or a physical chip across a process boundary — lives
behind this interface (McCaughan et al. 2023 §4/§6; Oripov et al. 2025
treat the device as a cost oracle throughout).

``Plant`` is the protocol the optimizer drives:

* ``write_params(params, *, step, prev=None)`` — commit a persistent
  parameter write; returns what actually *landed* on the device (ideal
  devices return the input unchanged; noisy/quantized devices do not).
  ``prev`` is the previously landed value, for slow-write modeling.
* ``read_cost(params, batch, *, step, tag)`` — transient probe write +
  cost readout.  ``tag`` disambiguates multiple reads at the same step so
  counter-keyed readout noise stays deterministic across restarts.
* ``read_cost_pair(params, theta, batch, *, step, tag)`` — antithetic
  probe C(θ+θ̃), C(θ−θ̃).  The default does two ``read_cost`` calls;
  devices with a cheaper paired readout (the Pallas pair kernel, a chip
  with differential probe lines) may override.
* ``apply_perturbed(params, batch, probe, *, step, tags)`` — the fused
  probe path: evaluate the model under θ ± θ̃ with the perturbation
  generated *at the parameter* (in-kernel / on-device), never
  materialized host-side.  Optional; ``supports_fused`` reports it.

Pure-JAX plants (Ideal/Noisy/Quantized) are traceable — the whole MGD
step jits/scans/shards exactly as before.  ``ExternalPlant`` lowers each
read to an ordered host callback instead (see ``external.py``).

``PlantMeta`` carries static device metadata (noise figures, DAC bits,
latencies) used by benchmarks to project wall-clock training time the way
the paper's Table 3 does.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax.numpy as jnp

from repro.core.utils import tree_add, tree_axpy

Pytree = Any


@dataclasses.dataclass(frozen=True)
class PlantMeta:
    """Static device metadata (hashable → safe to close over under jit)."""

    name: str = "ideal"
    cost_noise: float = 0.0          # σ_C, std of the cost readout noise
    write_noise: float = 0.0         # σ_θ, persistent-write noise in units of Δθ
    sigma_a: float = 0.0             # σ_a, static activation-defect scale
    weight_bits: Optional[int] = None  # DAC resolution of persistent writes
    adc_bits: Optional[int] = None     # ADC resolution of the cost readout
    write_latency_s: float = 0.0     # τ per persistent parameter write
    read_latency_s: float = 0.0      # τ per cost readout (≈ τ_p floor)
    external: bool = False           # True → host-callback / process boundary
    chips: int = 1                   # devices probed concurrently (chip farm)
    # drift/aging: the stored weights move BETWEEN writes (random walk per
    # step and/or relaxation toward a rest state) — the time-varying device
    # regime Oripov et al. 2025 flag as the open deployment question.
    drift_mode: Optional[str] = None  # walk | decay | None (stable device)
    drift_rate: float = 0.0          # σ_d, per-step random-walk std
    drift_tau: float = 0.0           # relaxation τ toward drift_rest (steps)
    drift_rest: float = 0.0          # rest value the weights decay toward
    # True → the host boundary is armed with a FaultPolicy (timeouts,
    # retries, per-chip masking); see hardware.faults.
    fault_tolerant: bool = False

    def step_latency_s(self, reads_per_step: int = 2,
                       writes_per_step: int = 1, *,
                       differential: bool = False,
                       pipelined: bool = False) -> float:
        """Projected seconds per MGD iteration on this device (Table 3
        style: reads dominate; one amortized persistent write per τ_θ).
        ``reads_per_step``/``writes_per_step`` count PER-CHIP operations:
        a k-chip farm issues its k probe pairs concurrently, so the
        wall-clock per step is one chip's latency while the C̃-estimator
        variance drops ∝ 1/k (benchmarks/farm_scaling.py).

        ``differential=True`` prices a differential probe line
        (``measure_pair``): the antithetic pair C(θ+θ̃), C(θ−θ̃) resolves
        in ONE readout conversion — the ±θ̃ branches settle concurrently
        and the ADC digitizes their difference — so the pair costs one
        ``read_latency_s`` instead of two.

        ``pipelined=True`` prices the double-buffered farm schedule
        (``ChipFarm(pipeline=True)``): step N+1's parameter write
        overlaps step N's readout, so a step pays
        ``max(read_time, write_time)`` instead of their sum — the device
        is never idle waiting on the other phase."""
        reads = reads_per_step * (0.5 if differential else 1.0)
        read_time = reads * self.read_latency_s
        write_time = writes_per_step * self.write_latency_s
        if pipelined:
            return max(read_time, write_time)
        return read_time + write_time


class Plant:
    """Base plant: ideal pass-through semantics; subclasses override the
    pieces their hardware model perturbs.  See the module docstring for
    the contract."""

    meta: PlantMeta = PlantMeta()
    probe_fn: Optional[Callable] = None

    # -- persistent writes --------------------------------------------------
    def write_params(self, params: Pytree, *, step, prev: Optional[Pytree] = None
                     ) -> Pytree:
        """Commit ``params`` to the device; return what actually landed."""
        return params

    # -- transient probe write + scalar readout -----------------------------
    def read_cost(self, params: Pytree, batch, *, step, tag: int = 0):
        raise NotImplementedError

    def read_cost_pair(self, params: Pytree, theta: Pytree, batch, *,
                       step, tag: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Antithetic readout (C(θ+θ̃), C(θ−θ̃)).  The default issues two
        independent reads with consecutive tags — bit-identical to the
        historical inlined central-difference path.  Devices with a
        cheaper paired readout override: the Pallas pair kernel reads
        each W tile once, and external devices with a differential probe
        line (``measure_pair``) write the base θ once per pair instead
        of two full perturbed trees (see ``external.py``)."""
        c_plus = self.read_cost(tree_add(params, theta), batch,
                                step=step, tag=tag)
        c_minus = self.read_cost(tree_axpy(-1.0, theta, params), batch,
                                 step=step, tag=tag + 1)
        return c_plus, c_minus

    # -- fused probe path ---------------------------------------------------
    @property
    def supports_fused(self) -> bool:
        return self.probe_fn is not None

    def apply_perturbed(self, params: Pytree, batch, probe, *, step, tags):
        """Evaluate costs under θ ± θ̃ with θ̃ generated at the parameter
        (Pallas kernels for in-process plants).  Returns ``(costs, aux)``:
        a [len(tags)] array of costs, one per sign in ``probe.ctx.signs``,
        and a dict of what the model's probe_fn counts beside them (empty
        unless the probe_fn returns ``(costs, aux)`` itself)."""
        if self.probe_fn is None:
            raise NotImplementedError(
                f"{type(self).__name__} has no perturbed-apply interface "
                "(construct it with probe_fn=... for the fused path)")
        out = self.probe_fn(params, batch, probe)
        return out if isinstance(out, tuple) else (out, {})


class IdealPlant(Plant):
    """Pure-JAX device: bit-identical (f32) to the historical in-process
    path — ``read_cost`` IS the loss function, writes land exactly."""

    def __init__(self, loss_fn: Callable, *, probe_fn: Optional[Callable] = None,
                 meta: Optional[PlantMeta] = None):
        self.loss_fn = loss_fn
        self.probe_fn = probe_fn
        self.meta = meta or PlantMeta(name="ideal")

    def read_cost(self, params, batch, *, step, tag: int = 0):
        return self.loss_fn(params, batch)
