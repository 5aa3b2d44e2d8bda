"""Pallas TPU kernels for MGD compute hot-spots.

* ``perturbed_matmul``      — x @ (W + Δθ·θ̃) with the Rademacher signs
  generated in VMEM during the MXU matmul (θ̃ never exists in HBM).
* ``perturbed_matmul_pair`` — the antithetic probe pair
  (x₊ @ (W+θ̃), x₋ @ (W−θ̃)) in one grid pass: W is read from HBM ONCE per
  central-difference probe pair.
* ``perturbed_grouped_matmul`` — the same pair over a bank of experts
  [E, K, N]: each stream's rows laid out by expert, ragged groups in
  scalar prefetch, signs at each expert's offset in the bank
  (``perturbed_grouped_matmul_pair``; the dropless MoE layer's products).
* ``mgd_update``            — fused scalar-replay window update
  W −= (η/Δθ)·Σ_j C̃_j·θ̃_j, HBM traffic = one read + one write of W.
* ``mgd_update_window``     — the same update in the optimizer's exact
  sequential-axpy float order (bit-identical f32 trajectories; this is the
  variant ``MGDConfig(fused=True)`` consumes).
* ``mla_flash_attention``   — causal multi-head latent attention with the
  score blocks kept in VMEM (qk and v widths apart, one rope key shared
  by every head); expand-form MLA's attention.

``ops`` holds the jit'd dispatch wrappers (pallas / interpret / ref);
``ref`` holds the pure-jnp oracles that share the exact counter hash.
"""
from . import ops, ref
from .ops import (mgd_update, mgd_update_window, mla_flash_attention,
                  perturbed_grouped_matmul, perturbed_matmul,
                  perturbed_matmul_pair)

__all__ = ["ops", "ref", "perturbed_matmul", "perturbed_matmul_pair",
           "perturbed_grouped_matmul", "mgd_update", "mgd_update_window",
           "mla_flash_attention"]
