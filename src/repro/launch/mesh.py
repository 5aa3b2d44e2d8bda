"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — required because the
dry-run overrides the host platform device count before first jax use.

Topology (TPU v5e-class pods):
    single-pod:  (16, 16)      axes ("data", "model")        — 256 chips
    multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

The "pod" axis is outer data parallelism by default; MGD re-purposes it as
the probe axis (core/probe_parallel.py) or a pipeline axis
(distributed/pipeline.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis Auto (jax's default is Explicit):
    the probe and pipeline steps shard by in/out specs, not by type."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_shapes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1-D "data" mesh (CPU tests/examples)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
