"""Pod-local probe parallelism — MGD's native way to use a multi-pod fleet.

Plain data parallelism under MGD would psum the per-pod costs into one
global C̃ and pair it with one global perturbation.  Instead, each pod k
draws its OWN perturbation θ̃_k and evaluates its OWN data shard, giving k
independent (C̃_k, θ̃_k) probe pairs per step:

    update = −η · (1/k) Σ_k C̃_k · θ̃_k / Δθ²

* Unbiased: E[C̃_k·θ̃_k/Δθ²] = ∇L_k, so the average estimates ∇(mean_k L_k)
  — the same target as synchronous DP.
* k× probe-variance reduction at ZERO extra forward FLOPs versus DP (each
  pod was computing its shard anyway).  This axis exists only because MGD
  is forward-only; backprop has no analogue.
* Cross-pod traffic: ONE all-gather of k f32 scalars per step.  Every pod
  then regenerates all k sign-trees locally (counter hash, elementwise,
  ≪ matmul FLOPs) and applies the identical update, keeping parameters
  bit-replicated across pods with no parameter collective at all.

Implemented as one shard_map over the whole mesh.  The probe axis is
always manual (each slice IS a distinct probe); the other axes join the
manual set exactly when the caller's specs mention them:

* ``data_axis=`` shards each pod's batch further over a data axis and
  pmean-combines the per-device costs into the pod's C̃ — plain data
  parallelism *inside* each probe.
* ``param_specs=`` places parameters via ``distributed/sharding.py``
  logical rules (or an explicit spec pytree), so each device holds only
  its model/fsdp shard and the Pallas kernels run on per-device shards.
  A sharded ``loss_fn`` must be shard-aware (psum its own collectives) —
  shard_map runs it manual over every axis the specs mention.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import perturbations as pert
from .mgd import MGDConfig
from .utils import leaf_meta, tree_axpy


def pod_seed(seed, k):
    """Probe seed of pod/chip ``k``: distinct, deterministic, uint32.
    ONE definition — the mesh and external drivers' bit-equality (a farm
    of ideal chips walks a k-pod mesh's trajectory) hangs on both using
    the same derivation.  ``k`` may be traced (lax.axis_index /
    fori_loop counter)."""
    return (jnp.uint32(seed)
            + jnp.asarray(k, jnp.uint32) * jnp.uint32(0x9E3779B9))


def _is_spec_rules(specs) -> bool:
    """True when ``specs`` is an ordered (regex, logical-names) rules list
    (the ``distributed.sharding.param_specs`` input) rather than a spec
    pytree."""
    if not isinstance(specs, (list, tuple)) or not specs:
        return False
    return all(
        isinstance(r, (list, tuple)) and len(r) == 2 and isinstance(r[0], str)
        and not isinstance(r, P) for r in specs)


def _spec_axes(spec_tree) -> set:
    """Every mesh axis a spec pytree mentions."""
    axes: set = set()
    leaves = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    for spec in leaves:
        if not isinstance(spec, P):
            continue
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                axes.update(entry)
            else:
                axes.add(entry)
    return axes


def build_probe_parallel_step(
    loss_fn: Callable,
    cfg: MGDConfig,
    mesh,
    *,
    probe_axis: str = "pod",
    data_axis=None,
    param_specs=None,
    batch_specs=None,
    plant=None,
    probe_fn=None,
):
    """Build step_fn(params, step, batch) → (params, metrics) — the
    registry's probe-parallel builder (``repro.driver("probe_parallel",
    cfg, loss_fn, mesh=mesh)`` wraps this behind the uniform contract).

    central-difference, τ_θ = 1 (immediate update) — the at-scale serving
    configuration.  ``mesh`` may be multi-axis: the ``probe_axis`` slices
    are the k probes; ``data_axis=`` additionally shards each pod's batch
    and pmean-combines the per-device costs into the pod's C̃;
    ``param_specs=`` (a PartitionSpec pytree, or an ordered
    (regex, logical-names) rules list resolved through
    ``distributed.sharding.param_specs``) places parameter shards so the
    kernels run per-device — the loss_fn must then be shard-aware.
    ``batch_specs`` overrides the batch placement (default: leading dim
    over ``probe_axis`` [× ``data_axis``]).  On a 1-D pod mesh with
    default specs the trajectory is bit-identical (f32) to the historical
    single-axis builder.

    With ``cfg.fused=True`` the probe evaluates through
    ``probe_fn(params, batch, probe)`` (the Pallas perturbed-matmul path —
    θ̃ never exists in HBM) and the update regenerates all k sign-trees
    inside ``kernels.mgd_update_window`` per ndim≥2 leaf: one read-W +
    write-W regardless of k.  Bit-identical (f32) to the materializing
    pod loop.

    Cost reads and the parameter write go through a ``hardware.Plant``
    (implicit ideal/noisy device when ``plant=None``), so every pod may be
    its own imperfect chip: readout-noise tags are keyed per (step, pod),
    and the post-all-gather write lands through the plant once per step.
    Pure-JAX plants only — the probe loop runs inside ``shard_map``.
    """
    if cfg.mode != "central":
        raise ValueError(
            f"probe-parallel uses central differences (its per-pod probe "
            f"shares no C₀ memory); got mode={cfg.mode!r} — set "
            f'mode="central"')
    if probe_axis not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} have no probe axis "
            f"{probe_axis!r} — name one axis of the mesh after the probe "
            f"dimension (or pass probe_axis=)")
    if data_axis is not None:
        if data_axis == probe_axis:
            raise ValueError(
                f"data_axis={data_axis!r} IS the probe axis — each pod "
                f"already gets its own batch shard along it; a data axis "
                f"shards *within* a pod")
        if data_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {tuple(mesh.axis_names)} have no data axis "
                f"{data_axis!r}")
    from repro.core.mgd import _resolve_plant
    plant = _resolve_plant(loss_fn, cfg, probe_fn=probe_fn, plant=plant)
    if plant.meta.external:
        raise ValueError("probe-parallel drives pure-JAX plants; an "
                         "ExternalPlant cannot run inside shard_map — "
                         "use repro.driver('probe_parallel_external', cfg, "
                         "plant=ChipFarm(...)) for k chips behind a host "
                         "boundary")
    if cfg.fused:
        if not plant.supports_fused:
            raise ValueError("cfg.fused=True needs a probe_fn (the model's "
                             "perturbed-apply interface) on the plant")
        if cfg.tau_theta != 1 or cfg.replay:
            raise ValueError("fused probe-parallel updates every step "
                             "(tau_theta=1, no replay)")
    n_pods = mesh.shape[probe_axis]
    inv_d2 = 1.0 / (cfg.dtheta * cfg.dtheta)
    # same rounding pin as core.mgd: keep the written float association in
    # every program so fused and materializing paths agree bitwise
    _pin = jax.lax.optimization_barrier

    param_rules = None
    if param_specs is not None and _is_spec_rules(param_specs):
        param_rules = list(param_specs)
        param_specs = None
    if batch_specs is None:
        batch_specs = (P(probe_axis) if data_axis is None
                       else P((probe_axis, data_axis)))

    def fused_pod_update(params, step, all_c):
        """All k pod windows through the fused kernel: ndim≥2 leaves pay
        read-W + write-W once regardless of k (signs regenerate against
        the resident tile); O(d) leaves materialize in a fori_loop that
        mirrors the pod loop's float association exactly."""
        from repro.kernels import ops as kops
        seeds = pod_seed(cfg.seed, jnp.arange(n_pods))            # [k]
        coefs = _pin(jnp.float32(-cfg.eta * inv_d2) * all_c
                     / jnp.float32(n_pods))

        def small(leaf, lid):
            def body(k, lf):
                theta = pert.rademacher_leaf(
                    lf.shape, lf.dtype, lid, step=step,
                    seed=pod_seed(cfg.seed, k), dtheta=cfg.dtheta,
                    tau_p=cfg.tau_p)
                return (lf.astype(jnp.float32)
                        + coefs[k] * theta.astype(jnp.float32)
                        ).astype(lf.dtype)
            return jax.lax.fori_loop(0, n_pods, body, leaf)

        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for (lid, _, _), leaf in zip(leaf_meta(params), leaves):
            if leaf.ndim >= 2:
                lseeds = pert.leaf_seed(
                    seeds, jnp.asarray(step, jnp.int32) // jnp.int32(cfg.tau_p),
                    lid)
                out.append(kops.mgd_update_window(
                    leaf, lseeds, coefs, alpha=1.0, dtheta=cfg.dtheta,
                    impl=cfg.kernel_impl))
            else:
                out.append(small(leaf, lid))
        return jax.tree_util.tree_unflatten(treedef, out)

    def run(params, step, batch):
        pod = jax.lax.axis_index(probe_axis)
        if cfg.fused:
            probe = pert.Probe(
                step, pod_seed(cfg.seed, pod),
                pert.ProbeCtx(signs=(1.0, -1.0), dtheta=cfg.dtheta,
                              tau_p=cfg.tau_p, impl=cfg.kernel_impl))
            costs, _ = plant.apply_perturbed(
                params, batch, probe, step=step, tags=(2 * pod, 2 * pod + 1))
            c_plus, c_minus = costs[0], costs[1]
        else:
            theta = pert.generate(
                params, ptype=cfg.ptype, step=step,
                seed=pod_seed(cfg.seed, pod),
                dtheta=cfg.dtheta, tau_p=cfg.tau_p)
            c_plus, c_minus = plant.read_cost_pair(
                params, theta, batch, step=step, tag=2 * pod)
        if data_axis is not None:
            # plain DP inside the pod: the pod's C is the mean over its
            # data-axis devices' shard costs (one scalar psum per read)
            c_plus = jax.lax.pmean(c_plus, data_axis)
            c_minus = jax.lax.pmean(c_minus, data_axis)
        c_local = (0.5 * (c_plus - c_minus)).astype(jnp.float32)
        all_c = jax.lax.all_gather(c_local, probe_axis)        # [k] scalars

        if cfg.fused:
            updated = fused_pod_update(params, step, all_c)
        else:
            def body(k, p):
                signs = pert.generate(
                    p, ptype=cfg.ptype, step=step, seed=pod_seed(cfg.seed, k),
                    dtheta=cfg.dtheta, tau_p=cfg.tau_p)
                # pinned to the written association — the fused kernel path
                # computes the identical coefficient vector, and XLA must
                # not re-fold the constants differently in either program
                coef = _pin(jnp.float32(-cfg.eta * inv_d2) * all_c[k]
                            / jnp.float32(n_pods))
                return tree_axpy(coef, signs, p)

            updated = jax.lax.fori_loop(0, n_pods, body, params)
        new_params = plant.write_params(updated, step=step, prev=params)
        cost = 0.5 * (c_plus + c_minus)
        return new_params, {"cost": cost.astype(jnp.float32),
                            "c_tilde_mean": jnp.mean(jnp.abs(all_c))}

    def _wrap(pspec_tree):
        manual = {probe_axis} | _spec_axes(pspec_tree) | _spec_axes(batch_specs)
        if data_axis is not None:
            manual.add(data_axis)
        shard = jax.shard_map(
            run, mesh=mesh,
            in_specs=(pspec_tree, P(), batch_specs),
            out_specs=(pspec_tree, P()),
            axis_names=frozenset(manual), check_vma=False,
        )

        @jax.jit
        def stepper(params, step, batch):
            return shard(params, jnp.asarray(step, jnp.int32), batch)

        return stepper

    if param_rules is None:
        fixed = _wrap(P() if param_specs is None else param_specs)

        def step_fn(params, step, batch):
            return fixed(params, step, batch)

        return step_fn

    # rules need the params *shapes* — resolve lazily on first call and
    # cache per (structure, shapes); jit inside recompiles on the same key
    built = {}

    def step_fn(params, step, batch):
        from repro.distributed.sharding import param_specs as resolve_specs
        key = (jax.tree_util.tree_structure(params),
               tuple(tuple(leaf.shape)
                     for leaf in jax.tree_util.tree_leaves(params)))
        try:
            stepper = built[key]
        except KeyError:
            stepper = built[key] = _wrap(
                resolve_specs(params, param_rules, mesh))
        return stepper(params, step, batch)

    return step_fn


def _mad_chip_mask(costs, valid, threshold):
    """Robust outlier rejection over the 2k gathered cost scalars:
    median-absolute-deviation gate, computed over VALID chips' readouts
    only (invalid entries are NaN-ed out of the medians).  A chip is
    kept when BOTH of its pair scalars sit within ``threshold`` robust
    standard deviations of the median — a spiked-but-finite C₊ raises no
    exception at the host boundary; only the statistics can reject it.
    The MAD floor guards the degenerate all-equal case (MAD = 0)."""
    flat = costs.reshape(-1)
    vmask = jnp.repeat(valid, 2)
    x = jnp.where(vmask, flat, jnp.nan)
    med = jnp.nanmedian(x)
    mad = jnp.nanmedian(jnp.abs(x - med))
    scale = jnp.maximum(jnp.float32(1.4826) * mad,
                        1e-6 * jnp.maximum(jnp.abs(med), 1.0))
    ok = jnp.abs(flat - med) <= threshold * scale
    return jnp.logical_and(valid, jnp.all(ok.reshape(-1, 2), axis=1))


def _trimmed_chip_mask(c_tilde, valid, trim_frac):
    """Symmetric trimmed mean as a mask: drop the ⌊trim_frac·k_valid⌋
    largest and smallest C̃ values among the valid chips.  Rank-based
    (argsort + inverse permutation), so it stays static-shape under jit;
    invalid chips sort to the top (+inf key) and are excluded by the
    ``ranks < n_valid − t`` cut as well as the final AND."""
    k = c_tilde.shape[0]
    n_valid = jnp.sum(valid.astype(jnp.int32))
    t = jnp.floor(trim_frac * n_valid.astype(jnp.float32)).astype(jnp.int32)
    key = jnp.where(valid, c_tilde, jnp.inf)
    order = jnp.argsort(key)
    ranks = jnp.zeros(k, jnp.int32).at[order].set(
        jnp.arange(k, dtype=jnp.int32))
    keep = jnp.logical_and(ranks >= t, ranks < n_valid - t)
    return jnp.logical_and(valid, keep)


def build_probe_parallel_external_step(
    cfg: MGDConfig,
    farm,
):
    """Build step_fn(params, step, batch) → (params, metrics) — the
    registry's ``probe_parallel_external`` builder: the SAME averaged
    update as ``build_probe_parallel_step``,

        θ ← θ − η · (1/k) Σ_k C̃_k · θ̃_k / Δθ²,

    but the k central-difference probes fan out to k EXTERNAL chips over
    the host boundary (``hardware.farm.ChipFarm``: one ordered
    ``io_callback`` per step gathers all 2k scalars, the chips evaluate
    concurrently on the farm's execution backend — per-chip runner
    threads, worker processes, or a cluster transport; see
    ``hardware/backend/``) instead of k shard_map mesh slices — the
    paper §6 "farm of imperfect chips" picture.  This builder is
    backend-agnostic BY CONSTRUCTION: it sees only
    ``farm.read_cost_pairs`` / ``farm.write_params``, and device noise
    is counter-keyed, so serial, thread and process farms (pipelined or
    not) walk the bit-identical trajectory.  All k sign-trees
    are then regenerated locally (counter hash) and the update applied
    with the identical float association as the mesh driver, so a farm
    of k ideal chips and a k-pod mesh walk the same trajectory.

    Chip k's probe seed is ``pod_seed(k)`` — the mesh driver's formula —
    and its readout tags are (2k, 2k+1), so counter-keyed device noise
    distinguishes every read and restarts replay deterministically.

    **Fault masking / η-rescaling** (armed when the farm carries a
    ``FaultPolicy``; the policy is read ONCE at build time, so the clean
    path compiles to the historical minimal graph): the farm's
    ``valid[k]`` mask — further tightened by a traced finiteness check
    and the policy's robust aggregation mode (``"mad"`` /
    ``"trimmed"``) — zeroes rejected chips' C̃_k while the per-chip
    coefficient ``−η/(k·Δθ²)`` stays UNCHANGED.  Because η is tuned ∝ k,
    dropping a chip's term at fixed η/k IS the "rescale η by the live
    chip count" rule: the surviving chips apply exactly the
    (η·k_live/k)-scaled masked average, degrading the step size
    gracefully instead of corrupting the direction.  With every chip
    valid, ``where(True, C̃, 0) ≡ C̃`` bitwise — the fault-tolerant
    trajectory is bit-identical to the historical one.  Aux gains
    ``n_valid`` (chips that answered with finite costs) and ``n_used``
    (chips surviving robust aggregation).
    """
    from repro.hardware.farm import ChipFarm
    if not isinstance(farm, ChipFarm):
        raise TypeError(
            f"probe_parallel_external needs a hardware.farm.ChipFarm "
            f"(k external chips behind one host boundary); got "
            f"{type(farm).__name__}")
    if cfg.mode != "central":
        raise ValueError(
            f"probe-parallel uses central differences (its per-chip probe "
            f"shares no C₀ memory); got mode={cfg.mode!r} — set "
            f'mode="central"')
    n_chips = farm.n_chips
    inv_d2 = 1.0 / (cfg.dtheta * cfg.dtheta)
    _pin = jax.lax.optimization_barrier
    # static at build time: a frozen FaultPolicy (or None) — the traced
    # masking/aggregation branch is selected here, not per step
    policy = getattr(farm, "policy", None)

    @jax.jit
    def step_fn(params, step, batch):
        step = jnp.asarray(step, jnp.int32)
        thetas = [pert.generate(
            params, ptype=cfg.ptype, step=step, seed=pod_seed(cfg.seed, k),
            dtheta=cfg.dtheta, tau_p=cfg.tau_p) for k in range(n_chips)]
        costs, valid = farm.read_cost_pairs(params, thetas, batch,
                                            step=step)    # [k, 2], [k]
        c_raw = (0.5 * (costs[:, 0] - costs[:, 1])).astype(jnp.float32)
        if policy is None:
            all_c = c_raw
            aux_cost = jnp.mean(0.5 * (costs[:, 0] + costs[:, 1]))
            aux = {"cost": aux_cost.astype(jnp.float32),
                   "c_tilde_mean": jnp.mean(jnp.abs(all_c))}
        else:
            # belt-and-braces: the host masks non-finite readouts already,
            # but a masked chip's placeholder is NaN by construction —
            # never let it through the arithmetic
            valid = jnp.logical_and(valid,
                                    jnp.all(jnp.isfinite(costs), axis=1))
            if policy.aggregate == "mad":
                used = _mad_chip_mask(costs, valid,
                                      jnp.float32(policy.mad_threshold))
            elif policy.aggregate == "trimmed":
                used = _trimmed_chip_mask(c_raw, valid,
                                          jnp.float32(policy.trim_frac))
            else:
                used = valid
            all_c = jnp.where(used, c_raw, jnp.float32(0.0))
            n_valid = jnp.sum(valid.astype(jnp.int32))
            n_used = jnp.sum(used.astype(jnp.int32))
            denom = jnp.maximum(n_used, 1).astype(jnp.float32)
            aux_cost = jnp.sum(jnp.where(
                used, 0.5 * (costs[:, 0] + costs[:, 1]), 0.0)) / denom
            aux = {"cost": aux_cost.astype(jnp.float32),
                   "c_tilde_mean": jnp.sum(jnp.abs(all_c)) / denom,
                   "n_valid": n_valid, "n_used": n_used}

        def body(k, p):
            signs = pert.generate(
                p, ptype=cfg.ptype, step=step, seed=pod_seed(cfg.seed, k),
                dtheta=cfg.dtheta, tau_p=cfg.tau_p)
            # same pinned association as the mesh driver — the k-chip farm
            # ≡ k-pod mesh bit-equality law includes the coefficient
            coef = _pin(jnp.float32(-cfg.eta * inv_d2) * all_c[k]
                        / jnp.float32(n_chips))
            return tree_axpy(coef, signs, p)

        new_params = farm.write_params(
            jax.lax.fori_loop(0, n_chips, body, params),
            step=step, prev=params)
        return new_params, aux

    return step_fn


def make_probe_parallel_step(*args, **kwargs):
    """RETIRED (PR 3 deprecation shim, removed PR 10)."""
    raise RuntimeError(
        "make_probe_parallel_step was retired; build the algorithm through "
        "the registry: repro.driver('probe_parallel', cfg, loss_fn, "
        "mesh=mesh).step — or build_probe_parallel_step for the raw "
        "(params, step, batch) contract")
