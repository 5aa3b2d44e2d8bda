"""Does what the timed path produced agree with the plain reference?

The reference replays the run's first ``K`` steps from the same seeds:
the same weights (drawn by its own init), the same rows (``textgen``),
the same perturbation hashes, in float32 at ``HIGHEST``.  Three numbers
are compared, each against its limit in ``limits/<cell>.json``:

* ``loss_gap_nats``: the largest gap between the program's reported cost
  (c⁺ + c⁻)/2 and the reference's, over the steps among the first ``K``
  whose cost the loop hands back (the last step of each chunk).
* ``grad_gap_nats``: at every one of the first ``K`` steps, the gradient
  the optimizer got is C̃_n·s_n/Δθ, so every leaf's gradient norm is
  |C̃_n|·√size/Δθ.  C̃_n is read back from the weights after ``K`` steps:
  the projection of θ_K − θ_0 on the step's sign vector s_n, over all
  leaves, is −(η/Δθ)·C̃_n per element (less what bfloat16 rounding
  drops, the same on both sides); the other steps' signs are orthogonal
  to it to one part in √size.  The number is the largest gap between the
  program's C̃_n and the reference's, in nats of cost, signed.  It is not
  taken relative to |C̃_n|: C̃ is one random projection of the gradient
  and lies near 0 on some steps.
* ``change_gap``: after ``K`` steps, per leaf, the gap between the norms
  of the parameters' change θ_K − θ_0 of the program and of the
  reference, over the larger of the reference's norm of that leaf and of
  the median leaf; the worst leaf counts.  A leaf whose reference
  gradient norm is under a thousandth of the median leaf's is left out.

A traffic mix with ``pods`` k > 1 runs probe-parallel MGD over a mesh of
k chips: pod i takes rows [i·b/k, (i+1)·b/k) of every step's batch of b
rows, probes with its own seed (``mgd.pod_seed``) and every pod applies
all k C̃_i, each divided by k.  The reference replays every pod.  The
program's loop reports the cost of pod 0 (``REPORTED_POD``: the mesh
step's cost leaves its ``shard_map`` as one replicated value, and a
replicated value is read from the mesh's first device), so
``loss_gap_nats`` compares that pod's; ``grad_gap_nats`` compares every
pod's C̃_i at every step, read back by projecting θ_K − θ_0 on that pod's
signs, where it is −(η/Δθ)·C̃_i/k per element; ``change_gap`` is as
above.  Without ``pods``, k = 1.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import textgen
from .references import mgd

REPORTED_POD = 0


def pods(traffic: dict) -> int:
    """Pods of the probe-parallel mesh, one per chip; 1 without a mesh."""
    return int(traffic.get("pods", 1))


def check_steps(traffic: dict) -> int:
    """Steps the reference follows: the first whole chunks that reach
    three steps (the loop hands back state only at a chunk's end)."""
    chunk = int(traffic["chunk"])
    return chunk * -(-3 // chunk)


def observed_steps(traffic: dict) -> list:
    """0-based steps among the first ``K`` whose cost the loop reports."""
    chunk, k = int(traffic["chunk"]), check_steps(traffic)
    return [n - 1 for n in range(chunk, k + 1, chunk)]


@dataclasses.dataclass
class Run:
    """What one side (program or reference) produced over the first K
    steps: the reported cost at the observed steps, the C̃ of each step
    and pod that the update applied, read back from the weights, and the
    per-leaf norm of θ_K − θ_0."""
    cost: dict          # step -> cost of the reported pod
    applied: list       # C̃ of step n, pod i at n·k + i, n < K, in nats
    change: dict        # leaf path -> ‖θ_K − θ_0‖


@jax.jit
def _change_stats(leaf, theta0, lseeds):
    """‖θ − θ_0‖ and Σ_i (θ − θ_0)_i·s_n,i for each leaf seed in ``lseeds``."""
    d = leaf.astype(jnp.float32) - theta0.astype(jnp.float32)
    idx = jax.lax.iota(jnp.uint32, leaf.size).reshape(leaf.shape)
    proj = jnp.stack([jnp.sum(d * mgd.signs(lseeds[n], idx))
                      for n in range(lseeds.shape[0])])
    return jnp.sqrt(jnp.sum(d * d)), proj


def change_stats(ref_mod, arch, params: dict, seeds: dict,
                 traffic: dict) -> tuple:
    """(per-leaf ‖θ_K − θ_0‖, [C̃ of step n, pod i for n < K, i < k]) of
    weights after the first K steps; θ_0 is drawn again from the seed one
    leaf at a time."""
    steps, k = check_steps(traffic), pods(traffic)
    probe_seeds = [mgd.pod_seed(seeds["mgd"], i) for i in range(k)]
    ids = ref_mod.leaf_ids(arch)
    norms, proj, size = {}, np.zeros(steps * k), 0
    for path, leaf in params.items():
        theta0 = ref_mod.init_leaf(arch, path, seeds["init"])
        lseeds = jnp.stack([mgd.leaf_seed(s, n, ids[path])
                            for n in range(steps) for s in probe_seeds])
        norm, p = _change_stats(leaf, theta0, lseeds)
        norms[path] = float(norm)
        proj += np.asarray(p, np.float64)
        size += leaf.size
    scale = -float(traffic["dtheta"]) / float(traffic["eta"]) * k / size
    return norms, [float(x) for x in proj * scale]


def follow(cell, seeds, *, compute="float32", token_frac=1.0,
           drop_pod=None) -> Run:
    """The reference's own run of the first K steps, every pod in turn.

    ``compute``, ``token_frac`` and ``drop_pod`` (a pod whose C̃ the update
    leaves out) turn it into the control or a planted fault."""
    from .bench import reference
    ref = reference(cell.config)
    arch = ref.Arch.from_config(cell.config)
    t = cell.traffic
    dtheta, eta = float(t["dtheta"]), float(t["eta"])
    k = pods(t)
    rows = int(t["batch"]) // k
    probe_seeds = [mgd.pod_seed(seeds["mgd"], i) for i in range(k)]
    ids = ref.leaf_ids(arch)
    params = ref.init(arch, seeds["init"])
    cost = {}
    for n in range(check_steps(t)):
        b = textgen.batch(seeds["data"], n, int(t["batch"]), int(t["seq_len"]),
                          arch.vocab)
        c_tildes = []
        for i, probe_seed in enumerate(probe_seeds):
            own = slice(i * rows, (i + 1) * rows)
            cp, cm = (float(c) for c in ref.costs(
                arch, params, b["tokens"][own], b["labels"][own], probe_seed,
                n, dtheta=dtheta, compute=compute, token_frac=token_frac))
            if i == REPORTED_POD:
                cost[n] = 0.5 * (cp + cm)
            c_tildes.append(0.0 if i == drop_pod else 0.5 * (cp - cm))
        params = mgd.update(params, ids, probe_seeds, n, c_tildes,
                            eta=eta, dtheta=dtheta)
    change, applied = change_stats(ref, arch, params, seeds, t)
    return Run(cost, applied, change)


def numbers(cell, program: Run, reference: Run) -> dict:
    """The compared numbers, each as {"value", "limit"}."""
    obs = observed_steps(cell.traffic)
    loss = max(abs(program.cost[n] - reference.cost[n]) for n in obs)
    grad = max(abs(p - r) for p, r in zip(program.applied, reference.applied))
    # every leaf's reference gradient norm is |C̃|·√size/Δθ
    from .bench import reference as ref_mod
    ref = ref_mod(cell.config)
    sizes = {p: float(np.sqrt(np.prod(shape))) for p, shape, _ in
             ref.leaves(ref.Arch.from_config(cell.config))}
    med_size = float(np.median(list(sizes.values())))
    kept = [p for p in reference.change if sizes[p] >= 1e-3 * med_size]
    med = float(np.median([reference.change[p] for p in kept]))
    change = max(abs(program.change[p] - reference.change[p])
                 / max(reference.change[p], med) for p in kept)
    vals = {"loss_gap_nats": loss, "grad_gap_nats": grad, "change_gap": change}
    return {k: {"value": v, "limit": cell.limits[k]} for k, v in vals.items()}


def passed(nums: dict) -> bool:
    """Every number with a limit within it; a limit of None marks a number
    that is reported but not compared (it has no upper reading)."""
    return all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in nums.values() if v["limit"] is not None)
