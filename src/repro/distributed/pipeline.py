"""Pipeline parallelism over the "pod" axis (GPipe-style, shard_map).

MGD's default use of the pod axis is data/probe parallelism (the scalar
feedback makes that nearly free), but very deep models may still want
pipeline stages.  This wrapper runs S stages over the "pod" mesh axis with
M microbatches using collective_permute between neighbours — forward-only
(MGD has no backward pass, so the classic GPipe bubble halves: fill is
S−1 microbatch-steps, no drain for gradients).

The schedule is the standard loop of (M + S − 1) ticks; device s computes
microbatch m = t − s when 0 ≤ t − s < M, then permutes its activation ring
one step toward stage s+1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, stage_params, x, *, mesh, axis="pod",
                     microbatches=None):
    """Run ``stage_fn(params_s, x)`` as a pipeline over ``axis``.

    stage_params: pytree stacked on a leading stage dim == mesh.shape[axis].
    x: [B, ...] global batch, split into ``microbatches`` chunks (default =
    number of stages).  Returns the final-stage outputs re-assembled.
    """
    n_stages = mesh.shape[axis]
    m = microbatches or n_stages
    b = x.shape[0]
    assert b % m == 0, (b, m)
    mb = b // m

    def run(params_local, x_local):
        # params_local: [1, ...] this stage's slice; x_local: [B/m? ...]
        params_s = jax.tree_util.tree_map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        # x_local holds this stage's shard of the microbatch queue:
        # stage 0 owns the real inputs; others start with zeros.
        queue = x_local  # [m_local_chunks, mb, ...] — here m chunks on stage0
        total = m + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            buf, out = carry
            # current microbatch for this stage: m_idx = t - s
            m_idx = t - s
            active = (m_idx >= 0) & (m_idx < m)
            cur = buf  # [mb, ...] activation arriving from the left
            y = stage_fn(params_s, cur)
            y = jnp.where(active, y, cur)
            # last stage writes outputs
            write_idx = jnp.clip(m_idx, 0, m - 1)
            is_last = s == n_stages - 1
            out = jax.lax.cond(
                active & is_last,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, y[None], write_idx, 0),
                lambda o: o, out)
            # rotate activations toward the next stage
            nxt = jax.lax.ppermute(y, axis, perm)
            # stage 0 injects the next microbatch from its local queue
            inject_idx = jnp.clip(t + 1, 0, m - 1)
            inj = jax.lax.dynamic_index_in_dim(queue, inject_idx, 0,
                                               keepdims=False)
            buf = jnp.where(s == 0, inj, nxt)
            return buf, out

        first = jax.lax.dynamic_index_in_dim(queue, 0, 0, keepdims=False)
        buf = jnp.where(s == 0, first, jnp.zeros_like(first))
        out0 = jnp.zeros((m,) + first.shape, first.dtype)
        _, outs = jax.lax.fori_loop(0, total, tick, (buf, out0))
        return outs[None]  # [1, m, mb, ...] — stacked over stages outside

    shard = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(axis), P()),   # params sharded by stage; x replicated
        out_specs=P(axis),         # per-stage outputs; last stage is real
        check_vma=False,
    )
    xq = x.reshape(m, mb, *x.shape[1:])
    outs = shard(stage_params, xq)          # [n_stages, m, mb, ...]
    return outs[-1].reshape(b, *x.shape[1:])
