"""Microbatch pipeline parallelism over a `pipe` mesh axis (DESIGN.md §Dist).

GPipe-style schedule inside one shard_map: stage s holds its own slice of
the stacked stage params; at tick t it runs microbatch t-s (when valid) and
hands its activation to stage s+1 via a single ring `ppermute` — the only
collective in the loop. A run of M microbatches over S stages takes
M + S - 1 ticks with the familiar (S-1)/(M+S-1) bubble.

`sequential_reference` is the semantics oracle: composing the stages in
order over all microbatches must match `pipeline_apply` bit-for-bit modulo
collective reassociation (tested on a forced 4-device host mesh).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def sequential_reference(stage_fn, params, x):
    """Compose the S stages in order on the full (M, Bm, ...) batch."""
    n_stages = jax.tree.leaves(params)[0].shape[0]
    for s in range(n_stages):
        x = stage_fn(jax.tree.map(lambda t: t[s], params), x)
    return x


def pipeline_apply(mesh: Mesh, stage_fn, params, x, *, axis: str = "pipe"):
    """Run `stage_fn` as an S-stage pipeline over microbatches.

    params: pytree with a leading stage dim of size mesh.shape[axis] on every
    leaf; x: (M, Bm, ...) microbatched input. Stages must preserve the
    microbatch shape (residual-stream style), as each stage's output is the
    next stage's input. Returns (M, Bm, ...) outputs, replicated.
    """
    n_stages = mesh.shape[axis]
    n_mb = x.shape[0]
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(p_stage, x_full):
        p = jax.tree.map(lambda t: jnp.squeeze(t, 0), p_stage)
        s = jax.lax.axis_index(axis)
        last = n_stages - 1

        def tick(t, carry):
            state, out_buf = carry
            # stage 0 ingests microbatch t; later stages consume the rotated
            # activation (microbatch t - s, pipelined in from stage s-1)
            feed = x_full[jnp.minimum(t, n_mb - 1)]
            out = stage_fn(p, jnp.where(s == 0, feed, state))
            # stage S-1 retires microbatch t - (S-1) once it is valid
            m_out = t - last
            write = jnp.logical_and(s == last, m_out >= 0)
            slot = jnp.clip(m_out, 0, n_mb - 1)
            out_buf = out_buf.at[slot].add(jnp.where(write, out, 0))
            # reprolint: disable=COL001 -- one ring ppermute per tick IS the
            # GPipe schedule: stage s hands microbatch t to stage s+1 each
            # step; there is nothing to hoist (audited in PR 1, DESIGN.md §4)
            state = jax.lax.ppermute(out, axis, ring)
            return state, out_buf

        init = (jnp.zeros(x_full.shape[1:], x_full.dtype),
                jnp.zeros(x_full.shape, x_full.dtype))
        _, out_buf = jax.lax.fori_loop(0, n_mb + last, tick, init)
        # only the last stage wrote anything; psum replicates the result
        return jax.lax.psum(out_buf, axis)

    param_specs = jax.tree.map(
        lambda t: P(axis, *([None] * (t.ndim - 1))), params)
    return shard_map(local, mesh=mesh, in_specs=(param_specs, P()),
                     out_specs=P(), check_vma=False)(params, x)
