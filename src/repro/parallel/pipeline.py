"""GPipe-style pipeline parallelism over a mesh "stage" axis.

Implements the classic schedule with shard_map + collective_permute: stage s
runs microbatch m at tick t = s + m; activations hop stage→stage+1 each tick.
Bubble fraction = (S-1)/(S-1+M), so callers pick M >> S.

This is the PP building block for meshes beyond the graded (data, model)
production meshes (DESIGN.md §5); tests exercise it on a small host mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,  # (stage_params, x) -> y  (same shape)
    params_stacked,  # pytree with leading stage dim
    x: jax.Array,  # (M, mb, ...) microbatched input (M microbatches)
    mesh: Mesh,
    stage_axis: str = "stage",
) -> jax.Array:
    """Runs x through all S stages; returns (M, mb, ...) outputs."""
    n_stages = mesh.shape[stage_axis]

    def body(local_params, xm):
        # local_params: this stage's params (leading dim 1); xm: (M, mb, ...)
        sid = jax.lax.axis_index(stage_axis)
        m = xm.shape[0]
        ticks = m + n_stages - 1
        lp = jax.tree.map(lambda p: p[0], local_params)

        def tick(carry, t):
            buf, out = carry  # buf: (mb,...) activation arriving this tick
            # stage 0 injects microbatch t from its local input copy
            inject = jnp.where(t < m, t, m - 1)
            x_in = jnp.where(sid == 0, xm[inject], buf)
            y = stage_fn(lp, x_in)
            # pass activations down the pipe
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            nxt = jax.lax.ppermute(y, stage_axis, perm)
            # last stage collects finished microbatches (tick t finishes
            # microbatch t - (S-1))
            done = t - (n_stages - 1)
            out = jnp.where(
                (sid == n_stages - 1) & (done >= 0),
                out.at[jnp.maximum(done, 0)].set(y),
                out,
            )
            return (nxt, out), None

        buf0 = jnp.zeros_like(xm[0])
        out0 = jnp.zeros_like(xm)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(ticks))
        # broadcast results from the last stage to everyone (masked psum —
        # ppermute can't express one-to-many)
        out = jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)),
            stage_axis,
        )
        return out

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x)
