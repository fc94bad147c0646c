"""GPipe-style pipeline parallelism skeleton (shard_map + ppermute).

Not enabled in the production mesh (DP x TP was sufficient to fit every
assigned architecture at 512 chips -- see EXPERIMENTS.md §Dry-run), but
shipped as the third parallelism dimension for >2-pod scale-out: stages
live on a 'stage' mesh axis, activations flow stage-to-stage with
collective_permute, and microbatches fill the bubble.

`pipeline_apply(stage_fn, stage_params, x, ...)` runs
    y = stage_fn(params_S-1, ... stage_fn(params_0, x))
for each of `n_micro` microbatches with the classic (S-1 + n_micro)-tick
schedule; bubble fraction = (S-1)/(S-1+n_micro).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_apply(
    stage_fn: Callable,        # (stage_params, x_mb) -> y_mb
    stage_params,              # pytree, leaves stacked [n_stages, ...]
    x: Array,                  # [n_micro, mb, ...] microbatched input
    mesh: Mesh,
    axis: str = "stage",
) -> Array:
    """Runs the staged computation over all microbatches; returns
    [n_micro, mb, ...] outputs (as produced by the LAST stage)."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert x.shape[0] == n_micro

    def body(params, xs):
        # params: this stage's slice (leading stage dim of size 1 kept by
        # shard_map -> squeeze); xs: the full microbatch stream, present
        # on every stage (only stage 0 consumes it).
        params = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        n_ticks = n_stages - 1 + n_micro
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf, outs = carry  # buf: activation entering this stage
            # stage 0 injects microbatch t (when in range)
            mb = jnp.where(
                t < n_micro,
                jax.lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, n_micro - 1), keepdims=False
                ),
                jnp.zeros(mb_shape, xs.dtype),
            )
            inp = jnp.where(idx == 0, mb, buf)
            out = stage_fn(params, inp)
            # last stage writes its result for microbatch t-(S-1)
            mb_id = t - (n_stages - 1)
            outs = jax.lax.cond(
                (idx == n_stages - 1) & (mb_id >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(mb_id, 0, n_micro - 1), 0
                ),
                lambda o: o,
                outs,
            )
            # shift activations downstream: stage i -> i+1
            nxt = jax.lax.ppermute(
                out, axis,
                [(i, i + 1) for i in range(n_stages - 1)],
            )
            return (nxt, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_ticks)
        )
        # only the last stage holds real outputs: psum broadcasts them
        # (all other stages contribute zeros)
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis,
        )
        return outs

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),    # params sharded by stage; x replicated
        out_specs=P(),               # outputs replicated after the psum
        check_vma=False,
    )
    return fn(stage_params, x)


def pipeline_reference(stage_fn, stage_params, x):
    """Sequential oracle: apply all stages to every microbatch."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def apply_all(x_mb):
        for s in range(n_stages):
            p_s = jax.tree.map(lambda a: a[s], stage_params)
            x_mb = stage_fn(p_s, x_mb)
        return x_mb

    return jax.vmap(apply_all)(x)
