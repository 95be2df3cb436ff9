"""Pipeline parallelism: GPipe-style microbatched pipeline via shard_map +
``ppermute`` over a "pipe" mesh axis.

The uniform decoder stack is split into S contiguous stages (layers
sharded over "pipe"); microbatches stream through with the classic
(M + S - 1)-step schedule.  ``ppermute`` is differentiable — its transpose
is the reverse permute — so ``jax.grad`` through the pipelined forward
yields the standard GPipe backward with no hand-written adjoint schedule.

This is an optional axis (off in the default production mesh); numerics
are validated against the non-pipelined stack on an 8-device host mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipelined_forward(
    layer_fn,  # (layer_params, x) -> x   (one layer)
    stage_params,  # pytree, leaves (L, ...) stacked over ALL layers
    x_microbatches,  # (M, mb, n, d)
    mesh,
    *,
    axis_name: str = "pipe",
):
    """Runs the stack over microbatches with pipeline parallelism.

    stage_params leaves must have leading dim L divisible by the pipe
    axis; each stage runs its L/S contiguous layers per tick.
    """
    S = mesh.shape[axis_name]
    M = x_microbatches.shape[0]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(params_local, xs):
        # params_local: (L/S, ...) this stage's layers; xs: (M, mb, n, d)
        stage = jax.lax.axis_index(axis_name)
        n_stage = jax.lax.psum(1, axis_name)
        mb_shape = xs.shape[1:]
        ticks = M + n_stage - 1

        def stage_apply(x):
            def body(h, lp):
                return layer_fn(lp, h), None

            out, _ = jax.lax.scan(body, x, params_local)
            return out

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (if any); others use recv buffer
            mb_idx = jnp.clip(t, 0, M - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0, False)
            x_in = jnp.where(stage == 0, inject, buf)
            y = stage_apply(x_in)
            # pass to next stage
            perm = [(i, i + 1) for i in range(n_stage - 1)]
            buf_next = jax.lax.ppermute(y, axis_name, perm)
            # last stage emits microbatch (t - (S-1)) at tick t
            # (jnp.where instead of lax.cond: shard_map varying-axis typing)
            out_idx = jnp.clip(t - (n_stage - 1), 0, M - 1)
            emit = (t >= n_stage - 1) & (stage == n_stage - 1)
            upd = jax.lax.dynamic_update_index_in_dim(outputs, y, out_idx, 0)
            outputs = jnp.where(emit, upd, outputs)
            return (buf_next, outputs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, xs.dtype)
        # mark the carries as device-varying over the pipe axis (the loop
        # body mixes in stage-dependent values): shard_map vma typing.
        if hasattr(jax.lax, "pcast"):
            buf0 = jax.lax.pcast(buf0, (axis_name,), to="varying")
            outs0 = jax.lax.pcast(outs0, (axis_name,), to="varying")
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(ticks)
        )
        # broadcast final outputs from the last stage to all (psum of
        # one-hot contribution keeps shard_map output replicated)
        outputs = jnp.where(stage == n_stage - 1, outputs, 0.0)
        return jax.lax.psum(outputs, axis_name)

    return run(stage_params, x_microbatches)
