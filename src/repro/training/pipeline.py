"""Pipeline parallelism: GPipe-style schedule as a shard_map over a
'stage' mesh axis (the config alternative promised in DESIGN.md §5).

The pipeline is the paper's streaming dataflow at yet another scale: each
stage is a pipeline register, microbatches are the pixel stream, and the
fill/drain ticks are priming/flushing. The schedule runs T = M + P − 1
ticks; at tick t, stage s processes microbatch t − s. Inter-stage
transfers are single `ppermute`s (the FPGA's stage-to-stage wires), and
because ppermute has a well-defined transpose, `jax.grad` through the
shard_map yields the backward pipeline (reverse flow) for free.

Intended for long uniform decoder stacks over the 'pod'/'stage' axis;
exposed as a composable building block + exercised by multi-device tests
at small scale (the production dry-run uses DP×TP, which dominates at the
assigned batch sizes).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def pipeline_apply(layer_fn: Callable, params_stacked, x_mb: jax.Array,
                   mesh: Mesh, *, axis: str = "stage") -> jax.Array:
    """Run a stacked layer sequence as a GPipe pipeline over ``axis``.

    layer_fn(params_one_stage, x) -> y        (one stage's computation)
    params_stacked: leaves [P_stages, ...] sharded over ``axis`` on dim 0.
    x_mb: [M, mb, ...] microbatched inputs (replicated across stages).
    Returns [M, mb, ...] outputs (replicated), differentiable.
    """
    n_stage = mesh.shape[axis]
    M = x_mb.shape[0]
    T = M + n_stage - 1

    def local(params_local, x_local):
        # params_local leaves: [1, ...] -> this stage's parameters
        p_stage = jax.tree.map(lambda a: a[0], params_local)
        sidx = jax.lax.axis_index(axis)
        fwd = [(i, i + 1) for i in range(n_stage - 1)]  # stage s -> s+1

        zero = jnp.zeros_like(x_local[0])
        out_buf = jnp.zeros_like(x_local)

        def tick(carry, t):
            prev_out, out_buf = carry
            # stage-to-stage wire: previous tick's output moves one stage up
            recv = jax.lax.ppermute(prev_out, axis, fwd)
            mb_idx = jnp.clip(t, 0, M - 1)
            first_in = jax.lax.dynamic_index_in_dim(x_local, mb_idx, 0,
                                                    keepdims=False)
            x_in = jnp.where(sidx == 0, first_in, recv)
            y = layer_fn(p_stage, x_in)
            # last stage emits microbatch t-(P-1) when it is valid
            emit_idx = jnp.clip(t - (n_stage - 1), 0, M - 1)
            valid = (t >= n_stage - 1) & (sidx == n_stage - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(valid, y, jax.lax.dynamic_index_in_dim(
                    out_buf, emit_idx, 0, keepdims=False)), emit_idx, 0)
            return (y, upd), None

        (last, out_buf), _ = jax.lax.scan(
            tick, (zero, out_buf), jnp.arange(T))
        # replicate the result: only the last stage holds real outputs
        total = jax.lax.psum(
            jnp.where(sidx == n_stage - 1, out_buf, jnp.zeros_like(out_buf)),
            axis)
        return total

    pspec = jax.tree.map(lambda _: P(axis), params_stacked)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(pspec, P()), out_specs=P(),
                   check_vma=False)
    return fn(params_stacked, x_mb)


def pipeline_loss_fn(layer_fn: Callable, loss_fn: Callable, mesh: Mesh,
                     *, axis: str = "stage") -> Callable:
    """(params_stacked, x_mb, y_mb) -> scalar loss through the pipeline.

    Differentiable: jax.grad of this gives the GPipe backward schedule
    (ppermute transposes reverse the wire direction)."""
    def f(params_stacked, x_mb, y_mb):
        out = pipeline_apply(layer_fn, params_stacked, x_mb, mesh,
                             axis=axis)
        return loss_fn(out, y_mb)
    return f
