"""Explicit data-parallel train step with hierarchical compressed gradients.

Under pure pjit the DP all-reduce is inserted by SPMD and cannot be
intercepted, so gradient compression is implemented where the reduction is
explicit: a shard_map over the DP axes. Reduction schedule (the
distributed-optimisation trick for 512+ chips):

  1. psum over 'data' (intra-pod ICI, fp32) — fast links carry full grads;
  2. int8 error-feedback quantise (4x fewer DCN bytes);
  3. psum over 'pod' (inter-pod DCN) on int8-as-int32 accumulators;
  4. dequantise; the quantisation residual is carried to the next step.

Model params are replicated in this mode (pure DP); the pjit TP/FSDP path
is the default for the big archs. This module demonstrates (and tests, on
a multi-device CPU mesh) the mechanism the trainer enables with
``grad_compression='int8_ef'``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.configs.base import RunConfig
from repro.optim import adamw_update, clip_by_global_norm, cosine_warmup


def _dp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_compressed_dp_step(bundle, rc: RunConfig, mesh: Mesh) -> Callable:
    """Pure-DP train step: batch sharded over (pod, data); params replicated;
    grads reduced hierarchically with int8 EF across 'pod'."""
    tc = rc.train
    axes = _dp_axes(mesh)
    batch_spec = P(axes)

    def loss_for(params, batch):
        return bundle.loss_fn(params, batch, shd=None,
                              remat_policy=tc.remat_policy,
                              loss_chunk=tc.loss_chunk, z_loss=tc.z_loss)

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    def local_step(params, opt_state, err, batch):
        """err leaves: [1, ...] — the per-pod error-feedback residual shard
        (replicated within a pod, distinct across pods)."""
        (loss, (aux, _)), grads = grad_fn(params, batch)
        # 1) fp32 psum over the fast intra-pod axis
        if "data" in axes:
            grads = jax.lax.pmean(grads, "data")
            loss = jax.lax.pmean(loss, "data")
        # 2-4) compressed reduction over the slow pod axis
        if "pod" in axes:
            def reduce_leaf(g, e):
                from repro.optim.compression import (int8_ef_compress,
                                                     int8_ef_decompress)
                q, scale, new_e = int8_ef_compress(g, e[0])
                acc = jax.lax.psum(q.astype(jnp.int32), "pod")
                scale = jax.lax.pmax(scale, "pod")  # shared dequant scale
                npod = jax.lax.psum(jnp.ones((), jnp.float32), "pod")
                g_out = int8_ef_decompress(acc, scale) / npod
                return g_out, new_e[None]
            flat_g, tdef = jax.tree.flatten(grads)
            flat_e = jax.tree.leaves(err)
            outs = [reduce_leaf(g, e) for g, e in zip(flat_g, flat_e)]
            grads = jax.tree.unflatten(tdef, [o[0] for o in outs])
            err = jax.tree.unflatten(tdef, [o[1] for o in outs])
            loss = jax.lax.pmean(loss, "pod")
        grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
        lr = cosine_warmup(opt_state.step + 1, peak_lr=tc.learning_rate,
                           warmup_steps=tc.warmup_steps,
                           total_steps=tc.total_steps)
        params, opt_state = adamw_update(
            params, grads, opt_state, lr=lr, b1=tc.b1, b2=tc.b2,
            eps=tc.eps, weight_decay=tc.weight_decay)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return params, opt_state, err, metrics

    rep = P()
    err_spec = P("pod") if "pod" in axes else rep
    fn = shard_map(
        local_step, mesh=mesh,
        in_specs=(rep, rep, err_spec, batch_spec),
        out_specs=(rep, rep, err_spec, rep),
        check_vma=False)
    return jax.jit(fn)


def init_error_feedback(params, mesh: Mesh):
    """Per-pod EF residuals: leaves [n_pod, ...] sharded over 'pod'."""
    n_pod = dict(zip(mesh.axis_names,
                     mesh.devices.shape)).get("pod", 1)
    return jax.tree.map(
        lambda p_: jnp.zeros((n_pod,) + p_.shape, jnp.float32), params)
