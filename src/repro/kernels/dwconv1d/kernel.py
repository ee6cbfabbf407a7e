"""Pallas TPU kernel: causal depthwise 1D convolution (streaming FIR).

This is the paper's 1D case — the structure DSP48E1 cascades were actually
designed for — reused as the conv path of SSM/hybrid blocks (mamba k=4).
Causality means the streaming form needs NO lookahead and NO output delay:
grid steps walk sequence chunks (``dimension_semantics=('arbitrary',)``)
with a VMEM scratch carrying the last k−1 positions — the 1D row buffer.
The taps are accumulated as a shift-MAC chain (transposed form): channels
live on lanes, so each tap is one VPU multiply-add over [chunk, C].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dwconv1d_kernel(x_ref, w_ref, b_ref, o_ref, carry_ref, *, k: int,
                     chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _prime():                      # new batch row: zero history (causal)
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[0]                       # [chunk, C]
    ext = jnp.concatenate([carry_ref[...], x], axis=0)  # [chunk + k-1, C]
    w = w_ref[...]                     # [k, C]
    acc = ext[0:chunk] * w[0]          # shift-MAC chain over the k taps
    for d in range(1, k):
        acc = acc + ext[d:d + chunk] * w[d]
    o_ref[0] = acc + b_ref[...]
    carry_ref[...] = ext[chunk:]       # last k-1 positions -> next step


def dwconv1d(x: jax.Array, w: jax.Array, b: jax.Array, *, chunk: int = 512,
             interpret: bool = True) -> jax.Array:
    """x: [B, S, C]; w: [k, C]; b: [C]. Returns [B, S, C] causal conv.

    y[t] = b + sum_d x[t-(k-1)+d] * w[d]  (zero history before t=0).
    S must divide by ``chunk`` (wrappers pad).
    """
    B, S, C = x.shape
    k = w.shape[0]
    assert S % chunk == 0 and chunk >= k - 1, (S, chunk, k)
    grid = (B, S // chunk)
    return pl.pallas_call(
        functools.partial(_dwconv1d_kernel, k=k, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((B, S, C), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, C), lambda b_, j: (b_, j, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, chunk, C), lambda b_, j: (b_, j, 0)),
        scratch_shapes=[pltpu.VMEM((k - 1, C), x.dtype)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="dwconv1d_stream",
    )(x, w, b)
