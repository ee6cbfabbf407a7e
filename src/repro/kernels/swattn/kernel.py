"""Pallas TPU kernel: banded (sliding-window) flash attention.

The paper's streaming-window principle applied to attention: a sliding
window of width W over the sequence is a 1D stencil, so the [S, S] score
plane is never materialised ("no full-frame buffering") and only the banded
blocks are ever computed or fetched. Per q block, the kernel walks the
``nkb = ceil(W/blk)+1`` k/v blocks of the band with an online-softmax
running (m, l, acc) state in VMEM — the row buffer of the score stream.

GQA is handled in the index map: q head h reads kv head h // group, so kv
is never repeated in HBM (repetition is the "padded copy" anti-pattern the
paper's border policy avoids).

``window=0`` degrades to full causal flash attention (band = whole history).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _swattn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   blk: int, nkb: int, window: int, scale: float, S: int,
                   banded: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # actual k block of this band step (may be out of range -> fully masked)
    kb = (qi - (nkb - 1) + ki) if banded else ki

    q = q_ref[0]                                        # [blk, hd]
    k = k_ref[0]                                        # [blk, hd]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    kpos = kb * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    ok = (kpos <= qpos) & (kpos < S) & (qpos < S) & (kb >= 0)
    if window > 0:
        ok = ok & (qpos - kpos < window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                 # [blk, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)                     # [blk, 1]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nkb - 1)
    def _emit():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def swattn(q: jax.Array, k: jax.Array, v: jax.Array, *, window: int,
           num_q_heads: int, num_kv_heads: int, scale: float,
           s_true: int, blk: int = 128, interpret: bool = True) -> jax.Array:
    """q: [B*H, Sp, hd]; k, v: [B*KV, Sp, hd]; Sp % blk == 0.

    ``window`` > 0: sliding-window causal; 0: full causal. Returns
    [B*H, Sp, hd]; rows/cols at positions >= ``s_true`` are masked out
    (padding introduced by the wrapper).
    """
    BH, Sp, hd = q.shape
    assert Sp % blk == 0, (Sp, blk)
    nq = Sp // blk
    group = num_q_heads // num_kv_heads
    banded = window > 0
    nkb = min(nq, 1 + math.ceil(window / blk)) if banded else nq

    def q_idx(bh, qi, ki):
        return (bh, qi, 0)

    def kv_idx(bh, qi, ki):
        b = bh // num_q_heads
        h = bh % num_q_heads
        bkv = b * num_kv_heads + h // group
        kb = (qi - (nkb - 1) + ki) if banded else ki
        return (bkv, jnp.maximum(kb, 0) if banded else kb, 0)

    return pl.pallas_call(
        functools.partial(_swattn_kernel, blk=blk, nkb=nkb, window=window,
                          scale=scale, S=s_true, banded=banded),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, hd), q.dtype),
        grid=(BH, nq, nkb),
        in_specs=[
            pl.BlockSpec((1, blk, hd), q_idx),
            pl.BlockSpec((1, blk, hd), kv_idx),
            pl.BlockSpec((1, blk, hd), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, blk, hd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((blk, 1), jnp.float32),
            pltpu.VMEM((blk, 1), jnp.float32),
            pltpu.VMEM((blk, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name=f"swattn_w{window}",
    )(q, k, v)
