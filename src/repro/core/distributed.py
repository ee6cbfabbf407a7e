"""Distributed 2D filtering: the row buffer, distributed (shard_map + ppermute).

For frames too tall for one device (or for throughput scaling), the frame is
row-sharded over a mesh axis. Each shard needs the r = (w−1)/2 boundary rows
of its neighbours — the *distributed* analogue of the paper's row buffer.
We exchange exactly those rows with two `jax.lax.ppermute`s (up and down),
then run the local filter with border remapping applied ONLY at the true
frame edges (first/last shard). No frame-sized gather, no padded HBM copy:
wire bytes = 2·r·W·C·dtype per shard boundary, independent of H.

This is the paper's lean-border principle at cluster scale: border handling
must not disturb the (sharded) stream.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core.border_spec import quantize_constant
from repro.core.borders import BorderSpec, gather_rows
from repro.core.filter2d import (_FORM_FNS, _as_nhwc, _un_nhwc,
                                 apply_requant_params, is_fixed_point,
                                 resolve_requant)
from repro.core.requant import RequantSpec

HALO_SCOPE = "repro.shard.halo"


def check_row_split(H: int, n_shards: int, r: int) -> None:
    """Raise ``ValueError`` unless ``H`` rows split evenly over
    ``n_shards`` shards that each hold at least the ``r`` halo rows their
    neighbours need."""
    if H % n_shards or H // n_shards < r:
        raise ValueError(
            f"a frame of {H} rows cannot be row-sharded over {n_shards} "
            f"shards with a halo of r={r} rows: the rows must split evenly "
            "and each shard must hold at least r of them")


def _filter2d_sharded_impl(frame: jax.Array, coeffs: jax.Array, mesh: Mesh,
                           q_params: Optional[jax.Array] = None,
                           *, axis: str = "data", form: str = "direct",
                           border_policy: str = "mirror",
                           border: Optional[BorderSpec] = None,
                           requant: Optional[RequantSpec] = None,
                           on_halo_bytes: Optional[Callable[[int], None]]
                           = None) -> jax.Array:
    """Row-shard ``frame`` over ``mesh[axis]`` and filter with halo exchange.

    frame: [B,H,W,C] (H split as :func:`check_row_split` allows, which the
    caller checks). Returns same shape.
    Every same-size policy is supported: ``wrap`` in particular is *free*
    here — the ppermute halo exchange already runs on a ring, so the first
    shard's top halo arrives from the last shard (the opposite frame edge),
    which is exactly wrap's semantics. Pass ``border`` (wins over
    ``border_policy``) for non-zero constants.

    Fixed-point frames keep their *storage* dtype through the sharding and
    the ppermute halo exchange — the ring moves 1-2 wire bytes per halo
    element, the paper's narrow bus at ICI scale — and widen to the int32
    accumulator only after the exchange, inside each shard's local MAC.
    ``requant`` applies the same fused epilogue contract as ``filter2d``
    per shard, so the ring's *output* tiles (and the gathered result) are
    storage-width too.

    ``on_halo_bytes``, when given, is called while tracing with the bytes
    the two ``ppermute`` operands carry per call, summed over the shards
    (0 on a one-shard mesh).
    """
    spec = border if border is not None else BorderSpec(border_policy)
    if spec.policy == "neglect":
        raise ValueError("sharded path does not support 'neglect'")
    rq = resolve_requant(frame.dtype, requant)
    # the (multiplier, shift) gains ride as a traced [1, 2] operand
    # (replicated across the mesh), defaulting to the spec's own: the
    # pipeline swaps gains without recompiling while each shard still
    # requantises its own tile (storage-width gather, the PR-4 contract)
    if rq is not None and q_params is None:
        q_params = jnp.asarray(rq.params(1), jnp.int32)
    # fixed-point: quantize constant(c) against the storage dtype (shared
    # rule) and keep the frame NARROW — only the coefficients widen here.
    # The storage-width halo rows cross the ring; each shard widens on the
    # register read feeding its MAC, exactly like the Pallas kernel.
    fixed = is_fixed_point(frame.dtype)
    if fixed:
        spec = dataclasses.replace(
            spec, constant=quantize_constant(spec.constant, frame.dtype))
        coeffs = coeffs.astype(jnp.int32)
    x, add_b, add_c = _as_nhwc(frame)
    B, H, W, C = x.shape
    w = coeffs.shape[-1]
    r = (w - 1) // 2
    n_shards = mesh.shape[axis]
    if n_shards == 1:
        if on_halo_bytes is not None:
            on_halo_bytes(0)
        from repro.core.filter2d import _filter2d_impl
        qc = jnp.asarray(quantize_constant(spec.constant, frame.dtype))
        y = _filter2d_impl(frame, coeffs, form=form,
                           border_policy=spec.policy, border_constant=qc)
        return y if rq is None else apply_requant_params(y, q_params, rq)

    in_specs = (P(None, axis, None, None), P())
    if rq is not None:
        in_specs = in_specs + (P(),)      # gains replicated to every shard
    out_specs = P(None, axis, None, None)

    def local(xs: jax.Array, k: jax.Array, q: jax.Array = None) -> jax.Array:
        Hs = xs.shape[1]
        idx = jax.lax.axis_index(axis)
        # halo exchange at storage width: send my top r rows
        # up-neighbour-ward, bottom r down — 2·r·W·C·storage bytes of wire
        fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        bwd = [(i, (i - 1) % n_shards) for i in range(n_shards)]
        with jax.named_scope(HALO_SCOPE):
            up, down = xs[:, Hs - r:], xs[:, :r]
            if on_halo_bytes is not None:
                on_halo_bytes(n_shards * (up.size * up.dtype.itemsize
                                          + down.size * down.dtype.itemsize))
            top_from_above = jax.lax.ppermute(up, axis, fwd)
            bot_from_below = jax.lax.ppermute(down, axis, bwd)
            ext = jnp.concatenate([top_from_above, xs, bot_from_below],
                                  axis=1)
            if spec.policy != "wrap":
                # true frame edges: remap locally (halo rows from the
                # wrap-neighbour are garbage there and are overwritten by
                # the remap). Under wrap the ring delivery IS the right
                # answer.
                first_src = jnp.concatenate([xs, bot_from_below], axis=1)
                hi_first = gather_rows(first_src, jnp.arange(-r, Hs + r),
                                       spec, axis=1)
                ext = jnp.where(idx == 0, hi_first, ext)
                last_src = jnp.concatenate([top_from_above, xs], axis=1)
                hi_last = gather_rows(last_src, jnp.arange(0, Hs + 2 * r),
                                      spec, axis=1)
                ext = jnp.where(idx == n_shards - 1, hi_last, ext)
        # column halo: plain index remap, local
        wi = jnp.arange(-r, W + r)
        ext = gather_rows(ext, wi, spec, axis=2)
        if fixed:                         # widen at the MAC, not before
            ext = ext.astype(jnp.int32)
        y = _FORM_FNS[form](ext, k, Hs, W)
        if rq is not None:
            # fused epilogue per shard: the tiles the mesh gathers (or a
            # downstream ring carries) are requantised, storage-width
            y = apply_requant_params(y, q, rq)
        return y

    fn = shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    y = fn(x, coeffs, q_params) if rq is not None else fn(x, coeffs)
    return _un_nhwc(y, add_b, add_c)


def filter2d_sharded(frame: jax.Array, coeffs: jax.Array, mesh: Mesh, *,
                     axis: str = "data", form: str = "direct",
                     border_policy: str = "mirror",
                     border: Optional[BorderSpec] = None,
                     requant: Optional[RequantSpec] = None) -> jax.Array:
    """Row-shard ``frame`` over ``mesh[axis]`` and filter with halo
    exchange — see :func:`_filter2d_sharded_impl` for the full contract
    (storage-width ppermute ring, per-shard requantising epilogue, wrap
    served by the ring itself).

    Thin wrapper over ``core.pipeline.Filter2D`` (``execution='sharded'``)
    — prefer the compiled front door for served pipelines.
    """
    from repro.core.pipeline import Filter2D
    spec_b = border if border is not None else BorderSpec(border_policy)
    rq = resolve_requant(frame.dtype, requant)
    spec = Filter2D(window=int(jnp.shape(coeffs)[-1]), form=form,
                    border=spec_b,
                    dtype=jnp.dtype(frame.dtype).name,
                    requant=rq.gain_free() if rq is not None else None)
    cf = spec.compile(frame, "sharded", mesh=mesh, axis=axis)
    return cf(frame, coeffs, gains=rq)
