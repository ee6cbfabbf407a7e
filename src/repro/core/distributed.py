"""Distributed 2D filtering: the row buffer, distributed (shard_map + ppermute).

For frames too tall for one device (or for throughput scaling), the frame is
row-sharded over a mesh axis. Each shard needs the r = (w−1)/2 boundary rows
of its neighbours — the *distributed* analogue of the paper's row buffer.
We exchange exactly those rows with two `jax.lax.ppermute`s (up and down),
then filter each shard as one band of the strip scan's band body
(``core.streaming.band_rows``/``filter_band``), border remapping applied
ONLY at the true frame edges (first/last shard). No frame-sized gather,
no padded HBM copy:
wire bytes = 2·r·W·C·dtype per shard boundary, independent of H.

This is the paper's lean-border principle at cluster scale: border handling
must not disturb the (sharded) stream.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core.border_spec import BorderSpec
from repro.core.borders import gather_rows
from repro.core.filter2d import _as_nhwc, _un_nhwc
from repro.core.requant import RequantSpec
from repro.core.streaming import band_rows, filter_band

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


def _filter2d_sharded_impl(frame: jax.Array, coeffs: jax.Array,
                           q: Optional[jax.Array], *, mesh: Mesh, axis: str,
                           border: BorderSpec, form: str,
                           requant: Optional[RequantSpec],
                           on_halo_bytes: Optional[Callable[[int], None]]
                           ) -> jax.Array:
    """Row-shard ``frame`` over ``mesh[axis]`` and filter each shard as
    one band of :func:`~repro.core.streaming.filter_band`.

    frame: [B,H,W,C] (H split as :func:`check_row_split` allows, which the
    caller checks). Returns same shape. ``border``, ``requant`` and ``q``
    are as the strip scan takes them (a same-size policy with its
    constant quantized; the static epilogue; the traced gains, replicated
    across the mesh, or ``None``).

    Every same-size policy is supported: ``wrap`` in particular is *free*
    here — the ppermute halo exchange already runs on a ring, so the first
    shard's top halo arrives from the last shard (the opposite frame edge),
    which is exactly wrap's semantics.

    Fixed-point frames keep their *storage* dtype through the sharding and
    the ppermute halo exchange — the ring moves 1-2 wire bytes per halo
    element, the paper's narrow bus at ICI scale — and widen to the int32
    accumulator only inside each shard's MAC. The requantising epilogue
    runs per shard, so the gathered tiles are storage-width too.

    ``on_halo_bytes``, when given, is called while tracing with the bytes
    the two ``ppermute`` operands carry per call, summed over the shards
    (0 on a one-shard mesh, which exchanges nothing).
    """
    x, add_b, add_c = _as_nhwc(frame)
    W = x.shape[2]
    r = (coeffs.shape[-1] - 1) // 2
    n_shards = mesh.shape[axis]
    rows_spec = P(None, axis, None, None)

    def local(xs: jax.Array, k: jax.Array, q: Optional[jax.Array]
              ) -> jax.Array:
        Hs = xs.shape[1]
        idx = jax.lax.axis_index(axis)
        # halo exchange at storage width: send my top r rows
        # up-neighbour-ward, bottom r down — 2·r·W·C·storage bytes of wire
        fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        bwd = [(i, (i - 1) % n_shards) for i in range(n_shards)]
        with jax.named_scope(HALO_SCOPE):
            up, down = xs[:, Hs - r:], xs[:, :r]
            if n_shards == 1:
                # a ring of one hands the shard its own edge rows
                nbytes, above, below = 0, up, down
            else:
                nbytes = n_shards * (up.size * up.dtype.itemsize
                                     + down.size * down.dtype.itemsize)
                above = jax.lax.ppermute(up, axis, fwd)
                below = jax.lax.ppermute(down, axis, bwd)
            if on_halo_bytes is not None:
                on_halo_bytes(nbytes)
            rows = band_rows(above, xs, below, idx, n_shards, border)
        # columns are remapped on the assembled band, after the exchange,
        # so the ring carries only the frame's own W columns
        ext = gather_rows(rows, jnp.arange(-r, W + r), border, axis=2)
        return filter_band(ext, k, q, form=form, requant=requant)

    fn = shard_map(local, mesh=mesh, in_specs=(rows_spec, P(), P()),
                   out_specs=rows_spec, check_vma=False)
    return _un_nhwc(fn(x, coeffs, q), add_b, add_c)
