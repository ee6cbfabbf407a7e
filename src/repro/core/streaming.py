"""Row-strip streaming executor — the paper's dataflow at the XLA level —
and the band body it shares with the row-sharded executor.

The FPGA design streams one pixel per clock through a (w−1)-row buffer so a
full frame never needs to be resident. The TPU translation processes one
*row strip* per step: a `jax.lax.scan` over strips where the carry is the
last r = (w−1)/2 rows of the previous strip — exactly the paper's row
buffer. The strip height is chosen so (strip + halo) fits a fixed VMEM
budget, which is what bounds on-chip memory exactly as the row buffer
bounds BRAM.

A *band* is a run of rows plus r rows of halo on each side: a strip of the
scan, or a shard of ``core.distributed``. :func:`band_rows` assembles one
and remaps its halo only at a true frame edge — the overlapped priming &
flushing idea: no stall, no extra pass, the stream of bands never stops.
:func:`filter_band` then widens the column-extended band to the
accumulator, runs the form and the requantising epilogue, so each band
leaves at storage width. The scan widens fixed-point frames and extends
their columns once, over the whole frame, before it starts: done per
strip, the column remap is a pass of its own over every band, which on a
TPU v5e cost more than the frame-wide pass it replaces.

``wrap`` needs the *opposite* frame edge, which a row buffer by
construction no longer holds — it is served by a **prologue**: the carry
starts as the frame's last r rows, and the lookahead closes a ring, so the
last strip's rows below are the frame's first r rows (the same ring the
shards' halo exchange runs on, and the scheme the Pallas halo engine
implements with prologue DMAs).

This file is the *jnp* streaming path; the Pallas kernel in
``kernels/filter2d`` implements the same schedule with an explicit VMEM
scratch and in-kernel halo DMA (``kernels/filter2d/halo``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.border_spec import BorderSpec
from repro.core.borders import gather_rows
from repro.core.filter2d import (_FORM_FNS, _as_nhwc, _un_nhwc,
                                 apply_requant_params, is_fixed_point)
from repro.core.requant import RequantSpec


def strip_height_for_vmem(width: int, channels: int, w: int,
                          vmem_bytes: int = 8 * 2 ** 20,
                          dtype_bytes: int = 4) -> int:
    """Largest strip height whose working set (strip+halo in, strip out,
    double-buffered) fits the VMEM budget. Mirrors the paper's BRAM bound."""
    per_row = width * channels * dtype_bytes
    # in-strip (+halo), out-strip, x2 double buffering
    h = vmem_bytes // (per_row * 4) - (w - 1)
    return max(8, int(h))


def band_rows(above: jax.Array, body: jax.Array, below: jax.Array,
              i: jax.Array, n_bands: int, border: BorderSpec) -> jax.Array:
    """The rows ``[above | body | below]`` of band ``i`` of ``n_bands``
    ([B, S + 2r, ...]), with the halo past a true frame edge remapped
    under ``border``.

    ``above`` and ``below`` are the r rows next to ``body`` on the ring of
    bands: for the first band ``above`` is the frame's last r rows, for
    the last band ``below`` its first r rows, which is what ``wrap``
    reads. Every other policy remaps the first band's top halo and the
    last band's bottom halo from the rows inside the frame. A single band
    (decided from the static ``n_bands``) has both edges remapped.
    """
    r, S = above.shape[1], body.shape[1]
    if border.policy != "wrap" and n_bands == 1:
        return gather_rows(body, jnp.arange(-r, S + r), border, axis=1)
    ext = jnp.concatenate([above, body, below], axis=1)
    if border.policy == "wrap":
        return ext
    first = gather_rows(jnp.concatenate([body, below], axis=1),
                        jnp.arange(-r, S + r), border, axis=1)
    ext = jnp.where(i == 0, first, ext)
    last = gather_rows(jnp.concatenate([above, body], axis=1),
                       jnp.arange(0, S + 2 * r), border, axis=1)
    return jnp.where(i == n_bands - 1, last, ext)


def filter_band(ext: jax.Array, coeffs: jax.Array,
                q: Optional[jax.Array], *, form: str,
                requant: Optional[RequantSpec]) -> jax.Array:
    """Filter the band ``ext`` ([B, S + 2r, W + 2r, C]: the rows of
    :func:`band_rows` with their columns extended) to its S output rows.

    Fixed-point rows widen to the int32 accumulator here, at the MAC, and
    ``requant`` (the static half; ``q`` the traced [1, 2] gains) brings
    the band back to storage width.
    """
    r = (coeffs.shape[-1] - 1) // 2
    if is_fixed_point(ext.dtype):
        ext = ext.astype(jnp.int32)
    y = _FORM_FNS[form](ext, coeffs, ext.shape[1] - 2 * r,
                        ext.shape[2] - 2 * r)
    return y if requant is None else apply_requant_params(y, q, requant)


def _filter2d_streaming_impl(frame: jax.Array, coeffs: jax.Array,
                             q: Optional[jax.Array], *, border: BorderSpec,
                             form: str, strip_h: int,
                             requant: Optional[RequantSpec]) -> jax.Array:
    """The strip scan behind ``Filter2D.compile(..., 'streaming')``.

    ``border`` is a same-size policy with its constant already quantized
    against the storage dtype; ``requant`` is the static half of the
    epilogue and ``q`` the traced gains (``None`` without requant), so
    gains swap without recompiling. Frame height must divide by
    ``strip_h`` and ``strip_h >= w-1`` (the carry must fit inside one
    strip).
    """
    x, add_b, add_c = _as_nhwc(frame)
    B, H, W, C = x.shape
    w = coeffs.shape[-1]
    r = (w - 1) // 2
    assert H % strip_h == 0 and strip_h >= w - 1, (H, strip_h, w)
    n_strips = H // strip_h
    if is_fixed_point(x.dtype):
        x = x.astype(jnp.int32)
    xc = gather_rows(x, jnp.arange(-r, W + r), border, axis=2)
    strips = xc.reshape(B, n_strips, strip_h, W + 2 * r, C).swapaxes(0, 1)
    if border.policy == "wrap":
        # the prologue: the ring of strips closes on the opposite edge
        above0, below_last = strips[-1][:, strip_h - r:], strips[:1]
    else:
        # band_rows remaps the rows past either frame edge: none is read
        above0 = jnp.zeros((B, r, W + 2 * r, C), xc.dtype)
        below_last = strips[-1:]
    nxt_strips = jnp.concatenate([strips[1:], below_last], axis=0)

    def step(carry, inputs):
        above, i = carry
        strip, nxt = inputs
        ext = band_rows(above, strip, nxt[:, :r], i, n_strips, border)
        y = filter_band(ext, coeffs, q, form=form, requant=requant)
        return (strip[:, strip_h - r:], i + 1), y

    _, ys = jax.lax.scan(step, (above0, jnp.asarray(0, jnp.int32)),
                         (strips, nxt_strips))
    y = ys.swapaxes(0, 1).reshape(B, H, W, C)
    return _un_nhwc(y, add_b, add_c)
