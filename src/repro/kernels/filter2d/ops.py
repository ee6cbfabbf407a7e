"""jit'd public wrappers for the filter2d Pallas kernels.

``filter2d_pallas``/``filter_bank_pallas`` are thin wrappers over the
plan-and-execute front door (``core.pipeline.Filter2D`` →
``CompiledFilter``); the plane-level executable ``_filter2d_pallas_planes``
lives here and owns what the FPGA control unit owned:
  * strip/tile sizing: Ho split into row strips, W into lane-aligned (128)
    column tiles, so the per-step VMEM working set is bounded by
    strip_h × tile_w regardless of frame dimensions (8K-wide frames stream
    under the same budget as VGA);
  * plane folding: batch/channel (and the filter bank) become kernel grid
    dimensions — no outer ``vmap`` of a 2D kernel;
  * form/regime dispatch (frame-resident ``small`` vs streaming ``stream``)
    and the separable fast path (``separable='auto'|True|False``).

Border management is **not** resolved here any more: the halo engine
(``kernels/filter2d/halo``) realises every policy — ``zero``/
``constant(c)``, ``replicate``/``duplicate``, ``reflect``/``mirror``,
``mirror_dup``, ``wrap`` and ``neglect`` — inside the kernel, by per-tile
DMA from the un-tiled frame plus an in-VMEM index mux. The old row-extended,
halo-duplicated HBM staging layout (one extra full-frame HBM pass ahead of
the kernel) is gone: the kernel's input operand IS the raw frame, read once.

Without an ``interpret`` argument, kernels run compiled on a TPU backend
and in ``interpret=True`` mode (bit-accurate Python execution of the
kernel body) elsewhere; ``CompiledFilter.interpret`` reports which.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.border_spec import BorderSpec
from repro.core.filter2d import resolve_requant, resolve_separable
from repro.core.requant import RequantSpec
from repro.kernels.filter2d import halo
from repro.kernels.filter2d import kernel as K

LANE = halo.LANE


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fold_planes(frame: jax.Array):
    """[H,W] | [H,W,C] | [B,H,W,C] -> ([M,H,W] planes, layout tag).

    The plane dim M = B·C rides the kernel grid (no vmap); the tag lets
    ``_unfold`` restore the caller's layout from the kernel's [M,N,Ho,Wo].
    """
    if frame.ndim == 2:
        return frame[None], ("hw",)
    if frame.ndim == 3:                    # [H, W, C]
        C = frame.shape[2]
        return jnp.transpose(frame, (2, 0, 1)), ("hwc", C)
    if frame.ndim == 4:                    # [B, H, W, C]
        B, _, _, C = frame.shape
        planes = jnp.transpose(frame, (0, 3, 1, 2)).reshape(
            B * C, frame.shape[1], frame.shape[2])
        return planes, ("bhwc", B, C)
    raise ValueError(frame.shape)


def _unfold(y: jax.Array, tag, keep_bank: bool) -> jax.Array:
    """y: [M, N, Ho, Wo] -> caller layout (bank dim last when kept)."""
    if tag[0] == "hw":
        y = y[0]                                   # [N, Ho, Wo]
        y = jnp.transpose(y, (1, 2, 0))            # [Ho, Wo, N]
    elif tag[0] == "hwc":
        y = jnp.transpose(y, (2, 3, 0, 1))         # [Ho, Wo, C, N]
    else:
        B, C = tag[1], tag[2]
        y = y.reshape(B, C, *y.shape[1:])          # [B, C, N, Ho, Wo]
        y = jnp.transpose(y, (0, 3, 4, 1, 2))      # [B, Ho, Wo, C, N]
    return y if keep_bank else y[..., 0]


def resolve_strip_tile(H: int, W: int, w: int, border: BorderSpec,
                       regime: str, strip_h: int, tile_w: int
                       ) -> Tuple[int, int, int, int]:
    """Clamp caller strip/tile knobs into plan geometry: ``(S, Tw, Ho, Wo)``.

    ``small`` is the pixel-cache regime (one strip × one lane-padded tile =
    the whole plane resident); ``stream`` aligns strips to whole sublane
    tiles of at least 2r rows (``halo.align_strip``: every strip origin
    stays tile-aligned, and only the first/last strips ever touch a frame
    edge) and lane-aligns column tiles. Shared by the kernel wrapper and
    the ``CompiledFilter`` planner so the accounting plan the pipeline
    reports is byte-identical to the plan the kernel runs."""
    r = (w - 1) // 2
    if border.same_size:
        Ho, Wo = H, W
    else:
        Ho, Wo = H - 2 * r, W - 2 * r
    if regime == "small":
        S, Tw = Ho, Wo + ((-Wo) % LANE)
    elif regime == "stream":
        S = halo.align_strip(max(int(strip_h), 1), Ho, r)
        Tw = min(tile_w + ((-tile_w) % LANE), Wo + ((-Wo) % LANE))
    else:
        raise ValueError(regime)
    return S, Tw, Ho, Wo


@functools.partial(
    jax.jit,
    static_argnames=("form", "border", "regime", "strip_h", "tile_w",
                     "interpret", "requant", "overlap", "grid_order"))
def _filter2d_pallas_planes(planes: jax.Array, coeffs: jax.Array,
                            q_params: Optional[jax.Array] = None, *,
                            form: str, border: BorderSpec, regime: str,
                            strip_h: int, tile_w: int, interpret: bool,
                            requant: Optional[RequantSpec] = None,
                            overlap: bool = True,
                            grid_order: str = "filters_innermost"
                            ) -> jax.Array:
    """planes: [M, H, W]; coeffs: [N, w, w] (or [N, 2, w] factors for
    ``form='separable'``). Returns [M, N, Ho, Wo].

    ``requant`` here is the *gain-free* static half of the spec (rounding
    mode + storage dtype — what shapes the trace and the plan); the
    actual per-filter (multiplier, shift) table is the traced ``q_params``
    operand, so a served pipeline swaps gains without recompiling.
    ``overlap`` selects the double-buffered LD∥EX∥ST kernel (default) or
    the serial reference; ``grid_order`` the innermost grid dim (the fill
    guard follows it — both orders are parity-pinned)."""
    M, H, W = planes.shape
    w = coeffs.shape[-1]
    S, Tw, Ho, Wo = resolve_strip_tile(H, W, w, border, regime, strip_h,
                                       tile_w)

    # the plan carries the *storage* dtype AND the output epilogue: byte
    # accounting and the quantized constant(c) follow the narrow stream,
    # and the requant spec (when set) makes the write side narrow too.
    plan = halo.make_plan(H, W, w, border, S, Tw, dtype=planes.dtype,
                          requant=requant)
    # the kernel's DMAs move whole (8, 128) tiles: a frame that is not a
    # whole number of tiles is zero-padded up to the plan's span (one
    # extra HBM pass; the halo mux never reads the pad as frame data)
    pad_h, pad_w = plan.rows.span - H, plan.cols.span - W
    if pad_h or pad_w:
        planes = jnp.pad(planes, ((0, 0), (0, pad_h), (0, pad_w)))
    # trace-time op-name prefix only (profiler/HLO readability):
    # named_scope costs nothing at runtime and survives jax.export
    with jax.named_scope(f"repro.filter2d.pallas.{regime}"):
        y = K.filter2d_halo(planes, coeffs, plan, q_params=q_params,
                            form=form, interpret=interpret, overlap=overlap,
                            grid_order=grid_order)
    return y[:, :, :Ho, :Wo]


def filter2d_pallas(frame: jax.Array, coeffs: jax.Array, *,
                    form: str = "direct",
                    border: BorderSpec = BorderSpec("mirror"),
                    regime: str = "stream", strip_h: int = 128,
                    tile_w: int = 512, separable=False,
                    requant: Optional[RequantSpec] = None,
                    overlap: bool = True,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Pallas-kernel 2D filter. frame: [H,W] | [H,W,C] | [B,H,W,C].

    ``regime='small'`` keeps each plane VMEM-resident (pixel-cache regime);
    ``'stream'`` streams row strips × column tiles, each DMA'd on demand
    from the un-tiled frame (row-buffer regime) — the VMEM working set is
    bounded by ``strip_h × tile_w`` for any frame size. Batch/channel
    planes ride the kernel grid. All border policies (``zero``/
    ``constant(c)``, ``replicate``, ``reflect``, ``mirror_dup``, ``wrap``,
    ``neglect``) are resolved natively inside the kernel by the halo
    engine — no fallback path. ``separable='auto'`` routes rank-1 filters
    through the fused 2w-MAC row/column-pass kernel; ``separable=(u, v)``
    supplies explicit factors (the only separable route for fixed-point
    frames, which need an exact integer factorization).

    Fixed-point contract (paper §IV, B=8): int8/uint8/int16 frames stream
    through HBM, the halo DMAs and the VMEM scratch at their 1-2 byte
    storage width — every border policy muxes on the integer dtype, with
    ``constant(c)`` quantized to it — widen to int32 only at the MAC, and
    return int32 bit-exact with ``core.filter2d``. Pass ``requant`` (a
    :class:`~repro.core.requant.RequantSpec`) to fuse the output scaler
    into the kernel: the int32 accumulator is scaled, rounded and
    saturated back to the spec's storage dtype *before the store*, so the
    stream is narrow in BOTH directions (an int8→int8 round trip moves
    ≈2 HBM bytes/pixel instead of ≈5). Without it the caller owns
    requantisation.

    ``overlap=True`` (default) runs the double-buffered kernel — two-bank
    scratch, prefetched strip DMA, async stores; ``overlap=False`` the
    serial reference path (bit-identical output, no LD/EX/ST overlap).

    Thin wrapper over the plan-and-execute front door: prefer
    ``core.pipeline.Filter2D(...).compile(frame, 'pallas')`` for served
    pipelines — it caches the compiled plan and swaps coefficients,
    separable factors and requant gains without retracing.
    """
    from repro.core.pipeline import Filter2D
    interpret = _default_interpret() if interpret is None else interpret
    rq = resolve_requant(frame.dtype, requant)
    uv = resolve_separable(frame.dtype, coeffs, separable)
    window = (int(jnp.shape(uv[0])[0]) if uv is not None
              else int(jnp.shape(coeffs)[-1]))
    spec = Filter2D(window=window, form=form, border=border,
                    separable=uv is not None,
                    dtype=jnp.dtype(frame.dtype).name,
                    requant=rq.gain_free() if rq is not None else None)
    cf = spec.compile(frame, "pallas", regime=regime, strip_h=strip_h,
                      tile_w=tile_w, interpret=interpret, overlap=overlap)
    return cf(frame, uv if uv is not None else coeffs, gains=rq)


def filter_bank_pallas(frame: jax.Array, bank: jax.Array, *,
                       form: str = "direct",
                       border: BorderSpec = BorderSpec("mirror"),
                       regime: str = "stream", strip_h: int = 128,
                       tile_w: int = 512,
                       requant: Optional[RequantSpec] = None,
                       overlap: bool = True,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Apply a bank of N filters in one kernel launch: bank [N, w, w] ->
    output [..., N]. The filter dim is a kernel grid dimension — the halo
    scratch is filled once per (plane, tile, strip) and reused for all N
    coefficient sets (the paper's coefficient file, folded into the grid),
    under every border policy. Fixed-point frames follow the contract of
    :func:`filter2d_pallas`: narrow storage end-to-end, one int32
    accumulator per bank filter, int32 out — or, with ``requant``, each
    bank lane requantised by its own (multiplier, shift) scaler (tuples in
    the spec, one entry per filter, riding the kernel's params operand)
    and stored at the spec's storage width.

    Thin wrapper over ``core.pipeline.Filter2D`` (``num_filters=N``) —
    prefer the compiled front door for served pipelines.
    """
    from repro.core.pipeline import Filter2D
    interpret = _default_interpret() if interpret is None else interpret
    n = int(jnp.shape(bank)[0])
    rq = resolve_requant(frame.dtype, requant, num_filters=n)
    spec = Filter2D(window=int(jnp.shape(bank)[-1]), form=form, border=border,
                    num_filters=n,
                    dtype=jnp.dtype(frame.dtype).name,
                    requant=rq.gain_free() if rq is not None else None)
    cf = spec.compile(frame, "pallas", regime=regime, strip_h=strip_h,
                      tile_w=tile_w, interpret=interpret, overlap=overlap)
    return cf(frame, bank, gains=rq)
