"""In-kernel halo engine: lean border management for read-once streaming.

The paper's second headline contribution (§III) is a *lean border pixel
management policy*: borders are resolved inside the streaming datapath by a
small index multiplexer in front of the window cache — never by stalling
the stream or materialising a padded frame. This module is that engine for
the Pallas kernels. Each grid step DMAs the strip × tile window it needs
**straight from the un-tiled frame in HBM** into a VMEM scratch with halo
margins — rounded out to whole (8, 128) tiles at aligned offsets, the only
DMA slices the TPU compiler accepts — then realises the border policy on
the scratch edges:

  * ``constant``/``zero``     — constant fill of the halo rows/cols;
  * ``duplicate``/``replicate`` — in-VMEM copy of the edge row/col;
  * ``mirror``/``reflect`` and ``mirror_dup`` — in-VMEM reversed copies;
  * ``wrap``                  — prologue DMAs that fetch the opposite frame
                                edge (rows at the first/last strip, columns
                                at the first/last tile, plus the four torus
                                corners) directly from HBM into staging
                                slots, which the mux copies from.

The frame is therefore never pre-extended, duplicated or re-laid-out in
HBM: the stream reads HBM once (plus the tile-aligned strip overlap —
8 rows a side, 128 columns a side between tiles — and the tile-wide wrap
edges), which is the paper's lean-border
property restated for a memory-bound accelerator: border handling must not
disturb the stream.

Everything here is *static* planning: ``make_plan`` turns (frame, window,
strip, tile, BorderSpec) geometry into per-edge ``AxisClass`` records with
Python-int offsets/sizes, so the kernel body (``fill_ext``) emits a fixed,
small set of ``pl.when``-guarded DMAs and mux copies — the hardware mux,
traced. Only interior block offsets are dynamic (a grid-index multiply).

The fill is two-phase so the kernel can double-buffer it: ``start_fill``
issues every DMA for a (strip, tile) window into one scratch *bank* and
returns with the copies in flight; ``wait_fill`` (same arguments, same
``pl.when`` structure, so the wait-side descriptors pair one-to-one with
the started copies) lands them and then runs the in-VMEM policy mux on
that bank. The kernel prefetches strip ``s+1`` into the alternate bank
while reducing strip ``s`` — the LD/EX overlap of an FPGA line buffer,
where the next w−1 rows shift in while the current window is consumed.
``fill_ext`` (phase ``'both'``) is the serial reference path: start+wait
back-to-back, one bank — bit-identical output, no overlap.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.border_spec import BorderSpec, min_extent, quantize_constant
from repro.core.requant import RequantSpec
from repro.obs import events as obs_events

LANE = 128      # TPU lane width: last-dim tiling of every memref
SUBLANE = 8     # second-minor tiling Mosaic lays frame and scratch out in

# Default per-step VMEM budget for derived strip/tile geometry: the bound
# core/streaming uses too, half the TPU compiler's default 16 MiB scoped
# VMEM limit (the kernel asks for twice its planned working set).
DEFAULT_VMEM_BUDGET = 8 * 2 ** 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Static geometry: axis classes and the halo plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AxisClass:
    """Static DMA/mux geometry of one *edge* block along one axis.

    The scratch window of block ``index`` covers frame elements
    ``[index·B - lead, index·B - lead + window)``. ``size`` elements
    starting at frame ``src0`` land at scratch offset ``dst0`` (both
    tile-aligned, clipped to the padded frame span); ``head`` elements
    before the frame and ``tail`` elements past it are halo slots the
    policy mux fills. ``fend`` is the scratch slot of frame element
    ``extent`` (one past the last real element): tail slots start there.
    Slots outside ``[shift, shift + B + 2r)`` feed no output, and slots
    past ``fend + tail`` feed only cropped outputs; both are left as the
    DMA (or nothing) wrote them.
    """

    index: int
    src0: int
    dst0: int
    size: int
    head: int
    tail: int
    fend: int


@dataclasses.dataclass(frozen=True)
class AxisPlan:
    """One axis (rows or cols) of the halo plan: frame extent ``extent``
    (``span`` once padded up to the ``align`` tiling the DMAs move in)
    split into ``n`` grid blocks of ``block`` output elements, window
    radius ``r`` and window offset ``off`` (r for same-size policies, 0
    for neglect).

    Every DMA window is tile-aligned: scratch slot 0 is frame element
    ``index·block - lead`` with ``lead = round_up(off, align)``, the
    window is ``window`` (a multiple of ``align``) elements long, and the
    w taps of output ``o`` read scratch slots ``shift + o + [0, 2r]``
    (``shift = lead - off``) — the over-fetch is shifted away inside
    VMEM. ``bands`` (wrap only) are the opposite-edge fetches as
    ``(src0, size, dst0)``: the head band holds the frame's last ``off``
    elements, the tail band its first, both staged past ``window`` for
    the mux to copy into the halo slots. Blocks not covered by an edge
    class are *interior*: full aligned windows at dynamic offset
    ``index·block - lead``, entirely inside the span."""

    extent: int
    span: int
    align: int
    block: int
    n: int
    r: int
    off: int
    lead: int
    window: int
    bands: Tuple[Tuple[int, int, int], ...]
    specials: Tuple[AxisClass, ...]

    @property
    def has_interior(self) -> bool:
        return self.n > len(self.specials)

    @property
    def shift(self) -> int:
        return self.lead - self.off

    @property
    def scratch(self) -> int:
        """Scratch extent: the DMA'd window plus the wrap staging bands."""
        return self.window + sum(b[1] for b in self.bands)


@dataclasses.dataclass(frozen=True)
class HaloPlan:
    """The full static plan: row axis × col axis × policy. ``eh × ew`` is
    the VMEM scratch (aligned window plus wrap staging); hashable, closed
    over by the kernel body. ``dtype_bytes`` is the *storage* width the
    stream moves at (1 for int8 frames — the paper's B=8 pixel bus), and
    ``constant`` is already quantized against that storage dtype. The
    kernel reads planes of ``rows.span × cols.span`` (the frame, padded
    up to whole tiles when it is not already).

    The output side is plan geometry too: ``out_dtype_bytes`` is the
    width each pixel is *written* at, and ``requant`` (when set) is the
    fused scale→round→saturate epilogue that narrows the int32
    accumulator back to storage width before the store — the write-side
    half of the paper's B-bit bus."""

    policy: str
    constant: float
    rows: AxisPlan
    cols: AxisPlan
    eh: int
    ew: int
    dtype_bytes: int = 4
    out_dtype_bytes: int = 4
    requant: Optional[RequantSpec] = None
    acc_bytes: int = 4                   # MAC accumulator width (int32/float)


def _axis_class(i: int, L: int, span: int, B: int, r: int, off: int,
                lead: int, window: int) -> AxisClass:
    a = i * B - lead                      # scratch 0 ≡ frame element a
    src0 = max(a, 0)
    size = min(span, a + window) - src0
    assert size >= 1, (i, L, B, r, off)
    need0 = a + lead - off                # first frame element a tap reads
    # halo slots outside the frame that still feed valid (un-cropped)
    # outputs: before element 0 (head) and from element L on (tail)
    head = max(0, min(off, -need0))
    tail = max(0, min(off, need0 + B + 2 * r - L))
    return AxisClass(index=i, src0=src0, dst0=src0 - a, size=size,
                     head=head, tail=tail, fend=L - a)


def _axis_plan(L: int, B: int, r: int, same_size: bool, align: int,
               wrap: bool = False) -> AxisPlan:
    off = r if same_size else 0
    out_extent = L if same_size else L - 2 * r
    assert out_extent >= 1 and B >= 1, (L, r, B)
    n = max(1, -(-out_extent // B))      # B may exceed out_extent (lane pad)
    span = _round_up(L, align)
    lead = _round_up(off, align)
    window = _round_up(lead - off + B + 2 * r, align)
    if n > 1:
        # aligned blocks keep every window origin on a tile boundary; with
        # B >= max(lead, 2r) only the first two and the last two blocks
        # can touch a frame edge, everything else is interior
        assert B % align == 0 and B >= max(lead, 2 * r), (B, r, align)
    bands = ()
    if wrap:
        h0 = (L - off) // align * align   # the last off elements, aligned
        bands = ((h0, span - h0, window), (0, lead, window + span - h0))
    specials = {}
    for i in (0, 1, n - 2, n - 1):
        if i < 0 or i >= n or i in specials:
            continue
        c = _axis_class(i, L, span, B, r, off, lead, window)
        if c.head or c.tail or c.size < window:
            specials[i] = c
    for i in range(n):                    # interior blocks are fully in-span
        if i not in specials:
            a = i * B - lead
            assert a >= 0 and a + window <= span, (i, a, L)
    return AxisPlan(extent=L, span=span, align=align, block=B, n=n, r=r,
                    off=off, lead=lead, window=window, bands=bands,
                    specials=tuple(specials[k] for k in sorted(specials)))


def datapath_byte_widths(dtype, requant: Optional[RequantSpec] = None
                         ) -> Tuple[int, int, int]:
    """(storage, accumulator, output) byte widths of one datapath.

    THE single statement of the fixed-point width rule (paper §IV):
    integer frames stream at storage width and accumulate in int32; the
    output leaves at the accumulator width unless a requantising epilogue
    narrows it back to its storage dtype. ``make_plan``,
    ``derive_strip_tile`` and the ``CompiledFilter`` planner all consume
    this one helper so the auto-selection estimate can never drift from
    the plan the kernel runs."""
    db = int(np.dtype(dtype).itemsize)
    integer = np.dtype(dtype).kind in ("i", "u")
    acc = 4 if integer else db
    out = requant.dtype_bytes if requant is not None else acc
    return db, acc, out


def make_plan(H: int, W: int, w: int, spec: BorderSpec, strip_h: int,
              tile_w: int, dtype=np.float32,
              requant: Optional[RequantSpec] = None) -> HaloPlan:
    """Build the static halo plan for an (H, W) frame, w×w window, strip
    height ``strip_h`` and lane-aligned tile width ``tile_w``. ``dtype``
    is the frame's *storage* dtype: it sets the plan's byte accounting
    (``read_bytes_per_pixel``) and quantizes the ``constant(c)`` border
    value to what the narrow stream can actually hold — the same shared
    rule (``border_spec.quantize_constant``) the core oracle applies.

    ``requant`` bakes the fused output scaler into the plan: integer
    frames then *write* at the spec's storage width instead of the int32
    accumulator's 4 bytes (``out_dtype_bytes`` follows suit — the number
    ``hbm_write_bytes_per_pixel`` reports). Float frames take no requant.
    """
    r = (w - 1) // 2
    need = min_extent(spec, r)
    if min(H, W) < need:
        raise ValueError(f"policy {spec.policy!r} with radius {r} needs "
                         f"frames of at least {need} rows/cols; got "
                         f"{(H, W)}")
    integer = np.dtype(dtype).kind in ("i", "u")
    if requant is not None and not integer:
        raise ValueError("requant is the fixed-point epilogue; "
                         f"storage dtype {np.dtype(dtype).name} takes none")
    db, acc_bytes, out_bytes = datapath_byte_widths(dtype, requant)
    wrap = spec.policy == "wrap"
    rows = _axis_plan(H, strip_h, r, spec.same_size, SUBLANE, wrap)
    cols = _axis_plan(W, tile_w, r, spec.same_size, LANE, wrap)
    return HaloPlan(policy=spec.policy,
                    constant=quantize_constant(spec.constant, dtype),
                    rows=rows, cols=cols, eh=rows.scratch, ew=cols.scratch,
                    dtype_bytes=db, out_dtype_bytes=out_bytes,
                    requant=requant, acc_bytes=acc_bytes)


def strip_floor(r: int) -> int:
    """The shallowest strip a multi-strip plan may take: whole sublane
    tiles (every strip origin stays tile-aligned), at least 2r deep."""
    return _round_up(max(2 * r, 1), SUBLANE)


def align_strip(strip_h: int, Ho: int, r: int) -> int:
    """Clamp a strip height into plan geometry: ``Ho`` (one strip) when it
    covers the frame, else whole sublane tiles, never below
    :func:`strip_floor` (rounding down keeps the VMEM ask)."""
    s = min(int(strip_h), Ho)
    if s >= Ho:
        return Ho
    s = max(s - s % SUBLANE, strip_floor(r))
    return min(s, Ho)


# -- VMEM accounting ---------------------------------------------------------
#
# What one grid step holds in VMEM: the buffers the kernel allocates (the
# halo scratch banks, the output tile banks, the coefficient file — the
# bytes ``repro.analysis`` finds in the traced kernel) plus what the body
# materialises on top: the scratch window widened to the accumulator
# dtype, the shifted tap slices of the reduction, the accumulator and the
# requant epilogue's temporaries. The budget checks the sum.
#
# The tap term is calibrated against the TPU compiler: Mosaic lays each
# shifted slice of the widened window out as its own VMEM buffer, so the
# scoped VMEM a v5e compile asks for grows with w² and with the window
# rows (bisected limits, 1920-wide tiles, strips of 16/32/64 rows, w=3..7,
# uint8 and float32). Counting one window-rows × tile-width slice per tap
# over-states every measured need by 1.5-1.8x, which the compile limit
# (:func:`repro.kernels.filter2d.kernel.vmem_limit_bytes`) then relies on.

# S×Tw temporaries the fused requant epilogue holds beside the accumulator
# (the scaled product, the shift splat, the rounding term, the result)
REQUANT_TILES = 4


def tap_slices(w: int, separable: bool = False) -> int:
    """Shifted window slices the reduction materialises: w² taps, or 2w
    for the separable column + row passes."""
    return 2 * w if separable else w * w


def plan_banks(plan: HaloPlan, num_filters: int = 1,
               overlap: bool = True) -> Tuple[int, int]:
    """(ext_banks, out_banks) the kernel allocates for this plan.

    The input scratch is double-banked only when there is a next strip to
    prefetch (``rows.n > 1``); the output buffer only when there is a
    later step to pre-wait behind (more than one (strip, filter) step per
    tile). Single-strip single-filter plans collapse both to 1 bank — the
    serial working set — so the pixel-cache regime pays nothing for the
    overlap machinery it cannot use."""
    if not overlap:
        return 1, 1
    ext_banks = 2 if plan.rows.n > 1 else 1
    out_banks = 2 if plan.rows.n * num_filters > 1 else 1
    return ext_banks, out_banks


def _vmem_bytes(*, S, Tw, win_h, win_w, eh, ew, w, db, acc_b, out_b,
                num_filters, separable, requant, ext_banks,
                out_banks) -> Tuple[int, int]:
    """(buffers, body) bytes of one grid step — the one formula every
    VMEM figure in the repo comes from."""
    coeff = num_filters * (2 * w if separable else w * w) * acc_b
    buffers = (ext_banks * eh * ew * db + out_banks * S * Tw * out_b
               + coeff)
    tile = S * Tw * acc_b
    body = (win_h * win_w * acc_b                 # widened window
            + tap_slices(w, separable) * win_h * Tw * acc_b
            + tile)                               # accumulator
    if requant:
        body += REQUANT_TILES * tile
    return buffers, body


def _plan_vmem(plan: HaloPlan, num_filters: int, separable: bool,
               overlap: bool) -> Tuple[int, int]:
    ext_banks, out_banks = plan_banks(plan, num_filters, overlap)
    return _vmem_bytes(
        S=plan.rows.block, Tw=plan.cols.block, win_h=plan.rows.window,
        win_w=plan.cols.window, eh=plan.eh, ew=plan.ew,
        w=2 * plan.rows.r + 1, db=plan.dtype_bytes, acc_b=plan.acc_bytes,
        out_b=plan.out_dtype_bytes, num_filters=num_filters,
        separable=separable, requant=plan.requant is not None,
        ext_banks=ext_banks, out_banks=out_banks)


def plan_vmem_buffers(plan: HaloPlan, *, num_filters: int = 1,
                      separable: bool = False, overlap: bool = True) -> int:
    """VMEM bytes the kernel *allocates* for this plan: ``ext_banks`` ×
    the ``eh × ew`` scratch at storage width, ``out_banks`` × the
    ``strip × tile`` output tile at the plan's write width, and the
    coefficient file at the accumulator width (bank counts from
    :func:`plan_banks`). Exactly what the static verifier traces."""
    return _plan_vmem(plan, num_filters, separable, overlap)[0]


def plan_vmem_working_set(plan: HaloPlan, *, num_filters: int = 1,
                          separable: bool = False,
                          overlap: bool = True) -> int:
    """VMEM bytes per grid step straight from a *built* plan: the
    allocations of :func:`plan_vmem_buffers` plus what the kernel body
    materialises (the widened scratch window, :func:`tap_slices` shifted
    slices, the accumulator, the requant temporaries). This is what the
    ``CompiledFilter`` front door reports, what ``execution='auto'`` and
    :func:`derive_strip_tile` hold to the ``vmem_budget``, and what the
    kernel's compiler VMEM limit is sized from."""
    return sum(_plan_vmem(plan, num_filters, separable, overlap))


def stream_vmem_working_set(strip_h: int, tile_w: int, w: int,
                            dtype_bytes: int = 4, *,
                            separable: bool = False,
                            num_filters: int = 1,
                            acc_dtype_bytes: int = None,
                            out_dtype_bytes: int = None,
                            ext_banks: int = 1,
                            out_banks: int = 1,
                            requant: bool = False) -> int:
    """Bytes resident in VMEM per stream grid step (the row-buffer bound)
    for an interior ``strip_h × tile_w`` block of a same-size plan — the
    terms of :func:`plan_vmem_working_set` with the aligned halo window
    (``round_up(r, 8)`` rows and ``round_up(r, 128)`` columns each side)
    and no wrap staging. A function of (strip_h, tile_w, w, banks) ONLY —
    never of the frame dimensions; this is the invariant the 2D tiling
    exists to provide.

    Dtype-aware in both directions: ``dtype_bytes`` is the *storage* width
    (the scratch the DMA fills), ``acc_dtype_bytes`` the accumulator width
    (defaults to the storage width — pass 4 for the fixed-point
    int8/int16-in datapath), and ``out_dtype_bytes`` the width of the
    output tile (defaults to the accumulator width; pass the storage width
    when the plan carries the requantising epilogue, with ``requant``).
    """
    if acc_dtype_bytes is None:
        acc_dtype_bytes = dtype_bytes
    if out_dtype_bytes is None:
        out_dtype_bytes = acc_dtype_bytes
    r = (w - 1) // 2
    win_h = _round_up(_round_up(r, SUBLANE) + strip_h + r, SUBLANE)
    win_w = _round_up(_round_up(r, LANE) + tile_w + r, LANE)
    return sum(_vmem_bytes(
        S=strip_h, Tw=tile_w, win_h=win_h, win_w=win_w, eh=win_h, ew=win_w,
        w=w, db=dtype_bytes, acc_b=acc_dtype_bytes, out_b=out_dtype_bytes,
        num_filters=num_filters, separable=separable, requant=requant,
        ext_banks=ext_banks, out_banks=out_banks))


def derive_strip_tile(H: int, W: int, w: int, *, dtype=np.float32,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET,
                      num_filters: int = 1, separable: bool = False,
                      requant: Optional[RequantSpec] = None,
                      border: Optional[BorderSpec] = None,
                      strip_h: Optional[int] = None,
                      tile_w: Optional[int] = None,
                      overlap: bool = True) -> Tuple[int, int]:
    """Pick ``(strip_h, tile_w)`` for a stream plan from a VMEM budget.

    From static accounting only: every candidate is a real
    :func:`make_plan` plan, scored by its own
    :func:`plan_vmem_working_set` (banks, widened window, tap slices,
    requant temporaries — whatever the kernel holds for that ``overlap``)
    and its own :func:`read_amplification`, so the geometry chosen here
    is the geometry the accounting reports.

    Both knobs free: every lane-aligned tile width from the full output
    width down to one lane is a candidate; each gets the deepest aligned
    strip the budget holds at that width, and the candidate minimising
    the read amplification wins — with a 2% slack in favour of *wider*
    tiles, which amortise the row-mux work and DMA descriptors over longer
    rows at equal traffic. Narrow storage dtypes and a requantised output
    tile free bytes, which lands here as deeper strips (or full-width
    tiles at the same depth).

    A caller-supplied ``strip_h``/``tile_w`` is honoured (clamped to the
    frame and aligned, :func:`align_strip`) and only the *free* knob is
    derived against it: a fixed tile gets the deepest strip the budget
    holds at that width; a fixed strip gets the widest tile that still
    fits that many rows.

    Edge cases clamp instead of overderiving: frames narrower than one
    lane tile or shallower than :func:`strip_floor` collapse to the
    degenerate 1-strip/1-tile plan, and starved budgets clamp to the
    minimum viable strip — the plan then overruns the budget rather than
    breaking the aligned-strip invariant multi-strip plans require.
    """
    # the policy shapes the geometry only through wrap's staging bands and
    # neglect's cropping: any other same-size policy plans alike
    border = BorderSpec("duplicate") if border is None else border
    r = (w - 1) // 2
    Ho = H if border.same_size else max(H - 2 * r, 1)
    Wo = W if border.same_size else max(W - 2 * r, 1)
    wo_pad = Wo + (-Wo) % LANE

    def _traced(s: int, t: int, cands=(), why: str = "") -> Tuple[int, int]:
        # decision-trace emission: the candidate scan and the winner land
        # as one PlanEvent when observability is on; pure pass-through off
        if obs_events.enabled():
            obs_events.emit(obs_events.PlanEvent(
                H=int(H), W=int(W), window=int(w),
                dtype=np.dtype(dtype).name, vmem_budget=int(vmem_budget),
                overlap=bool(overlap),
                candidates=tuple((int(ct), int(cs), float(ca))
                                 for ct, cs, ca in cands),
                strip_h=int(s), tile_w=int(t), why=why))
        return s, t

    def plan(s: int, t: int) -> HaloPlan:
        return make_plan(H, W, w, border, s, t, dtype=dtype, requant=requant)

    def fits(s: int, t: int) -> bool:
        return plan_vmem_working_set(
            plan(s, t), num_filters=num_filters, separable=separable,
            overlap=overlap) <= vmem_budget

    def max_strip(tile: int) -> int:
        # one strip when the whole frame fits, else the deepest aligned
        # strip that does (the working set grows with the strip)
        if fits(Ho, tile):
            return Ho
        lo = best = strip_floor(r) // SUBLANE
        hi = -(-Ho // SUBLANE) - 1            # candidate strips 8·k < Ho
        while lo <= hi:
            mid = (lo + hi) // 2
            if fits(align_strip(mid * SUBLANE, Ho, r), tile):
                best, lo = mid, mid + 1
            else:
                hi = mid - 1
        return align_strip(best * SUBLANE, Ho, r)

    if tile_w is not None:
        tile = max(min(tile_w + (-tile_w) % LANE, wo_pad), LANE)
        if strip_h is not None:
            return _traced(align_strip(strip_h, Ho, r), int(tile),
                           why="caller fixed both knobs (clamped to frame)")
        return _traced(max_strip(tile), int(tile),
                       why=f"caller fixed tile_w={int(tile)}: deepest "
                           "strip the budget holds at that width")

    if strip_h is not None:
        # fixed strip: widest tile whose budget holds that many rows
        s = align_strip(strip_h, Ho, r)
        tile = wo_pad
        while not fits(s, tile) and tile > LANE:
            tile = max(LANE, tile // 2 - (tile // 2) % LANE)
        return _traced(s, int(tile),
                       why=f"caller fixed strip_h={int(strip_h)}: widest "
                           "tile whose budget holds that depth")

    cands, fitting = [], []               # widest tile first
    tile = wo_pad
    while True:
        s = max_strip(tile)
        cands.append((tile, s, read_amplification(plan(s, tile))))
        if fits(s, tile):
            fitting.append(cands[-1])
        if tile <= LANE:
            break
        tile = max(LANE, tile // 2 - (tile // 2) % LANE)
    if not fitting:
        # nothing fits: the smallest plan (one lane tile, the strip floor)
        # overruns the budget least
        tile, s, _ = cands[-1]
        return _traced(s, int(tile), cands=cands,
                       why="no candidate fits the budget: the minimum "
                           "plan (one lane tile, the strip floor)")
    best = min(a for _, _, a in fitting)
    for tile, s, amp in fitting:
        if amp <= best * 1.02:            # widest within 2% of optimal
            return _traced(s, int(tile), cands=fitting,
                           why=f"widest tile within 2% of the minimum "
                               f"read amplification ({best:.4f}) over "
                               f"{len(fitting)} fitting lane-aligned "
                               "candidates")
    raise AssertionError("unreachable: best candidate always qualifies")


def read_amplification(plan: HaloPlan) -> float:
    """HBM elements DMA'd per plane / frame elements — the cost analysis of
    the read-once claim, counted from the DMAs the kernel issues. Every
    (strip, tile) step fetches its aligned window (clipped to the padded
    frame) plus, under wrap, the opposite-edge bands at the edge blocks
    and their corners, so the total factors as (Σ row extents)(Σ col
    extents). ≈1 + 2·round_up(r, 8)/S + 2·round_up(r, 128)/Tw for
    multi-block axes; a single-block axis reads its extent once."""
    def sizes(ax: AxisPlan):
        by_idx = {c.index: c for c in ax.specials}
        total = 0
        for i in range(ax.n):
            c = by_idx.get(i)
            total += c.size if c is not None else ax.window
            if ax.bands and c is not None:
                total += ax.bands[0][1] * bool(c.head)
                total += ax.bands[1][1] * bool(c.tail)
        return total

    return (sizes(plan.rows) * sizes(plan.cols)
            / float(plan.rows.extent * plan.cols.extent))


def read_bytes_per_pixel(plan: HaloPlan) -> float:
    """HBM bytes *read* per frame pixel — the dtype-aware restatement of
    the read-once claim. An int8 stream reads ≈1.05 bytes/pixel at the
    default strip/tile sizes where float32 reads ≈4.2: the paper's 4×
    narrow-wordlength win, asserted structurally from the plan rather
    than measured."""
    return read_amplification(plan) * plan.dtype_bytes


def hbm_write_bytes_per_pixel(plan: HaloPlan) -> float:
    """HBM bytes *written* per output pixel — the write-side twin of
    ``read_bytes_per_pixel``, from the same static plan. One store per
    output pixel at ``out_dtype_bytes``: 4 for the wide accumulator
    (int32 / float32), the storage width when the plan carries a
    requantising epilogue — an int8-in/int8-out plan writes 1 byte/pixel,
    closing the paper's B-bit bus in BOTH directions."""
    return float(plan.out_dtype_bytes)


def hbm_bytes_per_pixel(plan: HaloPlan,
                        out_dtype_bytes: Optional[int] = None) -> float:
    """Total HBM round-trip traffic per pixel: the read side from the plan
    (storage dtype × read amplification) plus one output write at the
    plan's write width (``out_dtype_bytes`` overrides — kept for callers
    accounting a different epilogue than the plan's). An int8 frame with
    an int8 requant epilogue rounds to ≈2 bytes/pixel where the
    pre-epilogue datapath paid ≈5."""
    if out_dtype_bytes is None:
        out_dtype_bytes = plan.out_dtype_bytes
    return read_bytes_per_pixel(plan) + float(out_dtype_bytes)


# ---------------------------------------------------------------------------
# Kernel-side: DMA + in-VMEM policy mux
# ---------------------------------------------------------------------------


def _copy(src, dst, sem, phase: str = "both") -> None:
    """One DMA in the requested phase. ``'start'`` issues the copy and
    returns with it in flight; ``'wait'`` reconstructs the byte-identical
    descriptor and blocks on its semaphore; ``'both'`` is the serial
    start+wait pair. Start and wait sides MUST be emitted under identical
    conditions so every started copy is waited exactly once."""
    cp = pltpu.make_async_copy(src, dst, sem)
    if phase in ("both", "start"):
        cp.start()
    if phase in ("both", "wait"):
        cp.wait()


def _variants(ax: AxisPlan):
    """(cond(idx) | None, src_off(idx), dst0, size, cls | None) per block
    class. ``cond`` is None when the class is unconditional (single-block
    axis). Interior offsets are ``idx·block - lead``: whole tiles, since
    both terms are multiples of the axis tiling."""
    out = []
    special_idx = tuple(c.index for c in ax.specials)
    for c in ax.specials:
        cond = None if ax.n == 1 else (lambda idx, k=c.index: idx == k)
        out.append((cond, (lambda idx, s=c.src0: s), c.dst0, c.size, c))
    if ax.has_interior:
        def cond(idx, ks=special_idx):
            t = None
            for k in ks:
                e = idx != k
                t = e if t is None else jnp.logical_and(t, e)
            return t
        out.append((cond if special_idx else None,
                    (lambda idx, ax=ax: idx * ax.block - ax.lead),
                    0, ax.window, None))
    return out


def _edge_bands(ax: AxisPlan, cls: Optional[AxisClass]):
    """The wrap bands block class ``cls`` fetches: the head band when it
    has head slots, the tail band when it has tail slots."""
    if not ax.bands or cls is None:
        return []
    return [b for b, n in zip(ax.bands, (cls.head, cls.tail)) if n]


def _mux_src_head(policy: str, ax: AxisPlan, c: AxisClass,
                  k: int) -> Optional[int]:
    """Scratch slot sourcing halo slot dst0-k ≡ frame element -k (head>0
    implies src0 == 0, so frame q sits at scratch dst0+q; wrap reads
    element L-k from the staged head band)."""
    if policy == "duplicate":
        return c.dst0
    if policy == "mirror":
        return c.dst0 + k
    if policy == "mirror_dup":
        return c.dst0 + k - 1
    if policy == "wrap":
        src0, _, dst0 = ax.bands[0]
        return dst0 + ax.extent - k - src0
    return None                           # constant


def _mux_src_tail(policy: str, ax: AxisPlan, c: AxisClass,
                  k: int) -> Optional[int]:
    """Scratch slot sourcing halo slot fend+k ≡ frame element L+k (frame
    L-1 sits at fend-1; wrap reads element k from the staged tail
    band)."""
    if policy == "duplicate":
        return c.fend - 1
    if policy == "mirror":
        return c.fend - 2 - k
    if policy == "mirror_dup":
        return c.fend - 1 - k
    if policy == "wrap":
        return ax.bands[1][2] + k
    return None                           # constant


def _const_fill(shape, value, dtype):
    """Constant splat the Mosaic backend can lower at every storage dtype:
    narrow-int scalar broadcasts (int16/uint8) hit NotImplementedError in
    current Mosaic, so integer fills splat at int32 and cast down to the
    storage dtype (``value`` is already quantized into its range)."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return jnp.full(shape, int(value), jnp.int32).astype(dtype)
    return jnp.full(shape, value, dtype)


def _mux_axis(ext_ref, c: AxisClass, plan: HaloPlan, axis: int) -> None:
    """Fill one edge class's halo slots by the in-VMEM policy mux. Row mux
    (axis 0) runs full scratch width (under wrap it also carries the
    staged torus corners into the halo rows); col mux (axis 1) runs the
    window's full height afterwards, so corners get row-muxed-then-col-
    muxed values — the same composition as numpy.pad axis-by-axis."""
    ax = plan.rows if axis == 0 else plan.cols

    def fill(e: int, src: Optional[int]) -> None:
        if axis == 0:
            if src is None:
                ext_ref[pl.ds(e, 1), :] = _const_fill(
                    (1, plan.ew), plan.constant, ext_ref.dtype)
            else:
                ext_ref[pl.ds(e, 1), :] = ext_ref[pl.ds(src, 1), :]
        else:
            rows = pl.ds(0, plan.rows.window)
            if src is None:
                ext_ref[rows, pl.ds(e, 1)] = _const_fill(
                    (plan.rows.window, 1), plan.constant, ext_ref.dtype)
            else:
                ext_ref[rows, pl.ds(e, 1)] = ext_ref[rows, pl.ds(src, 1)]

    for k in range(1, c.head + 1):
        fill(c.dst0 - k, _mux_src_head(plan.policy, ax, c, k))
    for k in range(c.tail):
        fill(c.fend + k, _mux_src_tail(plan.policy, ax, c, k))


def fill_ext(frame_ref, ext_ref, sem, i, j, plan: HaloPlan,
             phase: str = "both") -> None:
    """Fill the (eh, ew) VMEM scratch for grid step (strip ``i``, tile
    ``j``) from ``frame_ref``, the un-tiled [rows.span, cols.span] plane
    in ANY/HBM space.

    Emits, per (row-class × col-class) pair, one main-window DMA plus — for
    ``wrap`` — the opposite-edge band and torus-corner DMAs into the
    staging slots past the window; then the static in-VMEM edge fills of
    the policy mux. Every DMA moves whole (8, 128) tiles between
    tile-aligned offsets — the only slices Mosaic accepts — so the halo
    over-fetch is shifted away inside VMEM, by the mux's slot arithmetic
    and the reduction's tap offsets. All sizes are Python ints from the
    plan; only interior offsets are traced.

    ``phase='start'`` issues the DMAs (in flight on return, no mux);
    ``phase='wait'`` lands them and runs the policy mux; ``'both'`` is
    the serial reference. The ``pl.when`` guard structure depends only on
    (i, j, plan), so a ``'start'``/``'wait'`` pair with the same arguments
    emits byte-identical descriptor sets — every started DMA is waited
    exactly once, whichever scratch bank ``ext_ref`` views.
    """
    for rcond, rsrc, rdst0, rsize, rcls in _variants(plan.rows):
        for ccond, csrc, cdst0, csize, ccls in _variants(plan.cols):
            def emit(rsrc=rsrc, csrc=csrc, rdst0=rdst0, cdst0=cdst0,
                     rsize=rsize, csize=csize, rcls=rcls, ccls=ccls):
                # each axis' extents: the main window, then any wrap bands
                rows = [(rsrc(i), rsize, rdst0)]
                rows += _edge_bands(plan.rows, rcls)
                cols = [(csrc(j), csize, cdst0)]
                cols += _edge_bands(plan.cols, ccls)
                for ro, rn, rd in rows:
                    for co, cn, cd in cols:
                        _copy(frame_ref.at[pl.ds(ro, rn), pl.ds(co, cn)],
                              ext_ref.at[pl.ds(rd, rn), pl.ds(cd, cn)],
                              sem, phase)

            conds = [c for c in (rcond(i) if rcond else None,
                                 ccond(j) if ccond else None)
                     if c is not None]
            if not conds:
                emit()
            else:
                pl.when(functools.reduce(jnp.logical_and, conds))(emit)

    if phase == "start":
        return
    for c in plan.rows.specials:
        if c.head or c.tail:
            fn = functools.partial(_mux_axis, ext_ref, c, plan, 0)
            if plan.rows.n == 1:
                fn()
            else:
                pl.when(i == c.index)(fn)
    for c in plan.cols.specials:
        if c.head or c.tail:
            fn = functools.partial(_mux_axis, ext_ref, c, plan, 1)
            if plan.cols.n == 1:
                fn()
            else:
                pl.when(j == c.index)(fn)


def start_fill(frame_ref, bank_ref, sem, i, j, plan: HaloPlan) -> None:
    """Issue every fill DMA for (strip i, tile j) into scratch bank
    ``bank_ref`` (a per-bank view, e.g. ``ext_ref.at[b]``) and return with
    the copies in flight — including wrap's opposite-edge and torus-corner
    prologue fetches, which are parametric in ``i``/``j`` and so prefetch
    correctly for a *future* strip. ``sem`` is that bank's semaphore."""
    fill_ext(frame_ref, bank_ref, sem, i, j, plan, phase="start")


def wait_fill(frame_ref, bank_ref, sem, i, j, plan: HaloPlan) -> None:
    """Land the DMAs ``start_fill`` issued for the same (bank, i, j) and
    realise the border policy mux on that bank. Must mirror the start
    call's arguments exactly — the wait descriptors are reconstructed from
    them and pair with the in-flight copies by byte count."""
    fill_ext(frame_ref, bank_ref, sem, i, j, plan, phase="wait")
