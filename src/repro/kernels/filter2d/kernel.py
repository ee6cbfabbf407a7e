"""Pallas TPU kernels for streaming 2D spatial filtering (paper §II + §III).

One kernel, two buffering regimes (selected by the halo plan's geometry,
mirroring the paper's):

``small``   — the *pixel cache* regime: the plan degenerates to a single
              strip × a single tile, so the whole (halo-extended) plane
              lives in the VMEM scratch; one grid step computes one plane ×
              one filter. Valid for frames up to the VMEM budget.

``stream``  — the *row buffer* regime, generalised to **2D tiling**: the
              grid is (planes, column tiles, row strips, filters) and
              streams row strips sequentially within each lane-aligned
              column tile. Each strip step DMAs its S+2r input rows (the
              paper's w−1 row buffer, plus the strip body; rounded out to
              whole (8, 128) tiles, see ``halo``) straight from
              the **un-tiled frame in HBM** into the VMEM scratch — there
              is no pre-tiled, halo-duplicated HBM layout anywhere. The
              per-step VMEM working set is bounded by strip_h × tile_w
              (see ``halo.stream_vmem_working_set``), independent of frame
              height AND width — arbitrary-width (8K) frames stream under
              a fixed strip budget.

**Borders are resolved inside the kernel** by the halo engine
(``kernels/filter2d/halo``): the DMA gathers only in-frame pixels and the
policy (zero/constant, replicate, reflect, mirror-with-duplication, wrap)
is realised as an in-VMEM index mux on the scratch edges — wrap's
opposite-edge rows/cols/corners arrive by prologue DMAs. This is the
paper's lean border mux, traced: no stall, no extra HBM pass, every policy
native to the stream.

Both regimes fold **batch/channel planes and the filter bank into the
kernel grid** (no outer ``vmap``): input planes are [M, H, W], coefficients
[N, w, w], outputs [M, N, …]. Plane and column-tile grid dims are marked
``parallel`` (megacore-partitionable: each (plane, tile) owns its scratch);
the strip and filter dims stay ``arbitrary`` — strips so the stream order
is preserved, filters so the scratch filled at the first filter step is
reused by the rest of the bank (the coefficient file's read-once property;
the refill guard follows the grid order, so a ``strips_innermost`` grid
refills every step instead of reading stale scratch).

The stream regime is **double-buffered** by default (``overlap=True``):
the scratch and the output tile are two-bank, strip s+1's fill DMAs fly
while strip s is reduced, and each output store is issued async and
waited two steps later — the LD(s+1) ∥ EX(s) ∥ ST(s−1) pipeline of an
FPGA scratchpad design, with per-bank DMA semaphores keeping the
bookkeeping exact. ``overlap=False`` is the serial reference path
(bit-identical valid-region output; the parity sweep pins it).

The w² reduction supports the paper's four layouts (direct / transposed /
tree / compress) — see ``core/filter2d`` for the FPGA↔TPU mapping — plus a
**separable fast path**: rank-1 filters run a fused w-tap column pass +
w-tap row pass (2w MACs/pixel instead of w²).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.filter2d import apply_requant, is_fixed_point
from repro.kernels.filter2d import halo
from repro.kernels.filter2d.contract import KernelContract
from repro.kernels.filter2d.halo import (HaloPlan, plan_banks,
                                         plan_vmem_working_set)

LANE = halo.LANE  # TPU lane width: last-dim alignment target


def acc_dtype(storage_dtype):
    """The accumulator dtype for a given frame storage dtype.

    Fixed-point frames (int8/uint8/int16) stream and sit in VMEM at their
    narrow width but multiply-accumulate in int32 — the paper's B=8
    pixels onto wide DSP48 accumulation. Float frames accumulate at
    their own width.
    """
    return jnp.int32 if is_fixed_point(storage_dtype) else storage_dtype


def out_dtype(plan: HaloPlan, storage_dtype):
    """The dtype each output pixel is *stored* at — plan geometry, not an
    invariant: the accumulator dtype, unless the plan carries a
    requantising epilogue, in which case the fused scale→round→saturate
    stage narrows the int32 accumulator back to the spec's storage dtype
    before the store (the write-side half of the B-bit bus)."""
    if plan.requant is not None:
        return jnp.dtype(plan.requant.dtype)
    return acc_dtype(storage_dtype)


def _reduce_taps(ext, coeffs, Ho: int, Wo: int, w: int, form: str,
                 r0: int = 0, c0: int = 0):
    """w² shifted-product reduction in the requested layout. ext holds the
    halo window with the first tap at (r0, c0)."""
    prods = []
    acc = None
    for i in range(w):
        for j in range(w):
            plane = ext[r0 + i:r0 + i + Ho, c0 + j:c0 + j + Wo] * coeffs[i, j]
            if form == "transposed":     # MAC chain, running accumulator
                acc = plane if acc is None else acc + plane
            else:
                prods.append(plane)
    if form == "transposed":
        return acc
    if form == "direct":                 # systolic-style: single fused sum
        out = prods[0]
        for p_ in prods[1:]:
            out = out + p_
        return out
    if form == "tree":                   # pairwise log-depth tree
        while len(prods) > 1:
            nxt = [prods[k] + prods[k + 1] for k in range(0, len(prods) - 1, 2)]
            if len(prods) % 2:
                nxt.append(prods[-1])
            prods = nxt
        return prods[0]
    if form == "compress":               # groups of 6, then a short chain
        partials = []
        for k in range(0, len(prods), 6):
            g = prods[k:k + 6]
            s = g[0]
            for t in g[1:]:
                s = s + t
            partials.append(s)
        out = partials[0]
        for s in partials[1:]:
            out = out + s
        return out
    raise ValueError(form)


def _reduce_separable(ext, u, v, Ho: int, Wo: int, w: int,
                      r0: int = 0, c0: int = 0):
    """Fused separable reduction: w-tap column pass then w-tap row pass.

    ext: the halo window with the first tap at (r0, c0); u/v: [w] row/
    column factors. 2w MACs/pixel (the column pass runs on Ho+2r rows,
    amortised over the strip).
    """
    ext = ext[r0:r0 + Ho + w - 1]
    h = None
    for j in range(w):                   # column (horizontal) pass
        t = ext[:, c0 + j:c0 + j + Wo] * v[j]
        h = t if h is None else h + t
    y = None
    for i in range(w):                   # row (vertical) pass
        t = h[i:i + Ho] * u[i]
        y = t if y is None else y + t
    return y


# ---------------------------------------------------------------------------
# The halo-engine kernel: grid = (planes, column tiles, row strips, filters)
# ---------------------------------------------------------------------------


GRID_ORDERS = ("filters_innermost", "strips_innermost")


def kernel_contract(plan: HaloPlan, num_filters: int = 1,
                    overlap: bool = True,
                    grid_order: str = "filters_innermost",
                    form: str = "direct") -> KernelContract:
    """The declared dataflow contract of the ``filter2d_halo`` trace these
    knobs produce — operand/scratch/grid roles for the static verifier
    (``repro.analysis``). Built from the same inputs that shape the
    kernel, next to the kernel, so the two cannot drift silently: a
    kernel restructure that breaks the contract surfaces as a verifier
    finding, not a misread jaxpr."""
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"unknown grid_order {grid_order!r}; choose from "
                         f"{GRID_ORDERS}")
    ext_banks, out_banks = plan_banks(plan, num_filters, overlap)
    operands = ["frame", "coeffs"]
    if plan.requant is not None:
        operands.append("qparams")
    scratch = (("ext", "obuf", "fill_sem", "store_sem") if overlap
               else ("ext", "fill_sem"))
    inner = (("strip", "filter") if grid_order == "filters_innermost"
             else ("filter", "strip"))
    return KernelContract(operands=tuple(operands), outputs=("out",),
                          scratch=scratch,
                          axes=("plane", "tile") + inner,
                          grid_order=grid_order, overlap=overlap,
                          num_filters=num_filters, form=form,
                          ext_banks=ext_banks, out_banks=out_banks,
                          has_requant=plan.requant is not None)


def _when(*conds):
    """``pl.when`` over the non-None conds; immediate call when none."""
    conds = [c for c in conds if c is not None]
    if not conds:
        return lambda fn: fn()
    return pl.when(functools.reduce(jnp.logical_and, conds))


def _halo_kernel(x_ref, c_ref, *rest, plan: HaloPlan, form: str, w: int,
                 n_filters: int, grid_order: str, overlap: bool,
                 ext_banks: int, out_banks: int):
    """One grid step: fill/land the scratch bank for strip i of tile j,
    reduce the taps for filter f, and store the output tile.

    x_ref is the whole un-tiled [M, H, W] plane stack in ANY/HBM space —
    the kernel's own DMA is the only reader, so the stream is read-once
    from HBM (plus the aligned strip overlap). The scratch persists across the
    filter steps whenever filters are the innermost grid dim: the
    coefficient-file read-once property. With ``grid_order=
    'strips_innermost'`` every step is a fresh strip, so the fill is
    unconditional — the refill guard FOLLOWS the grid order instead of
    hard-coding ``f == 0`` against whatever dim happens to be innermost.

    Serial path (``overlap=False``): one scratch bank, start+wait fill,
    BlockSpec-managed output store — the bit-exact reference.

    Overlap path: two-bank LD ∥ EX ∥ ST software pipeline.
      LD  — strip i+1's fill DMAs (main window + wrap prologue/corners)
            are *started* into bank (i+1)%2 before strip i is reduced;
            strip i's own fill is only *waited* here (it was started one
            step earlier, or at the i==0 prologue).
      EX  — the reduction reads bank i%2; the policy mux ran at wait time
            on that bank only.
      ST  — the output tile is written to obuf bank t%2 (t the step index
            within this (m, j) tile) and DMA'd to the ANY-space output
            asynchronously; the copy is waited two steps later (pre-wait
            before the bank is rewritten) and the last two are drained at
            the final step. Steady state: LD(s+1) ∥ EX(s) ∥ ST(s−1).

    When the plan carries a requantising epilogue, ``rest`` leads with
    ``q_ref`` — the [N, 2] (multiplier, shift) scaler table in SMEM
    (scalar memory, where Mosaic wants dynamically-indexed scalars),
    runtime data exactly like the coefficients (one compiled executable
    serves every gain) — and the int32 accumulator is fused through
    scale→round→saturate down to the storage dtype before the store.
    """
    if plan.requant is not None:
        q_ref, o_ref, *scratch = rest
    else:
        q_ref = None
        o_ref, *scratch = rest
    m = pl.program_id(0)
    j = pl.program_id(1)
    if grid_order == "filters_innermost":
        i, f = pl.program_id(2), pl.program_id(3)
        n_i = pl.num_programs(2)
        # the scratch is shared by the whole bank: fill once per strip,
        # at the first filter step
        first_fill = (f == 0) if n_filters > 1 else None
        t = i * n_filters + f
    else:
        f, i = pl.program_id(2), pl.program_id(3)
        n_i = pl.num_programs(3)
        first_fill = None                 # every step is a fresh strip
        t = f * n_i + i
    T = plan.rows.n * n_filters           # steps per (m, j) tile

    S, Tw = plan.rows.block, plan.cols.block
    frame = x_ref.at[m]

    if not overlap:
        ext_ref, sem = scratch
        _when(first_fill)(
            lambda: halo.fill_ext(frame, ext_ref, sem, i, j, plan))
        ext_bank = ext_ref
    else:
        ext_ref, obuf_ref, fill_sem, store_sem = scratch
        bank = jax.lax.rem(i, ext_banks)
        nxt = jax.lax.rem(i + 1, ext_banks)
        # LD prologue: the first strip has no earlier step to prefetch it
        _when(first_fill, i == 0)(
            lambda: halo.start_fill(frame, ext_ref.at[bank],
                                    fill_sem.at[bank], i, j, plan))
        if ext_banks == 2:
            # LD: prefetch strip i+1 into the other bank; its DMAs fly
            # while strip i is muxed and reduced below
            _when(first_fill, i + 1 < n_i)(
                lambda: halo.start_fill(frame, ext_ref.at[nxt],
                                        fill_sem.at[nxt], i + 1, j, plan))
        # land this strip's DMAs + run the border mux, on its bank only
        _when(first_fill)(
            lambda: halo.wait_fill(frame, ext_ref.at[bank],
                                   fill_sem.at[bank], i, j, plan))
        ext_bank = ext_ref.at[bank]

    # fixed-point: the scratch holds the narrow storage dtype (the DMA'd
    # bytes stay 1-2 per pixel); the widening to the int32 accumulator
    # happens here, on the register-level read feeding the MAC.
    # The aligned window is loaded whole and the over-fetch is shifted
    # away by the tap offsets (r0, c0).
    adt = jnp.int32 if plan.requant is not None else o_ref.dtype
    ext = ext_bank[pl.ds(0, plan.rows.window),
                   pl.ds(0, plan.cols.window)].astype(adt)
    r0, c0 = plan.rows.shift, plan.cols.shift
    if form == "separable":
        y = _reduce_separable(ext, c_ref[0, 0], c_ref[0, 1], S, Tw, w,
                              r0, c0)
    else:
        y = _reduce_taps(ext, c_ref[0], S, Tw, w, form, r0, c0)
    if plan.requant is not None:
        # the fused epilogue: word growth managed inside the datapath, so
        # the store (and the HBM write behind it) is storage-width again
        y = apply_requant(y, q_ref[f, 0], q_ref[f, 1],
                          rounding=plan.requant.rounding,
                          out_dtype=o_ref.dtype)

    if not overlap:
        o_ref[0, 0] = y
        return

    # ST: async store through the obuf bank for step t. The wait-side
    # descriptors are reconstructed with the CURRENT step's slice — every
    # store moves the same S×Tw×out_dtype bytes, so the semaphore
    # bookkeeping matches the copy actually in flight on that bank.
    ob = jax.lax.rem(t, out_banks)
    dst = o_ref.at[m, f, pl.ds(i * S, S), pl.ds(j * Tw, Tw)]
    if out_banks == 2:
        # pre-wait: the copy issued from this bank two steps ago must
        # have landed before the bank is rewritten
        _when(t >= 2)(
            lambda: pltpu.make_async_copy(obuf_ref.at[ob], dst,
                                          store_sem.at[ob]).wait())
    obuf_ref[ob] = y
    pltpu.make_async_copy(obuf_ref.at[ob], dst, store_sem.at[ob]).start()

    # drain: the final step waits the last store on every bank (bank
    # parities of T-1 and T-2 are static — T is a Python int)
    last = (T - 1) % out_banks
    if out_banks == 2 and T >= 2:
        _when(t == T - 1)(
            lambda: pltpu.make_async_copy(obuf_ref.at[(T - 2) % 2], dst,
                                          store_sem.at[(T - 2) % 2]).wait())
    _when(t == T - 1)(
        lambda: pltpu.make_async_copy(obuf_ref.at[last], dst,
                                      store_sem.at[last]).wait())


def filter2d_halo(planes: jax.Array, coeffs: jax.Array, plan: HaloPlan, *,
                  q_params: Optional[jax.Array] = None,
                  form: str = "direct", interpret: bool = True,
                  overlap: bool = True,
                  grid_order: str = "filters_innermost") -> jax.Array:
    """Streaming 2D filter with in-kernel border management.

    planes: [M, H, W] raw (un-tiled, un-extended) frame planes — the only
    HBM-resident input, streamed at its *storage* dtype (int8/uint8/int16
    frames move 1-2 bytes/pixel through HBM and VMEM; the paper's narrow
    pixel bus). coeffs: [N, w, w] filter bank (or [N, 2, w] row/col factors
    for ``form='separable'``) — int32 for fixed-point frames. Returns
    [M, N, Ho_pad, Wo_pad] with Ho_pad = n_strips·S, Wo_pad = n_tiles·Tw
    (callers crop), at ``out_dtype(plan, planes.dtype)``: the plan's
    requant storage dtype when it carries the fused epilogue (narrow in
    BOTH directions), else int32 for fixed-point storage (exact
    accumulation; the caller requantises), else the frame dtype.

    The grid is (M, n_tiles, n_strips, N) (``grid_order=
    'filters_innermost'``, the default: each scratch fill serves the whole
    bank) or (M, n_tiles, N, n_strips) (``'strips_innermost'``: the fill
    guard follows — every step refills, no stale-scratch reads). Planes
    and column tiles are ``parallel`` (provably independent — megacore-
    partitionable), the inner two dims ``arbitrary`` (stream order;
    scratch reuse is core-local).

    ``overlap=True`` (default) runs the double-buffered LD ∥ EX ∥ ST
    pipeline: two scratch banks (strip i+1's fill DMAs — wrap prologue
    and torus corners included — fly while strip i is reduced), two
    output banks (each store is issued async and waited two steps later),
    per-bank DMA semaphores. ``overlap=False`` is the serial reference:
    one bank, start+wait fill, BlockSpec store — bit-identical output.
    planes must already be padded to the plan's tile-aligned span
    (``rows.span × cols.span``). VMEM per step: the banked scratch and
    output tiles, the coefficient file and what the body materialises
    (see :func:`plan_vmem_working_set`) — still the row-buffer bound,
    independent of both frame height and width; the compiler's VMEM
    limit is sized from it (:func:`vmem_limit_bytes`).
    """
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"unknown grid_order {grid_order!r}; choose from "
                         f"{GRID_ORDERS}")
    span = (plan.rows.span, plan.cols.span)
    if tuple(planes.shape[1:]) != span:
        raise ValueError(f"planes {tuple(planes.shape[1:])} are not the "
                         f"plan's tile-aligned span {span}; pad them")
    w = coeffs.shape[-1]
    M = planes.shape[0]
    N = coeffs.shape[0]
    S, Tw = plan.rows.block, plan.cols.block
    n_i, n_j = plan.rows.n, plan.cols.n
    filters_inner = grid_order == "filters_innermost"
    c_block = (1, 2, w) if form == "separable" else (1, w, w)
    if filters_inner:
        c_map = lambda m, jj, ii, f: (f, 0, 0)        # noqa: E731
        o_map = lambda m, jj, ii, f: (m, f, ii, jj)   # noqa: E731
        grid = (M, n_j, n_i, N)
    else:
        c_map = lambda m, jj, f, ii: (f, 0, 0)        # noqa: E731
        o_map = lambda m, jj, f, ii: (m, f, ii, jj)   # noqa: E731
        grid = (M, n_j, N, n_i)
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(c_block, c_map),
    ]
    operands = [planes, coeffs]
    name = f"filter2d_halo_{form}_{plan.policy}"
    if plan.requant is not None:
        # per-filter (multiplier, shift) output scalers ride as a [N, 2]
        # runtime operand in SMEM — scalar parameters, dynamically indexed
        # by the filter grid dim, like the coefficient file: one compiled
        # executable serves every gain (``q_params`` is traced; the
        # wrapper compiles against the gain-free spec). Direct callers
        # may omit ``q_params`` and take the plan spec's own gains.
        if q_params is None:
            q_params = jnp.asarray(plan.requant.params(N), jnp.int32)
        operands.append(q_params)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        name += f"_requant_{plan.requant.rounding}"
    odt = out_dtype(plan, planes.dtype)
    ext_banks, out_banks = plan_banks(plan, N, overlap)
    if overlap:
        # the output is ANY-space: the kernel owns the stores (manual
        # async copies from the obuf banks), not a BlockSpec
        out_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((ext_banks, plan.eh, plan.ew), planes.dtype),
                   pltpu.VMEM((out_banks, S, Tw), odt),
                   pltpu.SemaphoreType.DMA((ext_banks,)),
                   pltpu.SemaphoreType.DMA((out_banks,))]
        name += "_db"
    else:
        out_spec = pl.BlockSpec((1, 1, S, Tw), o_map)
        scratch = [pltpu.VMEM((plan.eh, plan.ew), planes.dtype),
                   pltpu.SemaphoreType.DMA]
    return pl.pallas_call(
        functools.partial(_halo_kernel, plan=plan, form=form, w=w,
                          n_filters=N, grid_order=grid_order,
                          overlap=overlap, ext_banks=ext_banks,
                          out_banks=out_banks),
        out_shape=jax.ShapeDtypeStruct((M, N, n_i * S, n_j * Tw), odt),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(plan_vmem_working_set(
                plan, num_filters=N, separable=form == "separable",
                overlap=overlap))),
        name=name,
    )(*operands)


# The compiler's own default scoped VMEM limit is 16 MiB. The limit each
# kernel asks for is its planned working set with 2x headroom for what
# Mosaic adds (its internal scratch, relayout copies), never below that
# default and well inside a v5e core's VMEM.
VMEM_LIMIT_FLOOR = 16 * 2 ** 20
VMEM_LIMIT_CAP = 100 * 2 ** 20


def vmem_limit_bytes(working_set: int) -> int:
    """The ``vmem_limit_bytes`` the kernel compiles under."""
    return int(min(max(2 * working_set, VMEM_LIMIT_FLOOR), VMEM_LIMIT_CAP))
