"""The verifier pass pipeline over the dataflow IR.

Five passes, each checking one invariant the double-buffered halo engine
claims (kernel docstrings, paper §II–§IV):

``dma_pairing``  Every started async copy is waited exactly once, on the
                 same semaphore with the same byte count — and, for the
                 halo fills (whose start/wait sides are reconstructed
                 from identical arguments), a byte-identical descriptor.
                 Starts still in flight after the final grid step (no
                 drain) are flagged. Output-store waits legitimately
                 rebuild their destination slice from the *current* step
                 (same byte count, same semaphore — the TPU semaphore
                 contract), so those match on (semaphore, bytes).

``bank_hazard``  WAR/RAW on the banked ``ext``/``obuf`` scratch across
                 consecutive grid steps, for whichever grid order the
                 trace runs. The serial reference kernel's fill schedule
                 defines the correct scratch contents per (plane, tile,
                 strip); a read whose bank holds anything else is the
                 stale-scratch bug (the PR 6 class), a read or write
                 overlapping an in-flight DMA is a race.

``read_once``    Frame-ref bytes started per sweep, bounded by
                 ``halo.read_amplification(plan)`` (× the bank size when
                 the grid order refills per filter) — the generalisation
                 of ``test_halo_engine.py``'s old ad-hoc jaxpr walk.

``width_lint``   Fixed-point storage discipline: the halo scratch is
                 allocated at the storage dtype, stream-provenance data
                 widens only to the int32 accumulator (never to float,
                 never wider), and constants written into the stream are
                 representable at storage width.

``vmem_budget``  The traced VMEM buffers (scratch allocations + blocked
                 operands + output blocks) equal the plan's
                 ``plan_vmem_buffers``, and with the body's
                 materialisations (``plan_vmem_working_set``) fit the
                 compile-time ``vmem_budget``.

All three dynamic passes run in ONE grid sweep (:func:`simulate`): the
grid is enumerated in Pallas order (last axis innermost), every op's
``pl.when`` predicate and window offsets are evaluated concretely, and
in-flight DMAs / bank contents are tracked step to step.

To add a pass: write ``def pass_x(ctx) -> list[Finding]``, register it in
``PASSES`` — ``run_passes`` threads the shared :class:`Context` (lowered
IR, reference fill map, plan, budget) through every entry.
"""
from __future__ import annotations

import dataclasses
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.ir import (AnalysisError, Access, Convert, DmaStart,
                               DmaWait, KernelIR, RefRead, RefWrite, ev)
from repro.analysis.report import Finding
from repro.core.border_spec import quantize_constant
from repro.kernels.filter2d import halo
from repro.kernels.filter2d.halo import HaloPlan


@dataclasses.dataclass
class Context:
    """Everything a pass sees: the lowered kernel, the serial reference's
    fill schedule, the plan, and the compile-time budget."""

    kir: KernelIR
    plan: HaloPlan
    key: str
    vmem_budget: Optional[int] = None
    ref_fills: Optional[Dict[tuple, tuple]] = None   # (m,j,i) -> fill sig
    num_filters: int = 1
    separable: bool = False


# ---------------------------------------------------------------------------
# Concrete evaluation helpers
# ---------------------------------------------------------------------------


def _conc(acc: Access, pids) -> tuple:
    """(ref, offsets, sizes) with offsets evaluated at this grid point."""
    offs = tuple(int(ev(off, pids)) for off, _, _ in acc.dims)
    return (acc.ref, offs, acc.sizes)


def _pred(op, pids) -> bool:
    return op.pred is None or bool(ev(op.pred, pids))


def _bytes_of(kir: KernelIR, conc) -> int:
    ref, _, sizes = conc
    return int(np.prod(sizes, dtype=np.int64)) * kir.refs[ref].itemsize


def _overlaps(a, b) -> bool:
    """Window intersection test: same ref and every dim's intervals meet."""
    return a[0] == b[0] and all(
        o1 + s1 > o2 and o2 + s2 > o1
        for (o1, s1), (o2, s2) in zip(zip(a[1], a[2]), zip(b[1], b[2])))


def _bank_of(kir: KernelIR, conc) -> int:
    """Bank index of a scratch access: the leading point dim when the ref
    is banked (rank 3 over a 2D payload), else 0."""
    if len(kir.refs[conc[0]].shape) > 2:
        return conc[1][0]
    return 0


def _local(kir: KernelIR, conc) -> tuple:
    """The within-bank trailing-2D window (drops a leading bank dim)."""
    _, offs, sizes = conc
    return (offs[-2:], sizes[-2:])


def _fill_sig(kir: KernelIR, src_conc, dst_conc) -> tuple:
    """Bank-independent signature of one fill DMA: the full source window
    plus the within-bank destination window."""
    return (src_conc[1], src_conc[2], _local(kir, dst_conc))


class _Dedup:
    """Caps repeated findings: one Finding per (pass, template), counting
    further occurrences instead of re-emitting."""

    def __init__(self, key: str):
        self.key = key
        self._found: Dict[tuple, dict] = {}

    def add(self, passname: str, template: str, message: str,
            step, ref: Optional[str] = None, detail: Optional[str] = None):
        k = (passname, template, ref)
        if k in self._found:
            self._found[k]["count"] += 1
            return
        self._found[k] = dict(passname=passname, message=message,
                              key=self.key, ref=ref,
                              grid_step=tuple(int(x) for x in step)
                              if step is not None else None,
                              detail=detail, count=1)

    def findings(self) -> List[Finding]:
        return [Finding(**d) for d in self._found.values()]


# ---------------------------------------------------------------------------
# The grid sweep (dma_pairing + bank_hazard + read_once share one pass
# over the grid)
# ---------------------------------------------------------------------------


def fill_schedule(kir: KernelIR) -> Dict[tuple, tuple]:
    """The per-(plane, tile, strip) halo-fill signature multiset of a
    kernel — run on the SERIAL reference trace, this is the ground truth
    ``bank_hazard`` compares scratch contents against."""
    m_ax, j_ax = kir.axis("plane"), kir.axis("tile")
    i_ax = kir.axis("strip")
    ext = kir.ref_by_role("ext")
    frame = kir.ref_by_role("frame")
    if ext is None or frame is None:
        raise AnalysisError("kernel contract names no ext/frame ref")
    sched: Dict[tuple, list] = {}
    for pids in np.ndindex(*kir.grid):
        key = (pids[m_ax], pids[j_ax], pids[i_ax])
        sigs = sched.setdefault(key, [])
        for op in kir.ops:
            if isinstance(op, DmaStart) and _pred(op, pids):
                dst = _conc(op.dst, pids)
                if dst[0] == ext.index:
                    sigs.append(_fill_sig(kir, _conc(op.src, pids), dst))
    return {k: tuple(sorted(v)) for k, v in sched.items() if v}


def simulate(ctx: Context) -> Tuple[List[Finding], Dict[str, float]]:
    """One in-order sweep of the whole grid, producing the dynamic
    passes' findings and the byte counters ``read_once`` bounds."""
    kir = ctx.kir
    dd = _Dedup(ctx.key)
    ext = kir.ref_by_role("ext")
    obuf = kir.ref_by_role("obuf")
    frame = kir.ref_by_role("frame")
    m_ax, j_ax = kir.axis("plane"), kir.axis("tile")
    i_ax = kir.axis("strip")

    inflight: Dict[tuple, list] = defaultdict(list)  # sem key -> starts
    # ext bank model: per (plane, tile) the banks are core-local state
    landed: Dict[int, Counter] = defaultdict(Counter)   # bank -> sigs
    pending: Dict[int, list] = defaultdict(list)        # bank -> dma recs
    tile_key = None
    frame_bytes_started = 0

    for pids in np.ndindex(*kir.grid):
        tk = (pids[m_ax], pids[j_ax])
        if tk != tile_key:
            tile_key = tk
            # fresh (plane, tile): scratch content from the previous tile
            # is stale by construction; the kernel must refill before use
            landed.clear()
            pending.clear()
        step_key = (pids[m_ax], pids[j_ax], pids[i_ax])
        for op in kir.ops:
            if not _pred(op, pids):
                continue
            if isinstance(op, DmaStart):
                src, dst = _conc(op.src, pids), _conc(op.dst, pids)
                sem = _conc(op.sem, pids)
                rec = {"src": src, "dst": dst, "sem": sem,
                       "bytes": _bytes_of(kir, src), "step": pids}
                inflight[sem].append(rec)
                if frame is not None and src[0] == frame.index:
                    frame_bytes_started += rec["bytes"]
                if ext is not None and dst[0] == ext.index:
                    b = _bank_of(kir, dst)
                    rec["sig"] = _fill_sig(kir, src, dst)
                    rec["bank"] = b
                    # a start into a bank clobbers whatever landed content
                    # its destination window overlaps (the in-flight copy
                    # may overwrite it at any time)
                    for s in list(landed[b]):
                        if _win_overlap(s[2], _local(kir, dst)):
                            del landed[b][s]
                    pending[b].append(rec)
            elif isinstance(op, DmaWait):
                src, dst = _conc(op.src, pids), _conc(op.dst, pids)
                sem = _conc(op.sem, pids)
                nbytes = _bytes_of(kir, src)
                cands = inflight.get(sem, [])
                exact = [r for r in cands
                         if r["src"] == src and r["dst"] == dst]
                bysize = [r for r in cands if r["bytes"] == nbytes]
                if exact:
                    rec = exact[0]
                elif bysize:
                    rec = bysize[0]
                    if ext is not None and dst[0] == ext.index:
                        dd.add("dma_pairing", "fill-desc-mismatch",
                               "halo-fill wait descriptor differs from the "
                               f"started copy on sem{sem[1]}: waited "
                               f"src@{src[1]} dst@{dst[1]}, in flight "
                               f"src@{rec['src'][1]} dst@{rec['dst'][1]}",
                               pids, ref="ext")
                else:
                    dd.add("dma_pairing", "unmatched-wait",
                           f"DMA wait with no matching start: sem{sem[1]}, "
                           f"{nbytes} B expected, "
                           f"{len(cands)} copies in flight "
                           f"({[r['bytes'] for r in cands]} B)",
                           pids,
                           ref=kir.refs[dst[0]].role)
                    continue
                cands.remove(rec)
                if "bank" in rec:                    # a halo fill landed
                    if rec in pending[rec["bank"]]:
                        pending[rec["bank"]].remove(rec)
                    landed[rec["bank"]][rec["sig"]] += 1
            elif isinstance(op, RefRead):
                acc = _conc(op.acc, pids)
                if ext is not None and acc[0] == ext.index:
                    b = _bank_of(kir, acc)
                    win = _local(kir, acc)
                    for rec in pending[b]:
                        if _win_overlap(_local(kir, rec["dst"]), win):
                            dd.add("bank_hazard", "raw-inflight",
                                   f"read of ext bank {b} overlaps a fill "
                                   "DMA still in flight (started at grid"
                                   f"{tuple(rec['step'])})", pids,
                                   ref="ext")
                            break
                    if ctx.ref_fills is not None:
                        # only fills under the read window count: wrap's
                        # staged bands outlive the strip that fetched
                        # them in slots later strips never read
                        want = ctx.ref_fills.get(step_key)
                        if want is not None:
                            want = tuple(g for g in want
                                         if _win_overlap(g[2], win))
                        have = tuple(sorted(
                            g for g in landed[b].elements()
                            if _win_overlap(g[2], win)))
                        if want is not None and have != want:
                            dd.add(
                                "bank_hazard", "stale-scratch",
                                f"ext bank {b} holds stale contents at "
                                f"grid{tuple(pids)}: the serial reference "
                                f"fills {len(want)} window(s) for (plane,"
                                f"tile,strip)={step_key}, the bank holds "
                                f"{len(have)} from "
                                + (_describe_sigs(have, want)),
                                pids, ref="ext")
            elif isinstance(op, RefWrite):
                acc = _conc(op.acc, pids)
                if ext is not None and acc[0] == ext.index:
                    b = _bank_of(kir, acc)
                    win = _local(kir, acc)
                    for rec in pending[b]:
                        if _win_overlap(_local(kir, rec["dst"]), win):
                            dd.add("bank_hazard", "war-ext",
                                   f"write to ext bank {b} overlaps a fill "
                                   "DMA still in flight", pids, ref="ext")
                            break
                if obuf is not None and acc[0] == obuf.index:
                    for recs in inflight.values():
                        for rec in recs:
                            if _overlaps(rec["src"], acc):
                                dd.add(
                                    "bank_hazard", "war-obuf",
                                    "output bank rewritten while its store "
                                    f"DMA is in flight: obuf window "
                                    f"@{acc[1]} feeds a copy started at "
                                    f"grid{tuple(rec['step'])}", pids,
                                    ref="obuf")

    for sem, recs in inflight.items():
        for rec in recs:
            dd.add("dma_pairing", "unwaited-start",
                   f"DMA started at grid{tuple(rec['step'])} "
                   f"({rec['bytes']} B on sem{sem[1]}, dst role "
                   f"{kir.refs[rec['dst'][0]].role!r}) is never waited — "
                   "it outlives the final grid step without a drain",
                   rec["step"], ref=kir.refs[rec["dst"][0]].role)

    stats = {"frame_bytes_started": float(frame_bytes_started)}
    return dd.findings(), stats


def _win_overlap(a: tuple, b: tuple) -> bool:
    """Overlap of two within-bank (offsets, sizes) windows."""
    return all(o1 + s1 > o2 and o2 + s2 > o1
               for (o1, s1), (o2, s2) in zip(zip(*a), zip(*b)))


def _describe_sigs(have, want) -> str:
    extra = [s for s in have if s not in want]
    if extra:
        return f"elsewhere (e.g. src rows@{extra[0][0]})"
    missing = [s for s in want if s not in have]
    if missing:
        return f"a partial fill (missing src rows@{missing[0][0]})"
    return "a different schedule"


# ---------------------------------------------------------------------------
# The passes
# ---------------------------------------------------------------------------


def pass_dynamic(ctx: Context) -> Tuple[List[Finding], Dict[str, float]]:
    """dma_pairing + bank_hazard raw findings from one simulated sweep."""
    return simulate(ctx)


def pass_read_once(ctx: Context,
                   stats: Dict[str, float]) -> List[Finding]:
    kir, plan = ctx.kir, ctx.plan
    frame = kir.ref_by_role("frame")
    if frame is None:
        return []
    frame_bytes = (int(np.prod(frame.shape, dtype=np.int64))
                   * frame.itemsize)
    amp = stats.get("frame_bytes_started", 0.0) / max(frame_bytes, 1)
    bound = halo.read_amplification(plan)
    if (kir.contract.grid_order == "strips_innermost"
            and ctx.num_filters > 1):
        # that order refills per filter by contract: N sweeps of the frame
        bound *= ctx.num_filters
    stats["read_amplification_traced"] = amp
    stats["read_amplification_bound"] = bound
    if amp > bound * (1 + 1e-9):
        return [Finding(
            passname="read_once", key=ctx.key, ref="frame",
            message=f"frame bytes DMA'd per sweep exceed the plan bound: "
                    f"traced amplification {amp:.4f}x vs "
                    f"halo.read_amplification {bound:.4f}x")]
    return []


def pass_width_lint(ctx: Context) -> List[Finding]:
    kir, plan = ctx.kir, ctx.plan
    out: List[Finding] = []
    frame = kir.ref_by_role("frame")
    ext = kir.ref_by_role("ext")
    if frame is None or ext is None:
        return out
    storage = np.dtype(frame.dtype)
    fixed = storage.kind in ("i", "u")
    if ext.dtype != frame.dtype:
        out.append(Finding(
            passname="width_lint", key=ctx.key, ref="ext",
            message=f"halo scratch is allocated at {ext.dtype}, not the "
                    f"storage dtype {frame.dtype} — the stream must sit "
                    "in VMEM at storage width"))
    if fixed:
        for op in kir.ops:
            if isinstance(op, Convert) and ext.index in op.prov:
                dst = np.dtype(op.dst_dtype)
                widened = dst.itemsize > storage.itemsize
                if dst.kind == "f":
                    out.append(Finding(
                        passname="width_lint", key=ctx.key, ref="ext",
                        message="stream data is converted to floating "
                                f"point ({op.src_dtype} -> {op.dst_dtype}) "
                                "before the MAC — the fixed-point path "
                                "must widen to int32 only"))
                elif widened and dst != np.dtype(np.int32):
                    out.append(Finding(
                        passname="width_lint", key=ctx.key, ref="ext",
                        message=f"stream data widens {op.src_dtype} -> "
                                f"{op.dst_dtype}; only the int32 "
                                "accumulator widening is allowed"))
        for op in kir.ops:
            if (isinstance(op, RefWrite) and op.acc.ref == ext.index
                    and op.const is not None):
                q = quantize_constant(op.const, storage)
                if float(q) != float(op.const):
                    out.append(Finding(
                        passname="width_lint", key=ctx.key, ref="ext",
                        message=f"border constant {op.const!r} written "
                                f"into the {storage.name} stream is not "
                                f"representable at storage width "
                                f"(quantizes to {q!r})"))
        if plan.constant != quantize_constant(plan.constant, storage):
            out.append(Finding(
                passname="width_lint", key=ctx.key, ref="ext",
                message=f"plan constant {plan.constant!r} is not "
                        f"quantized to the storage dtype {storage.name}"))
    return _cap(out)


def pass_vmem_budget(ctx: Context) -> List[Finding]:
    kir, plan = ctx.kir, ctx.plan
    out: List[Finding] = []
    traced = kir.vmem_bytes
    knobs = dict(num_filters=ctx.num_filters, separable=ctx.separable,
                 overlap=kir.contract.overlap)
    planned = halo.plan_vmem_buffers(plan, **knobs)
    if traced != planned:
        parts = ", ".join(f"{k}={v}" for k, v in kir.vmem_parts)
        out.append(Finding(
            passname="vmem_budget", key=ctx.key,
            message=f"traced VMEM buffers {traced} B != "
                    f"plan_vmem_buffers {planned} B",
            detail=f"traced parts: {parts}"))
    # the body's materialisations ride on top of the traced buffers
    total = traced + (halo.plan_vmem_working_set(plan, **knobs) - planned)
    if ctx.vmem_budget is not None and total > ctx.vmem_budget:
        out.append(Finding(
            passname="vmem_budget", key=ctx.key,
            message=f"VMEM working set {total} B (traced buffers + the "
                    f"body's widened window, tap slices and accumulator) "
                    f"exceeds the compile-time vmem_budget "
                    f"{ctx.vmem_budget} B"))
    return out


def _cap(findings: List[Finding]) -> List[Finding]:
    by: Dict[tuple, List[Finding]] = defaultdict(list)
    for f in findings:
        by[(f.passname, f.message[:40])].append(f)
    out = []
    for group in by.values():
        f = group[0]
        if len(group) > 1:
            f = dataclasses.replace(f, count=len(group))
        out.append(f)
    return out


# The pass catalogue: name -> one-line description (docs + CLI listing).
PASSES = {
    "dma_pairing": "every started async copy waited exactly once (same "
                   "semaphore and byte count; byte-identical descriptors "
                   "for halo fills), with a drain before the grid ends",
    "bank_hazard": "WAR/RAW on the banked ext/obuf scratch across grid "
                   "steps; bank contents checked against the serial "
                   "reference fill schedule (the stale-scratch class)",
    "read_once": "frame bytes DMA'd per sweep bounded by "
                 "halo.read_amplification(plan)",
    "width_lint": "fixed-point storage discipline: storage-width scratch, "
                  "int32-only widening, storage-representable constants",
    "vmem_budget": "traced VMEM buffers equal plan_vmem_buffers and, "
                   "with the body's materialisations, fit the budget",
}


def run_passes(ctx: Context) -> Tuple[List[Finding], Dict[str, float]]:
    """Run the full pipeline over one lowered kernel."""
    findings, stats = pass_dynamic(ctx)
    findings += pass_read_once(ctx, stats)
    findings += pass_width_lint(ctx)
    findings += pass_vmem_budget(ctx)
    return findings, stats
