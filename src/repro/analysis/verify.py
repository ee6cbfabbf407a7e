"""Verification entry points: trace, lower, run the pass pipeline.

:func:`verify` takes a built :class:`~repro.core.pipeline.CompiledFilter`
and verifies what it will actually run: the executable is traced to a
jaxpr, every pallas_call in it is counted, and — for the Pallas executors
— the kernel is re-traced and analyzed under BOTH grid orders (the
bank-hazard pass's whole point is that the refill guard must follow the
order). Non-Pallas executors trace clean by construction (no manual DMA
to race), which the report states rather than assumes: the trace must
succeed and contain zero pallas_calls.

:func:`verify_kernel` is the raw-kernel door: any callable with the
``filter2d_halo`` operand convention (planes, coeffs[, q]) is traced
against a :class:`~repro.kernels.filter2d.halo.HaloPlan` and a
:class:`~repro.kernels.filter2d.contract.KernelContract` — the seeded-bug
fixtures in ``tests/analysis_fixtures`` enter here. The serial reference
path (``overlap=False``) of the SHIPPED kernel is traced alongside and
its fill schedule becomes the bank-content ground truth.

:func:`sweep` runs the executor × dtype × border × overlap × grid-order
matrix (the CI ``kernel-verify`` lane); invalid combinations (the
strip-scan and shard executors take no ``neglect`` border) are skipped,
not failed. Every entry returns a Report — a trace/lowering failure is a
Report with ``error`` set (CLI exit code 2), never an unhandled raise.
"""
from __future__ import annotations

import traceback
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.analysis.ir import (AnalysisError, lower_pallas_call,
                               pallas_calls)
from repro.analysis.passes import Context, PASSES, fill_schedule, run_passes
from repro.analysis.report import Report
from repro.core.border_spec import POLICIES, BorderSpec
from repro.core.filter2d import is_fixed_point
from repro.kernels.filter2d import halo
from repro.kernels.filter2d import kernel as K
from repro.kernels.filter2d.halo import HaloPlan

PASS_NAMES = tuple(PASSES)


def _coeff_sds(num_filters: int, w: int, form: str, dtype):
    cdt = jnp.int32 if is_fixed_point(dtype) else dtype
    shape = ((num_filters, 2, w) if form == "separable"
             else (num_filters, w, w))
    return jax.ShapeDtypeStruct(shape, cdt)


def _default_kernel(plan: HaloPlan, form: str, overlap: bool,
                    grid_order: str):
    def fn(planes, coeffs, q=None):
        return K.filter2d_halo(planes, coeffs, plan, q_params=q, form=form,
                               interpret=False, overlap=overlap,
                               grid_order=grid_order)
    return fn


def _trace_one(kernel_fn, plan: HaloPlan, num_filters: int, form: str,
               dtype, M: int):
    """jaxpr of one kernel call on ShapeDtypeStruct operands."""
    planes = jax.ShapeDtypeStruct(
        (M, plan.rows.span, plan.cols.span), dtype)
    w = 2 * plan.rows.r + 1
    coeffs = _coeff_sds(num_filters, w, form, dtype)
    args = [planes, coeffs]
    if plan.requant is not None:
        args.append(jax.ShapeDtypeStruct((num_filters, 2), jnp.int32))
    return jax.make_jaxpr(kernel_fn)(*args)


def verify_kernel(plan: HaloPlan, *, num_filters: int = 1,
                  form: str = "direct", overlap: bool = True,
                  grid_order: str = "filters_innermost",
                  dtype="float32", M: int = 1,
                  vmem_budget: Optional[int] = None,
                  kernel_fn=None, contract=None, reference_fn=None,
                  key: Optional[str] = None) -> Report:
    """Trace one kernel configuration, lower it and run every pass.

    ``kernel_fn``/``contract``/``reference_fn`` default to the shipped
    ``filter2d_halo`` under the same plan — fixtures override
    ``kernel_fn`` with a seeded-bug body that keeps the shipped operand
    and scratch layout."""
    dtype = jnp.dtype(dtype)
    key = key or (f"kernel/{dtype.name}/{plan.policy}"
                  f"/{'overlap' if overlap else 'serial'}/{grid_order}")
    try:
        ct = contract or K.kernel_contract(plan, num_filters, overlap,
                                           grid_order, form)
        fn = kernel_fn or _default_kernel(plan, form, overlap, grid_order)
        jx = _trace_one(fn, plan, num_filters, form, dtype, M)
        calls = pallas_calls(jx)
        if len(calls) != 1:
            raise AnalysisError(
                f"expected exactly one pallas_call, traced {len(calls)}")
        kir = lower_pallas_call(calls[0], ct)

        ref_fn = reference_fn or _default_kernel(plan, form, False,
                                                 "filters_innermost")
        ref_ct = K.kernel_contract(plan, num_filters, False,
                                   "filters_innermost", form)
        ref_jx = _trace_one(ref_fn, plan, num_filters, form, dtype, M)
        ref_calls = pallas_calls(ref_jx)
        if len(ref_calls) != 1:
            raise AnalysisError("serial reference traced "
                                f"{len(ref_calls)} pallas_calls")
        ref_kir = lower_pallas_call(ref_calls[0], ref_ct)

        ctx = Context(kir=kir, plan=plan, key=key,
                      vmem_budget=vmem_budget,
                      ref_fills=fill_schedule(ref_kir),
                      num_filters=num_filters,
                      separable=form == "separable")
        findings, stats = run_passes(ctx)
        report = Report(key=key, passes=PASS_NAMES,
                        findings=tuple(findings),
                        stats=tuple(sorted(stats.items())))
    except Exception as e:                     # -> CLI exit code 2
        report = Report(key=key, error=_err(e))
    report.emit()
    return report


def _err(e: Exception) -> str:
    tb = traceback.format_exc(limit=3).strip().splitlines()
    return f"{type(e).__name__}: {e} | " + " / ".join(tb[-2:])


def _planes_of(frame_shape: Tuple[int, ...]) -> int:
    if len(frame_shape) == 4:
        return frame_shape[0] * frame_shape[3]
    if len(frame_shape) == 3:
        return frame_shape[2]
    return 1


def verify(cf, grid_orders: Optional[Sequence[str]] = None) -> Report:
    """Verify a compiled pipeline: trace the executable, and — on the
    Pallas executors — analyze its kernel under every grid order."""
    spec = cf.spec
    key = (f"{cf.execution}{'/' + cf.regime if cf.regime else ''}"
           f"/{spec.dtype}/{spec.border.policy}"
           f"/{'overlap' if cf.overlap else 'serial'}")
    dtype = jnp.dtype(spec.dtype)
    try:
        frame = jax.ShapeDtypeStruct(cf.frame_shape, dtype)
        w, n = spec.window, spec.num_filters
        if spec.separable:
            co = jax.ShapeDtypeStruct((2, w), dtype)
        else:
            cshape = (w, w) if n == 1 else (n, w, w)
            co = jax.ShapeDtypeStruct(
                cshape, jnp.int32 if is_fixed_point(dtype) else dtype)
        args = [frame, co]
        if spec.requant is not None:
            args.append(jax.ShapeDtypeStruct((n, 2), jnp.int32))
        jx = jax.make_jaxpr(cf._fn)(*args)
        n_calls = len(pallas_calls(jx))
    except Exception as e:
        report = Report(key=key, error=_err(e))
        report.emit()
        return report

    stats = [("pallas_calls", float(n_calls))]
    if cf.execution != "pallas":
        if n_calls:
            report = Report(key=key, error=f"executor {cf.execution!r} "
                            f"traced {n_calls} pallas_calls; the analysis "
                            "has no contract for them")
        else:
            report = Report(key=key, passes=("trace",),
                            stats=tuple(stats))
        report.emit()
        return report

    if n_calls != 1:
        report = Report(key=key, error=f"pallas executor traced {n_calls} "
                        "pallas_calls (expected 1)")
        report.emit()
        return report

    form = "separable" if spec.separable else spec.form
    report = Report(key=key, stats=tuple(stats))
    for go in (grid_orders or K.GRID_ORDERS):
        sub = verify_kernel(
            cf.plan, num_filters=spec.num_filters, form=form,
            overlap=cf.overlap, grid_order=go, dtype=dtype,
            M=_planes_of(cf.frame_shape), vmem_budget=cf.vmem_budget,
            key=f"{key}/{go}")
        report = report.merge(sub)
    report.emit()
    return report


# ---------------------------------------------------------------------------
# The sweep matrix (CLI + CI kernel-verify lane)
# ---------------------------------------------------------------------------

SWEEP_FRAME = (24, 300)          # 3 strips x 3 tiles at strip 8, tile 128
SWEEP_WINDOW = 5
SWEEP_STRIP, SWEEP_TILE = 8, 128
SWEEP_DTYPES = ("float32", "int8")
EXECUTORS = ("core", "xla", "streaming", "sharded", "pallas")


def _borders() -> List[BorderSpec]:
    out = []
    for p in POLICIES:
        out.append(BorderSpec(p, 7.25) if p == "constant" else BorderSpec(p))
    return out


def sweep_configs(executors: Optional[Sequence[str]] = None,
                  dtypes: Optional[Sequence[str]] = None,
                  borders: Optional[Sequence[str]] = None
                  ) -> List[dict]:
    """The shipped-configuration matrix: 5 executors × dtypes × border
    policies × overlap/serial (Pallas lanes also sweep both grid orders
    inside :func:`verify`, plus bank / separable / requant extras)."""
    execs = tuple(executors or EXECUTORS)
    dts = tuple(dtypes or SWEEP_DTYPES)
    bds = ([BorderSpec(b, 7.25) if b == "constant" else BorderSpec(b)
            for b in borders] if borders else _borders())
    cfgs: List[dict] = []
    for ex in execs:
        for dt in dts:
            for b in bds:
                if ex in ("streaming", "sharded") and b.policy == "neglect":
                    continue                 # those executors reject it
                overlaps = (True, False) if ex == "pallas" else (True,)
                for ov in overlaps:
                    cfgs.append(dict(execution=ex, dtype=dt, border=b,
                                     overlap=ov))
    if "pallas" in execs:
        # structure extras: the bank grid (guard per order), the fused
        # separable form and the requant epilogue all shape the kernel
        if "float32" in dts:
            cfgs.append(dict(execution="pallas", dtype="float32",
                             border=BorderSpec("mirror"), overlap=True,
                             num_filters=3))
            cfgs.append(dict(execution="pallas", dtype="float32",
                             border=BorderSpec("mirror"), overlap=True,
                             separable=True))
        if "int8" in dts:
            from repro.core.requant import RequantSpec
            cfgs.append(dict(execution="pallas", dtype="int8",
                             border=BorderSpec("mirror"), overlap=True,
                             requant=RequantSpec(1, 7, dtype="int8")))
    return cfgs


def _compile_cfg(cfg: dict):
    from repro.core.pipeline import Filter2D
    mesh = None
    if cfg["execution"] == "sharded":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    spec = Filter2D(window=SWEEP_WINDOW, border=cfg["border"],
                    dtype=cfg["dtype"],
                    num_filters=cfg.get("num_filters", 1),
                    separable=cfg.get("separable", False),
                    requant=cfg.get("requant"))
    return spec.compile(SWEEP_FRAME, cfg["execution"], mesh=mesh,
                        strip_h=SWEEP_STRIP, tile_w=SWEEP_TILE,
                        overlap=cfg["overlap"])


def cfg_key(cfg: dict) -> str:
    bits = [cfg["execution"], cfg["dtype"], cfg["border"].policy,
            "overlap" if cfg["overlap"] else "serial"]
    if cfg.get("num_filters", 1) > 1:
        bits.append(f"bank{cfg['num_filters']}")
    if cfg.get("separable"):
        bits.append("separable")
    if cfg.get("requant") is not None:
        bits.append("requant")
    return "/".join(bits)


def sweep(executors: Optional[Sequence[str]] = None,
          dtypes: Optional[Sequence[str]] = None,
          borders: Optional[Sequence[str]] = None,
          progress=None) -> Dict[str, Report]:
    """Run :func:`verify` over the whole shipped matrix; returns
    ``{config key: Report}``. Compile failures become error Reports."""
    out: Dict[str, Report] = {}
    for cfg in sweep_configs(executors, dtypes, borders):
        k = cfg_key(cfg)
        try:
            cf = _compile_cfg(cfg)
        except Exception as e:
            out[k] = Report(key=k, error=_err(e))
            continue
        out[k] = verify(cf)
        if progress is not None:
            progress(k, out[k])
    return out
