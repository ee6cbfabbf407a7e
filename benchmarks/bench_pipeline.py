"""The plan-and-execute front door under the bench gate.

Rows exercise ``execution='auto'`` end to end: the pixel-cache decision at
the default VMEM budget, the budget-forced row-buffer decision, and the
int8 unity-gain requantised pipeline — each row carries the resolved
executor plus the static plan accounting (``hbm_bytes_per_pixel``,
``vmem_working_set``) so the windowed CI gate (benchmarks/compare.py)
diffs the *derived* geometry, not just wall time: an auto-selection or
strip-derivation regression is a one-commit-visible byte increase. The
swap row pins the served-pipeline property itself — coefficient and gain
swaps on a compiled pipeline report ``recompiles=0`` from the jit cache
counter.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_call
from repro.core import filters
from repro.core.border_spec import BorderSpec
from repro.core.pipeline import Filter2D
from repro.core.requant import RequantSpec
from repro.kernels.filter2d.halo import plan_banks

PH, PW = 128, 256        # interpret-mode frame (kept CI-small)
STREAM_BUDGET = 3 * 2 ** 20  # forces the row-buffer decision for PH x PW

# the same acceptance pin the fixed-point bench lanes carry: int8 in,
# requantised int8 out, ≤ 2.2 HBM bytes/pixel from the static plan
INT8_ROUND_TRIP_BUDGET = 2.2


def _auto_row(name, spec, x, coeffs, gains=None, **compile_kw):
    cf = spec.compile(x, "auto", **compile_kw)
    us = time_call(lambda a, b: cf(a, b, gains=gains), x, coeffs)
    derived = (f"pixels_per_s={PH * PW / (us * 1e-6):.3e};"
               f"execution={cf.execution};"
               f"resident_vmem={cf.resident_vmem_bytes}")
    if cf.plan is not None:
        derived += (f";hbm_bytes_per_pixel={cf.hbm_bytes_per_pixel():.2f}"
                    f";vmem_working_set={cf.vmem_working_set()}")
        # analytic two-ceiling roofline prediction (repro.obs.roofline
        # via explain()) on this device: none where it has no peaks
        roof = cf.explain(as_dict=True)["roofline"]
        if roof["predicted_pixels_per_s"] is not None:
            derived += (f";predicted_pixels_per_s="
                        f"{roof['predicted_pixels_per_s']:.3e}")
    if cf.strip_h is not None:
        derived += f";strip_h={cf.strip_h}"
    if cf.execution == "pallas" and cf.plan is not None:
        # kernel-generation stamp: the gate re-seeds rather than diff a
        # double-buffered row against a serial-era baseline
        eb, ob = plan_banks(cf.plan, num_filters=spec.num_filters,
                            overlap=cf.overlap)
        derived += f";banks={eb};out_banks={ob}"
    return cf, row(name, us, derived)


def run():
    rng = np.random.default_rng(0)
    out = []
    xf = jnp.asarray(rng.standard_normal((PH, PW)).astype(np.float32))
    kf = jnp.asarray(filters.gaussian(5))
    spec = Filter2D(window=5, border=BorderSpec("mirror"))

    # pixel-cache decision at the default budget
    cf, r = _auto_row("pipeline/auto/float32/pixel_cache", spec, xf, kf)
    assert cf.execution == "pallas" and cf.regime == "small", cf.execution
    out.append(r)

    # budget-forced row-buffer decision (jnp streaming executor)
    cf, r = _auto_row("pipeline/auto/float32/row_buffer", spec, xf, kf,
                      vmem_budget=STREAM_BUDGET)
    assert cf.execution == "streaming", cf.execution
    out.append(r)

    # int8 unity-gain requantised pipeline: turnkey epilogue + narrow
    # words both directions, derived geometry pinned to the bench budget
    ki = jnp.asarray(rng.integers(-4, 5, (5, 5)).astype(np.int32))
    rq = RequantSpec.unity_gain(np.asarray(ki), "int8")
    xi = jnp.asarray(rng.integers(-20, 20, (PH, PW)).astype(np.int8))
    ispec = Filter2D(window=5, dtype="int8", requant=rq.gain_free())
    cf, r = _auto_row("pipeline/auto/int8/unity_requant", ispec, xi, ki,
                      gains=rq, vmem_budget=STREAM_BUDGET)
    out.append(r)
    plan_cf = ispec.compile(xi, "pallas", vmem_budget=STREAM_BUDGET)
    assert plan_cf.hbm_bytes_per_pixel() <= INT8_ROUND_TRIP_BUDGET, (
        plan_cf.hbm_bytes_per_pixel())

    # the served-pipeline property: swaps hit the jit cache
    cf = spec.compile(xf, "pallas", strip_h=64, tile_w=128)
    cf(xf, kf)
    us = time_call(lambda a, b: cf(a, b), xf,
                   jnp.asarray(filters.box(5)))
    recompiles = cf.cache_size() - 1
    assert recompiles == 0, recompiles
    out.append(row("pipeline/swap/coeffs", us,
                   f"pixels_per_s={PH * PW / (us * 1e-6):.3e};"
                   f"recompiles={recompiles};"
                   f"hbm_bytes_per_pixel={cf.hbm_bytes_per_pixel():.2f}"))
    return out
