"""Paper Tables I/II/III/VI/VII analogues.

FPGA metrics have no TPU meaning 1:1, so each table maps to its role (see
DESIGN.md §2): DSP-block count -> MACs/pixel issued; Fmax -> pixels/s;
LUT/reg area -> HLO bytes moved; latency cycles -> startup rows before the
first output strip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import HBM_BW, hlo_costs, row, time_call
from repro.core import filters
from repro.core.borders import BorderSpec
from repro.core.filter2d import (FORMS, filter2d, macs_per_pixel,
                                 reduction_depth, startup_latency_rows)
from repro.core.pipeline import Filter2D

H, W = 480, 640          # the paper's synthesis target frame


def table2_unit_usage():
    """Table II: compute units per output pixel, per form/layout."""
    out = []
    for w in (3, 5, 7):
        for form in FORMS:
            out.append(row(f"table2/w{w}/{form}", 0.0,
                           f"macs_per_pixel={macs_per_pixel(w, form)};"
                           f"reduction_stages={reduction_depth(w, form)}"))
    return out


def table3_startup_latency():
    """Table III: rows that must stream in before the first output."""
    out = []
    for w in (3, 5, 7):
        for form in ("direct", "transposed"):
            rows_ = startup_latency_rows(w, form)
            # cycles analogue at one row-strip per step, IW=640
            out.append(row(f"table3/w{w}/{form}", 0.0,
                           f"startup_rows={rows_};startup_pixels="
                           f"{int(rows_ * W)}"))
    return out


def table6_direct_vs_transposed():
    """Table VI: direct vs transposed — wall time + HLO flops/bytes."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((H, W)).astype(np.float32))
    k = jnp.asarray(filters.gaussian(7))
    out = []
    for form in ("direct", "transposed"):
        fn = lambda a, b, f=form: filter2d(a, b, form=f,
                                           border=BorderSpec("neglect"))
        us = time_call(fn, x, k)
        costs = hlo_costs(fn, jax.ShapeDtypeStruct(x.shape, x.dtype),
                          jax.ShapeDtypeStruct(k.shape, k.dtype))
        mpix_s = (H * W) / (us / 1e6) / 1e6
        out.append(row(f"table6/{form}", us,
                       f"mpix_per_s_cpu={mpix_s:.1f};"
                       f"hlo_flops={costs['flops']:.3e};"
                       f"hlo_bytes={costs['bytes']:.3e}"))
    return out


def table7_reduction_layouts():
    """Table VII: the three adder-tree layouts (+ systolic direct)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((H, W)).astype(np.float32))
    k = jnp.asarray(filters.gaussian(7))
    out = []
    for form in FORMS:
        fn = lambda a, b, f=form: filter2d(a, b, form=f,
                                           border=BorderSpec("mirror"))
        us = time_call(fn, x, k)
        costs = hlo_costs(fn, jax.ShapeDtypeStruct(x.shape, x.dtype),
                          jax.ShapeDtypeStruct(k.shape, k.dtype))
        # analytic TPU-side bound: single-pass streaming, fp32
        tpu_pix_s = HBM_BW / 8.0
        out.append(row(f"table7/{form}", us,
                       f"mpix_per_s_cpu={(H*W)/(us/1e6)/1e6:.1f};"
                       f"hlo_bytes={costs['bytes']:.3e};"
                       f"tpu_bound_mpix_s={tpu_pix_s/1e6:.0f}"))
    return out


def separable_vs_direct():
    """The separable fast path (2w MACs/pixel) vs the w² direct form —
    the RIPL/Campos decomposition claim, on a rank-1 gaussian."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((H, W)).astype(np.float32))
    out = []
    for w in (3, 5, 7):
        k = jnp.asarray(filters.gaussian(w))
        us_d = time_call(lambda a, b: filter2d(a, b, form="direct"), x, k)
        us_s = time_call(lambda a, b: filter2d(a, b, separable=True), x, k)
        out.append(row(
            f"separable/w{w}", us_s,
            f"direct_us={us_d:.1f};speedup={us_d / max(us_s, 1e-9):.2f};"
            f"macs_direct={macs_per_pixel(w)};"
            f"macs_separable={macs_per_pixel(w, separable=True)}"))
    return out


def streaming_vs_resident():
    """The row-buffer schedule vs whole-frame: same output, bounded VMEM."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((H, W)).astype(np.float32))
    k = jnp.asarray(filters.gaussian(7))
    us_res = time_call(lambda a, b: filter2d(a, b), x, k)
    us_str = time_call(
        Filter2D(window=7).compile(x, "streaming", strip_h=96), x, k)
    return [row("stream/resident", us_res, ""),
            row("stream/rowbuffer96", us_str,
                f"ratio={us_str / max(us_res, 1e-9):.2f}")]


def run():
    out = []
    for fn in (table2_unit_usage, table3_startup_latency,
               table6_direct_vs_transposed, table7_reduction_layouts,
               separable_vs_direct, streaming_vs_resident):
        out.extend(fn())
    return out
