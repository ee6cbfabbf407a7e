"""Analytic roofline arithmetic + the published peaks it is stated in.

One source of truth for the device peaks the whole repo quotes, keyed by
``device_kind`` as JAX reports it (``jax.devices()[0].device_kind``):
``CompiledFilter.explain()`` derives its predicted pixel rate from the
entry of the device it runs on, and ``benchmarks/common.py`` re-exports
the v5e entry for its analytic rows. A device that is not in the table
gets no prediction — never another device's numbers.

The model is the classic two-ceiling roofline (the dace ``RooflineModel``
pattern): a kernel that issues ``f`` operations and moves ``b`` HBM bytes
per output pixel sustains at most ``min(peak_ops / f, hbm_bw / b)``
pixels/s. The op peaks are the MXU's; the filter's MAC loop runs on the
VPU, so the compute ceiling is an upper bound it does not reach.
"""
from __future__ import annotations

from typing import Dict, Optional

__all__ = ["PEAKS", "V5E", "peaks", "predicted_pixel_rate"]

V5E = "TPU v5 lite"

# Published per-chip peaks. TPU v5e: Google Cloud documentation, "TPU
# v5e" (cloud.google.com/tpu/docs/v5e, system architecture table): 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> Optional[Dict[str, float]]:
    """The published peaks of ``device_kind``, or None when unknown."""
    return PEAKS.get(device_kind)


def predicted_pixel_rate(flops_per_pixel: float,
                         bytes_per_pixel: Optional[float],
                         device_kind: str,
                         integer: bool = False) -> Dict[str, object]:
    """Both roofline ceilings and the binding one, per output pixel, on
    ``device_kind`` (the int8 op peak for ``integer`` datapaths, else
    bf16).

    Returns ``compute_bound_pixels_per_s``, ``memory_bound_pixels_per_s``
    (``inf`` when the respective cost is zero/unknown), the ``min`` of the
    two as ``predicted_pixels_per_s``, and ``bound`` naming the ceiling.
    For a device with no published peaks the rates are None and ``why``
    says so.
    """
    out: Dict[str, object] = {
        "device_kind": device_kind,
        "flops_per_pixel": float(flops_per_pixel),
        "bytes_per_pixel": (float(bytes_per_pixel)
                            if bytes_per_pixel else None),
    }
    p = peaks(device_kind)
    if p is None:
        out.update(predicted_pixels_per_s=None, bound=None,
                   why=f"no published peaks for device kind "
                       f"{device_kind!r}: no roofline prediction")
        return out
    peak_ops = p["int8_ops"] if integer else p["bf16_flops"]
    compute = (peak_ops / flops_per_pixel if flops_per_pixel
               else float("inf"))
    memory = (p["hbm_bw"] / bytes_per_pixel if bytes_per_pixel
              else float("inf"))
    out.update(
        compute_bound_pixels_per_s=compute,
        memory_bound_pixels_per_s=memory,
        predicted_pixels_per_s=min(compute, memory),
        bound="compute" if compute < memory else "memory",
        peak_flops=float(peak_ops),
        hbm_bw=float(p["hbm_bw"]))
    return out
