"""The yardstick of the roofline share: published peaks and the filter's
work, counted from the algorithm and not from any executor's plan.

A w×w filter does 2·w² operations per output pixel (w² multiplies and
w² adds, the accumulator's first add included), reads each input pixel
once and writes each output pixel once, at storage width. Whatever code
runs it, it cannot beat the larger of ops / peak ops and bytes / HBM
bandwidth. Integer datapaths are held to the int8 peak, float ones to
the bf16 peak: the fastest the chip does any such multiply-add.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

# Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.
# TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/
# docs/v5e, system architecture table): 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bw": 819e9},
}

_BYTES = {"uint8": 1, "int8": 1, "int16": 2, "float32": 4, "bfloat16": 2,
          "float16": 2}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never another device's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/roofline.py "
                       "with their source") from None


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float
    integer: bool

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes,
                    self.integer)

    def __truediv__(self, n: float) -> "Work":
        return Work(self.ops / n, self.bytes / n, self.integer)


def filter_work(height: int, width: int, window: int, in_dtype: str,
                out_dtype: str, planes: int = 1) -> Work:
    """The work of filtering ``planes`` same-size frames."""
    px = height * width * planes
    return Work(ops=2.0 * window * window * px,
                bytes=float(px * (_BYTES[in_dtype] + _BYTES[out_dtype])),
                integer=in_dtype in ("uint8", "int8", "int16"))


def least_time(work: Work, device_kind: str) -> Dict[str, object]:
    """The least seconds ``work`` can take on one chip of
    ``device_kind``, and which of the two bounds binds."""
    p = peaks(device_kind)
    compute = work.ops / (p["int8_ops"] if work.integer else p["bf16_flops"])
    memory = work.bytes / p["hbm_bw"]
    return {"seconds": max(compute, memory), "compute_s": compute,
            "memory_s": memory,
            "bound": "compute" if compute > memory else "memory"}
