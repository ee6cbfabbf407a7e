"""The plain reference the benchmark holds the program to, and its control.

Straight numpy, written from the paper's semantics and importing nothing
of the program:

* border extension by ``numpy.pad`` (``mirror`` is numpy's ``reflect``:
  the edge pixel is not repeated);
* a w×w correlation (no kernel flip): ``y[h, w] = Σ k[i, j] ·
  x_ext[h + i, w + j]``, exact in integers for fixed-point frames and in
  float64 for float frames;
* the requantising epilogue ``clip(round((acc · m) / 2**s))`` with the
  two's-complement identities of a hardware shifter (arithmetic shift =
  floor; ``nearest`` adds the half LSB first; ``nearest_even`` ties to
  even), saturating to the storage dtype;
* the unity-gain scaler ``m / 2**s ≈ 1 / Σk`` with the largest shift whose
  product keeps the int32 headroom.

``control`` computes the same filter one precision step below what the
configuration states (an int16 accumulator for the int32 one, bfloat16
operands for float32): the change a later PR could be tempted to make,
which the comparison has to catch.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

PAD_MODES = {"mirror": "reflect", "mirror_dup": "symmetric",
             "duplicate": "edge", "wrap": "wrap", "constant": "constant"}
INT_DTYPES = ("uint8", "int8", "int16")
ROW_BLOCK = 64                         # rows per block: keeps taps in cache


def is_integer(dtype: str) -> bool:
    return dtype in INT_DTYPES


def unity_gain(coeffs: np.ndarray, in_dtype: str,
               rounding: str = "nearest") -> Tuple[int, int]:
    """(multiplier, shift) with ``m / 2**s ≈ 1 / Σk``: the largest shift
    for which ``|acc_max · m|`` plus the rounding bias fits int32."""
    k = np.asarray(coeffs, np.int64)
    g = int(k.sum())
    if g == 0:
        raise ValueError("a zero-sum filter has no unity gain")
    info = np.iinfo(np.dtype(in_dtype))
    acc_max = int(np.abs(k).sum()) * max(abs(int(info.min)), int(info.max))
    lim = 2 ** 31 - 1
    for s in range(31, -1, -1):
        m = int(np.rint(2 ** s / g))
        if m == 0:
            continue
        bias = (1 << (s - 1)) if (s and rounding == "nearest") else 0
        if abs(m) * acc_max + bias <= lim:
            return m, s
    raise ValueError(f"no unity-gain scaler keeps {acc_max} in int32")


def extend(frame: np.ndarray, r: int, border: str) -> np.ndarray:
    return np.pad(frame, r, mode=PAD_MODES[border])


def remap(idx: np.ndarray, n: int, border: str):
    """Where border extension takes pixel ``idx`` of an axis of length
    ``n`` from (``numpy.pad``'s rule for the policy), and whether it is in
    the frame at all (``constant`` fills the rest with 0)."""
    inside = (idx >= 0) & (idx < n)
    if border == "mirror":
        p = max(2 * (n - 1), 1)
        m = np.mod(idx, p)
        return np.where(m < n, m, p - m), np.ones_like(inside)
    if border == "mirror_dup":
        m = np.mod(idx, 2 * n)
        return np.where(m < n, m, 2 * n - 1 - m), np.ones_like(inside)
    if border == "duplicate":
        return np.clip(idx, 0, n - 1), np.ones_like(inside)
    if border == "wrap":
        return np.mod(idx, n), np.ones_like(inside)
    if border == "constant":
        return np.clip(idx, 0, n - 1), inside
    raise ValueError(f"unknown border {border!r}")


def _acc_dtype(frame: np.ndarray, coeffs: np.ndarray):
    """int32 where the widest sum fits it (as the stated datapath's
    accumulator does), else int64; float64 for float frames."""
    if frame.dtype.kind not in "iu":
        return np.float64
    info = np.iinfo(frame.dtype)
    widest = (int(np.abs(np.asarray(coeffs, np.int64)).sum())
              * max(abs(int(info.min)), int(info.max)))
    return np.int32 if widest < 2 ** 31 else np.int64


def correlate(frame: np.ndarray, coeffs: np.ndarray, border: str,
              acc_dtype=None) -> np.ndarray:
    """The w×w correlation of ``frame`` after border extension, summed in
    ``acc_dtype`` (by default exact: integers in a type no sum overflows,
    floats in float64), tap by tap in raster order, a block of rows at a
    time."""
    w = coeffs.shape[-1]
    r = (w - 1) // 2
    H, W = frame.shape
    if acc_dtype is None:
        acc_dtype = _acc_dtype(frame, coeffs)
    xp = extend(frame, r, border).astype(acc_dtype)
    k = np.asarray(coeffs).astype(acc_dtype)
    out = np.empty((H, W), acc_dtype)
    for h0 in range(0, H, ROW_BLOCK):
        h1 = min(h0 + ROW_BLOCK, H)
        acc = np.zeros((h1 - h0, W), acc_dtype)
        for i in range(w):
            rows = xp[h0 + i:h1 + i]
            for j in range(w):
                acc += k[i, j] * rows[:, j:j + W]
        out[h0:h1] = acc
    return out


def round_shift(prod: np.ndarray, shift: int, rounding: str) -> np.ndarray:
    prod = np.asarray(prod, np.int64)
    if shift == 0:
        return prod
    if rounding == "truncate":
        return prod >> shift
    half = np.int64(1) << (shift - 1)
    if rounding == "nearest":
        return (prod + half) >> shift
    if rounding == "nearest_even":
        base = prod >> shift
        rem = prod & ((np.int64(1) << shift) - 1)
        up = (rem > half) | ((rem == half) & ((base & 1) == 1))
        return base + up.astype(np.int64)
    raise ValueError(f"unknown rounding {rounding!r}")


def requantize(acc: np.ndarray, gains: Tuple[int, int], rounding: str,
               out_dtype: str) -> np.ndarray:
    m, s = gains
    q = round_shift(np.asarray(acc, np.int64) * np.int64(m), s, rounding)
    info = np.iinfo(np.dtype(out_dtype))
    return np.clip(q, info.min, info.max).astype(out_dtype)


def filter_frame(frame: np.ndarray, coeffs: np.ndarray, cfg: dict,
                 gains: Optional[Tuple[int, int]] = None) -> np.ndarray:
    """What the configuration says the filter returns for ``frame``."""
    acc = correlate(frame, coeffs, cfg["border"])
    rq = cfg.get("requant")
    if rq is None:
        return acc
    return requantize(acc, gains, rq["rounding"], rq["dtype"])


def filter_at(frame: np.ndarray, coeffs: np.ndarray, cfg: dict,
              gains: Optional[Tuple[int, int]], rows: np.ndarray,
              cols: np.ndarray) -> np.ndarray:
    """``filter_frame(...)[rows, cols]``, computed at those pixels only."""
    w = coeffs.shape[-1]
    r = (w - 1) // 2
    acc_dtype = np.int64 if frame.dtype.kind in "iu" else np.float64
    taps = np.arange(-r, w - r)
    ri, rin = remap(rows[:, None] + taps, frame.shape[0], cfg["border"])
    ci, cin = remap(cols[:, None] + taps, frame.shape[1], cfg["border"])
    win = frame[ri[:, :, None], ci[:, None, :]].astype(acc_dtype)
    win *= rin[:, :, None] & cin[:, None, :]
    acc = (win * np.asarray(coeffs).astype(acc_dtype)).sum(axis=(1, 2))
    rq = cfg.get("requant")
    if rq is None:
        return acc
    return requantize(acc, gains, rq["rounding"], rq["dtype"])


def control(frame: np.ndarray, coeffs: np.ndarray, cfg: dict,
            gains: Optional[Tuple[int, int]] = None) -> np.ndarray:
    """The filter one precision step below the configuration: integer
    frames accumulate in int16 (wrapping) instead of int32, float32
    frames and coefficients are rounded to bfloat16 before a float32
    multiply-accumulate."""
    if frame.dtype.kind in "iu":
        acc = correlate(frame, coeffs, cfg["border"], acc_dtype=np.int16)
    else:
        import ml_dtypes
        bf = ml_dtypes.bfloat16
        acc = correlate(frame.astype(bf).astype(np.float32),
                        np.asarray(coeffs).astype(bf).astype(np.float32),
                        cfg["border"], acc_dtype=np.float32)
    rq = cfg.get("requant")
    if rq is None:
        return acc
    return requantize(acc, gains, rq["rounding"], rq["dtype"])


def max_abs_gap(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64)
                               - np.asarray(want, np.float64)),
                        initial=0.0))
