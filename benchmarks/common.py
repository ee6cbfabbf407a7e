"""Shared benchmark utilities: timing + compiled-cost inspection.

The peak constants live in :mod:`repro.obs.roofline` (one source of
truth shared with ``CompiledFilter.explain()``); this module re-exports
them so existing bench code keeps reading ``common.PEAK_FLOPS`` etc.
"""
from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import numpy as np

from repro.obs.metrics import percentiles
from repro.obs.roofline import PEAKS, V5E

# the v5e entry of the keyed peak table, for the analytic v5e rows
PEAK_FLOPS = PEAKS[V5E]["bf16_flops"]
HBM_BW = PEAKS[V5E]["hbm_bw"]

# Set by ``benchmarks.run --smoke``: CI-budget timing (fewer warmups/iters).
SMOKE = False

# IQR/median above this fraction marks a Timing ``noisy``: the compare
# gate then *warns* on rate regressions in that row instead of failing.
NOISY_IQR_FRACTION = 0.25


class Timing(float):
    """Median wall time per call in µs — a float (every existing call
    site keeps working) carrying the spread of the sample set:

      ``iqr_us``/``p50_us``/``p90_us``/``p99_us``, ``n``,
      ``noisy`` (IQR/median > :data:`NOISY_IQR_FRACTION`), and
      ``__iter__`` yielding ``(median, iqr)`` for tuple unpacking.
    """

    def __new__(cls, samples_us):
        samples_us = [float(s) for s in samples_us]
        p25, p50, p75, p90, p99 = percentiles(samples_us,
                                              (25, 50, 75, 90, 99))
        self = super().__new__(cls, p50)
        self.p50_us = p50
        self.p90_us = p90
        self.p99_us = p99
        self.iqr_us = p75 - p25
        self.n = len(samples_us)
        return self

    @property
    def noisy(self) -> bool:
        return self.iqr_us > NOISY_IQR_FRACTION * float(self)

    def __iter__(self):
        yield float(self)
        yield self.iqr_us

    def __repr__(self) -> str:
        flag = " noisy" if self.noisy else ""
        return (f"Timing({float(self):.1f}us, iqr={self.iqr_us:.1f}, "
                f"n={self.n}{flag})")


def time_call(fn: Callable, *args, warmup: int = 2, iters: int = 10
              ) -> Timing:
    """Median wall time per call in microseconds (CPU this container).

    Returns a :class:`Timing`: a float (the median) that also carries
    IQR/p90/p99 and the ``noisy`` flag — ``row()`` stamps those spread
    keys onto the bench row so the compare gate can judge stability.
    """
    if SMOKE:
        warmup, iters = 1, 2
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return Timing(ts)


def hlo_costs(fn: Callable, *abstract_args) -> Dict[str, float]:
    c = jax.jit(fn).lower(*abstract_args).compile()
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):     # older jax returns [dict]
        ca = ca[0] if ca else {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def row(name: str, us: float, derived: str = "") -> str:
    """One CSV bench row. A :class:`Timing` ``us`` also stamps its
    latency-spread keys (``p50_us``/``p90_us``/``p99_us``/``iqr_us``)
    and, when unstable, ``noisy=1`` into the derived segment."""
    if isinstance(us, Timing):
        spread = (f"p50_us={us.p50_us:.1f};p90_us={us.p90_us:.1f};"
                  f"p99_us={us.p99_us:.1f};iqr_us={us.iqr_us:.1f}")
        if us.noisy:
            spread += ";noisy=1"
        derived = f"{derived};{spread}" if derived else spread
    return f"{name},{us:.1f},{derived}"
