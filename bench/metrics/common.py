"""Arithmetic several readers share."""
from __future__ import annotations

import numpy as np

FAILED_MS = 1e9            # a failed or unserved request: over every limit


def served_latency_ms(obs):
    """Due time → result in host memory, per request due in the window;
    a request that failed or never came counts as ``FAILED_MS``."""
    rec = obs.requests
    if rec is None or not len(rec["due"]):
        return None
    lat = (rec["done"] - rec["due"]) * 1e3
    return np.where(rec["ok"], lat, FAILED_MS)


def per_device_mean(values):
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None
