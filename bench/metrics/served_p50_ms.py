"""Median of due time → result on the host over the window's requests."""
import numpy as np

from bench.metrics.common import served_latency_ms


def read(obs):
    lat = served_latency_ms(obs)
    return None if lat is None else float(np.percentile(lat, 50))
