"""Bytes the sharded executor's halo exchange moved per filter call over
the traced window, summed over the shards: ``pipeline.halo_bytes /
pipeline.sharded_calls`` from ``repro.obs.REGISTRY``. The program counts
them only while a profiler session collects, so they hold the traced
window alone. A program without the counters reads None."""
from __future__ import annotations

CALLS, BYTES = "pipeline.sharded_calls", "pipeline.halo_bytes"


def _counts():
    from repro import obs
    counters = obs.REGISTRY.counters()
    if CALLS not in counters:
        return None
    return counters[CALLS], counters.get(BYTES, 0)


def read(obs):
    counts = _counts()
    if counts is None or not counts[0]:
        return None
    calls, nbytes = counts
    return nbytes / calls


def describe(obs) -> str:
    counts = _counts()
    if counts is None:
        return "no counters"
    return f"sharded_calls={counts[0]} halo_bytes={counts[1]}"
