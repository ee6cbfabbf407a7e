"""Share of the filter calls in the traced window whose requant gains
were uploaded to the device, in %: ``100 × uploads / (hits + uploads)``
from the program's ``pipeline.gain_uploads`` and ``pipeline.gain_hits``
counters in ``repro.obs.REGISTRY``. The program counts them only while a
profiler session collects, so they hold the traced window alone. A
program without the counters reads None."""
from __future__ import annotations

HITS, UPLOADS = "pipeline.gain_hits", "pipeline.gain_uploads"


def _counts():
    from repro import obs
    counters = obs.REGISTRY.counters()
    if HITS not in counters and UPLOADS not in counters:
        return None
    return counters.get(HITS, 0), counters.get(UPLOADS, 0)


def read(obs):
    counts = _counts()
    if counts is None or not sum(counts):
        return None
    hits, uploads = counts
    return 100.0 * uploads / (hits + uploads)


def describe(obs) -> str:
    counts = _counts()
    if counts is None:
        return "no counters"
    return f"gain_hits={counts[0]} gain_uploads={counts[1]}"
