"""The program's own host spans (``repro.obs.span``). Each records its
duration in µs into ``repro.obs.REGISTRY`` under ``span/<name>`` while a
profiler session collects; in a run of the benchmark that is the traced
window alone, so the histogram holds exactly the window's spans. A
program without the span leaves no histogram, and the reader returns
None."""
from __future__ import annotations


def _histogram(name: str):
    from repro import obs
    h = obs.REGISTRY.histograms().get("span/" + name)
    return h if h is not None and h.count else None


def p50_us(name: str):
    h = _histogram(name)
    return None if h is None else h.percentile(50)


def describe(name: str) -> str:
    h = _histogram(name)
    if h is None:
        return "no spans"
    s = h.summary()
    return " ".join(f"{k}={s[k]}" for k in ("count", "mean", "p50", "p90",
                                             "p99", "max"))
