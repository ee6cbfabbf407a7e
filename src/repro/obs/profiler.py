"""Host spans on the profiler's clock, and the opt-in capture knob.

  * :func:`span` — the one span primitive. While *recording* (a
    ``jax.profiler`` session is collecting, or ``repro.obs`` is on) it
    opens a ``jax.profiler.TraceAnnotation`` — the span lands on the
    calling thread's line of the profiler's ``/host:CPU`` plane, on the
    same clock as the device's ``XLA Ops`` — and records its duration in
    µs into ``REGISTRY.histogram("span/<name>")``. Otherwise it costs one
    attribute test and one ``is_enabled()`` branch, and formats no
    metadata. Spans are leaves: none is opened inside another, so a
    span's duration is its self time.
  * ``jax.named_scope`` — used *inside* jitted impls (see
    ``core/pipeline.py`` / ``kernels/filter2d/ops.py``). Those are pure
    trace-time metadata (XLA op name prefixes): zero runtime cost, so
    they are unconditional — and the tpu-lowering CI lane proves they
    survive ``jax.export``.
  * :func:`profile_dump` — the opt-in capture knob
    (``Filter2D.compile(..., profile_dump=dir)``): wraps one call in
    ``jax.profiler.trace(dir)`` so the XLA/TensorBoard trace lands on
    disk without the caller touching the profiler API.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from repro.obs import events as _events
from repro.obs import metrics as _metrics

__all__ = ["profile_dump", "recording", "span"]

_collecting = TraceAnnotation.is_enabled
_OFF = contextlib.nullcontext()


def recording() -> bool:
    """True while a profiler session collects or ``repro.obs`` is on."""
    return _events._TRACE is not None or _collecting()


class _Span:
    """One recording span; ``set(**meta)`` adds metadata before it
    closes (for values known only at the end, or costly to build)."""

    __slots__ = ("_name", "_ann", "_t0")

    def __init__(self, name: str, meta: dict):
        self._name = name
        self._ann = TraceAnnotation(name, **meta)

    def set(self, **meta) -> None:
        self._ann.set_metadata(**meta)

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        us = (time.perf_counter() - self._t0) * 1e6
        self._ann.__exit__(*exc)
        _metrics.REGISTRY.histogram("span/" + self._name).record(us)


def span(name: str, **meta):
    """``with span("repro.call.launch") as s:`` — ``s`` is the open span
    while recording (``s.set(k=v)`` adds metadata) and ``None`` when not,
    in which case nothing is recorded and ``meta`` is never formatted."""
    if _events._TRACE is None and not _collecting():
        return _OFF
    return _Span(name, meta)


@contextlib.contextmanager
def profile_dump(log_dir: Optional[str]):
    """``jax.profiler.trace`` into ``log_dir`` (no-op when ``None``)."""
    if log_dir is None:
        yield
        return
    import jax.profiler
    with jax.profiler.trace(str(log_dir)):
        yield
