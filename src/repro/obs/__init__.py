"""repro.obs — the observability subsystem: event trace, metrics, hooks.

Zero-overhead-when-off instrumentation of the whole pipeline stack:

  * ``obs.enable(jsonl=...)`` / ``obs.disable()`` / ``obs.tracing()`` —
    the one switch. On: plan decisions (``derive_strip_tile`` candidate
    scans), ``execution='auto'`` selections, compiles and per-call
    executions (wall time, pixels/s, cache hit vs recompile) land as
    typed events in a bounded ring and, optionally, a JSONL sink; call
    latencies land in the process-wide :data:`metrics.REGISTRY`.
    Off (the default): every hook is a single attribute-test branch.
  * ``obs.span(name, **meta)`` — the one span primitive. Recording while
    a ``jax.profiler`` session collects or the switch is on: a
    ``TraceAnnotation`` on the profiler's host plane plus a
    ``span/<name>`` µs histogram in ``REGISTRY``. The filter call has
    ``repro.call.operands`` / ``repro.call.launch``, the serving engine
    ``repro.serve.admit`` / ``repro.serve.copy_out``. Not recording: one
    attribute test and one ``is_enabled()`` branch.
  * ``CompiledFilter.explain()`` — the queryable plan report built on the
    same accounting (see ``core/pipeline.py``).
  * ``obs.roofline`` — the peak constants + two-ceiling roofline model
    every analytic pixel-rate claim is stated in.

The no-retrace contract holds with tracing on: events are host-side
records about compiled executables, never traced operands — pinned by
``tests/test_compiled_filter.py``; ring/sink/registry semantics by
``tests/test_obs.py``. Schema + usage: ``docs/observability.md``.
"""
from repro.obs import events, metrics, roofline
# NOTE: ``events`` stays bound to the *submodule* (so
# ``from repro.obs import events`` is never shadowed by the accessor
# function); the module-level ``events(kind=...)`` accessor is reachable
# as ``obs.events.events`` or via ``obs.get_trace().events(...)``.
from repro.obs.events import (AutoSelectEvent, CompileEvent, ExecuteEvent,
                              PlanEvent, ServeWaveEvent, Trace, disable,
                              emit, enable, enabled, get_trace, tracing)
from repro.obs.metrics import REGISTRY
from repro.obs.profiler import profile_dump, recording, span

__all__ = [
    "AutoSelectEvent", "CompileEvent", "ExecuteEvent", "PlanEvent",
    "REGISTRY", "ServeWaveEvent", "Trace", "disable", "emit", "enable",
    "enabled", "events", "get_trace", "metrics", "profile_dump",
    "recording", "roofline", "span", "tracing",
]
