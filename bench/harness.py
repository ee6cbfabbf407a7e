"""One run of one cell: set up, measure, check, and report.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name. The cell's entry in ``BENCHMARK.json`` names its configuration
(a JSON file), its traffic (``bench/traffic/<traffic>.json``, whose
``loop`` picks the generator's loop) and, through the metric entries, the
readers in ``bench/metrics/<name before the first dot>.py``.
"""
from __future__ import annotations

import importlib
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

from bench import generator, trace_reduce, workload


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's count (so
    the interpreter's own start-up is in it); the module's import time
    where ``/proc`` is not there."""
    try:
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        with open("/proc/self/stat") as fh:
            start = float(fh.read().rsplit(")", 1)[1].split()[19])
        return uptime - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however quick it compiles."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX traces, compilations and cache loads while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._event)


def metrics_for(bench: dict, cell_name: str, traced: bool) -> List[dict]:
    """The metric entries a cell reports: end-to-end ones in an untraced
    run, per-layer ones in a traced run."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(metric: str):
    return importlib.import_module(f"bench.metrics.{metric.split('.')[0]}")


def read_metrics(entries: List[dict], obs: generator.Observation,
                 required: bool) -> Dict[str, dict]:
    out = {}
    for m in entries:
        value = reader(m["name"]).read(obs)
        if value is None:
            if required:
                raise RuntimeError(f"metric {m['name']!r} read nothing in "
                                   "this cell")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def _traced_window(loop, seconds: float, obs_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(obs_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            obs = loop.window(seconds, annotate=True)
    pbs = [os.path.join(d, f) for d, _, fs in os.walk(obs_dir)
           for f in fs if f.endswith(".xplane.pb")]
    if len(pbs) != 1:
        raise RuntimeError(f"expected one trace file, found {pbs}")
    obs.trace = trace_reduce.load_xspace(pbs[0])
    obs.trace_window = obs.trace.window()
    return obs


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, devices: list, log=sys.stderr) -> dict:
    """Set up the cell, run one window, check it; the result line."""
    entry = workload.cell(bench, cell_name)
    cfg = workload.config(bench, entry["config"])
    tr = workload.traffic(entry["traffic"])
    return run_loaded(bench, cell_name, cfg, tr, seed, seconds, trace,
                      devices, log)


def run_loaded(bench: dict, cell_name: str, cfg: dict, tr: dict, seed: int,
               seconds: float, trace: bool, devices: list,
               log=sys.stderr) -> dict:
    ready = process_age_s()
    loop = generator.LOOPS[tr["loop"]](cfg, tr, seed, devices)
    print(f"plan: {loop.describe()}", file=log, flush=True)
    print(f"set-up: {ready} s to the devices, {process_age_s() - ready} s "
          "for data, compilation and warm-up", file=log, flush=True)
    counter = CompileCounter()
    setup_seconds = process_age_s()
    counter.on = True
    if trace:
        obs_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            obs = _traced_window(loop, min(seconds, tr["trace_seconds"]),
                                 obs_dir)
        finally:
            shutil.rmtree(obs_dir, ignore_errors=True)
    else:
        obs = loop.window(seconds)
    counter.on = False
    counter.close()
    obs.setup_seconds = setup_seconds
    obs.compiles_in_window = counter.count
    obs.device_kind = devices[0].device_kind
    memory = _peak_bytes(devices)
    entries = metrics_for(bench, cell_name, trace)
    metrics = read_metrics(entries, obs, required=not trace)
    if obs.late_s is not None and len(obs.late_s):
        late = obs.late_s[np.isfinite(obs.late_s)] * 1e3
        print(f"generator lateness: p95={np.percentile(late, 95)} ms "
              f"max={late.max()} ms over {len(late)} requests", file=log)
    print(f"compilations inside the window: {obs.compiles_in_window}",
          file=log)
    for m in entries:
        describe = getattr(reader(m["name"]), "describe", None)
        if describe is not None and m["name"] in metrics:
            print(f"{m['name']}: {describe(obs)}", file=log)

    found = loop.check()
    limits = cfg["limits"]
    checks = {k: {"value": float(v), "limit": float(limits[k])}
              for k, v in found.items() if k in limits}
    compared = int(found.get("outputs_compared", 0))
    correct = compared > 0 and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    print(f"outputs compared with the reference: {compared}", file=log)
    for k, c in checks.items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}", file=log)
    log.flush()

    import jax
    result = {"correct": bool(correct), "attempted": int(obs.attempted),
              "failed": int(obs.failed), "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": memory}}
    if trace:
        t, w = obs.trace, obs.trace_window
        result["device"]["busy_s"] = float(np.mean(
            [trace_reduce.busy_s(d, w) for d in t.devices]))
        result["device"]["window_s"] = (w[1] - w[0]) * 1e-9
        result["breakdown"] = trace_reduce.breakdown(t, w)
    result["checks"] = checks
    return result
