"""The load generator: one traffic file's parameters drive one of two loops.

* ``closed``: a ring of seeded frames lives on the device and the caller
  keeps ``in_flight`` frames outstanding, blocking on frame i − in_flight
  before dispatching frame i, through ``Filter2D.compile(shape)`` (the
  default executor choice, ``auto``; with a mesh where the configuration
  spreads a frame over several chips). A new coefficient set every
  ``coeff_period`` frames.
* ``open``: requests arrive on a schedule drawn up front and are submitted
  to ``FilterServeEngine()`` at its defaults whether or not earlier ones
  have finished. Each request's latency runs from when it was due to when
  its result is in host memory, so a stall shows on every request behind
  it, and the generator's own lateness is reported beside it.

Both keep what the check compares: a sample of outputs drawn from the seed
(the closed loop by reservoir, as the count is not known in advance; the
open loop by index, plus a few probe pixels of every request).
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from bench import reference, roofline, workload

DRAIN_S = 60.0            # an answer may come this long after the close


@dataclasses.dataclass
class Observation:
    """What one window showed, on the host clock; metric readers take
    their numbers from here."""

    window_s: float
    pixels_done: float
    attempted: int
    failed: int
    requests: Optional[Dict[str, np.ndarray]] = None
    engine: Optional[Dict[str, int]] = None
    late_s: Optional[np.ndarray] = None
    setup_seconds: Optional[float] = None
    trace: object = None
    trace_window: Optional[tuple] = None
    work_per_call: Optional[roofline.Work] = None
    device_kind: str = ""
    compiles_in_window: int = 0


def annotation(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def program_spec(cfg: dict):
    """The configuration as the program's ``Filter2D``."""
    from repro.core.border_spec import BorderSpec
    from repro.core.pipeline import Filter2D
    from repro.core.requant import RequantSpec
    rq = cfg.get("requant")
    return Filter2D(window=cfg["window"], border=BorderSpec(cfg["border"]),
                    dtype=cfg["dtype"],
                    requant=(RequantSpec(1, 0, rounding=rq["rounding"],
                                         dtype=rq["dtype"])
                             if rq else None))


def program_gains(cfg: dict, gains):
    """A (multiplier, shift) pair as the caller hands it to the program:
    a ``RequantSpec`` with the configuration's rounding and storage."""
    if gains is None:
        return None
    from repro.core.requant import RequantSpec
    rq = cfg["requant"]
    return RequantSpec(gains[0], gains[1], rounding=rq["rounding"],
                       dtype=rq["dtype"])


def out_dtype(cfg: dict) -> str:
    rq = cfg.get("requant")
    return rq["dtype"] if rq else cfg["dtype"]


def _gap_check(outs, inputs, cfg) -> Dict[str, float]:
    """Largest gap between each output and the reference on its inputs
    (frame, coefficients, gains), and how many outputs came back with the
    wrong shape or dtype."""
    gap, malformed = 0.0, 0
    shape = (cfg["height"], cfg["width"])
    for got, (frame, k, g) in zip(outs, inputs):
        if got.shape != shape or got.dtype != np.dtype(out_dtype(cfg)):
            malformed += 1
            continue
        want = reference.filter_frame(frame, k, cfg, g)
        gap = max(gap, reference.max_abs_gap(got, want))
    return {"max_abs_gap": gap, "malformed": malformed,
            "outputs_compared": len(outs)}


class ClosedLoop:
    """A device-resident stream with ``in_flight`` frames outstanding."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices: list):
        import jax
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                                  SingleDeviceSharding)
        self.cfg, self.tr = cfg, tr
        r = workload.rngs(seed)
        self.sample_rng = r["sample"]
        mesh = (Mesh(np.array(devices), ("data",)) if len(devices) > 1
                else None)
        frame_at = (NamedSharding(mesh, P("data", None)) if mesh
                    else SingleDeviceSharding(devices[0]))
        coeff_at = (NamedSharding(mesh, P()) if mesh
                    else SingleDeviceSharding(devices[0]))
        t0 = time.perf_counter()
        self.ring = workload.device_frames(cfg, seed, tr["ring_frames"],
                                           frame_at)
        self.host_sets = workload.coeff_sets(cfg, r["coeffs"],
                                             tr["coeff_sets"])
        self.sets = [(jax.device_put(k, coeff_at), program_gains(cfg, g))
                     for k, g in self.host_sets]
        jax.block_until_ready(self.ring)
        t1 = time.perf_counter()
        self.filter = program_spec(cfg).compile(
            (cfg["height"], cfg["width"]), "auto", mesh=mesh)
        self.n_devices = len(devices)
        self.kept: List[tuple] = []
        self._run(frames=1, keep=False)
        t2 = time.perf_counter()
        self._run(frames=2 * tr["ring_frames"], keep=False)
        self.setup_parts = (t1 - t0, t2 - t1, time.perf_counter() - t2)

    def describe(self) -> str:
        f = self.filter
        return (f"executor={f.execution} regime={f.regime} "
                f"strip_h={f.strip_h} tile_w={f.tile_w} "
                f"rule={f.selection[0]} devices={self.n_devices}; "
                "set-up s: data={:.3f} plan+first call={:.3f} "
                "warm-up={:.3f}".format(*self.setup_parts))

    def _offer(self, n: int, item: tuple) -> None:
        """Reservoir sampling: every frame of the window is kept with the
        same chance, ``sample_outputs`` of them in all."""
        k = self.tr["sample_outputs"]
        if n < k:
            self.kept.append(item)
        else:
            j = int(self.sample_rng.integers(0, n + 1))
            if j < k:
                self.kept[j] = item

    def _run(self, seconds: Optional[float] = None,
             frames: Optional[int] = None, keep: bool = True,
             ann=annotation(False)):
        tr = self.tr
        ring, sets, period = self.ring, self.sets, tr["coeff_period"]
        inflight: deque = deque()
        n = 0
        t0 = time.perf_counter()
        end = t0 + (seconds or 0.0)
        while (time.perf_counter() < end) if frames is None else n < frames:
            if len(inflight) == tr["in_flight"]:
                with ann("bench.block"):
                    inflight.popleft().block_until_ready()
            ri, si = n % len(ring), (n // period) % len(sets)
            with ann("bench.dispatch"):
                y = self.filter(ring[ri], *sets[si])
            inflight.append(y)
            if keep:
                self._offer(n, (ri, si, y))
            n += 1
        with ann("bench.block"):
            for y in inflight:
                y.block_until_ready()
        return n, time.perf_counter() - t0

    def window(self, seconds: float, annotate: bool = False) -> Observation:
        cfg = self.cfg
        n, dt = self._run(seconds=seconds, ann=annotation(annotate))
        work = roofline.filter_work(cfg["height"], cfg["width"],
                                    cfg["window"], cfg["dtype"],
                                    out_dtype(cfg)) / self.n_devices
        return Observation(window_s=dt,
                           pixels_done=float(n * cfg["height"]
                                             * cfg["width"]),
                           attempted=n, failed=0, work_per_call=work)

    def check(self) -> Dict[str, float]:
        """Compare the kept outputs with the reference, once the program's
        state is freed."""
        outs = [np.asarray(y) for _, _, y in self.kept]
        hosts = {ri: np.asarray(self.ring[ri]) for ri, _, _ in self.kept}
        self.inputs = [(hosts[ri],) + self.host_sets[si]
                       for ri, si, _ in self.kept]
        self.kept, self.ring, self.sets, self.filter = [], [], [], None
        return _gap_check(outs, self.inputs, self.cfg)


class OpenLoop:
    """Open-loop arrivals from many tenants through the serving engine."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices: list):
        from repro.core.pipeline import admit_batch, batched_shape
        from repro.serving.engine import FilterServeEngine
        self.cfg, self.tr = cfg, tr
        self.r = workload.rngs(seed)
        self.pool = workload.host_frames(cfg, self.r["frames"],
                                         tr["frame_pool"])
        self.host_sets = workload.coeff_sets(cfg, self.r["coeffs"],
                                             tr["tenants"])
        self.sets = [(k, program_gains(cfg, g)) for k, g in self.host_sets]
        self.spec = program_spec(cfg)
        self.engine = FilterServeEngine()
        t0 = time.perf_counter()
        # every wave size's admission ops, then one request per tenant
        for k in range(1, self.engine.batch_size + 1):
            admit_batch(list(self.pool[:k]), self.engine.batch_size)
        self._submit(0, 0).result(timeout=900)
        t1 = time.perf_counter()
        for t in range(1, tr["tenants"]):
            self._submit(t, t % tr["frame_pool"]).result(timeout=900)
        self.pipeline = self.spec.compile(
            batched_shape(self.pool[0].shape, self.engine.batch_size),
            self.engine.execution)
        self.outs: List[tuple] = []
        self.setup_parts = (t1 - t0, time.perf_counter() - t1)

    def describe(self) -> str:
        f, e = self.pipeline, self.engine
        return (f"executor={f.execution} regime={f.regime} "
                f"strip_h={f.strip_h} rule={f.selection[0]} "
                f"batch={e.batch_size} cache_slots={e.cache_slots}; "
                "set-up s: admission ops+plan+first request={:.3f} "
                "other tenants={:.3f}".format(*self.setup_parts))

    def _submit(self, tenant: int, frame: int):
        k, g = self.sets[tenant]
        return self.engine.submit(self.pool[frame], k, spec=self.spec,
                                  gains=g, tenant=f"tenant{tenant}")

    def window(self, seconds: float, annotate: bool = False) -> Observation:
        ann = annotation(annotate)
        tr, cfg = self.tr, self.cfg
        sch = workload.schedule(tr, seconds, self.r["schedule"])
        n = len(sch["due"])
        keep = set(workload.sample(self.r["sample"], n,
                                   tr["sample_outputs"]).tolist())
        rows, cols = workload.probes(self.r["probes"], n, cfg,
                                     tr["probe_pixels"])
        rec = {k: np.full(n, np.nan) for k in
               ("due", "submit", "admit", "done")}
        rec["ok"] = np.zeros(n, bool)
        got = np.zeros((n, rows.shape[1]), np.float64)
        self.outs = []
        handed: "queue.SimpleQueue" = queue.SimpleQueue()
        t0 = time.perf_counter() + 0.05
        close = t0 + seconds

        def collect():
            for _ in range(n):
                i, req = handed.get()
                with ann("bench.result"):
                    served = req.wait(max(close + DRAIN_S
                                          - time.perf_counter(), 0.0))
                rec["submit"][i] = req.submit_t
                if req.admit_t is not None:
                    rec["admit"][i] = req.admit_t
                if not served:
                    continue
                try:
                    out = req.result(timeout=0)
                except Exception:  # noqa: BLE001 - a failed request
                    continue
                rec["done"][i] = req.done_t
                rec["ok"][i] = True
                got[i] = out[rows[i], cols[i]]
                if i in keep:
                    self.outs.append((np.array(out), i))

        before = self.engine.stats()
        worker = threading.Thread(target=collect, name="bench-collect")
        worker.start()
        for i in range(n):
            due = t0 + sch["due"][i]
            rec["due"][i] = due
            wait = due - time.perf_counter()
            if wait > 0:
                with ann("bench.wait_arrival"):
                    time.sleep(wait)
            with ann("bench.submit"):
                req = self._submit(int(sch["tenant"][i]),
                                   int(sch["frame"][i]))
            handed.put((i, req))
        rest = close - time.perf_counter()
        if rest > 0:
            with ann("bench.wait_arrival"):
                time.sleep(rest)
        worker.join(DRAIN_S + 5.0)
        after = self.engine.stats()
        self.sch, self.rows, self.cols, self.got = sch, rows, cols, got
        self.rec = rec
        px = cfg["height"] * cfg["width"]
        done_in = int(np.sum(rec["ok"] & (rec["done"] <= close)))
        work = roofline.filter_work(cfg["height"], cfg["width"],
                                    cfg["window"], cfg["dtype"],
                                    out_dtype(cfg),
                                    planes=self.engine.batch_size)
        return Observation(
            window_s=seconds, pixels_done=float(done_in * px), attempted=n,
            failed=int(n - rec["ok"].sum()), requests=rec,
            engine={k: after[k] - before[k] for k in after},
            late_s=rec["submit"] - rec["due"], work_per_call=work)

    def check(self) -> Dict[str, float]:
        """Every request at its probe pixels, and the sampled ones whole,
        against the reference, once the engine is shut down."""
        self.engine.shutdown()
        self.engine = self.pipeline = None
        cfg, sch, ok = self.cfg, self.sch, self.rec["ok"]
        probe_gap = 0.0
        for i in np.flatnonzero(ok):
            k, g = self.host_sets[sch["tenant"][i]]
            want = reference.filter_at(self.pool[sch["frame"][i]], k, cfg,
                                       g, self.rows[i], self.cols[i])
            probe_gap = max(probe_gap,
                            reference.max_abs_gap(self.got[i], want))

        self.inputs = [(self.pool[sch["frame"][i]],)
                       + self.host_sets[sch["tenant"][i]]
                       for _, i in self.outs]
        checks = _gap_check([o for o, _ in self.outs], self.inputs, cfg)
        checks["max_abs_gap"] = max(checks["max_abs_gap"], probe_gap)
        checks["unserved"] = int((~ok).sum())
        return checks


LOOPS = {"closed": ClosedLoop, "open": OpenLoop}
