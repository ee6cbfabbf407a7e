"""Batched filter serving: a multi-tenant request queue over the
``Filter2D`` -> ``CompiledFilter`` front door.

The paper's cores sustain one pixel per cycle *under continuous load*;
the TPU port's analogue of continuous load is a stream of heterogeneous
frame-filter requests from many tenants. ``FilterServeEngine`` is that
front end, structured the way offline LM inference engines wrap their
decode step (maxtext's ``OfflineInference``: fixed slots, warm compiled
executables, background result threads):

  * **Buckets.** Every request carries a :class:`~repro.core.pipeline.
    Filter2D` spec and a frame; requests with the same (spec, frame
    geometry, dtype, compile knobs) identity — ``core.pipeline.
    bucket_key`` — are servable by the same compiled executable. The
    engine keeps a bounded LRU of warm ``CompiledFilter``s, one per
    bucket; a cold bucket compiles (``serve.recompiles``), a warm one
    dispatches immediately (``serve.cache_hits``).
  * **Waves.** Within a bucket, requests whose coefficients/gains agree
    (grouped per tenant) are batched into the pipeline's *plane grid
    dim* — k frames stack into one ``[B, H, W, C]`` dispatch
    (``core.pipeline.admit_batch``), zero-padded to the engine's static
    batch size so every wave reuses the one executable.
  * **Tenant swaps are free.** Coefficients, separable factors and
    requant gains are traced operands of the compiled pipeline (the
    pinned zero-recompile contract), so tenant A's wave and tenant B's
    wave alternate through the same bucket executable with zero
    recompiles — the paper's runtime coefficient file, multi-tenant.
  * **Overlap.** One background worker thread runs admission, dispatch
    and copy-out as a software pipeline: wave k+1 is admitted and
    dispatched (JAX async dispatch) *before* wave k's results are copied
    out, so host-side batching/copy-out overlaps device compute, and
    submitters never block on the device at all.

Instrumentation: the engine keeps its own always-on counters
(:meth:`FilterServeEngine.stats`, among them ``h2d_bytes``, the host
frames copied in, and ``d2h_bytes``, the results copied out with their
padding). Each wave opens two ``obs.span``s, ``repro.serve.admit``
(copy-in, stack, padding) and ``repro.serve.copy_out`` (the wait for the
device and the copy back), with the wave's number and its requests' ids
as metadata; they record while a profiler session collects or
``repro.obs`` is on. When ``repro.obs`` tracing is on, the engine also
mirrors its counters into ``obs.REGISTRY`` (``serve.requests``,
``serve.waves``, ``serve.cache_hits``, ``serve.recompiles``,
``serve.evictions``, ``serve.pixels``, ``serve.errors``,
``serve.cancelled``; histograms ``serve/request_us``, ``serve/wave_us``,
``serve/wave_us/<bucket8>``, ``serve/queue_depth``) and emits one
:class:`~repro.obs.events.ServeWaveEvent` per wave. ``serving/bench.py``
drives the engine under an open-loop Poisson arrival process and turns
those numbers into the ``SERVE_smoke.json`` CI lane.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict, deque
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import (Filter2D, admit_batch, batched_shape,
                                 bucket_key, split_batch)
from repro.core.requant import RequantSpec
from repro.obs import events as obs_events
from repro.obs import metrics as obs_metrics
from repro.obs import profiler as obs_profiler


def _wave_meta(seq: int, wave) -> dict:
    """A wave's span metadata (its dispatch number and its riders' ids),
    built only while spans record."""
    if not obs_profiler.recording():
        return {}
    return {"wave": seq, "requests": " ".join(str(r.rid) for r in wave)}


def _operand_digest(x):
    """In-process identity of a coefficient/factor/gain operand: waves
    only batch requests whose operands are bytewise identical, so one
    dispatch's traced operands are correct for every rider."""
    if x is None:
        return None
    if isinstance(x, RequantSpec):
        return repr(x)
    if isinstance(x, (tuple, list)):
        return tuple(_operand_digest(e) for e in x)
    a = np.asarray(x)
    return (a.shape, a.dtype.str, hash(a.tobytes()))


@dataclasses.dataclass
class FilterRequest:
    """One in-flight job: a frame, the filter structure to run it
    through, and the tenant's runtime operands. The engine fills
    ``result`` (or ``error``) and the timestamps; callers block on
    :meth:`result` or poll :meth:`done`."""

    rid: int
    frame: object                       # [H, W] | [H, W, C] array
    spec: Filter2D
    coeffs: object                      # [w, w] | [N, w, w] | (u, v)
    gains: object = None
    tenant: str = "default"
    submit_t: float = 0.0
    admit_t: Optional[float] = None
    done_t: Optional[float] = None

    def __post_init__(self):
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        self._key: Optional[str] = None
        self._sig = None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)

    def result(self, timeout: Optional[float] = None):
        """Block until served; returns the filtered frame (request rank
        restored) or raises the error the wave hit."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} not served within "
                               f"{timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    @property
    def latency_s(self) -> Optional[float]:
        """Submit-to-result wall time (None until served)."""
        if self.done_t is None:
            return None
        return self.done_t - self.submit_t

    @property
    def pixels(self) -> int:
        h, w = self.frame.shape[:2]
        planes = self.frame.shape[2] if len(self.frame.shape) == 3 else 1
        return int(h) * int(w) * int(planes)


class FilterServeEngine:
    """The batched, bucketed, LRU-warmed serving front end (see module
    docstring). Construction starts the worker; ``shutdown(drain=True)``
    (or the context manager) stops it after the queue empties.

    ``batch_size``   static planes per dispatch — waves are zero-padded
                     up to it, so each bucket owns exactly ONE compiled
                     executable regardless of traffic.
    ``cache_slots``  warm buckets resident at once. The LRU models the
                     paper's "one bitstream serves every filter" claim
                     under multi-tenant heterogeneity: hot (spec,
                     geometry) pairs stay compiled, cold ones recompile
                     on return (``stats()['recompiles']`` counts engine-
                     level cold-bucket compiles).
    ``execution``/``vmem_budget``/``overlap``/``interpret`` pass through
    to ``Filter2D.compile`` for every bucket.
    ``compile_fn``   test seam: ``(spec, batched_shape) -> callable`` —
                     the scheduler is exercised with a fake executor in
                     ``tests/test_serving.py``; default is the real
                     front door.
    """

    def __init__(self, *, batch_size: int = 4, cache_slots: int = 8,
                 execution: str = "auto",
                 vmem_budget: Optional[int] = None,
                 overlap: bool = True,
                 interpret: Optional[bool] = None,
                 compile_fn: Optional[Callable] = None):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1; got {batch_size}")
        if cache_slots < 1:
            raise ValueError(f"cache_slots must be >= 1; got {cache_slots}")
        self.batch_size = int(batch_size)
        self.cache_slots = int(cache_slots)
        self.execution = execution
        self.vmem_budget = vmem_budget
        self.overlap = bool(overlap)
        self.interpret = interpret
        self._compile_fn = compile_fn or self._default_compile

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._queue: deque[FilterRequest] = deque()
        self._cache: "OrderedDict[str, object]" = OrderedDict()
        self._pending = 0
        self._stop = False
        self._rid = 0
        self._stats = {
            "requests": 0, "completed": 0, "waves": 0, "cache_hits": 0,
            "recompiles": 0, "evictions": 0, "pixels": 0,
            "padded_planes": 0, "errors": 0, "cancelled": 0,
            "h2d_bytes": 0, "d2h_bytes": 0,
        }
        self._dispatched = 0
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="filter-serve-worker")
        self._worker.start()

    # -- public API ---------------------------------------------------------

    def submit(self, frame, coeffs, *, spec: Filter2D, gains=None,
               tenant: str = "default") -> FilterRequest:
        """Enqueue one frame-filter job; returns immediately with the
        request handle. Thread-safe: any number of submitters."""
        if not isinstance(spec, Filter2D):
            raise TypeError("spec must be a Filter2D; got "
                            f"{type(spec).__name__}")
        if len(frame.shape) not in (2, 3):
            raise ValueError("serving frames are [H, W] or [H, W, C]; "
                             f"got shape {tuple(frame.shape)}")
        got = jnp.dtype(frame.dtype).name
        if got != spec.dtype:
            raise ValueError(f"frame dtype {got!r} disagrees with the "
                             f"spec's storage contract {spec.dtype!r}")
        req = FilterRequest(rid=0, frame=frame, spec=spec, coeffs=coeffs,
                            gains=gains, tenant=tenant,
                            submit_t=time.perf_counter())
        req._key = self.bucket_key_for(spec, frame.shape)
        req._sig = (tenant, _operand_digest(coeffs), _operand_digest(gains))
        with self._work:
            if self._stop:
                raise RuntimeError("engine is shut down")
            self._rid += 1
            req.rid = self._rid
            self._queue.append(req)
            self._pending += 1
            self._stats["requests"] += 1
            depth = len(self._queue)
            self._work.notify_all()
        if obs_events.enabled():
            reg = obs_metrics.REGISTRY
            reg.counter("serve.requests").inc()
            reg.histogram("serve/queue_depth").record(depth)
        return req

    def bucket_key_for(self, spec: Filter2D, frame_shape) -> str:
        """The warm-cache bucket a (spec, frame geometry) pair lands in
        under this engine's knobs (``core.pipeline.bucket_key``)."""
        return bucket_key(spec, tuple(frame_shape), batch=self.batch_size,
                          execution=self.execution,
                          vmem_budget=self.vmem_budget,
                          overlap=self.overlap, interpret=self.interpret)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted request has been served (or
        errored). Returns False on timeout."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._work:
            while self._pending > 0:
                rem = (None if deadline is None
                       else deadline - time.perf_counter())
                if rem is not None and rem <= 0:
                    return False
                self._work.wait(rem)
        return True

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the worker. ``drain=True`` (default) serves everything
        already queued first; ``drain=False`` cancels queued requests
        (their ``result()`` raises). Idempotent."""
        cancelled: List[FilterRequest] = []
        with self._work:
            self._stop = True
            if not drain:
                cancelled = list(self._queue)
                self._queue.clear()
            self._work.notify_all()
        for req in cancelled:
            req._error = RuntimeError("engine shut down before this "
                                      "request was served")
            req.done_t = time.perf_counter()
            req._event.set()
        if cancelled:
            with self._work:
                self._pending -= len(cancelled)
                self._stats["cancelled"] += len(cancelled)
                self._work.notify_all()
            if obs_events.enabled():
                obs_metrics.REGISTRY.counter("serve.cancelled").inc(
                    len(cancelled))
        self._worker.join(timeout)

    def __enter__(self) -> "FilterServeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown(drain=exc_type is None)

    def cache_size(self) -> int:
        """Warm buckets resident right now (<= ``cache_slots``)."""
        with self._lock:
            return len(self._cache)

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def stats(self) -> dict:
        """Snapshot of the engine counters (always on, obs or not)."""
        with self._lock:
            return dict(self._stats)

    # -- scheduling ---------------------------------------------------------

    def _default_compile(self, spec: Filter2D, shape: Tuple[int, ...]):
        return spec.compile(shape, self.execution,
                            vmem_budget=self.vmem_budget,
                            overlap=self.overlap, interpret=self.interpret)

    def _next_wave(self, block: bool):
        """Pop the head-of-line request plus every queued request that
        can ride its dispatch (same bucket, same operand signature), up
        to the batch size; everything skipped keeps its queue order."""
        with self._work:
            while block and not self._queue and not self._stop:
                self._work.wait()
            if not self._queue:
                return None
            head = self._queue.popleft()
            wave = [head]
            keep: deque[FilterRequest] = deque()
            while self._queue and len(wave) < self.batch_size:
                r = self._queue.popleft()
                if r._key == head._key and r._sig == head._sig:
                    wave.append(r)
                else:
                    keep.append(r)
            keep.extend(self._queue)
            self._queue = keep
            depth = len(self._queue)
        return head._key, wave, depth

    def _get_pipeline(self, key: str, req: FilterRequest):
        """Warm-LRU lookup; a miss compiles (outside the lock) and may
        evict the least-recently-used bucket."""
        with self._lock:
            pipe = self._cache.get(key)
            if pipe is not None:
                self._cache.move_to_end(key)
                self._stats["cache_hits"] += 1
                return pipe, True
        shape = batched_shape(req.frame.shape, self.batch_size)
        pipe = self._compile_fn(req.spec, shape)
        with self._lock:
            self._cache[key] = pipe
            self._cache.move_to_end(key)
            self._stats["recompiles"] += 1
            while len(self._cache) > self.cache_slots:
                self._cache.popitem(last=False)
                self._stats["evictions"] += 1
        if obs_events.enabled():
            obs_metrics.REGISTRY.counter("serve.recompiles").inc()
        return pipe, False

    def _dispatch(self, key: str, wave: List[FilterRequest], depth: int):
        """Admit + launch one wave; returns the in-flight record without
        blocking on the device (JAX dispatch is async — copy-out happens
        in :meth:`_complete`, by which time the *next* wave has already
        been admitted)."""
        pipe, hit = self._get_pipeline(key, wave[0])
        if hit and obs_events.enabled():
            obs_metrics.REGISTRY.counter("serve.cache_hits").inc()
        self._dispatched += 1
        seq = self._dispatched
        t0 = time.perf_counter()
        for r in wave:
            r.admit_t = t0
        with obs_profiler.span("repro.serve.admit", **_wave_meta(seq, wave)):
            x = admit_batch([r.frame for r in wave], self.batch_size)
        # frames already on the device are not copied in
        h2d = sum(int(r.frame.nbytes) for r in wave
                  if not isinstance(r.frame, jax.Array))
        head = wave[0]
        if head.gains is not None:
            y = pipe(x, head.coeffs, gains=head.gains)
        else:
            y = pipe(x, head.coeffs)
        return key, wave, y, t0, hit, depth, seq, h2d

    def _complete(self, inflight) -> None:
        """Copy one wave's results out (blocks until the device is done),
        split them back per request, and wake the waiters."""
        key, wave, y, t0, hit, depth, seq, h2d = inflight
        with obs_profiler.span("repro.serve.copy_out",
                               **_wave_meta(seq, wave)):
            y = np.asarray(y)
        now = time.perf_counter()
        wall_s = max(now - t0, 1e-9)
        outs = split_batch(y, len(wave), len(wave[0].frame.shape))
        pixels = 0
        for r, out in zip(wave, outs):
            r._result = out
            r.done_t = now
            pixels += r.pixels
            r._event.set()
        padded = self.batch_size - len(wave)
        with self._work:
            self._pending -= len(wave)
            self._stats["completed"] += len(wave)
            self._stats["waves"] += 1
            self._stats["pixels"] += pixels
            self._stats["padded_planes"] += padded
            self._stats["h2d_bytes"] += h2d
            self._stats["d2h_bytes"] += y.nbytes
            self._work.notify_all()
        if obs_events.enabled():
            reg = obs_metrics.REGISTRY
            reg.counter("serve.waves").inc()
            reg.counter("serve.pixels").inc(pixels)
            wall_us = wall_s * 1e6
            reg.histogram("serve/wave_us").record(wall_us)
            reg.histogram(f"serve/wave_us/{key[:8]}").record(wall_us)
            for r in wave:
                reg.histogram("serve/request_us").record(
                    (now - r.submit_t) * 1e6)
            obs_events.emit(obs_events.ServeWaveEvent(
                key=key, tenant=wave[0].tenant, batch=len(wave),
                padded=padded, cache_hit=hit, queue_depth=depth,
                wall_us=wall_us, pixels_per_s=pixels / wall_s))

    def _fail_wave(self, wave: List[FilterRequest],
                   err: BaseException) -> None:
        now = time.perf_counter()
        for r in wave:
            r._error = err
            r.done_t = now
            r._event.set()
        with self._work:
            self._pending -= len(wave)
            self._stats["errors"] += len(wave)
            self._work.notify_all()
        if obs_events.enabled():
            obs_metrics.REGISTRY.counter("serve.errors").inc(len(wave))

    def _run(self) -> None:
        """The worker: a two-stage software pipeline. Each turn admits +
        dispatches wave k+1 (if any work is queued) and only *then*
        copies out wave k — so the host-side batching of the next wave
        overlaps the device computing the current one."""
        inflight = None
        while True:
            picked = self._next_wave(block=inflight is None)
            nxt = None
            if picked is not None:
                key, wave, depth = picked
                try:
                    nxt = self._dispatch(key, wave, depth)
                except Exception as e:  # noqa: BLE001 — fail the wave only
                    self._fail_wave(wave, e)
            if inflight is not None:
                try:
                    self._complete(inflight)
                except Exception as e:  # noqa: BLE001
                    _, wave, *_ = inflight
                    self._fail_wave([r for r in wave if not r.done()], e)
            inflight = nxt
            if inflight is None:
                with self._work:
                    if self._stop and not self._queue:
                        return
