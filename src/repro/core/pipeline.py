"""The plan-and-execute front door: ``Filter2D`` spec → ``CompiledFilter``.

The paper's thesis is that a 2D filter is a *static structure* — window,
form, border policy, wordlengths — that is planned once and then streamed
at line rate with runtime-swappable coefficients (§I: one bitstream serves
every filter). RIPL makes the same split declaratively (spec compiled to a
streaming pipeline); Campos et al.'s generator parameterises the
wordlengths the same way. This module is that split for the TPU port:

  * :class:`Filter2D` — the hashable spec: window size, reduction form,
    :class:`~repro.core.border_spec.BorderSpec`, separable mode, bank
    size, the frame's storage-dtype contract and the (gain-free half of
    the) :class:`~repro.core.requant.RequantSpec` epilogue.
  * ``spec.compile(frame_spec, execution=...)`` — plans once: picks the
    executor (``'auto'`` selects from the static ``HaloPlan`` accounting
    in ``kernels/filter2d/halo`` — VMEM working set vs a ``vmem_budget``
    knob, mesh presence), derives ``strip_h``/``tile_w`` from the budget
    instead of fixed defaults, and builds ONE jitted executable.
  * :class:`CompiledFilter` — ``__call__(frame, coeffs_or_factors,
    gains=None)`` treats coefficients, separable factors and per-filter
    requant gains as *traced* operands: swapping any of them hits the jit
    cache (``cache_size()`` is the counter tests pin); changing the spec,
    the frame geometry or the executor compiles fresh by construction
    (each compiled pipeline owns its cache).

The five historical entry points (``filter2d``, ``filter_bank``,
``filter2d_xla``, ``filter2d_pallas``, ``filter_bank_pallas``) are thin
wrappers over this path; ``compile`` results are memoised so the wrappers
stay cheap. The strip scan and the row-sharded executor have no entry
point of their own: ``compile(..., 'streaming')`` and ``compile(...,
'sharded', mesh=...)`` reach them.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.border_spec import BorderSpec, quantize_constant
from repro.core.distributed import _filter2d_sharded_impl, check_row_split
from repro.core.filter2d import (FORMS, _filter2d_impl, _filter2d_sep_impl,
                                 _filter2d_xla_impl, _filter_bank_impl,
                                 apply_requant, apply_requant_params,
                                 is_fixed_point, macs_per_pixel)
from repro.core.requant import RequantSpec
from repro.core.streaming import (_filter2d_streaming_impl,
                                  strip_height_for_vmem)
from repro.kernels.filter2d import halo
from repro.kernels.filter2d import ops
from repro.obs import events as obs_events
from repro.obs import metrics as obs_metrics
from repro.obs import profiler as obs_profiler
from repro.obs import roofline as obs_roofline

DEFAULT_VMEM_BUDGET = halo.DEFAULT_VMEM_BUDGET

EXECUTIONS = ("auto", "core", "xla", "pallas", "streaming", "sharded")

# distinct gain specs a pipeline keeps on the device; past this, the least
# recently used one is dropped and uploaded again when it comes back
GAIN_MEMO_SIZE = 64


@dataclasses.dataclass(frozen=True)
class Filter2D:
    """The static structure of a 2D filter — everything that shapes the
    compiled pipeline, nothing that can be swapped at line rate.

    ``window``      w of the w×w stencil (the ``(w-1)/2``-radius halo).
    ``form``        reduction layout (paper §II): direct | transposed |
                    tree | compress. The XLA executor infers its own.
    ``border``      :class:`BorderSpec` policy (+ constant) — paper §III.
                    A bare policy string is accepted and normalised.
    ``separable``   ``True`` compiles the 2w-MAC two-pass pipeline; calls
                    then take ``(u, v)`` factor operands instead of a
                    ``[w, w]`` coefficient block. (Mode only: the factors
                    themselves are runtime data.)
    ``num_filters`` bank size N; calls take ``[N, w, w]`` coefficients and
                    outputs grow a trailing bank axis (the coefficient
                    file, paper §I).
    ``dtype``       the frame's *storage* dtype contract (name): float
                    dtypes stream as-is; int8/uint8/int16 take the
                    fixed-point datapath (storage-width stream, int32
                    MAC — paper §IV).
    ``requant``     the fused output-scaler epilogue policy. Only the
                    gain-free half (rounding mode + storage dtype) shapes
                    the pipeline; the (multiplier, shift) gains ride every
                    call as traced operands (``gains=``), defaulting to
                    the ones carried here.

    Hashable and order-comparable by value: usable as a jit static
    argument and as the compile-cache key.
    """

    window: int
    form: str = "direct"
    border: BorderSpec = BorderSpec("mirror")
    separable: bool = False
    num_filters: int = 1
    dtype: str = "float32"
    requant: Optional[RequantSpec] = None

    def __post_init__(self):
        object.__setattr__(self, "window", int(self.window))
        if self.window < 1:
            raise ValueError(f"window must be >= 1; got {self.window}")
        if self.form not in FORMS:
            raise ValueError(f"unknown form {self.form!r}; choose from "
                             f"{FORMS}")
        if isinstance(self.border, str):
            object.__setattr__(self, "border", BorderSpec(self.border))
        if not isinstance(self.border, BorderSpec):
            raise TypeError("border must be a BorderSpec (or a policy "
                            f"name); got {type(self.border).__name__}")
        object.__setattr__(self, "separable", bool(self.separable))
        object.__setattr__(self, "num_filters", int(self.num_filters))
        if self.num_filters < 1:
            raise ValueError("num_filters must be >= 1")
        if self.separable and self.num_filters > 1:
            raise ValueError("separable pipelines are single-filter: "
                             "factor banks are not supported")
        dt = jnp.dtype(self.dtype)
        object.__setattr__(self, "dtype", dt.name)
        if not (jnp.issubdtype(dt, jnp.floating) or is_fixed_point(dt)):
            raise ValueError(
                f"dtype {dt.name!r} is not a supported storage contract: "
                "float dtypes or the fixed-point set int8/uint8/int16")
        if self.requant is not None:
            # shared validation: requant is the fixed-point epilogue and
            # its per-filter tuples must match the bank size
            from repro.core.filter2d import resolve_requant
            resolve_requant(dt, self.requant, num_filters=self.num_filters)

    @property
    def radius(self) -> int:
        return (self.window - 1) // 2

    def compile(self, frame_spec, execution: str = "auto", *,
                mesh=None, axis: str = "data",
                vmem_budget: Optional[int] = None,
                strip_h: Optional[int] = None,
                tile_w: Optional[int] = None,
                regime: Optional[str] = None,
                overlap: bool = True,
                interpret: Optional[bool] = None,
                profile_dump: Optional[str] = None) -> "CompiledFilter":
        """Plan the pipeline for one frame geometry and executor.

        ``frame_spec``: a shape tuple ([H,W] | [H,W,C] | [B,H,W,C]), a
        ``jax.ShapeDtypeStruct`` or an array — dtype-carrying specs must
        match the spec's storage contract. ``execution='auto'`` selects
        from the static plan accounting (see :class:`CompiledFilter`);
        ``vmem_budget`` (default 8 MiB) bounds the per-step working set
        and is what ``strip_h``/``tile_w`` are derived from when not
        given. ``overlap`` (Pallas executors; default on) selects the
        double-buffered LD∥EX∥ST kernel — the planner then budgets the
        two-bank scratch, so the derived strip/tile geometry shifts —
        versus the serial reference path. Results are memoised: the same
        (spec, geometry, knobs) returns the same ``CompiledFilter`` —
        and therefore the same jit cache — so wrapping entry points stay
        cheap per call. ``profile_dump`` (opt-in) captures the first
        executed call under ``jax.profiler.trace`` into that directory.
        """
        shape = _frame_shape(frame_spec, self.dtype)
        if execution not in EXECUTIONS:
            raise ValueError(f"unknown execution {execution!r}; choose "
                             f"from {EXECUTIONS}")
        return _compiled(self, shape, execution, mesh, axis, vmem_budget,
                         strip_h, tile_w, regime, bool(overlap), interpret,
                         profile_dump)


def _frame_shape(frame_spec, dtype_name: str) -> Tuple[int, ...]:
    if isinstance(frame_spec, (tuple, list)):
        shape = tuple(int(s) for s in frame_spec)
    else:
        try:
            shape = tuple(int(s) for s in frame_spec.shape)
            got = jnp.dtype(frame_spec.dtype).name
        except AttributeError:
            raise TypeError(
                "frame_spec must be a shape tuple, a ShapeDtypeStruct or "
                f"an array; got {type(frame_spec).__name__}") from None
        if got != dtype_name:
            raise ValueError(
                f"frame dtype {got!r} disagrees with the spec's storage "
                f"contract {dtype_name!r}; build a spec for this dtype")
    if len(shape) not in (2, 3, 4):
        raise ValueError("frames are [H,W] | [H,W,C] | [B,H,W,C]; got "
                         f"shape {shape}")
    return shape


@functools.lru_cache(maxsize=256)
def _compiled(spec, shape, execution, mesh, axis, vmem_budget, strip_h,
              tile_w, regime, overlap, interpret,
              profile_dump=None) -> "CompiledFilter":
    return CompiledFilter(spec, shape, execution, mesh=mesh, axis=axis,
                          vmem_budget=vmem_budget, strip_h=strip_h,
                          tile_w=tile_w, regime=regime, overlap=overlap,
                          interpret=interpret, profile_dump=profile_dump)


class CompiledFilter:
    """One planned, jitted filter pipeline (build via ``Filter2D.compile``).

    ``__call__(frame, coeffs_or_factors, gains=None)`` executes it:
    coefficients (``[w, w]``, ``[N, w, w]`` for banks, or ``(u, v)``
    factors for separable pipelines) and requant gains are *traced*
    operands — swapping them reuses the compiled executable
    (``cache_size()`` stays put), which is the served-pipeline property
    the paper's runtime coefficient file provides in hardware.

    ``execution='auto'`` selection, from static accounting only:

      1. a mesh was supplied            → ``'sharded'`` (halo-exchange);
      2. the whole plane fits the VMEM budget (pixel-cache regime —
         ``plan_vmem_working_set`` of the frame-resident plan ≤
         ``vmem_budget``)               → ``'pallas'`` (``regime='small'``);
      3. otherwise                      → ``'streaming'`` (row-buffer
         strip scan, strip height derived from the budget), falling back
         to the Pallas stream regime for shapes the strip scan cannot take
         (banks, separable pipelines, ``neglect`` borders).

    The resolved choice is ``self.execution``; ``self.plan`` carries the
    static :class:`~repro.kernels.filter2d.halo.HaloPlan` accounting
    (``hbm_bytes_per_pixel()``, ``vmem_working_set()``) for the derived
    geometry, so budget/bandwidth claims are auditable per pipeline.
    """

    def __init__(self, spec: Filter2D, frame_shape: Tuple[int, ...],
                 execution: str, *, mesh=None, axis: str = "data",
                 vmem_budget: Optional[int] = None,
                 strip_h: Optional[int] = None,
                 tile_w: Optional[int] = None,
                 regime: Optional[str] = None,
                 overlap: bool = True,
                 interpret: Optional[bool] = None,
                 profile_dump: Optional[str] = None):
        t_compile0 = time.perf_counter()
        self.spec = spec
        self.frame_shape = frame_shape
        self.mesh = mesh
        self.axis = axis
        self.overlap = bool(overlap)
        self.profile_dump = profile_dump
        self._profiled = False
        self._verify_report = None     # cached by verify()
        # device-resident gains operands keyed by RequantSpec value, least
        # recently used first; the lock covers lookup, order and counts
        self._gain_memo = collections.OrderedDict()
        self._gain_lock = threading.Lock()
        self._gain_hits = 0
        self._gain_uploads = 0
        self.vmem_budget = (DEFAULT_VMEM_BUDGET if vmem_budget is None
                            else int(vmem_budget))
        self.interpret = (ops._default_interpret() if interpret is None
                          else bool(interpret))

        nd = len(frame_shape)
        self._H, self._W = frame_shape[1:3] if nd == 4 else frame_shape[:2]
        self._C = frame_shape[-1] if nd >= 3 else 1
        w, r = spec.window, spec.radius
        dt = jnp.dtype(spec.dtype)
        acc_b = halo.datapath_byte_widths(dt, spec.requant)[1]
        same = spec.border.same_size
        Ho = self._H if same else max(self._H - 2 * r, 1)
        Wo = self._W if same else max(self._W - 2 * r, 1)
        # the pixel-cache (frame-resident) working set: the number 'auto'
        # compares against the budget — regime selection IS the paper's
        # small-frame vs row-buffer split, decided from static accounting.
        # It is plan_vmem_working_set of the very plan 'small' would build
        # (buffers and the body's widened window, live products and
        # accumulator), so 'auto' never picks a plane the kernel's VMEM
        # cannot hold.
        self.resident_vmem_bytes = self._resident_vmem_bytes()
        requested = execution
        if execution == "auto":
            if mesh is not None:
                execution = "sharded"
                self.selection = ("mesh", "a mesh was supplied -> "
                                  "halo-exchange shard_map executor")
            elif self.resident_vmem_bytes <= self.vmem_budget:
                execution = "pallas"
                regime = "small" if regime is None else regime
                self.selection = (
                    "pixel_cache",
                    f"frame-resident working set "
                    f"{self.resident_vmem_bytes} B fits vmem_budget "
                    f"{self.vmem_budget} B -> pallas regime='small'")
            elif (spec.num_filters == 1 and not spec.separable and same
                  and self._H >= max(w - 1, 1)):
                execution = "streaming"
                self.selection = (
                    "row_buffer",
                    f"frame-resident working set "
                    f"{self.resident_vmem_bytes} B exceeds vmem_budget "
                    f"{self.vmem_budget} B -> jnp strip scan with "
                    "budget-derived strip height")
            else:
                execution = "pallas"
                regime = "stream" if regime is None else regime
                self.selection = (
                    "stream_fallback",
                    "over budget but the strip scan cannot take this "
                    "shape (bank/separable/cropping) -> pallas "
                    "regime='stream'")
        else:
            self.selection = ("explicit",
                              f"execution={execution!r} requested")
        self.execution = execution

        if execution == "sharded" and mesh is None:
            raise ValueError("execution='sharded' needs a mesh")
        if mesh is not None and execution != "sharded":
            raise ValueError(f"a mesh was supplied but execution is "
                             f"{execution!r}; meshes drive 'sharded' "
                             "(or 'auto')")
        if execution in ("xla", "streaming", "sharded"):
            if spec.num_filters > 1:
                raise ValueError(f"execution={execution!r} runs single "
                                 "filters; banks take 'core' or 'pallas'")
            if spec.separable:
                raise ValueError(f"execution={execution!r} has no "
                                 "separable path; use 'core' or 'pallas'")
        if execution in ("streaming", "sharded") and not same:
            raise ValueError(f"execution={execution!r} keeps the frame "
                             "size; 'neglect' takes 'core' or 'pallas'")
        # bytes the halo exchange moves per call, summed over the shards;
        # the sharded executor sets it when its program is first traced
        self.halo_bytes = None
        if execution == "sharded":
            check_row_split(self._H, mesh.shape[axis], r)

        self.regime = None
        self.strip_h = None
        self.tile_w = None
        self.plan = None
        if execution == "pallas":
            self.regime = "stream" if regime is None else regime
            if self.regime == "stream" and (strip_h is None
                                            or tile_w is None):
                # derive the free knob(s) from the budget, holding any
                # caller-supplied one fixed
                strip_h, tile_w = halo.derive_strip_tile(
                    self._H, self._W, w, dtype=dt,
                    vmem_budget=self.vmem_budget,
                    num_filters=spec.num_filters, separable=spec.separable,
                    requant=spec.requant, border=spec.border,
                    strip_h=strip_h, tile_w=tile_w, overlap=self.overlap)
            elif self.regime == "small":
                strip_h = Ho if strip_h is None else strip_h
                tile_w = Wo if tile_w is None else tile_w
            S, Tw, _, _ = ops.resolve_strip_tile(
                self._H, self._W, w, spec.border, self.regime, strip_h,
                tile_w)
            self.strip_h, self.tile_w = S, Tw
            # the same plan the kernel will run (gain-free requant half):
            # geometry errors (frame below the policy's minimum extent)
            # surface here, at plan time
            self.plan = halo.make_plan(
                self._H, self._W, w, spec.border, S, Tw, dtype=dt,
                requant=(spec.requant.gain_free()
                         if spec.requant is not None else None))
        else:
            if execution == "streaming":
                # the jnp scan widens fixed-point strips to the int32
                # accumulator before filtering: derive the strip at the
                # ACCUMULATOR width so the budget holds for the working
                # set the scan actually carries, not the storage bytes
                self.strip_h = (self._streaming_strip(acc_b)
                                if strip_h is None else int(strip_h))
            # accounting-only plan (informational for the non-Pallas
            # executors; their own impls own validation/errors)
            S = self.strip_h if self.strip_h is not None else Ho
            try:
                self.plan = halo.make_plan(
                    self._H, self._W, w, spec.border, S, Wo, dtype=dt,
                    requant=(spec.requant.gain_free()
                             if spec.requant is not None else None))
            except Exception:
                self.plan = None

        impl = self._build()
        scope = (f"repro.filter2d.{self.execution}"
                 + (f".{self.regime}" if self.regime else ""))

        def scoped(*call_args):
            # named_scope is trace-time metadata (XLA op-name prefix):
            # zero runtime cost, survives jax.export — see tpu-lowering CI
            with jax.named_scope(scope):
                return impl(*call_args)

        self._fn = jax.jit(scoped)

        # one plane = H*W pixels; batch/channel planes all stream through
        # the same compiled grid, so the per-call pixel count scales by M
        planes = 1
        if len(frame_shape) == 4:
            planes = frame_shape[0] * frame_shape[3]
        elif len(frame_shape) == 3:
            planes = frame_shape[2]
        self._pixels_per_call = self._H * self._W * planes
        self._obs_key = (f"{self.execution}"
                         f"{'/' + self.regime if self.regime else ''}"
                         f"/{spec.dtype}/w{spec.window}"
                         f"/{self._H}x{self._W}")
        if obs_events.enabled():
            self._emit_compile_events(requested,
                                      time.perf_counter() - t_compile0)

    # -- planning helpers --------------------------------------------------

    def _emit_compile_events(self, requested: str, wall_s: float) -> None:
        if requested == "auto":
            obs_events.emit(obs_events.AutoSelectEvent(
                rule=self.selection[0], execution=self.execution,
                reason=self.selection[1],
                resident_vmem_bytes=int(self.resident_vmem_bytes),
                vmem_budget=int(self.vmem_budget),
                has_mesh=self.mesh is not None))
        eb = ob = None
        if self.execution == "pallas" and self.plan is not None:
            eb, ob = halo.plan_banks(self.plan,
                                     num_filters=self.spec.num_filters,
                                     overlap=self.overlap)
        ws = self.vmem_working_set()
        bpp = self.hbm_bytes_per_pixel()
        obs_events.emit(obs_events.CompileEvent(
            key=self._obs_key, spec=repr(self.spec),
            spec_hash=hash(self.spec), frame_shape=self.frame_shape,
            execution=self.execution, regime=self.regime,
            strip_h=self.strip_h, tile_w=self.tile_w,
            ext_banks=eb, out_banks=ob,
            vmem_working_set=None if ws is None else int(ws),
            hbm_bytes_per_pixel=None if bpp is None else float(bpp),
            wall_ms=wall_s * 1e3))
        obs_metrics.REGISTRY.counter("pipeline.compiles").inc()

    def _resident_vmem_bytes(self) -> int:
        """plan_vmem_working_set of the frame-resident ('small') plan."""
        spec, w = self.spec, self.spec.window
        S, Tw, _, _ = ops.resolve_strip_tile(self._H, self._W, w,
                                             spec.border, "small", 0, 0)
        plan = halo.make_plan(
            self._H, self._W, w, spec.border, S, Tw, dtype=spec.dtype,
            requant=(spec.requant.gain_free()
                     if spec.requant is not None else None))
        return halo.plan_vmem_working_set(
            plan, num_filters=spec.num_filters, separable=spec.separable,
            overlap=self.overlap)

    def _streaming_strip(self, dtype_bytes: int) -> int:
        """Largest divisor of H within the budget-derived strip height
        (the scan needs H % strip == 0 and strip >= w-1)."""
        H, w = self._H, self.spec.window
        target = strip_height_for_vmem(self._W, self._C, w,
                                       self.vmem_budget, dtype_bytes)
        lo = max(w - 1, 1)
        divs = [d for d in range(1, H + 1) if H % d == 0]
        ok = [d for d in divs if lo <= d <= max(target, lo)]
        if ok:
            return max(ok)
        over = [d for d in divs if d >= lo]
        return min(over) if over else H

    # -- executable --------------------------------------------------------

    def _build(self):
        spec = self.spec
        border = spec.border
        rq = spec.requant
        dt = jnp.dtype(spec.dtype)
        fixed = is_fixed_point(dt)

        def _epilogue(y, q):
            if rq is None or q is None:
                return y
            if spec.num_filters > 1:    # bank axis is last: [.., N]
                return apply_requant(y, q[:, 0], q[:, 1],
                                     rounding=rq.rounding,
                                     out_dtype=rq.np_dtype)
            return apply_requant_params(y, q, rq)

        # constant(c) as the storage dtype holds it (the shared rule), for
        # the core oracle and the two row-buffer executors
        qc = quantize_constant(border.constant, dt)
        band = dataclasses.replace(border, constant=qc)
        rq_static = rq.gain_free() if rq is not None else None

        if self.execution == "core":
            if spec.separable:
                def impl(frame, co, q=None):
                    y = _filter2d_sep_impl(
                        frame, co[0], co[1], border_policy=border.policy,
                        border_constant=jnp.asarray(qc))
                    return _epilogue(y, q)
            elif spec.num_filters == 1:
                def impl(frame, co, q=None):
                    y = _filter2d_impl(
                        frame, co, form=spec.form,
                        border_policy=border.policy,
                        border_constant=jnp.asarray(qc))
                    return _epilogue(y, q)
            else:
                def impl(frame, co, q=None):
                    y = _filter_bank_impl(frame, co, form=spec.form,
                                          border=border)
                    return _epilogue(y, q)
            return impl

        if self.execution == "xla":
            def impl(frame, co, q=None):
                return _epilogue(_filter2d_xla_impl(frame, co,
                                                    border=border), q)
            return impl

        if self.execution == "streaming":
            strip_h = self.strip_h

            def impl(frame, co, q=None):
                # the scan requantises each emitted strip itself (traced
                # gains operand): the output stream leaves at storage
                # width strip by strip, not via a post-scan pass
                return _filter2d_streaming_impl(
                    frame, co, q, border=band, form=spec.form,
                    strip_h=strip_h, requant=rq_static)
            return impl

        if self.execution == "sharded":
            mesh, ax = self.mesh, self.axis

            def keep_halo_bytes(nbytes):
                self.halo_bytes = int(nbytes)

            def impl(frame, co, q=None):
                # gains ride into the shard_map as a replicated traced
                # operand: each shard requantises its own tile, so the
                # gathered tiles stay storage-width
                return _filter2d_sharded_impl(
                    frame, co, q, mesh=mesh, axis=ax, border=band,
                    form=spec.form, requant=rq_static,
                    on_halo_bytes=keep_halo_bytes)
            return impl

        assert self.execution == "pallas", self.execution
        form = "separable" if spec.separable else spec.form
        n = spec.num_filters
        regime, S, Tw = self.regime, self.strip_h, self.tile_w
        interpret = self.interpret
        overlap = self.overlap

        def impl(frame, co, q=None):
            planes, tag = ops._fold_planes(frame)
            if spec.separable:
                co_k = co.astype(jnp.int32 if fixed else planes.dtype)[None]
            elif fixed:
                co_k = co.astype(jnp.int32)
                co_k = co_k[None] if n == 1 else co_k
            else:
                co_k = co[None] if n == 1 else co
            y = ops._filter2d_pallas_planes(
                planes, co_k, q, form=form, border=border, regime=regime,
                strip_h=S, tile_w=Tw, interpret=interpret,
                requant=rq_static, overlap=overlap)
            return ops._unfold(y, tag, keep_bank=n > 1)
        return impl

    # -- operand normalisation ---------------------------------------------

    def _coeff_operand(self, coeffs):
        w, n = self.spec.window, self.spec.num_filters
        if self.spec.separable:
            if isinstance(coeffs, (tuple, list)):
                if len(coeffs) != 2:
                    raise ValueError("separable pipelines take (u, v) — "
                                     "exactly two 1D factors")
                co = jnp.stack([jnp.asarray(coeffs[0]),
                                jnp.asarray(coeffs[1])])
            else:
                co = jnp.asarray(coeffs)
            if co.shape != (2, w):
                raise ValueError(
                    f"separable pipeline takes (u, v) factors of length "
                    f"{w} (operand shape (2, {w})); got {co.shape}")
            return co
        co = jnp.asarray(coeffs)
        want = (w, w) if n == 1 else (n, w, w)
        if co.shape != want:
            raise ValueError(f"this pipeline takes coefficients of shape "
                             f"{want}; got {co.shape}")
        return co

    def _gain_operand(self, gains, reg=None):
        """The ``[n, 2]`` int32 gains operand. A ``RequantSpec`` (``None``
        stands for the compiled spec's own) is uploaded on its first call
        and kept on the device for every later call with an equal spec.
        ``reg`` is the registry to count hits and uploads in (while
        recording), or ``None``."""
        rq, n = self.spec.requant, self.spec.num_filters
        key = rq if gains is None else gains
        if isinstance(key, RequantSpec):
            with self._gain_lock:
                g = self._gain_memo.get(key)
                uploaded = g is None
                if uploaded:
                    g = self._upload_gains(key)
                    self._gain_memo[key] = g
                    if len(self._gain_memo) > GAIN_MEMO_SIZE:
                        self._gain_memo.popitem(last=False)
                    self._gain_uploads += 1
                else:
                    self._gain_memo.move_to_end(key)
                    self._gain_hits += 1
            if reg is not None:
                reg.counter("pipeline.gain_uploads" if uploaded
                            else "pipeline.gain_hits").inc()
            return g
        if (isinstance(gains, jax.Array) and gains.dtype == jnp.int32
                and gains.shape == (n, 2)):
            return gains
        g = jnp.asarray(gains, jnp.int32)
        if g.shape == (2,):
            g = jnp.broadcast_to(g[None], (n, 2))
        if g.shape != (n, 2):
            raise ValueError(f"gains must be a RequantSpec, a "
                             f"(multiplier, shift) pair or an [{n}, 2] "
                             f"table; got shape {g.shape}")
        return g

    def _upload_gains(self, gains: RequantSpec):
        """A spec's table on the device — replicated over the mesh when
        there is one — after checking it against the compiled epilogue."""
        rq = self.spec.requant
        if gains.gain_free() != rq.gain_free():
            raise ValueError(
                "gains spec disagrees with the compiled epilogue "
                f"(rounding/storage dtype): {gains.gain_free()} vs "
                f"{rq.gain_free()}; recompile for a new epilogue")
        params = gains.params(self.spec.num_filters)
        if self.mesh is None:
            return jnp.asarray(params, jnp.int32)
        return jax.device_put(np.asarray(params, np.int32),
                              NamedSharding(self.mesh, P()))

    # -- execution ---------------------------------------------------------

    def __call__(self, frame, coeffs, gains=None):
        # the default path: nothing recording and no capture asked for —
        # one attribute test and one profiler branch, then straight into
        # the jitted executable, with no span opened
        if self.profile_dump is None and not obs_profiler.recording():
            return self._fn(*self._operands(frame, coeffs, gains))
        reg = obs_metrics.REGISTRY
        with obs_profiler.span("repro.call.operands"):
            args = self._operands(frame, coeffs, gains, reg)
        if obs_events._TRACE is None and self.profile_dump is None:
            y = self._launch(args)
        else:
            y = self._instrumented_call(args)
        if self.execution == "sharded":
            # after the launch: the first call traces the program, which
            # is when the exchange's bytes become known
            reg.counter("pipeline.sharded_calls").inc()
            reg.counter("pipeline.halo_bytes").inc(self.halo_bytes)
        return y

    def _operands(self, frame, coeffs, gains, reg=None):
        """The executable's operands: the frame checked against the
        compiled geometry, coefficients and gains normalised (and, for
        host values, uploaded; gain specs once each, see
        :meth:`_gain_operand`)."""
        if tuple(frame.shape) != self.frame_shape:
            raise ValueError(
                f"pipeline compiled for frame shape {self.frame_shape}; "
                f"got {tuple(frame.shape)} — compile for the new geometry")
        if jnp.dtype(frame.dtype).name != self.spec.dtype:
            raise ValueError(
                f"pipeline compiled for dtype {self.spec.dtype!r}; got "
                f"{jnp.dtype(frame.dtype).name!r}")
        co = self._coeff_operand(coeffs)
        if self.spec.requant is None:
            if gains is not None:
                raise ValueError("gains supplied but the spec carries no "
                                 "requant epilogue")
            return (frame, co)
        return (frame, co, self._gain_operand(gains, reg))

    def _launch(self, args):
        """The executable's dispatch, up to the returned future (the
        device work itself is on the trace's device planes). The span
        carries ``compiled=1`` when the call grew the jit cache."""
        with obs_profiler.span("repro.call.launch") as s:
            if s is None:
                return self._fn(*args)
            size0 = self._fn._cache_size()
            y = self._fn(*args)
            if self._fn._cache_size() > size0:
                s.set(compiled=1)
            return y

    def _instrumented_call(self, args):
        """Timed execution: wall time via ``block_until_ready``, recompile
        detection from the jit cache counter, one :class:`ExecuteEvent` +
        a latency histogram sample per call. The operands stay exactly the
        ones the fast path passes — nothing here enters the trace, so
        tracing on adds zero retraces (pinned in test_compiled_filter)."""
        dump = None
        if self.profile_dump is not None and not self._profiled:
            self._profiled = True          # capture the first call only
            dump = self.profile_dump
        size0 = self._fn._cache_size()
        t0 = time.perf_counter()
        with obs_profiler.profile_dump(dump):
            y = jax.block_until_ready(self._launch(args))
        wall_s = time.perf_counter() - t0
        size1 = self._fn._cache_size()
        if obs_events._TRACE is not None:
            wall_us = wall_s * 1e6
            obs_events.emit(obs_events.ExecuteEvent(
                key=self._obs_key, wall_us=wall_us,
                pixels_per_s=self._pixels_per_call / wall_s,
                cache_hit=size1 == size0, cache_size=size1))
            reg = obs_metrics.REGISTRY
            reg.histogram(f"call/{self._obs_key}").record(wall_us)
            reg.counter("pipeline.calls").inc()
            if size1 > size0:
                reg.counter("pipeline.recompiles").inc()
            else:
                reg.counter("pipeline.cache_hits").inc()
        return y

    # -- introspection -----------------------------------------------------

    def cache_size(self) -> int:
        """Compiled-executable count for this pipeline: 1 after the first
        call, and *still* 1 after any number of coefficient / factor /
        gain swaps — the served-pipeline invariant tests pin."""
        return self._fn._cache_size()

    def operand_stats(self) -> dict:
        """How often a call's gains operand was already on the device
        (``gain_hits``) and how often it was uploaded (``gain_uploads``);
        raw gains tables count in neither."""
        with self._gain_lock:
            return {"gain_hits": self._gain_hits,
                    "gain_uploads": self._gain_uploads}

    def vmem_working_set(self) -> Optional[int]:
        """Per-step VMEM bytes of the planned geometry (from the plan) —
        both scratch banks counted when the double-buffered path runs."""
        if self.plan is None:
            return None
        return halo.plan_vmem_working_set(
            self.plan, num_filters=self.spec.num_filters,
            separable=self.spec.separable,
            overlap=self.overlap if self.execution == "pallas" else False)

    def hbm_bytes_per_pixel(self) -> Optional[float]:
        """Static HBM round-trip bytes/pixel of the planned geometry."""
        if self.plan is None:
            return None
        return halo.hbm_bytes_per_pixel(self.plan)

    def _plan_banks(self) -> Tuple[Optional[int], Optional[int]]:
        """(halo-scratch, output-tile) bank counts of the planned kernel —
        the double-buffering degree; ``(None, None)`` off the Pallas path."""
        if self.execution != "pallas" or self.plan is None:
            return None, None
        return halo.plan_banks(self.plan, num_filters=self.spec.num_filters,
                               overlap=self.overlap)

    def verify(self, grid_orders=None):
        """Run the static kernel verifier over this compiled pipeline.

        Traces the jitted executable, lowers any pallas_call to the
        analysis IR and runs the full pass pipeline (DMA pairing, bank
        hazards, read-once, width lint, VMEM budget — the Pallas
        executors are checked under BOTH grid orders). Returns the
        :class:`~repro.analysis.report.Report`; the result is cached and
        surfaces in :meth:`explain`. See ``docs/analysis.md``.
        """
        from repro import analysis      # deferred: analysis sits above us
        self._verify_report = analysis.verify(self, grid_orders=grid_orders)
        return self._verify_report

    def explain(self, as_dict: bool = False, verify: bool = False):
        """The plan report: what compiled, why, and what it should cost.

        Every byte figure here IS the existing static accounting —
        ``vmem_working_set()`` / ``hbm_bytes_per_pixel()`` /
        ``halo.read_amplification`` — restated, not re-derived (pinned to
        exact agreement in ``tests/test_obs.py``), plus the two-ceiling
        roofline prediction from :mod:`repro.obs.roofline` for the device
        this process runs on (none for a device without published peaks).
        ``as_dict=True`` returns the machine-readable twin the bench
        harness consumes.
        ``verify=True`` runs :meth:`verify` first (if not already cached)
        so the report carries the static checker's verdict.
        """
        if verify and self._verify_report is None:
            self.verify()
        spec, plan = self.spec, self.plan
        eb, ob = self._plan_banks()
        ws = self.vmem_working_set()
        bpp = self.hbm_bytes_per_pixel()
        macs = macs_per_pixel(spec.window, form=spec.form,
                              separable=spec.separable)
        flops = 2.0 * macs * spec.num_filters
        roof = obs_roofline.predicted_pixel_rate(
            flops, bpp, jax.devices()[0].device_kind,
            integer=is_fixed_point(jnp.dtype(spec.dtype)))
        d = {
            "spec": {
                "window": spec.window, "form": spec.form,
                "border": spec.border.policy, "separable": spec.separable,
                "num_filters": spec.num_filters, "dtype": spec.dtype,
                "requant": None if spec.requant is None
                           else repr(spec.requant),
            },
            "frame": {"shape": self.frame_shape,
                      "pixels_per_call": self._pixels_per_call},
            "execution": {"executor": self.execution, "regime": self.regime,
                          "rule": self.selection[0],
                          "why": self.selection[1],
                          "overlap": self.overlap,
                          "interpret": self.interpret},
            "geometry": None if plan is None else {
                "strip_h": self.strip_h, "tile_w": self.tile_w,
                "strips": plan.rows.n, "tiles": plan.cols.n,
                "ext_banks": eb, "out_banks": ob,
                "scratch_eh": plan.eh, "scratch_ew": plan.ew,
            },
            "vmem": {
                "working_set_bytes": None if ws is None else int(ws),
                "budget_bytes": int(self.vmem_budget),
                "resident_estimate_bytes": int(self.resident_vmem_bytes),
                "fits_budget": None if ws is None
                               else bool(ws <= self.vmem_budget),
            },
            "hbm": None if plan is None else {
                "read_bytes_per_pixel": halo.read_bytes_per_pixel(plan),
                "write_bytes_per_pixel":
                    halo.hbm_write_bytes_per_pixel(plan),
                "bytes_per_pixel": bpp,
                "read_amplification": halo.read_amplification(plan),
            },
            "roofline": roof,
            "verify": None if self._verify_report is None else {
                "clean": self._verify_report.clean,
                "findings": [
                    {"passname": f.passname, "message": f.message,
                     "ref": f.ref, "count": f.count}
                    for f in self._verify_report.findings],
                "error": self._verify_report.error,
                "passes": list(self._verify_report.passes),
            },
        }
        if as_dict:
            return d
        return self._render_explain(d)

    def _render_explain(self, d) -> str:
        def _b(n):
            if n is None:
                return "n/a"
            return (f"{n / 2**20:.2f} MiB" if n >= 2**20
                    else f"{n / 2**10:.1f} KiB" if n >= 2**10
                    else f"{n} B")
        s, e, g, v, h, r = (d["spec"], d["execution"], d["geometry"],
                            d["vmem"], d["hbm"], d["roofline"])
        lines = [
            f"CompiledFilter: {s['window']}x{s['window']} "
            + ("separable " if s["separable"] else "")
            + f"{s['form']} filter"
            + (f" bank[{s['num_filters']}]" if s["num_filters"] > 1 else "")
            + f", {s['dtype']}, border={s['border']}"
            + (f", requant={s['requant']}" if s["requant"] else ""),
            f"  frame     {d['frame']['shape']} "
            f"({d['frame']['pixels_per_call']} px/call)",
            f"  executor  {e['executor']}"
            + (f" regime={e['regime']!r}" if e["regime"] else "")
            + f" [{e['rule']}] — {e['why']}",
        ]
        if g is not None:
            lines.append(
                f"  geometry  {g['strips']} strips x {g['tiles']} tiles "
                f"(strip_h={g['strip_h']}, tile_w={g['tile_w']}), scratch "
                f"{g['scratch_eh']}x{g['scratch_ew']}"
                + (f", banks ext={g['ext_banks']} out={g['out_banks']}"
                   if g["ext_banks"] is not None else ""))
        lines.append(
            f"  vmem      working set {_b(v['working_set_bytes'])} of "
            f"{_b(v['budget_bytes'])} budget"
            + ("" if v["fits_budget"] is None
               else " (fits)" if v["fits_budget"] else " (OVER)")
            + f"; frame-resident est. {_b(v['resident_estimate_bytes'])}")
        if h is not None:
            lines.append(
                f"  hbm       {h['bytes_per_pixel']:.3f} B/px round trip "
                f"(read {h['read_bytes_per_pixel']:.3f} + write "
                f"{h['write_bytes_per_pixel']:.3f}), read amplification "
                f"{h['read_amplification']:.4f}x")
        if r["predicted_pixels_per_s"] is None:
            lines.append(f"  roofline  {r['why']}")
        else:
            lines.append(
                f"  roofline  {r['predicted_pixels_per_s']:.3e} px/s on "
                f"{r['device_kind']} ({r['bound']}-bound; "
                f"{r['flops_per_pixel']:.0f} flop/px, "
                + (f"{r['bytes_per_pixel']:.3f} B/px)"
                   if r["bytes_per_pixel"] is not None
                   else "bytes unknown)"))
        vr = d.get("verify")
        if vr is not None:
            if vr["error"] is not None:
                lines.append(f"  verify    TRACE ERROR — {vr['error']}")
            elif vr["clean"]:
                lines.append(f"  verify    clean "
                             f"({len(vr['passes'])} passes)")
            else:
                lines.append(f"  verify    {len(vr['findings'])} "
                             "finding(s):")
                for f in vr["findings"]:
                    n = f" x{f['count']}" if f["count"] > 1 else ""
                    lines.append(f"    [{f['passname']}]{n} {f['message']}")
        return "\n".join(lines)

    def _explain_line(self) -> str:
        """One-line plan summary (folded into ``__repr__``)."""
        eb, ob = self._plan_banks()
        bits = [self._obs_key, f"rule={self.selection[0]}"]
        if self.plan is not None:
            bits.append(f"{self.plan.rows.n}x{self.plan.cols.n} grid")
        if eb is not None:
            bits.append(f"banks ext={eb} out={ob}")
        ws = self.vmem_working_set()
        if ws is not None:
            bits.append(f"vmem {ws}/{self.vmem_budget} B")
        bpp = self.hbm_bytes_per_pixel()
        if bpp is not None:
            bits.append(f"{bpp:.2f} B/px")
        return " | ".join(bits)

    def __repr__(self) -> str:
        geo = ""
        if self.execution == "pallas":
            geo = (f", regime={self.regime!r}, strip_h={self.strip_h}, "
                   f"tile_w={self.tile_w}, overlap={self.overlap}")
        elif self.execution == "streaming":
            geo = f", strip_h={self.strip_h}"
        return (f"CompiledFilter({self.spec!r}, frame={self.frame_shape}, "
                f"execution={self.execution!r}{geo})"
                f"\n  <{self._explain_line()}>")


# -- batch admission (the serving engine's substrate) -----------------------
#
# A compiled pipeline already folds batch and channel planes into the
# kernel grid ([B, H, W, C] frames stream as B*C planes through one
# executable), which is exactly the degree of freedom a *serving* layer
# wants: k independent same-geometry requests stack into the plane grid
# dim of ONE dispatch. These helpers are the admission arithmetic —
# stable bucket identity, stacking with zero-padding to a static batch
# (one executable per bucket, like the LM engines' fixed slot count),
# and the inverse split — kept next to the front door so the geometry
# rules live in one place.


def batched_shape(frame_shape: Sequence[int], batch: int) -> Tuple[int, ...]:
    """The [B, H, W, C] pipeline geometry a wave of ``batch`` frames of
    ``frame_shape`` ([H, W] or [H, W, C]) compiles for. Already-batched
    4-D shapes are rejected: the batch dim belongs to the admission
    layer, not the request."""
    shape = tuple(int(s) for s in frame_shape)
    if len(shape) == 2:
        shape = shape + (1,)
    if len(shape) != 3:
        raise ValueError("serving frames are [H, W] or [H, W, C]; got "
                         f"shape {tuple(frame_shape)}")
    if batch < 1:
        raise ValueError(f"batch must be >= 1; got {batch}")
    return (int(batch),) + shape


def bucket_key(spec: Filter2D, frame_shape: Sequence[int], *,
               batch: int = 1, execution: str = "auto",
               vmem_budget: Optional[int] = None, overlap: bool = True,
               interpret: Optional[bool] = None) -> str:
    """Stable digest naming one warm-cache bucket: the (spec, frame
    geometry, dtype) identity plus every compile knob that shapes the
    executable. Two requests with equal keys are servable by the same
    ``CompiledFilter``; anything that would compile fresh — a different
    window, border, storage dtype, geometry, batch or executor knob —
    changes the key. (``Filter2D`` reprs are value-complete, so the
    digest is deterministic within a process and across processes.)"""
    shape = batched_shape(frame_shape, batch)
    payload = (repr(spec), shape, execution, vmem_budget, bool(overlap),
               interpret)
    return hashlib.sha1(repr(payload).encode()).hexdigest()[:16]


def admit_batch(frames: Sequence, batch: int):
    """Stack up to ``batch`` same-geometry frames into the [B, H, W, C]
    plane-grid layout (``batched_shape``), zero-padding the tail so the
    dispatch shape is static — a light wave must not compile a second
    executable. Returns the stacked array; callers split results back
    with :func:`split_batch`."""
    if not frames:
        raise ValueError("admit_batch needs at least one frame")
    if len(frames) > batch:
        raise ValueError(f"wave of {len(frames)} frames exceeds the "
                         f"batch size {batch}")
    shape = tuple(frames[0].shape)
    dtype = jnp.dtype(frames[0].dtype)
    for f in frames[1:]:
        if tuple(f.shape) != shape:
            raise ValueError("waves are same-geometry by construction: "
                             f"got {tuple(f.shape)} in a {shape} wave")
        if jnp.dtype(f.dtype) != dtype:
            raise ValueError("waves are same-dtype by construction (jnp."
                             f"stack would silently promote): got "
                             f"{jnp.dtype(f.dtype)} in a {dtype} wave")
    x = jnp.stack([jnp.asarray(f) for f in frames])
    if x.ndim == 3:
        x = x[..., None]
    if x.ndim != 4:
        raise ValueError("serving frames are [H, W] or [H, W, C]; got "
                         f"shape {shape}")
    if len(frames) < batch:
        pad = jnp.zeros((batch - len(frames),) + x.shape[1:], x.dtype)
        x = jnp.concatenate([x, pad])
    return x


def split_batch(y, count: int, frame_ndim: int) -> List:
    """Undo :func:`admit_batch` on a pipeline output: the first ``count``
    planes (padding dropped), each squeezed back to the request's rank —
    2-D requests lose the synthesised channel axis; bank pipelines keep
    their trailing bank axis."""
    outs = []
    for i in range(count):
        yi = y[i]
        if frame_ndim == 2:
            # [H, W, 1] or [H, W, 1, N] -> [H, W] / [H, W, N]
            yi = yi[:, :, 0]
        outs.append(yi)
    return outs
