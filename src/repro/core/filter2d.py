"""2D spatial filter forms — the paper's §II, TPU-native (pure-jnp layer).

The paper maps a general `w×w` runtime-coefficient filter onto DSP48E1
blocks in two *forms* and three *adder-tree layouts*. On TPU the analogous
design space is *how the w² multiply-reduce is scheduled onto the MXU/VPU*:

  ``direct``      the w² shifted-frame×scalar products summed on the VPU
                  in elementwise fusions of at most 32 taps each (no
                  patch tensor) — the paper's **DSP layout**, whose
                  cascade adds in silicon; in the Pallas kernel, one
                  fused sum.
  ``transposed``  shift-and-accumulate: w² shifted frame×scalar MACs on the
                  VPU, running accumulator — the paper's transposed form
                  (MAC chains, no tree, no patch materialisation).
  ``tree``        like transposed but the w² products are reduced pairwise
                  (log2 depth) — the paper's **LOG layout** (fabric adders).
  ``compress``    products reduced in groups of 6 then summed — the paper's
                  **DSPCOMP layout** (6:3 compressors + DSP adders).

All forms are numerically the same filter (tests assert allclose across
forms and against numpy); they differ in the *structure* XLA/Mosaic sees,
which is the paper's point: structure determines throughput.

Layout convention: frames are NHWC ``[B, H, W, C]`` (C=1 for mono). The
coefficient operand is runtime data (a traced array), never baked into the
graph — one compiled executable serves every filter (paper §I).
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.borders import BorderSpec, extend, out_shape
from repro.core.border_spec import quantize_constant
from repro.core.filters import decompose_separable
from repro.core.requant import RequantSpec

FORMS = ("direct", "transposed", "tree", "compress")

# Narrow storage dtypes that run the fixed-point contract: stream/store at
# the narrow width, multiply-accumulate in int32, return int32 (the paper's
# B=8 pixels onto 48-bit DSP48 accumulation). The caller requantises.
FIXED_POINT_DTYPES = (jnp.int8, jnp.uint8, jnp.int16)


def is_fixed_point(dtype) -> bool:
    """True for frame dtypes that take the int32-accumulate datapath."""
    return jnp.dtype(dtype) in (jnp.dtype(d) for d in FIXED_POINT_DTYPES)


# ---------------------------------------------------------------------------
# Requantising epilogue (paper §IV: pixels LEAVE at storage width too)
# ---------------------------------------------------------------------------


def resolve_requant(frame_dtype, requant: Optional[RequantSpec],
                    num_filters: int = 1) -> Optional[RequantSpec]:
    """Validate the ``requant`` knob against the frame's datapath.

    ``None`` keeps the wide accumulator on the output bus (int32 for
    fixed-point frames — the pre-epilogue contract). A :class:`RequantSpec`
    is only meaningful on the fixed-point datapath (there is nothing to
    requantise on a float stream) and its per-filter multiplier/shift
    tuples, if any, must match the bank size. Shared by the core oracle,
    the Pallas wrappers and the streaming/distributed executors so every
    entry point rejects the same misuses identically.
    """
    if requant is None:
        return None
    if not isinstance(requant, RequantSpec):
        raise TypeError(f"requant must be a core.requant.RequantSpec; got "
                        f"{type(requant).__name__}")
    if not is_fixed_point(frame_dtype):
        raise ValueError(
            "requant is the fixed-point epilogue: frames of dtype "
            f"{jnp.dtype(frame_dtype).name} accumulate and leave at their "
            "own width; pass requant=None")
    requant.params(num_filters)          # validates per-filter lengths
    return requant


def apply_requant(acc: jax.Array, multiplier, shift, *, rounding: str,
                  out_dtype) -> jax.Array:
    """The fused scale→round→saturate epilogue, in jnp (int32 in/out ops).

    The jnp twin of ``core.requant.requantize_ref``: identical
    two's-complement identities (arithmetic shift = floor, masked
    remainder for ties), so core, streaming, distributed AND the Pallas
    kernel (which calls this with *traced* per-filter scalars read from
    its params operand) land bit-identically on the numpy oracle. The
    caller guarantees ``acc·multiplier`` (+ the half-LSB bias for
    ``nearest``) fits int32 — the headroom contract the reference asserts.
    """
    one = jnp.asarray(1, acc.dtype)
    zero = jnp.asarray(0, acc.dtype)
    prod = acc * jnp.asarray(multiplier, acc.dtype)
    # broadcast the (possibly per-filter, possibly traced-scalar) shift to
    # the full tile: Mosaic lowers VMEM scalar reads as 0-d vectors, and
    # mixed 0-d-vector/scalar arithmetic fails verification — tile-shaped
    # operands keep every op below a plain VPU vector op on both the
    # interpret and the Mosaic path (XLA folds the splat for static ints).
    sh = jnp.broadcast_to(jnp.asarray(shift, acc.dtype), prod.shape)
    shm1 = jnp.maximum(sh - one, zero)   # shift-1, clamped: 1<<(sh-1) @ sh=0
    if rounding == "truncate":
        q = jnp.right_shift(prod, sh)
    elif rounding == "nearest":
        half = jnp.where(sh > zero, jnp.left_shift(one, shm1), zero)
        q = jnp.right_shift(prod + half, sh)
    elif rounding == "nearest_even":
        base = jnp.right_shift(prod, sh)
        mask = jnp.left_shift(one, sh) - one
        rem = jnp.bitwise_and(prod, mask)
        half = jnp.left_shift(one, shm1)
        odd = jnp.bitwise_and(base, one) == one
        up = (rem > half) | ((rem == half) & odd)
        q = base + jnp.where((sh > zero) & up, one, zero)
    else:
        raise ValueError(f"unknown rounding mode {rounding!r}")
    info = np.iinfo(np.dtype(out_dtype))
    return jnp.clip(q, info.min, info.max).astype(out_dtype)


def apply_requant_params(y: jax.Array, q_params: jax.Array,
                         requant: RequantSpec) -> jax.Array:
    """The traced-gains epilogue: scale/round/saturate ``y`` by the
    ``[1, 2]`` (multiplier, shift) operand under ``requant``'s static half
    (rounding mode + storage dtype).

    THE one call every single-filter jnp executor makes (the pipeline's
    core/xla epilogue, each streaming strip, each distributed shard), so
    a future spec field is threaded through exactly one place; banks
    index their ``[N, 2]`` table per lane instead."""
    return apply_requant(y, q_params[0, 0], q_params[0, 1],
                         rounding=requant.rounding,
                         out_dtype=requant.np_dtype)


def _as_nhwc(frame: jax.Array) -> Tuple[jax.Array, bool, bool]:
    """Accept [H,W], [H,W,C] or [B,H,W,C]; return NHWC + flags to undo."""
    add_c = frame.ndim == 2
    if add_c:
        frame = frame[..., None]
    add_b = frame.ndim == 3
    if add_b:
        frame = frame[None]
    return frame, add_b, add_c


def _un_nhwc(y: jax.Array, add_b: bool, add_c: bool) -> jax.Array:
    if add_b:
        y = y[0]
    if add_c:
        y = y[..., 0]
    return y


def _shifted(xp: jax.Array, i: int, j: int, H: int, W: int) -> jax.Array:
    """Window-tap view: xp is the (H+w-1, W+w-1)-extended frame."""
    return jax.lax.dynamic_slice_in_dim(
        jax.lax.dynamic_slice_in_dim(xp, i, H, axis=1), j, W, axis=2)


def _taps(xp: jax.Array, coeffs: jax.Array, H: int, W: int):
    """All w² (shifted-frame, scalar-coeff) product terms, in raster order."""
    w = coeffs.shape[-1]
    terms = []
    for i in range(w):
        for j in range(w):
            terms.append((_shifted(xp, i, j, H, W), coeffs[i, j]))
    return terms


# ---------------------------------------------------------------------------
# Forms
# ---------------------------------------------------------------------------


# A TPU loop fusion takes about 32 scalar operands. Past that, XLA moves
# the extra coefficients out of the fusion as broadcasts to full planes,
# each written to and read back from memory. So the direct form sums its
# taps in passes of at most this many, each pass one fusion.
_TAPS_PER_PASS = 32


def _direct(xp: jax.Array, coeffs: jax.Array, H: int, W: int) -> jax.Array:
    """The w² taps summed in raster order in the accumulator dtype,
    ``acc = Σ c[i,j]·xp[:, i:i+H, j:j+W, :]``, in as few balanced passes
    of at most ``_TAPS_PER_PASS`` taps as there can be. Each pass is one
    elementwise fusion that reads the extended frame and the running sum
    and holds its coefficients as scalars; an optimization barrier keeps
    XLA from merging the passes. No value carries a trailing w² axis."""
    terms = _taps(xp, coeffs.astype(xp.dtype), H, W)
    passes = math.ceil(len(terms) / _TAPS_PER_PASS)
    per_pass = math.ceil(len(terms) / passes)
    acc = None
    for k, (plane, c) in enumerate(terms):
        if k and k % per_pass == 0:
            acc = jax.lax.optimization_barrier(acc)
        acc = plane * c if acc is None else acc + plane * c
    return acc


def _transposed(xp: jax.Array, coeffs: jax.Array, H: int, W: int) -> jax.Array:
    """Running-accumulator MAC chain over the w² taps (no patch tensor)."""
    terms = _taps(xp, coeffs.astype(xp.dtype), H, W)
    acc = terms[0][0] * terms[0][1]
    for plane, c in terms[1:]:
        acc = acc + plane * c
    return acc


def _tree(xp: jax.Array, coeffs: jax.Array, H: int, W: int) -> jax.Array:
    """Pairwise (log2-depth) reduction of the w² products — LOG layout."""
    prods = [pl * c for pl, c in _taps(xp, coeffs.astype(xp.dtype), H, W)]
    while len(prods) > 1:
        nxt = [prods[i] + prods[i + 1] for i in range(0, len(prods) - 1, 2)]
        if len(prods) % 2:
            nxt.append(prods[-1])
        prods = nxt
    return prods[0]


def _compress(xp: jax.Array, coeffs: jax.Array, H: int, W: int,
              group: int = 6) -> jax.Array:
    """Group-of-6 partial sums, then a final chain — DSPCOMP layout."""
    prods = [pl * c for pl, c in _taps(xp, coeffs.astype(xp.dtype), H, W)]
    partials = []
    for i in range(0, len(prods), group):
        g = prods[i:i + group]
        s = g[0]
        for t in g[1:]:
            s = s + t
        partials.append(s)
    acc = partials[0]
    for s1 in partials[1:]:
        acc = acc + s1
    return acc


_FORM_FNS = {
    "direct": _direct,
    "transposed": _transposed,
    "tree": _tree,
    "compress": _compress,
}


def _extend_policy(frame: jax.Array, r: int, border_policy: str,
                   border_constant: jax.Array) -> jax.Array:
    """Border-extend an NHWC frame along (H, W) under the policy."""
    B, H, W, C = frame.shape
    if border_policy == "neglect" or r == 0:
        return frame
    if border_policy == "constant":
        # extend() handles the value through the mask path; inline it here
        xp = extend(frame, r, BorderSpec("duplicate"), axes=(1, 2))
        # overwrite out-of-frame ring with the constant
        hi = jnp.arange(-r, H + r)
        wi = jnp.arange(-r, W + r)
        mh = ((hi >= 0) & (hi < H))[None, :, None, None]
        mw = ((wi >= 0) & (wi < W))[None, None, :, None]
        return jnp.where(mh & mw, xp, border_constant.astype(xp.dtype))
    return extend(frame, r, BorderSpec(border_policy), axes=(1, 2))


@functools.partial(jax.jit, static_argnames=("form", "border_policy"))
def _filter2d_impl(frame: jax.Array, coeffs: jax.Array, *, form: str,
                   border_policy: str, border_constant: jax.Array
                   ) -> jax.Array:
    # fixed-point path (paper: B=8 pixels, DSP48 accumulates at 48 bits):
    # int8/uint8 frames multiply-accumulate in int32 and return int32 —
    # the caller owns the requantisation, as the FPGA datapath does. The
    # border constant reaching this point is already quantized against the
    # *storage* dtype (see quantize_constant), so widening before the
    # border extension cannot smuggle an unrepresentable c into the frame.
    if is_fixed_point(frame.dtype):
        frame = frame.astype(jnp.int32)
        coeffs = coeffs.astype(jnp.int32)
    spec = BorderSpec(border_policy)  # constant value applied via gather mask
    frame, add_b, add_c = _as_nhwc(frame)
    B, H, W, C = frame.shape
    w = coeffs.shape[-1]
    r = (w - 1) // 2
    xp = _extend_policy(frame, r, border_policy, border_constant)
    Ho, Wo = out_shape(H, W, w, spec)
    y = _FORM_FNS[form](xp, coeffs, Ho, Wo)
    return _un_nhwc(y, add_b, add_c)


@functools.partial(jax.jit, static_argnames=("border_policy",))
def _filter2d_sep_impl(frame: jax.Array, u: jax.Array, v: jax.Array, *,
                       border_policy: str, border_constant: jax.Array
                       ) -> jax.Array:
    """Separable fast path: a w-tap column pass then a w-tap row pass
    (2w MACs/pixel instead of w²). u filters rows (vertical), v columns.
    Fixed-point frames (explicit exact integer factors only — see
    resolve_separable) widen to int32 here and accumulate exactly."""
    if is_fixed_point(frame.dtype):
        frame = frame.astype(jnp.int32)
        u = u.astype(jnp.int32)
        v = v.astype(jnp.int32)
    spec = BorderSpec(border_policy)
    frame, add_b, add_c = _as_nhwc(frame)
    B, H, W, C = frame.shape
    w = u.shape[0]
    r = (w - 1) // 2
    xp = _extend_policy(frame, r, border_policy, border_constant)
    Ho, Wo = out_shape(H, W, w, spec)
    u = u.astype(xp.dtype)
    v = v.astype(xp.dtype)
    h = None                              # horizontal (column) pass: w MACs
    for j in range(w):
        t = jax.lax.dynamic_slice_in_dim(xp, j, Wo, axis=2) * v[j]
        h = t if h is None else h + t
    y = None                              # vertical (row) pass: w MACs
    for i in range(w):
        t = jax.lax.dynamic_slice_in_dim(h, i, Ho, axis=1) * u[i]
        y = t if y is None else y + t
    return _un_nhwc(y, add_b, add_c)


# one-time flag for the separable='auto' traced-coefficient fallback
# warning (tests reset it via repro.core.filter2d._SEP_AUTO_TRACED_WARNED)
_SEP_AUTO_TRACED_WARNED = False


def _warn_traced_auto_once() -> None:
    """``separable='auto'`` under jit silently eats the w² cost: SVD rank
    detection needs concrete coefficients, so every traced call falls back
    to the full form. Served pipelines should pass explicit
    ``separable=(u, v)`` factors; warn once per process so they find out."""
    global _SEP_AUTO_TRACED_WARNED
    if _SEP_AUTO_TRACED_WARNED:
        return
    _SEP_AUTO_TRACED_WARNED = True
    warnings.warn(
        "separable='auto' received traced coefficients: SVD rank-1 "
        "detection runs at trace time and cannot see traced values, so "
        "this (and every further traced) call silently falls back to the "
        "full w² form. Pass explicit separable=(u, v) factors to keep "
        "the 2w-MAC fast path in jitted/served pipelines.",
        UserWarning, stacklevel=4)


def resolve_separable(frame_dtype, coeffs, separable,
                      tol: float = 1e-5):
    """Resolve the ``separable`` knob to ``(u, v)`` or ``None`` (2D path).

    ``separable=False`` never decomposes; ``True`` requires a concrete
    rank-1 float filter (raises otherwise); ``"auto"`` decomposes when it
    can and silently falls back to the full w² form when it can't (traced
    coefficients, fixed-point frames, non-separable filters). An explicit
    ``separable=(u, v)`` pair of 1D factors always takes the 2w path —
    the only way fixed-point frames get it, and then only with *integer*
    factors whose outer product reproduces ``coeffs`` exactly (verified
    when both are concrete): SVD factors would break bit-exact int32
    accumulation, so they are never inferred for integer frames.
    """
    if separable is False or separable is None:
        return None
    if isinstance(separable, (tuple, list)):
        if len(separable) != 2:
            raise ValueError("separable=(u, v) takes exactly two 1D factors")
        u, v = jnp.asarray(separable[0]), jnp.asarray(separable[1])
        if u.ndim != 1 or v.ndim != 1 or u.shape != v.shape:
            raise ValueError("separable factors must be same-length 1D "
                             f"arrays; got {u.shape} and {v.shape}")
        concrete = not any(isinstance(a, jax.core.Tracer)
                           for a in (coeffs, u, v))
        if jnp.issubdtype(jnp.dtype(frame_dtype), jnp.integer):
            if not (jnp.issubdtype(u.dtype, jnp.integer)
                    and jnp.issubdtype(v.dtype, jnp.integer)):
                raise ValueError(
                    "fixed-point frames take the separable path only with "
                    "an exact *integer* rank-1 factorization; got factor "
                    f"dtypes {u.dtype}/{v.dtype}")
            if concrete and not np.array_equal(
                    np.outer(np.asarray(u), np.asarray(v)),
                    np.asarray(coeffs)):
                raise ValueError(
                    "separable=(u, v) does not factor coeffs exactly; the "
                    "fixed-point path must stay bit-exact with the w² form")
        elif concrete and not np.allclose(
                np.outer(np.asarray(u, np.float64),
                         np.asarray(v, np.float64)),
                np.asarray(coeffs, np.float64), rtol=1e-4, atol=1e-6):
            raise ValueError(
                "separable=(u, v) does not factor coeffs (outer(u, v) != "
                "coeffs); traced factors skip this check for "
                "runtime-swapped pipelines")
        return u, v
    if separable not in (True, "auto"):
        raise ValueError(
            f"separable must be 'auto', True, False or a (u, v) pair; "
            f"got {separable!r}")
    strict = separable is True
    if jnp.issubdtype(jnp.dtype(frame_dtype), jnp.integer):
        if strict:
            raise NotImplementedError(
                "separable fast path needs an explicit exact integer "
                "factorization for fixed-point frames: pass "
                "separable=(u, v); SVD detection is float-only")
        return None
    if isinstance(coeffs, jax.core.Tracer):
        if strict:
            raise ValueError("separable=True needs concrete coefficients "
                             "(SVD rank detection runs at trace time)")
        _warn_traced_auto_once()
        return None
    uv = decompose_separable(np.asarray(coeffs), tol=tol)
    if uv is None and strict:
        raise ValueError("separable=True but the filter is not rank-1 "
                         "within tol; use separable='auto' to fall back")
    return uv


def filter2d(frame: jax.Array, coeffs: jax.Array, *, form: str = "direct",
             border: BorderSpec = BorderSpec("mirror"),
             separable=False,
             requant: Optional[RequantSpec] = None) -> jax.Array:
    """Apply a runtime `w×w` filter to a frame.

    frame: [H,W] | [H,W,C] | [B,H,W,C]. coeffs: [w,w] (traced operand).
    Output keeps the frame size unless ``border.policy == 'neglect'``
    (paper: Direct keeps H×W, Transposed/neglect shrinks by w−1).

    ``separable``: ``"auto"`` detects rank-1 filters (gaussian, box, …) by
    SVD and routes them through two 1D passes at 2w MACs/pixel; ``True``
    requires separability (raises otherwise); ``False`` (default) always
    runs the full w² form.

    ``requant``: optional :class:`~repro.core.requant.RequantSpec` —
    fixed-point frames only. The int32 accumulator is scaled
    (``·multiplier >> shift``), rounded per the spec's mode and saturated
    into the spec's storage dtype, so pixels *leave* at storage width too
    (the paper's B-bit output bus). ``None`` keeps the int32 output and
    the caller requantises.

    Thin wrapper over the plan-and-execute front door: prefer
    ``core.pipeline.Filter2D(...).compile(frame)`` for served pipelines —
    it caches the compiled executable and swaps coefficients, separable
    factors and requant gains without retracing.
    """
    from repro.core.pipeline import Filter2D
    if form not in FORMS:
        raise ValueError(f"unknown form {form!r}; choose from {FORMS}")
    rq = resolve_requant(frame.dtype, requant)
    uv = resolve_separable(frame.dtype, coeffs, separable)
    window = (int(jnp.shape(uv[0])[0]) if uv is not None
              else int(jnp.shape(coeffs)[-1]))
    spec = Filter2D(window=window, form=form, border=border,
                    separable=uv is not None,
                    dtype=jnp.dtype(frame.dtype).name,
                    requant=rq.gain_free() if rq is not None else None)
    cf = spec.compile(frame, "core")
    return cf(frame, uv if uv is not None else coeffs, gains=rq)


@functools.partial(jax.jit, static_argnames=("form", "border"))
def _filter_bank_impl(frame: jax.Array, bank: jax.Array, *, form: str,
                      border: BorderSpec) -> jax.Array:
    """The bank executable: one extension + one MXU contraction for all N
    filters, wide accumulator out (int32 for fixed-point frames). The
    requantising epilogue is the caller's (the pipeline applies it with
    *traced* gains so gain swaps hit the jit cache)."""
    qc = quantize_constant(border.constant, frame.dtype)
    if is_fixed_point(frame.dtype):
        frame = frame.astype(jnp.int32)
        bank = bank.astype(jnp.int32)
    frame_n, add_b, add_c = _as_nhwc(frame)
    B, H, W, C = frame_n.shape
    w = bank.shape[-1]
    r = (w - 1) // 2
    if border.policy == "neglect":
        xp = frame_n
    else:
        # one extension serves the whole bank (constant included): the
        # input is read ONCE for all N filters, matching the Pallas path
        xp = _extend_policy(frame_n, r, border.policy,
                            jnp.asarray(qc, frame_n.dtype))
    Ho, Wo = out_shape(H, W, w, border)
    planes = jnp.stack(
        [_shifted(xp, i, j, Ho, Wo) for i in range(w) for j in range(w)],
        axis=-1)  # [B,Ho,Wo,C,w2]
    y = jnp.einsum("bhwck,kn->bhwcn", planes,
                   bank.reshape(bank.shape[0], -1).T.astype(xp.dtype))
    y = _un_nhwc(y, add_b, False)
    if add_c:
        y = y[..., 0, :]
    return y


def filter_bank(frame: jax.Array, bank: jax.Array, *, form: str = "direct",
                border: BorderSpec = BorderSpec("mirror"),
                requant: Optional[RequantSpec] = None) -> jax.Array:
    """Apply N filters in one pass: bank [N,w,w] -> output [..., N].

    The multi-filter analogue of the paper's coefficient file: on the MXU
    the N coefficient vectors become the matmul RHS [w², N], so the whole
    bank costs one pass over the frame (input read ONCE for all filters).
    Integer frames follow the fixed-point contract of :func:`filter2d`:
    multiply-accumulate in int32, int32 out — unless ``requant`` gives the
    bank its per-filter output scalers (multiplier/shift tuples, one entry
    per filter), in which case each bank lane leaves at storage width.

    Thin wrapper over ``core.pipeline.Filter2D`` (``num_filters=N``) —
    prefer the compiled front door for served pipelines.
    """
    from repro.core.pipeline import Filter2D
    n = int(jnp.shape(bank)[0])
    rq = resolve_requant(frame.dtype, requant, num_filters=n)
    spec = Filter2D(window=int(jnp.shape(bank)[-1]), form=form, border=border,
                    num_filters=n,
                    dtype=jnp.dtype(frame.dtype).name,
                    requant=rq.gain_free() if rq is not None else None)
    cf = spec.compile(frame, "core")
    return cf(frame, bank, gains=rq)


# ---------------------------------------------------------------------------
# XLA-inferred baseline (the paper's "Vivado HLS" analogue)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("border",))
def _filter2d_xla_impl(frame: jax.Array, coeffs: jax.Array, *,
                       border: BorderSpec) -> jax.Array:
    """`lax.conv_general_dilated` — let the compiler infer the structure,
    as Vivado HLS does in the paper's Table X comparison. Fixed-point
    frames follow the shared contract: the ``constant(c)`` border value is
    quantized against the *storage* dtype before widening and the
    convolution accumulates in int32; the requantising epilogue is the
    pipeline's (applied with traced gains after this impl)."""
    qc = quantize_constant(border.constant, frame.dtype)
    if is_fixed_point(frame.dtype):
        frame = frame.astype(jnp.int32)
        coeffs = coeffs.astype(jnp.int32)
    frame_n, add_b, add_c = _as_nhwc(frame)
    B, H, W, C = frame_n.shape
    w = coeffs.shape[-1]
    r = (w - 1) // 2
    xp = frame_n if border.policy == "neglect" else _extend_policy(
        frame_n, r, border.policy, jnp.asarray(qc, frame_n.dtype))
    # depthwise: apply same 2D kernel to each channel
    rhs = jnp.broadcast_to(coeffs.astype(xp.dtype)[:, :, None, None],
                           (w, w, 1, C))
    y = jax.lax.conv_general_dilated(
        xp, rhs, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=C)
    return _un_nhwc(y, add_b, add_c)


def filter2d_xla(frame: jax.Array, coeffs: jax.Array,
                 border_policy: str = "mirror", *,
                 border: Optional[BorderSpec] = None,
                 requant: Optional[RequantSpec] = None) -> jax.Array:
    """The compiler-inferred baseline executor (paper Table X's Vivado HLS
    analogue). Pass a full ``BorderSpec`` via ``border`` (wins over
    ``border_policy``) for non-zero constants; ``requant`` applies the
    same fused epilogue contract as :func:`filter2d` — fixed-point frames
    accumulate in int32 through the convolution and leave at the spec's
    storage width.

    Thin wrapper over ``core.pipeline.Filter2D`` (``execution='xla'``) —
    prefer the compiled front door for served pipelines.
    """
    from repro.core.pipeline import Filter2D
    spec_b = border if border is not None else BorderSpec(border_policy)
    rq = resolve_requant(frame.dtype, requant)
    spec = Filter2D(window=int(jnp.shape(coeffs)[-1]), border=spec_b,
                    dtype=jnp.dtype(frame.dtype).name,
                    requant=rq.gain_free() if rq is not None else None)
    cf = spec.compile(frame, "xla")
    return cf(frame, coeffs, gains=rq)


# ---------------------------------------------------------------------------
# Accounting (paper Tables II/III analogues — used by benchmarks)
# ---------------------------------------------------------------------------


def macs_per_pixel(w: int, form: str = "direct",
                   separable: bool = False) -> int:
    """MXU/VPU MAC issue count per output pixel (paper Table II analogue).

    All 2D forms issue w² MACs (they differ in reduction shape); the
    separable fast path issues 2w (one w-tap pass per axis)."""
    if separable:
        return 2 * w
    return w * w


def reduction_depth(w: int, form: str) -> int:
    """Adder stages after the multiplies (paper Table I 'stages')."""
    n = w * w
    if form == "direct":
        return 1                      # systolic: inside the MXU pass
    if form == "transposed":
        return n - 1                  # chain
    if form == "tree":
        return math.ceil(math.log2(n))
    if form == "compress":
        groups = math.ceil(n / 6)
        return 2 + (groups - 1)       # compress (2) + partial-sum chain
    raise ValueError(form)


def startup_latency_rows(w: int, form: str,
                         separable: bool = False) -> float:
    """Rows that must stream in before the first output row (Table III
    analogue): direct-form needs (w−1)/2 +border rows; transposed/neglect
    needs w−1 (it discards borders, first valid row is row w−1).
    Separability changes the MAC count, not the stencil's vertical
    support — the row pass still spans w input rows, so latency depends
    only on the form."""
    if form == "transposed":
        return float(w - 1)
    return (w - 1) / 2.0


def hbm_bytes_per_pixel(dtype_bytes: int = 4, extra_passes: int = 0) -> int:
    """Single-pass streaming: in once + out once (+ any extra passes)."""
    return dtype_bytes * (2 + 2 * extra_passes)
