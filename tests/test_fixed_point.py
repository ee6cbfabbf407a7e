"""In-kernel fixed-point datapath: int8/uint8/int16 storage, int32 MAC.

The Pallas halo engine streams integer frames at their narrow storage
dtype (scratch, border muxes and wrap DMAs all on the integer dtype,
``constant(c)`` quantized against it) and widens to int32 only at the
MAC — so every path must match the int32 numpy oracle EXACTLY, with no
tolerance: integer arithmetic leaves nowhere for error to hide.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.border_spec import (BorderSpec, SAME_SIZE_POLICIES,
                                    np_pad_mode, quantize_constant)
from repro.core.filter2d import filter2d, filter_bank
from repro.core.pipeline import Filter2D
from repro.kernels.filter2d import (filter2d_pallas, filter_bank_pallas,
                                    make_plan, read_bytes_per_pixel)

DTYPES = (np.int8, np.uint8, np.int16)
# the five border policies of the paper's Table IV that keep frame size
FIVE_POLICIES = SAME_SIZE_POLICIES
SPLITS = ((8, 128), (128, 512))     # multi-strip/tile and single-block plans


def np_filter_int32(x, k, policy, constant=0):
    """Reference integer filter: quantized pad + int64 accumulate, checked
    into int32. The constant is quantized against the *storage* dtype
    before padding — the shared rule under test."""
    r = k.shape[-1] // 2
    c = quantize_constant(constant, x.dtype)
    x64 = x.astype(np.int64)
    k64 = k.astype(np.int64)
    mode = np_pad_mode(policy)
    if mode is None:                      # neglect
        xp, (H, W) = x64, (x.shape[0] - 2 * r, x.shape[1] - 2 * r)
    elif mode == "constant":
        xp = np.pad(x64, r, mode="constant", constant_values=c)
        H, W = x.shape
    else:
        xp = np.pad(x64, r, mode=mode)
        H, W = x.shape
    nk = k64.reshape(-1, *k.shape[-2:])   # [N, w, w] bank or single
    out = np.zeros((nk.shape[0], H, W), np.int64)
    for n in range(nk.shape[0]):
        for i in range(k.shape[-1]):
            for j in range(k.shape[-1]):
                out[n] += xp[i:i + H, j:j + W] * nk[n, i, j]
    assert np.abs(out).max() < 2 ** 31    # oracle itself must fit int32
    out = out.astype(np.int32)
    return out[0] if k.ndim == 2 else out


def _frame(rng, dtype, shape=(24, 150)):
    lo, hi = (0, 50) if dtype == np.uint8 else (-20, 20)
    return rng.integers(lo, hi, shape).astype(dtype)


# -- the tentpole sweep: dtype × policy × direct/bank × strip/tile split ----


@pytest.mark.parametrize("strip,tile", SPLITS)
@pytest.mark.parametrize("policy", FIVE_POLICIES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_direct_bit_exact(dtype, policy, strip, tile, rng):
    x = _frame(rng, dtype)
    k = rng.integers(-8, 9, (5, 5)).astype(np.int32)
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec(policy, 3.0), regime="stream",
                          strip_h=strip, tile_w=tile)
    assert got.dtype == jnp.int32
    want = np_filter_int32(x, k, policy, constant=3.0)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("strip,tile", SPLITS)
@pytest.mark.parametrize("policy", FIVE_POLICIES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_bank_bit_exact(dtype, policy, strip, tile, rng):
    x = _frame(rng, dtype)
    bank = rng.integers(-5, 6, (3, 5, 5)).astype(np.int32)
    got = filter_bank_pallas(jnp.asarray(x), jnp.asarray(bank),
                             border=BorderSpec(policy, 3.0), regime="stream",
                             strip_h=strip, tile_w=tile)
    assert got.dtype == jnp.int32
    want = np_filter_int32(x, bank, policy, constant=3.0)
    # kernel returns [..., N] with the bank dim last
    np.testing.assert_array_equal(
        np.moveaxis(np.asarray(got), -1, 0), want)


@pytest.mark.parametrize("dtype", DTYPES)
def test_neglect_bit_exact(dtype, rng):
    x = _frame(rng, dtype)
    k = rng.integers(-8, 9, (5, 5)).astype(np.int32)
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("neglect"), regime="stream",
                          strip_h=8, tile_w=128)
    np.testing.assert_array_equal(np.asarray(got),
                                  np_filter_int32(x, k, "neglect"))


# -- overflow edge: int32 accumulation must not saturate early --------------


def test_overflow_edge_allmax_int8():
    """All-max int8 frame × all-max coeffs: every partial sum past the
    second tap overflows int8 (and int16 by the 3rd row of taps); the
    result is only right if the accumulator is int32 END TO END."""
    x = np.full((16, 130), 127, np.int8)
    k = np.full((5, 5), 127, np.int32)
    expect = 127 * 127 * 25               # 403,225: > i16 max, < i31
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("duplicate"), regime="stream",
                          strip_h=8, tile_w=128)
    assert got.dtype == jnp.int32
    assert int(np.asarray(got)[8, 64]) == expect
    np.testing.assert_array_equal(np.asarray(got),
                                  np.full((16, 130), expect, np.int32))


def test_overflow_edge_allmax_uint8():
    x = np.full((12, 40), 255, np.uint8)
    k = np.full((3, 3), 127, np.int32)
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("wrap"), regime="stream",
                          strip_h=8, tile_w=128)
    np.testing.assert_array_equal(
        np.asarray(got), np.full((12, 40), 255 * 127 * 9, np.int32))


# -- quantized constant: one rule across core / kernel / stream -------------


@pytest.mark.parametrize("dtype,c,qc", [
    (np.int8, 300.0, 127), (np.int8, -300.0, -128), (np.uint8, 300.0, 255),
    (np.uint8, -5.0, 0), (np.int16, 300.0, 300), (np.int8, 0.75, 1),
])
def test_quantize_constant_rule(dtype, c, qc):
    assert quantize_constant(c, dtype) == qc
    assert isinstance(quantize_constant(c, dtype), int)


def test_quantize_constant_float_passthrough():
    assert quantize_constant(0.75, np.float32) == 0.75


@pytest.mark.parametrize("c", [300.0, -300.0, 0.75])
@pytest.mark.parametrize("dtype", DTYPES)
def test_out_of_range_constant_same_everywhere(dtype, c, rng):
    """constant(c) with unrepresentable c: core (which widens to int32
    before extending), the Pallas kernel (which stores c in the int8
    scratch) and the streaming executor must all quantize c the same way
    — this is the silent-widening bug the shared helper fixes."""
    x = _frame(rng, dtype, (16, 40))
    k = rng.integers(-3, 4, (3, 3)).astype(np.int32)
    spec = BorderSpec("constant", c)
    want = np_filter_int32(x, k, "constant", constant=c)
    core = filter2d(jnp.asarray(x), jnp.asarray(k), border=spec)
    np.testing.assert_array_equal(np.asarray(core), want)
    pallas = filter2d_pallas(jnp.asarray(x), jnp.asarray(k), border=spec,
                             regime="stream", strip_h=8, tile_w=128)
    np.testing.assert_array_equal(np.asarray(pallas), want)
    stream = Filter2D(window=3, border=spec, dtype=x.dtype.name).compile(
        x.shape, "streaming", strip_h=8)(jnp.asarray(x), jnp.asarray(k))
    np.testing.assert_array_equal(np.asarray(stream), want)


# -- separable: explicit exact integer factorization only -------------------


def test_separable_explicit_integer_factors_bit_exact(rng):
    x = _frame(rng, np.int16, (32, 140))
    u = np.array([1, 4, 6, 4, 1], np.int32)
    v = np.array([1, 2, 4, 2, 1], np.int32)
    k = np.outer(u, v).astype(np.int32)
    want = np_filter_int32(x, k, "mirror")
    for fn in (lambda: filter2d(jnp.asarray(x), jnp.asarray(k),
                                border=BorderSpec("mirror"),
                                separable=(u, v)),
               lambda: filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                                       border=BorderSpec("mirror"),
                                       separable=(u, v), regime="stream",
                                       strip_h=8, tile_w=128)):
        got = fn()
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want)


def test_separable_guards_for_integer_frames(rng):
    x = jnp.asarray(_frame(rng, np.int8, (12, 20)))
    u = np.array([1, 2, 1], np.int32)
    k = jnp.asarray(np.outer(u, u).astype(np.int32))
    # auto silently keeps the exact w² form
    got = filter2d(x, k, separable="auto")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(filter2d(x, k)))
    with pytest.raises(NotImplementedError):
        filter2d(x, k, separable=True)     # SVD detection is float-only
    with pytest.raises(ValueError):        # float factors rejected for int
        filter2d(x, k, separable=(u.astype(np.float32),
                                  u.astype(np.float32)))
    with pytest.raises(ValueError):        # inexact factorization rejected
        filter2d(x, k, separable=(u, u + 1))


# -- streaming executor parity ----------------------------------------------


# strip 8: four strips; 16: every strip is the first or the last (wrap's
# prologue rows on both); 32: one strip, both edges remapped in it
@pytest.mark.parametrize("policy,strip_h", [
    *(pytest.param(p, 8, id=p) for p in FIVE_POLICIES),
    *(pytest.param(p, s, id=f"{p}-strip{s}") for s in (16, 32)
      for p in FIVE_POLICIES)])
def test_streaming_executor_int_parity(policy, strip_h, rng):
    x = _frame(rng, np.int8, (32, 40))
    k = rng.integers(-4, 5, (3, 3)).astype(np.int32)
    spec = BorderSpec(policy, 2.0)
    got = Filter2D(window=3, border=spec, dtype="int8").compile(
        x.shape, "streaming", strip_h=strip_h)(jnp.asarray(x),
                                               jnp.asarray(k))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got),
                                  np_filter_int32(x, k, policy, constant=2.0))


# -- structural byte accounting: the 4× HBM win -----------------------------


def test_read_bytes_per_pixel_is_dtype_aware():
    """The read-once claim restated in bytes: an int8 plan reads ≤ ~1.1
    bytes of HBM per pixel where the same float32 plan reads 4× that —
    the paper's narrow-wordlength throughput multiplier, asserted from
    the static plan (full-width tiles, as the derived geometry picks)."""
    spec = BorderSpec("mirror")
    p8 = make_plan(2160, 3840, 5, spec, 256, 3840, dtype=np.int8)
    p16 = make_plan(2160, 3840, 5, spec, 256, 3840, dtype=np.int16)
    p32 = make_plan(2160, 3840, 5, spec, 256, 3840, dtype=np.float32)
    b8, b16, b32 = map(read_bytes_per_pixel, (p8, p16, p32))
    assert b8 <= 1.1
    assert abs(b16 - 2 * b8) < 1e-9 and abs(b32 - 4 * b8) < 1e-9
    assert p8.dtype_bytes == 1 and p16.dtype_bytes == 2


def test_plan_constant_is_quantized():
    plan = make_plan(64, 128, 5, BorderSpec("constant", 300.0), 32, 128,
                     dtype=np.int8)
    assert plan.constant == 127 and isinstance(plan.constant, int)
    planf = make_plan(64, 128, 5, BorderSpec("constant", 300.0), 32, 128)
    assert planf.constant == 300.0
