"""The fused requantising epilogue: narrow words in BOTH directions.

Every datapath (core oracle, Pallas halo kernel in both regimes, the
streaming executor, the filter bank with per-filter scalers) must land
bit-identically on ``core.requant.requantize_ref`` — integer arithmetic
leaves nowhere for error to hide — including the saturation edges: all-max
frames, negative multipliers, every rounding mode. The write-side byte
accounting (the paper's ≤2-bytes/pixel round trip for int8) is asserted
from the static halo plan, not timed.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.border_spec import BorderSpec, SAME_SIZE_POLICIES
from repro.core.filter2d import apply_requant, filter2d, filter_bank
from repro.core.requant import (ROUNDING_MODES, RequantSpec, requantize_ref,
                                round_shift_ref)
from repro.core.pipeline import Filter2D
from repro.kernels.filter2d import (filter2d_pallas, filter_bank_pallas,
                                    hbm_bytes_per_pixel,
                                    hbm_write_bytes_per_pixel, make_plan,
                                    stream_vmem_working_set)
from tests.test_fixed_point import np_filter_int32

DTYPES = (np.int8, np.uint8, np.int16)


def _frame(rng, dtype, shape=(24, 150)):
    lo, hi = (0, 50) if dtype == np.uint8 else (-20, 20)
    return rng.integers(lo, hi, shape).astype(dtype)


def _ref(x, k, policy, rq, c=0.0):
    return requantize_ref(np_filter_int32(x, k, policy, constant=c), rq)


# -- rounding-mode semantics, pinned against exact rational arithmetic ------


@pytest.mark.parametrize("mode", ROUNDING_MODES)
def test_round_shift_ref_semantics(mode):
    """floor / half-up(+inf) / half-to-even over a dense ± grid, checked
    against exact fractions — the contract every twin implements."""
    for shift in (1, 2, 5):
        prod = np.arange(-300, 300, dtype=np.int64)
        got = round_shift_ref(prod, shift, mode)
        exact = prod / float(2 ** shift)
        if mode == "truncate":
            want = np.floor(exact)
        elif mode == "nearest":
            want = np.floor(exact + 0.5)
        else:
            want = np.rint(exact)          # numpy rint ties to even
        np.testing.assert_array_equal(got, want.astype(np.int64))


@pytest.mark.parametrize("mode", ROUNDING_MODES)
def test_jnp_twin_matches_ref(mode):
    """core.filter2d.apply_requant (the jnp twin the kernel fuses) is
    bit-identical to the numpy reference, shift 0 edge included."""
    rng = np.random.default_rng(3)
    acc = rng.integers(-2 ** 20, 2 ** 20, (64, 64)).astype(np.int32)
    for mult in (1, -1, 7, -7):
        for shift in (0, 1, 8, 15):
            rq = RequantSpec(multiplier=mult, shift=shift, rounding=mode,
                             dtype="int8")
            got = apply_requant(jnp.asarray(acc), mult, shift,
                                rounding=mode, out_dtype=np.int8)
            np.testing.assert_array_equal(np.asarray(got),
                                          requantize_ref(acc, rq))


# -- the satellite sweep: all-max frames × every mode × negative mults ------


@pytest.mark.parametrize("mode", ROUNDING_MODES)
@pytest.mark.parametrize("mult", (3, -3))
@pytest.mark.parametrize("dtype", (np.int8, np.int16))
def test_saturation_edge_allmax(dtype, mult, mode):
    """All-max frame × all-max-ish coeffs: the scaled accumulator pins
    the clamp on one rail (both rails across the ±multiplier pair), and
    every partial past the first tap would have overflowed the storage
    dtype — right answers require int32 END TO END, then one saturating
    narrowing at the very end."""
    info = np.iinfo(dtype)
    x = np.full((16, 130), info.max, dtype)
    k = np.full((5, 5), 11, np.int32)
    rq = RequantSpec(multiplier=mult, shift=9, rounding=mode,
                     dtype=np.dtype(dtype).name)
    want = _ref(x, k, "duplicate", rq)
    # the edge actually saturates: the whole frame sits on a clamp rail
    assert int(want[8, 64]) == (info.max if mult > 0 else info.min)
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("duplicate"), regime="stream",
                          strip_h=8, tile_w=128, requant=rq)
    assert got.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(got), want)
    core = filter2d(jnp.asarray(x), jnp.asarray(k),
                    border=BorderSpec("duplicate"), requant=rq)
    np.testing.assert_array_equal(np.asarray(core), want)


@pytest.mark.parametrize("mode", ROUNDING_MODES)
def test_saturation_edge_allmax_uint8(mode):
    """uint8: the negative-multiplier rail is 0, the positive one 255."""
    x = np.full((12, 140), 255, np.uint8)
    k = np.full((3, 3), 9, np.int32)
    for mult in (2, -2):
        rq = RequantSpec(multiplier=mult, shift=4, rounding=mode,
                         dtype="uint8")
        want = _ref(x, k, "wrap", rq)
        assert int(want[6, 70]) == (255 if mult > 0 else 0)
        got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                              border=BorderSpec("wrap"), regime="stream",
                              strip_h=8, tile_w=128, requant=rq)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_headroom_contract_asserts():
    """Out-of-contract (multiplier too large for the accumulator) fails
    loudly in the reference instead of comparing two wraparounds."""
    acc = np.full((4, 4), 127 * 127 * 25, np.int32)      # ≈4e5
    with pytest.raises(AssertionError, match="headroom"):
        requantize_ref(acc, RequantSpec(multiplier=2 ** 14, shift=20,
                                        rounding="nearest", dtype="int8"))


# -- full-path parity: every policy / regime / executor ---------------------


@pytest.mark.parametrize("mode", ROUNDING_MODES)
@pytest.mark.parametrize("policy", SAME_SIZE_POLICIES)
def test_pallas_requant_bit_exact(policy, mode, rng):
    x = _frame(rng, np.int8)
    k = rng.integers(-8, 9, (5, 5)).astype(np.int32)
    rq = RequantSpec(multiplier=-5, shift=8, rounding=mode, dtype="int8")
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec(policy, 3.0), regime="stream",
                          strip_h=8, tile_w=128, requant=rq)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got),
                                  _ref(x, k, policy, rq, c=3.0))


@pytest.mark.parametrize("dtype", DTYPES)
def test_small_regime_and_neglect(dtype, rng):
    x = _frame(rng, dtype)
    k = rng.integers(-8, 9, (5, 5)).astype(np.int32)
    rq = RequantSpec(multiplier=3, shift=7, rounding="nearest_even",
                     dtype=np.dtype(dtype).name)
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("mirror"), regime="small",
                          requant=rq)
    np.testing.assert_array_equal(np.asarray(got), _ref(x, k, "mirror", rq))
    gotn = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                           border=BorderSpec("neglect"), regime="stream",
                           strip_h=8, tile_w=128, requant=rq)
    np.testing.assert_array_equal(np.asarray(gotn), _ref(x, k, "neglect", rq))


def test_separable_requant_bit_exact(rng):
    x = _frame(rng, np.int16, (32, 140))
    u = np.array([1, 4, 6, 4, 1], np.int32)
    v = np.array([1, 2, 4, 2, 1], np.int32)
    k = np.outer(u, v).astype(np.int32)
    rq = RequantSpec(multiplier=1, shift=6, rounding="nearest", dtype="int16")
    want = _ref(x, k, "mirror", rq)
    for got in (filter2d(jnp.asarray(x), jnp.asarray(k),
                         border=BorderSpec("mirror"), separable=(u, v),
                         requant=rq),
                filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                                border=BorderSpec("mirror"),
                                separable=(u, v), regime="stream",
                                strip_h=8, tile_w=128, requant=rq)):
        assert got.dtype == jnp.int16
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("policy", SAME_SIZE_POLICIES)
def test_streaming_executor_requant_parity(policy, rng):
    x = _frame(rng, np.int8, (32, 40))
    k = rng.integers(-4, 5, (3, 3)).astype(np.int32)
    rq = RequantSpec(multiplier=7, shift=9, rounding="truncate", dtype="int8")
    spec = Filter2D(window=3, border=BorderSpec(policy, 2.0), dtype="int8",
                    requant=rq.gain_free())
    got = spec.compile(x.shape, "streaming", strip_h=8)(
        jnp.asarray(x), jnp.asarray(k), gains=rq)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got),
                                  _ref(x, k, policy, rq, c=2.0))


def test_bank_per_filter_scalers(rng):
    """Each bank lane gets its own (multiplier, shift) — the per-filter
    coefficient-file analogue, through core AND the kernel's SMEM params
    operand."""
    x = _frame(rng, np.int8)
    bank = rng.integers(-5, 6, (3, 5, 5)).astype(np.int32)
    rq = RequantSpec(multiplier=(1, -2, 3), shift=(4, 5, 6),
                     rounding="nearest", dtype="int8")
    acc = np_filter_int32(x, bank, "mirror")
    want = np.stack([requantize_ref(acc[n], rq, filter_index=n)
                     for n in range(3)])
    got = filter_bank_pallas(jnp.asarray(x), jnp.asarray(bank),
                             border=BorderSpec("mirror"), regime="stream",
                             strip_h=8, tile_w=128, requant=rq)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.moveaxis(np.asarray(got), -1, 0), want)
    core = filter_bank(jnp.asarray(x), jnp.asarray(bank),
                       border=BorderSpec("mirror"), requant=rq)
    np.testing.assert_array_equal(np.moveaxis(np.asarray(core), -1, 0), want)


def test_cross_dtype_requant(rng):
    """Storage-in and storage-out dtypes are independent plan geometry:
    an int16 frame can leave as int8 (and the bytes follow)."""
    x = _frame(rng, np.int16)
    k = rng.integers(-4, 5, (3, 3)).astype(np.int32)
    rq = RequantSpec(multiplier=1, shift=8, rounding="nearest", dtype="int8")
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("duplicate"), regime="stream",
                          strip_h=8, tile_w=128, requant=rq)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got),
                                  _ref(x, k, "duplicate", rq))
    plan = make_plan(128, 256, 3, BorderSpec("duplicate"), 64, 128,
                     dtype=np.int16, requant=rq)
    assert plan.dtype_bytes == 2 and plan.out_dtype_bytes == 1


# -- spec validation: every entry point rejects the same misuses ------------


def test_spec_validation():
    with pytest.raises(ValueError, match="rounding"):
        RequantSpec(rounding="stochastic")
    with pytest.raises(ValueError, match="shift"):
        RequantSpec(shift=-1)
    with pytest.raises(ValueError, match="shift"):
        RequantSpec(shift=32)
    with pytest.raises(ValueError, match="storage dtype"):
        RequantSpec(dtype="int32")
    with pytest.raises(ValueError, match="storage dtype"):
        RequantSpec(dtype="float32")
    # normalisation: dtype objects and numpy scalars are canonicalised
    spec = RequantSpec(multiplier=np.int64(3), shift=(np.int64(1), 2),
                       dtype=np.int8)
    assert spec.multiplier == 3 and spec.shift == (1, 2)
    assert spec.dtype == "int8" and spec.dtype_bytes == 1
    assert spec.params(2) == ((3, 1), (3, 2))
    with pytest.raises(ValueError, match="per-filter"):
        spec.params(3)


def test_float_frames_reject_requant(rng):
    x = jnp.asarray(rng.standard_normal((16, 130)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((3, 3)).astype(np.float32))
    rq = RequantSpec(dtype="int8")
    with pytest.raises(ValueError, match="fixed-point"):
        filter2d(x, k, requant=rq)
    with pytest.raises(ValueError, match="fixed-point"):
        filter2d_pallas(x, k, regime="stream", strip_h=8, tile_w=128,
                        requant=rq)
    with pytest.raises(ValueError, match="fixed-point"):
        make_plan(16, 130, 3, BorderSpec("mirror"), 8, 128,
                  dtype=np.float32, requant=rq)
    with pytest.raises(TypeError, match="RequantSpec"):
        filter2d(jnp.asarray(np.zeros((8, 8), np.int8)),
                 jnp.asarray(np.ones((3, 3), np.int32)), requant=(3, 7))


# -- static accounting: the ≤2.2 bytes/pixel round trip ---------------------


def test_round_trip_bytes_close_the_bus():
    """The acceptance pin: an int8→int8 plan moves ≤2.2 HBM bytes/pixel
    round trip (read amplification × 1 byte + 1 byte written), where the
    pre-epilogue datapath paid ≈5 — asserted from the plan, not timed.
    int16→int16 halves the old 6.1 to ≈4.1 the same way. (Full-width
    tiles: the aligned column halo is a whole 128-lane tile a side, so a
    512-wide tile alone would re-read half its width.)"""
    spec = BorderSpec("mirror")
    S, T = 256, 3840
    rq8 = RequantSpec(multiplier=1, shift=8, dtype="int8")
    p8 = make_plan(2160, 3840, 5, spec, S, T, dtype=np.int8, requant=rq8)
    assert hbm_write_bytes_per_pixel(p8) == 1.0
    assert hbm_bytes_per_pixel(p8) <= 2.2
    p8_wide = make_plan(2160, 3840, 5, spec, S, T, dtype=np.int8)
    assert hbm_write_bytes_per_pixel(p8_wide) == 4.0
    assert hbm_bytes_per_pixel(p8_wide) - hbm_bytes_per_pixel(p8) == 3.0
    rq16 = RequantSpec(multiplier=1, shift=8, dtype="int16")
    p16 = make_plan(2160, 3840, 5, spec, S, T, dtype=np.int16,
                    requant=rq16)
    assert hbm_write_bytes_per_pixel(p16) == 2.0
    assert hbm_bytes_per_pixel(p16) <= 4.4
    # float plans: write side at the frame's own width, requant rejected
    pf = make_plan(2160, 3840, 5, spec, S, T, dtype=np.float32)
    assert hbm_write_bytes_per_pixel(pf) == 4.0


def test_swapping_gains_hits_the_jit_cache(rng):
    """The (multiplier, shift) table is runtime data like the coefficient
    file (paper §I): same shapes + same rounding/dtype with new gains must
    reuse the compiled executable — only the gain-free static half shapes
    the trace — and still produce the new gains' bit-exact result."""
    from repro.kernels.filter2d.ops import _filter2d_pallas_planes

    x = _frame(rng, np.int8)
    k = rng.integers(-8, 9, (5, 5)).astype(np.int32)
    rq_a = RequantSpec(multiplier=3, shift=7, rounding="nearest",
                       dtype="int8")
    rq_b = RequantSpec(multiplier=-5, shift=9, rounding="nearest",
                       dtype="int8")
    assert rq_a.gain_free() == rq_b.gain_free()

    def run(rq):
        return np.asarray(filter2d_pallas(
            jnp.asarray(x), jnp.asarray(k), border=BorderSpec("mirror"),
            regime="stream", strip_h=8, tile_w=128, requant=rq))

    got_a = run(rq_a)
    size_after_a = _filter2d_pallas_planes._cache_size()
    got_b = run(rq_b)
    assert _filter2d_pallas_planes._cache_size() == size_after_a
    np.testing.assert_array_equal(got_a, _ref(x, k, "mirror", rq_a))
    np.testing.assert_array_equal(got_b, _ref(x, k, "mirror", rq_b))


def test_vmem_working_set_shrinks_with_requant_output():
    """The requantised output tile sits in VMEM at storage width: the
    working-set bound reflects it (more VMEM for deeper strips)."""
    wide = stream_vmem_working_set(128, 512, 5, 1, acc_dtype_bytes=4)
    narrow = stream_vmem_working_set(128, 512, 5, 1, acc_dtype_bytes=4,
                                     out_dtype_bytes=1)
    assert wide - narrow == 128 * 512 * 3


# -- unity-gain calibration (the turnkey epilogue helper) -------------------


@pytest.mark.parametrize("dtype", ["int8", "uint8", "int16"])
@pytest.mark.parametrize("w", [3, 5, 7])
def test_unity_gain_round_trip(dtype, w, rng):
    """A flat frame through a box filter with the derived scaler comes
    back at its own level (the filter's DC gain divided back out, ±1 LSB
    of rounding) — bit-exact through requantize_ref, and the headroom
    contract the reference asserts holds at the all-max accumulator."""
    k = np.ones((w, w), np.int32)
    rq = RequantSpec.unity_gain(k, dtype)
    info = np.iinfo(np.dtype(dtype))
    for v in (0, 1, 37, info.max // 2, info.max):
        acc = np.full((4, 4), v * w * w, np.int32)    # flat-frame interior
        got = requantize_ref(acc, rq)                 # asserts headroom
        # derivable error bound: |m/2^s - 1/g| <= 0.5/2^s (m = rint(2^s/g))
        # scaled by the accumulator, plus one rounding LSB
        tol = int(v * w * w * 0.5 / 2 ** rq.shift) + 1
        assert abs(int(got[0, 0]) - v) <= tol, (v, got[0, 0], tol)
    # precision: the quantised gain sits within 1e-4 of 1/sum(k)
    assert abs(rq.multiplier / 2 ** rq.shift - 1 / (w * w)) < 1e-4


def test_unity_gain_negative_and_large_sums(rng):
    """Negative coefficient sums derive negative multipliers; large sums
    still find a representable (m, s) pair under the headroom contract."""
    kn = -3 * np.ones((3, 3), np.int32)
    rq = RequantSpec.unity_gain(kn, "int8")
    assert rq.multiplier < 0
    acc = np.full((2, 2), 9 * -3 * 100, np.int32)     # flat frame of 100
    np.testing.assert_array_equal(requantize_ref(acc, rq),
                                  np.full((2, 2), 100, np.int8))
    big = np.full((7, 7), 80, np.int32)               # sum 3920, int16 in
    rq16 = RequantSpec.unity_gain(big, "int16")
    x = np.full((2, 2), 1000 * 3920, np.int32)
    got = requantize_ref(x, rq16)
    # wide-gain filters trade precision for headroom: still within the
    # derivable |m/2^s - 1/g| <= 0.5/2^s bound scaled by the accumulator
    tol = int(1000 * 3920 * 0.5 / 2 ** rq16.shift) + 1
    assert abs(int(got[0, 0]) - 1000) <= tol, (got[0, 0], tol)


def test_unity_gain_per_bank_lane(rng):
    """The [N, w, w] form derives one scaler per coefficient-file lane —
    each lane of a mixed-gain bank lands at unity independently, through
    the real bank datapath (core AND pallas, traced per-filter gains)."""
    bank = np.stack([np.ones((3, 3), np.int32),
                     2 * np.ones((3, 3), np.int32),
                     4 * np.ones((3, 3), np.int32)])
    rq = RequantSpec.unity_gain(bank, "int8", frame_dtype="int8")
    assert rq.num_filters == 3
    x = jnp.asarray(np.full((8, 130), 11, np.int8))
    got = filter_bank(x, jnp.asarray(bank), border=BorderSpec("mirror"),
                      requant=rq)
    got_p = filter_bank_pallas(x, jnp.asarray(bank),
                               border=BorderSpec("mirror"), strip_h=8,
                               tile_w=128, requant=rq)
    for lane in range(3):
        np.testing.assert_array_equal(np.asarray(got[..., lane]),
                                      np.full((8, 130), 11, np.int8))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got_p))


def test_unity_gain_validation():
    with pytest.raises(ValueError, match="integer"):
        RequantSpec.unity_gain(np.ones((3, 3), np.float32), "int8")
    with pytest.raises(ValueError, match="zero coefficient sum"):
        RequantSpec.unity_gain(np.asarray(
            [[1, 0, -1], [0, 0, 0], [0, 0, 0]], np.int32), "int8")
    with pytest.raises(ValueError, match=r"\[w, w\] or \[N, w, w\]"):
        RequantSpec.unity_gain(np.ones(3, np.int32), "int8")
    with pytest.raises(ValueError, match="integer storage"):
        RequantSpec.unity_gain(np.ones((3, 3), np.int32), "int8",
                               frame_dtype="float32")
