"""The plain reference against the program's ``core`` oracle, on the CPU:
bit-exact on small integer frames with requant, within float tolerance on
float32 frames; and its control really is a step down in precision."""
import numpy as np
import pytest

from bench import reference, workload

BORDERS = ("mirror", "mirror_dup", "duplicate", "wrap", "constant")


def _program(frame, k, cfg, gains):
    import jax.numpy as jnp
    from bench.generator import program_gains, program_spec
    cf = program_spec(cfg).compile(frame.shape, "core")
    g = program_gains(cfg, gains)
    y = cf(jnp.asarray(frame), jnp.asarray(k)) if g is None else cf(
        jnp.asarray(frame), jnp.asarray(k), g)
    return np.asarray(y)


def _cfg(dtype, border="mirror", rounding="nearest", window=7):
    rq = ({"dtype": "uint8", "rounding": rounding}
          if dtype == "uint8" else None)
    return {"height": 40, "width": 56, "dtype": dtype, "window": window,
            "border": border, "requant": rq,
            "coeffs": ({"kind": "uniform_int", "low": 0, "high": 16,
                        "center_add": 1} if dtype == "uint8" else
                       {"kind": "normal", "scale": 1 / 7,
                        "first": "gaussian"})}


@pytest.mark.parametrize("border", BORDERS)
@pytest.mark.parametrize("rounding", ("nearest", "truncate", "nearest_even"))
def test_uint8_requant_bit_exact_with_core(border, rounding):
    cfg = _cfg("uint8", border, rounding)
    r = workload.rngs(3)
    frames = workload.host_frames(cfg, r["frames"], 2)
    for (k, g), x in zip(workload.coeff_sets(cfg, r["coeffs"], 2), frames):
        want = _program(x, k, cfg, g)
        got = reference.filter_frame(x, k, cfg, g)
        assert got.dtype == np.uint8
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("window", (3, 5, 7))
def test_float32_within_tolerance_of_core(window):
    cfg = _cfg("float32", window=window)
    r = workload.rngs(4)
    x = workload.host_frames(cfg, r["frames"], 1)[0]
    for k, _ in workload.coeff_sets(cfg, r["coeffs"], 3):
        got = reference.filter_frame(x, k, cfg)
        assert reference.max_abs_gap(got, _program(x, k, cfg, None)) < 3e-6


def test_unity_gain_matches_the_programs_scaler():
    from repro.core.requant import RequantSpec
    cfg = _cfg("uint8")
    for k, g in workload.coeff_sets(cfg, workload.rngs(5)["coeffs"], 20):
        rq = RequantSpec.unity_gain(k, "uint8")
        assert g == (rq.multiplier, rq.shift)


@pytest.mark.parametrize("border", BORDERS)
def test_filter_at_equals_the_whole_frame_at_those_pixels(border):
    for dtype in ("uint8", "float32"):
        cfg = _cfg(dtype, border)
        r = workload.rngs(6)
        x = workload.host_frames(cfg, r["frames"], 1)[0]
        (k, g), = workload.coeff_sets(cfg, r["coeffs"], 1)
        rows, cols = workload.probes(r["probes"], 1, cfg, 64)
        whole = reference.filter_frame(x, k, cfg, g)
        at = reference.filter_at(x, k, cfg, g, rows[0], cols[0])
        # float64 sums in another order: equal to rounding
        np.testing.assert_allclose(at, whole[rows[0], cols[0]], rtol=0,
                                   atol=1e-12)


def test_correlation_is_not_flipped_and_mirror_skips_the_edge():
    x = np.arange(12, dtype=np.int64).reshape(3, 4)
    k = np.zeros((3, 3), np.int64)
    k[0, 1] = 1                              # the pixel above
    y = reference.correlate(x, k, "mirror")
    np.testing.assert_array_equal(y[1:], x[:-1])
    np.testing.assert_array_equal(y[0], x[1])  # reflect: row -1 is row 1


def test_control_breaks_the_stated_precision():
    cfg = dict(_cfg("uint8"), height=64, width=64)
    r = workload.rngs(7)
    x = workload.host_frames(cfg, r["frames"], 1)[0]
    (k, g), = workload.coeff_sets(cfg, r["coeffs"], 1)
    gap = reference.max_abs_gap(reference.control(x, k, cfg, g),
                                reference.filter_frame(x, k, cfg, g))
    assert gap >= 100                        # the int16 accumulator wraps
    cfg = dict(_cfg("float32"), height=64, width=64)
    x = workload.host_frames(cfg, r["frames"], 1)[0]
    (k, _), = workload.coeff_sets(cfg, r["coeffs"], 1)
    gap = reference.max_abs_gap(reference.control(x, k, cfg),
                                reference.filter_frame(x, k, cfg))
    assert 1e-4 < gap < 1e-1                 # bfloat16 operands


def test_requantize_saturates_and_rounds():
    acc = np.array([-5, 0, 5, 6, 7, 1000])
    np.testing.assert_array_equal(
        reference.requantize(acc, (1, 1), "nearest", "uint8"),
        [0, 0, 3, 3, 4, 255])
    np.testing.assert_array_equal(
        reference.requantize(acc, (1, 1), "nearest_even", "uint8"),
        [0, 0, 2, 3, 4, 255])
    np.testing.assert_array_equal(
        reference.requantize(acc, (1, 1), "truncate", "uint8"),
        [0, 0, 2, 3, 3, 255])
