"""Filter forms (paper §II): all four reduction layouts compute the same
filter; the XLA-inferred baseline agrees; the bank applies N filters in one
pass; streaming (row-buffer) equals the frame-resident path."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import filters
from repro.core.borders import BorderSpec, np_pad_mode
from repro.core.filter2d import (FORMS, filter2d, filter2d_xla, filter_bank,
                                 macs_per_pixel, reduction_depth,
                                 startup_latency_rows)
from repro.core.pipeline import Filter2D


def np_filter(x, k, mode):
    r = k.shape[0] // 2
    if mode is None:
        xp, (H, W) = x, (x.shape[0] - 2 * r, x.shape[1] - 2 * r)
    else:
        xp, (H, W) = np.pad(x, r, mode=mode), x.shape
    out = np.zeros((H, W), np.float32)
    for i in range(k.shape[0]):
        for j in range(k.shape[1]):
            out += xp[i:i + H, j:j + W] * k[i, j]
    return out


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("policy", ["mirror", "duplicate", "neglect"])
@pytest.mark.parametrize("w", [3, 5, 7])
def test_forms_match_numpy(form, policy, w, rng):
    x = rng.standard_normal((21, 17)).astype(np.float32)
    k = filters.gaussian(w)
    got = filter2d(jnp.asarray(x), jnp.asarray(k), form=form,
                   border=BorderSpec(policy))
    want = np_filter(x, k, np_pad_mode(policy))
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-5, atol=3e-5)


def test_xla_baseline_agrees(rng):
    x = rng.standard_normal((32, 40)).astype(np.float32)
    k = filters.log_filter(7)
    a = filter2d(jnp.asarray(x), jnp.asarray(k))
    b = filter2d_xla(jnp.asarray(x), jnp.asarray(k))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5,
                               atol=3e-5)


def test_runtime_coefficients_no_recompile(rng):
    """One jitted executable serves different coefficients (paper §I)."""
    x = jnp.asarray(rng.standard_normal((16, 16)).astype(np.float32))
    k1, k2 = jnp.asarray(filters.gaussian(3)), jnp.asarray(filters.sharpen())
    y1 = filter2d(x, k1)
    y2 = filter2d(x, k2)
    assert not np.allclose(np.asarray(y1), np.asarray(y2))


def test_zero_ring_embedding(rng):
    """A 3x3 filter embedded in a 7x7 zero ring gives identical output
    (paper: one w_max window serves all smaller filters)."""
    x = jnp.asarray(rng.standard_normal((20, 20)).astype(np.float32))
    k3 = filters.sharpen()
    k7 = np.asarray(filters.embed_window(jnp.asarray(k3), 7))
    y3 = filter2d(x, jnp.asarray(k3))
    y7 = filter2d(x, jnp.asarray(k7))
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y7), rtol=2e-5,
                               atol=2e-5)


def test_filter_bank(rng):
    x = rng.standard_normal((18, 14)).astype(np.float32)
    bank = jnp.stack([jnp.asarray(filters.gaussian(5)),
                      jnp.asarray(filters.box(5)),
                      jnp.asarray(filters.identity(5))])
    y = filter_bank(jnp.asarray(x), bank)
    assert y.shape == (18, 14, 3)
    np.testing.assert_allclose(np.asarray(y[..., 2]), x, rtol=2e-5,
                               atol=2e-5)  # identity slot


@pytest.mark.parametrize("sh", [8, 16, 32, 64])   # 64: one strip
@pytest.mark.parametrize("w", [3, 5, 7])
@pytest.mark.parametrize("policy", ["mirror", "mirror_dup", "duplicate",
                                    "constant", "wrap"])
def test_streaming_equals_resident(sh, w, policy):
    """Property: the row-buffer streaming schedule is output-invariant
    (wrap included — served by the prologue's opposite-edge rows)."""
    rng = np.random.default_rng(42)
    x = rng.standard_normal((64, 24)).astype(np.float32)
    k = jnp.asarray(filters.gaussian(w))
    ref = filter2d(jnp.asarray(x), k, border=BorderSpec(policy))
    spec = Filter2D(window=w, border=BorderSpec(policy))
    got = spec.compile(x.shape, "streaming", strip_h=sh)(jnp.asarray(x), k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5,
                               atol=3e-5)


def test_streaming_nonzero_constant():
    """BorderSpec with a non-zero constant flows through the streaming
    executor's column mux and first/last-strip remaps."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal((64, 24)).astype(np.float32)
    k = jnp.asarray(filters.gaussian(5))
    spec = BorderSpec("constant", 4.5)
    ref = filter2d(jnp.asarray(x), k, border=spec)
    got = Filter2D(window=5, border=spec).compile(
        x.shape, "streaming", strip_h=16)(jnp.asarray(x), k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5,
                               atol=3e-5)


@pytest.mark.parametrize("policy", ["mirror", "mirror_dup", "duplicate",
                                    "wrap", "constant"])
def test_filter_bank_equals_per_filter_loop(policy, rng):
    """One bank pass == N independent filter2d calls (every same-size
    policy): the MXU coefficient-file path changes structure, not values."""
    x = jnp.asarray(rng.standard_normal((20, 18)).astype(np.float32))
    bank = jnp.stack([jnp.asarray(filters.gaussian(5)),
                      jnp.asarray(filters.box(5)),
                      jnp.asarray(filters.log_filter(5)),
                      jnp.asarray(filters.identity(5))])
    got = filter_bank(x, bank, border=BorderSpec(policy))
    for i in range(bank.shape[0]):
        want = filter2d(x, bank[i], border=BorderSpec(policy))
        np.testing.assert_allclose(np.asarray(got[..., i]),
                                   np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("policy", ["mirror", "constant"])
def test_filter_bank_fixed_point_accumulates_in_int32(policy, rng):
    """Integer frames keep the exact int32 path through the bank: no
    frame-dtype overflow, bank == per-filter filter2d loop."""
    x = jnp.asarray(rng.integers(0, 50, (12, 14)).astype(np.int8))
    bank = jnp.stack([jnp.ones((3, 3), jnp.int32),
                      jnp.asarray(filters.sobel_x()).astype(jnp.int32)])
    got = filter_bank(x, bank, border=BorderSpec(policy))
    assert got.dtype == jnp.int32
    for i in range(bank.shape[0]):
        want = filter2d(x, bank[i], border=BorderSpec(policy))
        np.testing.assert_array_equal(np.asarray(got[..., i]),
                                      np.asarray(want))


def test_unit_accounting():
    """Paper Tables I/II analogues (+ the separable fast path's 2w)."""
    assert macs_per_pixel(7, "direct") == 49
    assert macs_per_pixel(7, separable=True) == 14       # 2w fast path
    assert macs_per_pixel(5, "tree", separable=True) == 10
    assert reduction_depth(7, "tree") == 6       # ceil(log2 49)
    assert reduction_depth(7, "direct") == 1     # systolic
    assert reduction_depth(7, "compress") == 2 + 8  # ceil(49/6)=9 groups
    assert startup_latency_rows(7, "direct") == 3.0
    assert startup_latency_rows(7, "transposed") == 6.0
    # separability cuts MACs, not the stencil's vertical support
    assert startup_latency_rows(7, "transposed", separable=True) == 6.0
