"""Column-tiled streaming regime: stream ≡ small ≡ core across strip
heights, non-divisible output heights, and frame widths spanning several
lane-aligned column tiles — plus the 8K bounded-VMEM claim and the
grid-folded batch/channel/filter-bank paths."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import filters
from repro.core.borders import BorderSpec
from repro.core.filter2d import filter2d, filter_bank
from repro.kernels.filter2d import (filter2d_pallas, filter_bank_pallas,
                                    stream_vmem_working_set)
from repro.kernels.filter2d.halo import LANE, SUBLANE


@pytest.mark.parametrize("strip_h", [8, 32, 128])
@pytest.mark.parametrize("H,W", [(70, 300), (129, 260), (64, 513)])
def test_stream_small_core_parity(strip_h, H, W, rng):
    """stream ≡ small ≡ core.filter2d: Ho not divisible by the strip,
    widths spanning 2–5 column tiles at tile_w=128."""
    x = jnp.asarray(rng.standard_normal((H, W)).astype(np.float32))
    k = jnp.asarray(filters.gaussian(5))
    ref = filter2d(x, k, border=BorderSpec("mirror"))
    small = filter2d_pallas(x, k, regime="small")
    stream = filter2d_pallas(x, k, regime="stream", strip_h=strip_h,
                             tile_w=128)
    np.testing.assert_allclose(np.asarray(small), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(stream), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("policy", ["mirror", "mirror_dup", "duplicate",
                                    "constant", "neglect", "wrap"])
@pytest.mark.parametrize("form", ["direct", "transposed", "tree",
                                  "compress"])
def test_tiled_halo_every_policy_form(policy, form, rng):
    """Tile-local halo remap is policy-correct at interior AND frame-edge
    tile boundaries (W=300 -> 3 tiles of 128)."""
    x = jnp.asarray(rng.standard_normal((40, 300)).astype(np.float32))
    k = jnp.asarray(filters.log_filter(7))
    ref = filter2d(x, k, form=form, border=BorderSpec(policy))
    got = filter2d_pallas(x, k, form=form, border=BorderSpec(policy),
                          regime="stream", strip_h=16, tile_w=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_8k_frame_bounded_vmem_working_set(rng):
    """The tentpole claim: a [2160, 7680] (8K) frame filters correctly
    while the per-step VMEM working set stays a function of
    (strip_h, tile_w, w) ONLY — asserted, not benched."""
    H, W = 2160, 7680
    strip_h, tile_w, w = 128, 512, 5
    x = rng.standard_normal((H, W)).astype(np.float32)
    k = filters.gaussian(w)
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k), regime="stream",
                          strip_h=strip_h, tile_w=tile_w)
    # low-memory numpy oracle: shift-and-accumulate over the padded frame
    r = w // 2
    xp = np.pad(x, r, mode="reflect")
    want = np.zeros((H, W), np.float32)
    for i in range(w):
        for j in range(w):
            want += xp[i:i + H, j:j + W] * k[i, j]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)

    # working set: frame-size independent by construction (no frame args),
    # and bounded by strip_h × tile_w times the per-pixel terms.
    ws = stream_vmem_working_set(strip_h, tile_w, w)
    dtype_bytes = 4
    # the halo window (one aligned tile of margin a side) at storage and
    # at accumulator width, one shifted slice per tap over the window's
    # rows, the output tile + accumulator, and the coefficient file.
    win_h, win_w = strip_h + 2 * SUBLANE, tile_w + 2 * LANE
    bound = ((2 * win_h * win_w + w * w * win_h * tile_w
              + 2 * strip_h * tile_w + w * w) * dtype_bytes)
    assert ws <= bound, (ws, bound)
    assert ws < 16 * 2 ** 20             # fits one core's VMEM
    # the SAME budget serves a frame 256x smaller: no frame term anywhere
    small = jnp.asarray(x[:270, :960])
    got_small = filter2d_pallas(small, jnp.asarray(k), regime="stream",
                                strip_h=strip_h, tile_w=tile_w)
    np.testing.assert_allclose(np.asarray(got_small),
                               np.asarray(filter2d(small, jnp.asarray(k))),
                               rtol=2e-4, atol=2e-4)


def test_batched_channels_fold_into_grid(rng):
    """[B,H,W,C] rides the kernel grid (no outer vmap) and matches core."""
    x = jnp.asarray(rng.standard_normal((2, 45, 200, 3)).astype(np.float32))
    k = jnp.asarray(filters.gaussian(3))
    ref = filter2d(x, k, border=BorderSpec("mirror"))
    for regime in ("small", "stream"):
        got = filter2d_pallas(x, k, regime=regime, strip_h=16, tile_w=128)
        assert got.shape == x.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("policy", ["mirror", "mirror_dup", "duplicate",
                                    "constant", "wrap"])
def test_filter_bank_pallas_equals_per_filter_loop(policy, rng):
    """The grid-folded bank == N separate filter2d_pallas calls == core
    filter_bank, for every same-size policy the Pallas path supports."""
    x = jnp.asarray(rng.standard_normal((40, 260)).astype(np.float32))
    bank = jnp.stack([jnp.asarray(filters.gaussian(5)),
                      jnp.asarray(filters.box(5)),
                      jnp.asarray(filters.identity(5))])
    got = filter_bank_pallas(x, bank, border=BorderSpec(policy),
                             strip_h=16, tile_w=128)
    assert got.shape == (40, 260, 3)
    core = filter_bank(x, bank, border=BorderSpec(policy))
    np.testing.assert_allclose(np.asarray(got), np.asarray(core),
                               rtol=3e-4, atol=3e-4)
    for i in range(bank.shape[0]):
        want = filter2d_pallas(x, bank[i], border=BorderSpec(policy),
                               strip_h=16, tile_w=128)
        np.testing.assert_allclose(np.asarray(got[..., i]),
                                   np.asarray(want), rtol=3e-4, atol=3e-4)


def test_bank_on_batched_frames(rng):
    """Bank × batch × channel all fold into one grid launch."""
    x = jnp.asarray(rng.standard_normal((2, 24, 140, 2)).astype(np.float32))
    bank = jnp.stack([jnp.asarray(filters.gaussian(3)),
                      jnp.asarray(filters.identity(3))])
    got = filter_bank_pallas(x, bank, strip_h=8, tile_w=128)
    assert got.shape == (2, 24, 140, 2, 2)
    np.testing.assert_allclose(np.asarray(got[..., 1]), np.asarray(x),
                               rtol=2e-5, atol=2e-5)   # identity slot
