"""The in-kernel halo engine: plan geometry, full policy × form parity vs
the numpy.pad oracle (wrap and non-zero constants included), frames smaller
than one strip/tile, the bank fast path, and the read-once-from-HBM claim
(no pre-materialized halo layout anywhere in the traced graph)."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.core import filters
from repro.core.border_spec import BorderSpec, np_pad_mode
from repro.core.filter2d import filter_bank
from repro.kernels.filter2d import (filter2d_pallas, filter_bank_pallas,
                                    make_plan, read_amplification)
from repro.kernels.filter2d.halo import LANE, SUBLANE, _axis_plan
from repro.kernels.filter2d.ops import _filter2d_pallas_planes

TOL = dict(rtol=3e-4, atol=3e-4)


def np_filter(x, k, policy, c=0.0):
    """Low-memory numpy oracle: shift-and-accumulate over the padded frame."""
    w = k.shape[-1]
    r = (w - 1) // 2
    mode = np_pad_mode(policy)
    if mode is None:
        xp, (H, W) = x, (x.shape[0] - 2 * r, x.shape[1] - 2 * r)
    else:
        kw = {"constant_values": c} if mode == "constant" else {}
        xp = np.pad(x, r, mode=mode, **kw)
        H, W = x.shape
    out = np.zeros((H, W), np.float32)
    for i in range(w):
        for j in range(w):
            out += xp[i:i + H, j:j + W] * k[i, j]
    return out


# -- static plan geometry ----------------------------------------------------


@pytest.mark.parametrize("same_size", [True, False])
@pytest.mark.parametrize("L,B,r", [
    (70, 16, 2), (70, 8, 3), (65, 32, 3), (64, 64, 2), (9, 9, 3),
    (513, 128, 2), (300, 128, 3), (128, 128, 1), (41, 40, 2), (2160, 128, 3),
])
def test_axis_plan_serves_every_valid_output(L, B, r, same_size):
    """Property: for every block, every un-cropped output's 2r+1-tap window
    resolves to a scratch slot that is either DMA'd in-frame data or a
    head/tail halo slot the mux fills — and every DMA moves whole sublane
    tiles between tile-aligned offsets (what Mosaic accepts)."""
    if not same_size and L <= 2 * r:
        pytest.skip("no valid neglect output")
    al = SUBLANE
    ax = _axis_plan(L, B, r, same_size, al)
    out_extent = L if same_size else L - 2 * r
    by_idx = {c.index: c for c in ax.specials}
    for i in range(ax.n):
        a = i * B - ax.lead               # scratch slot 0 ≡ frame element a
        assert a % al == 0 and ax.window % al == 0
        c = by_idx.get(i)
        if c is None:                     # interior: a full window in-span
            assert a >= 0 and a + ax.window <= ax.span
            continue
        assert c.src0 % al == 0 and c.dst0 % al == 0 and c.size % al == 0
        assert c.dst0 == c.src0 - a and c.src0 + c.size <= ax.span
        lo, hi = c.dst0 - c.head, c.fend + c.tail
        for o in range(min(B, out_extent - i * B)):   # valid outputs only
            first = ax.shift + o
            assert lo <= first and first + 2 * r < hi, (i, o, c)
            assert first + 2 * r < ax.window
        # head/tail slots map to frame elements just outside the frame,
        # and the in-frame slots between them were DMA'd
        assert c.head <= r and c.tail <= r
        assert c.fend == L - a and max(lo, c.dst0) >= c.dst0
        if c.head:
            assert c.src0 == 0            # head implies the top/left edge
        if c.tail:
            assert c.fend <= c.dst0 + c.size   # frame end was DMA'd


def test_read_amplification_is_about_one():
    """Cost analysis of the read-once claim: HBM elements DMA'd per frame
    stay within the aligned strip/tile overlap of 1× for every policy —
    each window over-fetches whole tiles, round_up(r, 8) rows and
    round_up(r, 128) columns a side, never more."""
    for pol in ("mirror", "constant", "wrap", "neglect"):
        for H, W, S, T, w in [(2160, 7680, 128, 512, 5), (70, 300, 16, 128, 7),
                              (480, 640, 128, 640, 3)]:
            plan = make_plan(H, W, w, BorderSpec(pol), S,
                             T + (-T) % 128)
            amp = read_amplification(plan)
            r = (w - 1) // 2
            off = 0 if pol == "neglect" else r

            def axis(L, B, al):
                # n aligned windows (the tile-rounded lead a side), plus
                # wrap's two opposite-edge bands (≤ lead + one tile each)
                n = -(-(L - 2 * r + 2 * off) // B)
                lead = -(-off // al) * al
                win = -(-(lead - off + B + 2 * r) // al) * al
                band = 2 * (lead + al) if pol == "wrap" else 0
                return (n * win + band) / L

            bound = axis(H, S, SUBLANE) * axis(W, T, LANE) + 0.1
            assert 0.9 <= amp <= bound, (pol, H, W, amp, bound)


def test_stream_is_read_once_no_prematerialized_layout():
    """The tentpole deletion, asserted structurally: the kernel's frame
    operand is exactly the un-tiled [M, H, W] planes, zero-padded to whole
    (8, 128) tiles (≈1× frame bytes), and NO intermediate in the traced
    graph exceeds ~1.4× the frame — the old row-extended, halo-duplicated
    staging layout (≥2.5× for this geometry) cannot hide anywhere."""
    M, H, W = 1, 128, 300
    planes = jax.ShapeDtypeStruct((M, H, W), jnp.float32)
    coeffs = jax.ShapeDtypeStruct((1, 5, 5), jnp.float32)
    frame_elems = M * H * W
    span_elems = M * H * (W + (-W) % LANE)
    for pol in ("mirror", "wrap", "constant"):
        fn = functools.partial(
            _filter2d_pallas_planes, form="direct", border=BorderSpec(pol),
            regime="stream", strip_h=64, tile_w=128, interpret=True)
        jaxpr = jax.make_jaxpr(fn)(planes, coeffs)

        # the shared analysis walker replaces the old hand-rolled
        # recursion (ref-level ops inside the kernel are block-shaped,
        # so pallas bodies stay excluded — iter_eqns' default)
        calls = analysis.pallas_calls(jaxpr)
        kernel_in = [int(np.prod(v.aval.shape))
                     for call in calls for v in call.invars]
        sizes = [int(np.prod(v.aval.shape))
                 for eqn in analysis.iter_eqns(jaxpr)
                 if eqn.primitive.name != "pallas_call"
                 for v in eqn.outvars if v.aval.shape]
        assert kernel_in, "no pallas_call in the traced graph"
        # the kernel reads the tile-padded planes + the w² coefficients
        assert max(kernel_in) == span_elems, (pol, kernel_in)
        # nothing frame-shaped is staged beyond lane/strip padding
        assert max(sizes) <= 1.4 * frame_elems, (pol, max(sizes))


# -- parity vs the numpy oracle ---------------------------------------------


@pytest.mark.parametrize("c", [-1.0, 0.5, 255.0])
@pytest.mark.parametrize("H,W,strip,tile", [
    (40, 300, 8, 128), (40, 300, 32, 256), (12, 40, 8, 128),
])
def test_constant_border_nonzero_values(c, H, W, strip, tile, rng):
    """constant(c) for c != 0 runs natively in-kernel (no core fallback),
    for multi-tile and smaller-than-one-tile frames alike."""
    x = rng.standard_normal((H, W)).astype(np.float32)
    k = np.asarray(filters.gaussian(5))
    want = np_filter(x, k, "constant", c)
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("constant", c),
                          regime="stream", strip_h=strip, tile_w=tile)
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


@pytest.mark.parametrize("form", ["direct", "transposed", "tree", "compress"])
@pytest.mark.parametrize("strip,tile", [(8, 128), (32, 256)])
def test_wrap_parity_every_form(form, strip, tile, rng):
    """wrap (opposite-edge rows AND columns, plus torus corners) vs the
    numpy oracle across strip/tile splits — the last policy that used to
    bail out to core.filter2d."""
    x = rng.standard_normal((40, 300)).astype(np.float32)
    k = np.asarray(filters.log_filter(5))
    want = np_filter(x, k, "wrap")
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k), form=form,
                          border=BorderSpec("wrap"), regime="stream",
                          strip_h=strip, tile_w=tile)
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


@pytest.mark.parametrize("policy", ["mirror", "mirror_dup", "duplicate",
                                    "constant", "wrap", "neglect"])
@pytest.mark.parametrize("H,W", [(10, 50), (9, 17)])
def test_frames_smaller_than_one_tile(policy, H, W, rng):
    """Frames smaller than one strip AND one lane tile collapse to a
    single-block plan where the first and last edge classes coincide."""
    x = rng.standard_normal((H, W)).astype(np.float32)
    k = np.asarray(filters.gaussian(5))
    want = np_filter(x, k, policy, 1.25)
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec(policy, 1.25), regime="stream",
                          strip_h=128, tile_w=512)
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


@pytest.mark.parametrize("policy,c", [("wrap", 0.0), ("constant", -2.0),
                                      ("zero", 0.0)])
def test_bank_under_wrap_and_constant(policy, c, rng):
    """The grid-folded bank path shares the halo engine: one scratch fill
    serves all N filters under every policy (including the two that used
    to fall back)."""
    x = jnp.asarray(rng.standard_normal((40, 260)).astype(np.float32))
    bank = jnp.stack([jnp.asarray(filters.gaussian(5)),
                      jnp.asarray(filters.box(5)),
                      jnp.asarray(filters.identity(5))])
    spec = BorderSpec(policy, c)
    got = filter_bank_pallas(x, bank, border=spec, strip_h=16, tile_w=128)
    want = filter_bank(x, bank, border=spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    if spec.policy == "constant":         # identity slot sees the frame
        np.testing.assert_allclose(np.asarray(got[..., 2]), np.asarray(x),
                                   rtol=2e-5, atol=2e-5)


def test_batched_planes_wrap(rng):
    """[B,H,W,C] planes ride the grid; wrap prologue DMAs are per-plane."""
    x = rng.standard_normal((2, 30, 150, 2)).astype(np.float32)
    k = np.asarray(filters.gaussian(3))
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("wrap"), regime="stream",
                          strip_h=8, tile_w=128)
    for b in range(2):
        for ch in range(2):
            want = np_filter(x[b, :, :, ch], k, "wrap")
            np.testing.assert_allclose(np.asarray(got[b, :, :, ch]), want,
                                       **TOL)


def test_separable_fast_path_shares_engine(rng):
    """The fused 2w-MAC separable kernel consumes the same halo scratch."""
    x = rng.standard_normal((40, 200)).astype(np.float32)
    k = np.asarray(filters.gaussian(5))
    want = np_filter(x, k, "wrap")
    got = filter2d_pallas(jnp.asarray(x), jnp.asarray(k),
                          border=BorderSpec("wrap"), separable=True,
                          regime="stream", strip_h=16, tile_w=128)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)
