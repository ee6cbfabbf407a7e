"""Mosaic compile checks for a described TPU v5e: no chip needed.

Tier-1 exercises every kernel in ``interpret=True`` (bit-accurate Python
execution); what it cannot catch is a kernel that *interprets* fine but
the chip's compiler refuses — an unsupported op, a DMA slice that is not
tile-aligned, more VMEM than a kernel may use. The filter2d cases here
compile for real against a described ``v5e:2x2`` topology (the TPU
compiler is installed; nothing runs), so those refusals surface in tier-1
instead of on the chip. ``jax.export`` stops before Mosaic's checks, so
only the leftover dwconv1d/swattn kernels still use it.

The topology is described inside a module-scoped fixture — never at
import — and the persistent compilation cache is off around these
compiles (an entry written here cannot be read back without a chip).
What this does NOT prove: results or times; those need the chip
(``chip_smoke.py``).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import export as jax_export
from jax.sharding import SingleDeviceSharding

from repro.core.border_spec import BorderSpec
from repro.core.requant import ROUNDING_MODES, RequantSpec
from repro.kernels.dwconv1d import dwconv1d_pallas
from repro.kernels.filter2d import filter2d_pallas, filter_bank_pallas
from repro.kernels.swattn import swattn_pallas


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off while this module compiles for it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _on(chip, args):
    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
            for a in args]


def _assert_compiles(chip, fn, *args):
    """Compile for the described chip; the Mosaic kernel must be in it."""
    try:
        compiled = jax.jit(fn).lower(*_on(chip, args)).compile()
    except Exception as e:  # noqa: BLE001 - any failure = compile break
        pytest.fail(f"Mosaic compile failed: {type(e).__name__}: "
                    f"{str(e)[:2000]}")
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


def _assert_lowers(fn, *args):
    """Export for TPU (lowering only) and check the kernel made it in."""
    try:
        exp = jax_export.export(jax.jit(fn), platforms=("tpu",))(*args)
    except Exception as e:  # noqa: BLE001 - any failure = lowering break
        pytest.fail(f"Mosaic lowering failed: {type(e).__name__}: {e}")
    assert "tpu_custom_call" in exp.mlir_module()


FRAME = _sds((128, 256), jnp.float32)
K5 = _sds((5, 5), jnp.float32)


@pytest.mark.parametrize("form,policy", [
    ("direct", "mirror"), ("transposed", "duplicate"), ("tree", "constant"),
    ("compress", "neglect"), ("direct", "wrap"), ("direct", "mirror_dup"),
])
def test_filter2d_float_lowers(form, policy, one_chip):
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, form=form,
                          border=BorderSpec(policy, 2.0), regime="stream",
                          strip_h=64, tile_w=128, interpret=False),
        FRAME, K5)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.uint8, jnp.int16])
@pytest.mark.parametrize("policy", ["mirror", "wrap", "constant"])
def test_filter2d_fixed_point_lowers(dtype, policy, one_chip):
    """The fixed-point datapath: int storage scratch, int32 accumulate."""
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec(policy, 3.0),
                          regime="stream", strip_h=64, tile_w=128,
                          interpret=False),
        _sds((128, 256), dtype), _sds((5, 5), jnp.int32))


@pytest.mark.parametrize("rounding", ROUNDING_MODES)
@pytest.mark.parametrize("dtype,out", [(jnp.int8, "int8"),
                                       (jnp.uint8, "uint8"),
                                       (jnp.int16, "int16")])
def test_filter2d_requant_lowers(dtype, out, rounding, one_chip):
    """The fused requantising epilogue: int32 MAC, scale→round→saturate,
    *storage-dtype* output BlockSpec — the shift/mask ops and the narrow
    store must all make it through Mosaic."""
    rq = RequantSpec(multiplier=3, shift=7, rounding=rounding, dtype=out)
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec("mirror"),
                          regime="stream", strip_h=64, tile_w=128,
                          requant=rq, interpret=False),
        _sds((128, 256), dtype), _sds((5, 5), jnp.int32))


def test_filter_bank_requant_per_filter_lowers(one_chip):
    """Per-filter (multiplier, shift) scalers ride the kernel's params
    operand; every bank lane stores at storage width."""
    rq = RequantSpec(multiplier=(1, -2, 3), shift=(4, 5, 6),
                     rounding="nearest_even", dtype="int8")
    _assert_compiles(
        one_chip,
        functools.partial(filter_bank_pallas, border=BorderSpec("wrap"),
                          regime="stream", strip_h=64, tile_w=128,
                          requant=rq, interpret=False),
        _sds((128, 256), jnp.int8), _sds((3, 5, 5), jnp.int32))


def test_filter2d_separable_requant_lowers(one_chip):
    rq = RequantSpec(multiplier=1, shift=4, rounding="nearest", dtype="int8")
    u = np.array([1, 2, 1], np.int32)
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec("duplicate"),
                          separable=(u, u), regime="stream", strip_h=64,
                          tile_w=128, requant=rq, interpret=False),
        _sds((128, 256), jnp.int8), _sds((3, 3), jnp.int32))


def test_filter2d_separable_lowers(one_chip):
    u = np.array([0.25, 0.5, 0.25], np.float32)
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec("mirror"),
                          separable=(u, u), regime="stream", strip_h=64,
                          tile_w=128, interpret=False),
        FRAME, _sds((3, 3), jnp.float32))


def test_filter2d_separable_fixed_point_lowers(one_chip):
    u = np.array([1, 2, 1], np.int32)
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec("mirror"),
                          separable=(u, u), regime="stream", strip_h=64,
                          tile_w=128, interpret=False),
        _sds((128, 256), jnp.int8), _sds((3, 3), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_filter_bank_lowers(dtype, one_chip):
    cdtype = jnp.int32 if dtype == jnp.int8 else jnp.float32
    _assert_compiles(
        one_chip,
        functools.partial(filter_bank_pallas, border=BorderSpec("wrap"),
                          regime="stream", strip_h=64, tile_w=128,
                          interpret=False),
        _sds((128, 256), dtype), _sds((3, 5, 5), cdtype))


# -- double-buffered (overlap) vs serial lanes -------------------------------
# strip_h=64 on a 128-row frame = 2 strips: the overlap kernel prefetches
# strip 1's window (wrap prologue DMAs included) into the second scratch
# bank while reducing strip 0, and the async-store epilogue drains through
# the banked output buffer — the dynamic-bank DMA descriptors and per-bank
# semaphore arrays all have to make it through Mosaic. ``overlap=False``
# keeps the serial reference kernel lowering too.


@pytest.mark.parametrize("overlap", [True, False])
def test_filter2d_float_overlap_and_serial_lower(overlap, one_chip):
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec("wrap"),
                          regime="stream", strip_h=64, tile_w=128,
                          overlap=overlap, interpret=False),
        FRAME, K5)


@pytest.mark.parametrize("overlap", [True, False])
def test_filter2d_int8_overlap_and_serial_lower(overlap, one_chip):
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec("mirror"),
                          regime="stream", strip_h=64, tile_w=128,
                          overlap=overlap, interpret=False),
        _sds((128, 256), jnp.int8), _sds((5, 5), jnp.int32))


@pytest.mark.parametrize("overlap", [True, False])
def test_filter2d_requant_overlap_and_serial_lower(overlap, one_chip):
    """The async store carries the *narrow* requantised tile: the banked
    int8 output buffer and its late-waited copies must lower."""
    rq = RequantSpec(multiplier=3, shift=7, rounding="nearest_even",
                     dtype="int8")
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec("constant", 3.0),
                          regime="stream", strip_h=64, tile_w=128,
                          requant=rq, overlap=overlap, interpret=False),
        _sds((128, 256), jnp.int8), _sds((5, 5), jnp.int32))


@pytest.mark.parametrize("overlap", [True, False])
def test_filter_bank_overlap_and_serial_lower(overlap, one_chip):
    """N=3 bank: T = strips × N store steps through the two output banks."""
    _assert_compiles(
        one_chip,
        functools.partial(filter_bank_pallas, border=BorderSpec("wrap"),
                          regime="stream", strip_h=64, tile_w=128,
                          overlap=overlap, interpret=False),
        _sds((128, 256), jnp.float32), _sds((3, 5, 5), jnp.float32))


def test_filter2d_strips_innermost_overlap_lowers(one_chip):
    """The alternate grid order (strips innermost, unconditional refill)
    drives the same banked machinery through Mosaic."""
    from repro.kernels.filter2d import ops

    _assert_compiles(
        one_chip,
        functools.partial(ops._filter2d_pallas_planes, form="direct",
                          border=BorderSpec("wrap"), regime="stream",
                          strip_h=64, tile_w=128, interpret=False,
                          overlap=True, grid_order="strips_innermost"),
        _sds((1, 128, 256), jnp.float32), _sds((3, 5, 5), jnp.float32))


def test_filter2d_small_regime_lowers(one_chip):
    _assert_compiles(
        one_chip,
        functools.partial(filter2d_pallas, border=BorderSpec("mirror"),
                          regime="small", interpret=False),
        FRAME, K5)


# -- the plan-and-execute front door -----------------------------------------
# CompiledFilter._fn is the one jitted executable a served pipeline calls;
# these lanes prove the float, fixed-point and requantised-int pipelines all
# make it through Mosaic (compiled for the described chip, as above).


def _pipeline_lowers(chip, spec, frame_dtype, coeff_sds, with_gains=False):
    cf = spec.compile(jax.ShapeDtypeStruct((128, 256), frame_dtype),
                      "pallas", strip_h=64, tile_w=128, interpret=False)
    args = [_sds((128, 256), frame_dtype), coeff_sds]
    if with_gains:
        args.append(_sds((spec.num_filters, 2), jnp.int32))
    return _assert_compiles(chip, cf._fn, *args)


def test_compiled_filter_float_lowers(one_chip):
    from repro.core.pipeline import Filter2D
    _pipeline_lowers(one_chip, Filter2D(window=5), jnp.float32,
                     _sds((5, 5), jnp.float32))


def test_compiled_filter_fixed_point_lowers(one_chip):
    from repro.core.border_spec import BorderSpec as BS
    from repro.core.pipeline import Filter2D
    _pipeline_lowers(one_chip,
                     Filter2D(window=5, border=BS("wrap"), dtype="int8"),
                     jnp.int8, _sds((5, 5), jnp.int32))


def test_compiled_filter_requant_lowers(one_chip):
    """The served requantised pipeline: traced [N, 2] gains operand, fused
    scale-round-saturate epilogue, int8 store — through Mosaic."""
    from repro.core.pipeline import Filter2D
    rq = RequantSpec(multiplier=3, shift=7, rounding="nearest_even",
                     dtype="int8")
    _pipeline_lowers(one_chip, Filter2D(window=5, dtype="int8", requant=rq),
                     jnp.int8, _sds((5, 5), jnp.int32), with_gains=True)


def test_compiled_filter_bank_requant_lowers(one_chip):
    from repro.core.pipeline import Filter2D
    rq = RequantSpec(multiplier=(1, -2, 3), shift=(4, 5, 6), dtype="int8")
    _pipeline_lowers(
        one_chip,
        Filter2D(window=5, num_filters=3, dtype="int8", requant=rq),
        jnp.int8, _sds((3, 5, 5), jnp.int32), with_gains=True)


# -- real frame sizes --------------------------------------------------------
# The geometry the planner derives for the paper's frames, compiled as the
# chip will: one 1080p uint8 frame and a batch-4 served wave on the paper
# stream (7x7, mirror, requant to uint8), the 2160p stream both
# double-buffered and serial, the 1080p float32 stream of
# configs/spatial_filter_hd.py, and a 3-filter bank.

_U8_RQ = RequantSpec(multiplier=1, shift=8, dtype="uint8")
_BANK_RQ = RequantSpec(multiplier=(1, 1, 1), shift=(8, 8, 8), dtype="uint8")


@pytest.mark.parametrize("case,shape,knobs", [
    ("paper_1080p_u8", (1080, 1920), {}),
    ("served_wave_b4", (4, 1080, 1920, 1), {}),
    ("stream_2160p_u8", (2160, 3840), {"regime": "stream"}),
    ("stream_2160p_u8_serial", (2160, 3840),
     {"regime": "stream", "overlap": False}),
    ("hd_1080p_f32", (1080, 1920), {"regime": "stream"}),
    ("bank3_1080p_u8", (1080, 1920), {}),
])
def test_real_size_pipeline_compiles(case, shape, knobs, one_chip):
    from repro.core.pipeline import Filter2D
    if case == "hd_1080p_f32":
        spec = Filter2D(window=7)
        coeffs = _sds((7, 7), jnp.float32)
    elif case == "bank3_1080p_u8":
        spec = Filter2D(window=7, num_filters=3, dtype="uint8",
                        requant=_BANK_RQ.gain_free())
        coeffs = _sds((3, 7, 7), jnp.int32)
    else:
        spec = Filter2D(window=7, dtype="uint8", requant=_U8_RQ.gain_free())
        coeffs = _sds((7, 7), jnp.int32)
    cf = spec.compile(shape, "pallas", interpret=False, **knobs)
    assert cf.vmem_working_set() <= cf.vmem_budget
    if knobs.get("regime") == "stream" and case.startswith("stream"):
        assert cf.plan.rows.n > 1          # a real multi-strip stream
    args = [_sds(shape, jnp.dtype(spec.dtype)), coeffs]
    if spec.requant is not None:
        args.append(_sds((spec.num_filters, 2), jnp.int32))
    _assert_compiles(one_chip, cf._fn, *args)


def test_dwconv1d_lowers():
    _assert_lowers(
        functools.partial(dwconv1d_pallas, chunk=64, interpret=False),
        _sds((2, 128, 8), jnp.float32), _sds((8, 4), jnp.float32),
        _sds((8,), jnp.float32))


def test_swattn_lowers():
    _assert_lowers(
        functools.partial(swattn_pallas, window=64, blk=64,
                          interpret=False),
        _sds((1, 256, 4, 64), jnp.float32), _sds((1, 256, 2, 64),
                                                 jnp.float32),
        _sds((1, 256, 2, 64), jnp.float32))


def test_compiled_filter_lowers_with_tracing_enabled(one_chip):
    """The obs satellite: with tracing ON, the pipeline still compiles —
    the named_scope / TraceAnnotation hooks are host-side or trace-time
    metadata, never ops the compiler can't take — and the compile is
    observable (exactly one compile event for the fresh geometry)."""
    from repro import obs
    from repro.core.pipeline import Filter2D
    obs.disable()
    try:
        obs.enable()
        # fresh strip_h: a compile-memo hit would emit no compile event
        spec = Filter2D(window=5)
        cf = spec.compile(jax.ShapeDtypeStruct((128, 256), jnp.float32),
                          "pallas", strip_h=32, tile_w=128,
                          interpret=False)
        assert len(obs.events.events(kind="compile")) == 1
        text = _assert_compiles(one_chip, cf._fn, FRAME, K5)
        # the named_scope annotation rode into the compiled module
        assert "repro.filter2d" in text
    finally:
        obs.disable()
        obs.REGISTRY.reset()
