"""The plan-and-execute front door (Filter2D -> CompiledFilter).

Acceptance pins of the API redesign:
  * executor parity, driven through CompiledFilter: every executor ×
    form × border policy × int8/float32 agrees with the core oracle
    (bit-exact on the fixed-point datapath);
  * cache stability: swapping coefficients, separable factors or requant
    gains on a compiled pipeline triggers ZERO recompiles (the jit
    cache-size counter), while changing form/border/dtype/execution
    compiles fresh;
  * 'auto' selection: sharded when a mesh is supplied, streaming when the
    frame-resident working set exceeds the vmem_budget, pixel-cache
    Pallas when it fits — and every auto-derived strip_h/tile_w keeps the
    static hbm_bytes_per_pixel accounting inside the bench gate.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import filters
from repro.core.border_spec import BorderSpec
from repro.core.filter2d import filter2d, filter_bank
from repro.core.pipeline import (DEFAULT_VMEM_BUDGET, EXECUTIONS,
                                 CompiledFilter, Filter2D)
from repro.core.requant import RequantSpec, requantize_ref
from repro.kernels.filter2d import halo
from repro.kernels.filter2d.halo import (plan_vmem_working_set,
                                         stream_vmem_working_set)

H, W = 32, 24
EXECUTORS = tuple(e for e in EXECUTIONS if e != "core")  # the five modes


def _frame(rng, dtype):
    if np.dtype(dtype).kind in ("i", "u"):
        return rng.integers(-20, 20, (H, W)).astype(dtype)
    return rng.standard_normal((H, W)).astype(dtype)


def _kernel(rng, dtype, w=5):
    if np.dtype(dtype).kind in ("i", "u"):
        return rng.integers(-4, 5, (w, w)).astype(np.int32)
    return filters.gaussian(w).astype(np.float32)


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def _knobs(execution):
    if execution == "sharded":
        return {"mesh": _mesh1()}
    return {"strip_h": 8, "tile_w": 128}


def _compile(spec, x, execution):
    return spec.compile(x, execution, **_knobs(execution))


# ---------------------------------------------------------------------------
# Executor parity vs the core oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("execution", EXECUTORS)
@pytest.mark.parametrize("form", ["direct", "transposed", "tree",
                                  "compress"])
@pytest.mark.parametrize("policy", ["mirror", "constant", "wrap"])
@pytest.mark.parametrize("dtype", [np.float32, np.int8])
def test_executor_parity(execution, form, policy, dtype, rng):
    """One spec, five executors, one oracle: every compiled pipeline
    agrees with core.filter2d (bit-exact on the int8 datapath; the XLA
    executor infers its own reduction structure, so float parity there is
    to tolerance like every other form pair)."""
    x = jnp.asarray(_frame(rng, dtype))
    k = jnp.asarray(_kernel(rng, dtype))
    border = BorderSpec(policy, 2.0)
    ref = filter2d(x, k, form=form, border=border)
    spec = Filter2D(window=5, form=form, border=border,
                    dtype=np.dtype(dtype).name)
    cf = _compile(spec, x, execution)
    got = cf(x, k)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    if np.dtype(dtype).kind in ("i", "u"):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("execution,dtype", [
    *(pytest.param(e, "int8", id=e) for e in EXECUTORS),
    # the band body of both row buffers: four strips, one shard
    *(pytest.param(e, "uint8", id=f"uint8-{e}")
      for e in ("streaming", "sharded"))])
def test_executor_parity_requant(execution, dtype, rng):
    """The requantising epilogue lands bit-identically on every executor
    (the pipeline applies it with traced gains; the oracle with static
    ones) — pinned against the numpy reference, not just the oracle."""
    x = _frame(rng, dtype)
    k = _kernel(rng, dtype)
    rq = RequantSpec.unity_gain(k, dtype)
    ref = filter2d(jnp.asarray(x), jnp.asarray(k), requant=rq)
    spec = Filter2D(window=5, dtype=dtype, requant=rq.gain_free())
    cf = _compile(spec, jnp.asarray(x), execution)
    got = cf(jnp.asarray(x), jnp.asarray(k), gains=rq)
    assert got.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # and the epilogue itself against the int64 numpy reference
    acc = filter2d(jnp.asarray(x), jnp.asarray(k))
    np.testing.assert_array_equal(np.asarray(got),
                                  requantize_ref(np.asarray(acc), rq))


def test_bank_and_separable_parity(rng):
    """Bank pipelines (num_filters=N) and separable pipelines ((u, v)
    factor operands) agree with their core oracles on both executors that
    support them."""
    x = jnp.asarray(_frame(rng, np.float32))
    bank = jnp.stack([jnp.asarray(filters.gaussian(5)),
                      jnp.asarray(filters.box(5)),
                      jnp.asarray(filters.identity(5))])
    ref = filter_bank(x, bank)
    bspec = Filter2D(window=5, num_filters=3)
    for execution in ("core", "pallas"):
        cf = _compile(bspec, x, execution)
        np.testing.assert_allclose(np.asarray(cf(x, bank)),
                                   np.asarray(ref), rtol=3e-4, atol=3e-4)
    u = np.array([0.25, 0.5, 0.25], np.float32)
    sref = filter2d(x, jnp.asarray(np.outer(u, u)))
    sspec = Filter2D(window=3, separable=True)
    for execution in ("core", "pallas"):
        cf = _compile(sspec, x, execution)
        np.testing.assert_allclose(np.asarray(cf(x, (u, u))),
                                   np.asarray(sref), rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# Cache stability: traced operands never recompile; spec changes do
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("execution", ["core", "pallas", "streaming"])
def test_coefficient_swap_zero_recompiles(execution, rng):
    x = jnp.asarray(_frame(rng, np.float32))
    spec = Filter2D(window=5)
    cf = _compile(spec, x, execution)
    a = cf(x, jnp.asarray(filters.gaussian(5)))
    assert cf.cache_size() == 1
    b = cf(x, jnp.asarray(filters.log_filter(5)))
    assert cf.cache_size() == 1, "coefficient swap must hit the jit cache"
    assert not np.allclose(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("execution", ["core", "pallas"])
def test_factor_swap_zero_recompiles(execution, rng):
    x = jnp.asarray(_frame(rng, np.float32))
    spec = Filter2D(window=3, separable=True)
    cf = _compile(spec, x, execution)
    g = np.array([0.25, 0.5, 0.25], np.float32)
    b = np.full(3, 1 / 3, np.float32)
    cf(x, (g, g))
    assert cf.cache_size() == 1
    cf(x, (b, b))
    assert cf.cache_size() == 1, "factor swap must hit the jit cache"


@pytest.mark.parametrize("execution", ["core", "pallas", "streaming"])
def test_gain_swap_zero_recompiles(execution, rng):
    """Per-call requant gains are runtime data like the coefficients: a
    new (multiplier, shift) pair reuses the executable and still lands
    bit-exactly on the numpy reference."""
    x = _frame(rng, np.int8)
    k = _kernel(rng, np.int8)
    rq_a = RequantSpec(multiplier=3, shift=7, rounding="nearest",
                       dtype="int8")
    rq_b = RequantSpec(multiplier=-5, shift=9, rounding="nearest",
                       dtype="int8")
    spec = Filter2D(window=5, dtype="int8", requant=rq_a.gain_free())
    cf = _compile(spec, jnp.asarray(x), execution)
    acc = np.asarray(filter2d(jnp.asarray(x), jnp.asarray(k)))
    got_a = cf(jnp.asarray(x), jnp.asarray(k), gains=rq_a)
    assert cf.cache_size() == 1
    got_b = cf(jnp.asarray(x), jnp.asarray(k), gains=rq_b)
    assert cf.cache_size() == 1, "gain swap must hit the jit cache"
    got_default = cf(jnp.asarray(x), jnp.asarray(k))     # spec's own gains
    assert cf.cache_size() == 1
    np.testing.assert_array_equal(np.asarray(got_a),
                                  requantize_ref(acc, rq_a))
    np.testing.assert_array_equal(np.asarray(got_b),
                                  requantize_ref(acc, rq_b))
    np.testing.assert_array_equal(np.asarray(got_default),
                                  requantize_ref(acc, rq_a.gain_free()))


def test_spec_changes_compile_fresh(rng):
    """form/border/dtype/execution are structure: each combination owns a
    fresh executable (and the compile cache hands back the SAME pipeline
    for the same combination — the wrappers rely on that)."""
    x = jnp.asarray(_frame(rng, np.float32))
    base = Filter2D(window=5)
    cf = base.compile(x, "pallas", strip_h=8, tile_w=128)
    assert base.compile(x, "pallas", strip_h=8, tile_w=128) is cf
    cf(x, jnp.asarray(filters.gaussian(5)))
    assert cf.cache_size() == 1
    variants = [
        base.compile(x, "core"),
        Filter2D(window=5, form="tree").compile(x, "pallas", strip_h=8,
                                                tile_w=128),
        Filter2D(window=5, border=BorderSpec("wrap")).compile(
            x, "pallas", strip_h=8, tile_w=128),
        Filter2D(window=5, dtype="int8").compile(
            jnp.asarray(_frame(rng, np.int8)), "pallas", strip_h=8,
            tile_w=128),
    ]
    for other in variants:
        assert other is not cf
        assert other.cache_size() == 0, "a spec change must start cold"
    assert cf.cache_size() == 1          # ...without disturbing the first


# ---------------------------------------------------------------------------
# execution='auto' selection + derived geometry accounting
# ---------------------------------------------------------------------------


def test_auto_selects_sharded_with_mesh(rng):
    spec = Filter2D(window=5)
    cf = spec.compile((4, 64, 40, 1), "auto", mesh=_mesh1())
    assert cf.execution == "sharded"


def test_auto_selects_streaming_over_budget():
    """The acceptance rule: when the frame-resident working set exceeds
    the vmem_budget, auto compiles the row-buffer streaming pipeline —
    with a budget-derived strip height the scan accepts."""
    spec = Filter2D(window=5)
    budget = 256 * 1024
    shape = (2048, 2048)
    resident = stream_vmem_working_set(2048, 2048, 5, 4)
    assert resident > budget
    cf = spec.compile(shape, "auto", vmem_budget=budget)
    assert cf.execution == "streaming"
    assert 2048 % cf.strip_h == 0 and cf.strip_h >= 4
    assert cf.resident_vmem_bytes == resident


def test_auto_selects_pixel_cache_within_budget():
    spec = Filter2D(window=5)
    cf = spec.compile((128, 256), "auto")
    assert cf.resident_vmem_bytes <= DEFAULT_VMEM_BUDGET
    assert cf.execution == "pallas" and cf.regime == "small"


def test_auto_falls_back_to_pallas_stream_for_banks():
    """Shapes the strip scan cannot take (banks, separable) stream through
    the Pallas row-buffer regime instead."""
    spec = Filter2D(window=5, num_filters=4)
    cf = spec.compile((2048, 2048), "auto", vmem_budget=256 * 1024)
    assert cf.execution == "pallas" and cf.regime == "stream"
    sspec = Filter2D(window=5, separable=True)
    cfs = sspec.compile((2048, 2048), "auto", vmem_budget=256 * 1024)
    assert cfs.execution == "pallas" and cfs.regime == "stream"


@pytest.mark.parametrize("budget", [256 * 1024, 2 ** 20,
                                    DEFAULT_VMEM_BUDGET])
def test_derived_geometry_keeps_bench_gate_budgets(budget):
    """Every auto-derived strip/tile choice keeps the static accounting
    inside its gates, for budgets spanning 32x: the working set fits the
    budget whenever the minimum plan does (a starved budget gets that
    minimum plan, one lane tile at the strip floor), read amplification
    never exceeds the aligned halo's floor bound, and at the default
    budget the int8->int8 round trip stays <= 2.7 bytes/pixel."""
    rq = RequantSpec(multiplier=3, shift=9, dtype="int8")
    spec = Filter2D(window=5, dtype="int8", requant=rq)
    cf = spec.compile((2160, 3840), "pallas", vmem_budget=budget)
    fspec = Filter2D(window=5)
    cff = fspec.compile((2160, 3840), "pallas", vmem_budget=budget)
    r = 2
    s_floor = halo.strip_floor(r)
    for pipe in (cf, cff):
        minimal = (pipe.strip_h, pipe.tile_w) == (s_floor, halo.LANE)
        assert pipe.vmem_working_set() <= budget or minimal
    # the planner's hard floor (strip >= 8, tile >= 128, each window a
    # whole tile wider a side) bounds the read amplification at
    # (1 + 2·8/8)(1 + 2·128/128) even for starved budgets
    amp_floor = (1 + 2 * halo.SUBLANE / s_floor) * (1 + 2 * halo.LANE
                                                     / halo.LANE)
    for pipe in (cf, cff):
        assert halo.read_amplification(pipe.plan) <= amp_floor
    assert cff.hbm_bytes_per_pixel() <= 4.0 * amp_floor + 4.0
    if budget >= DEFAULT_VMEM_BUDGET:   # a sane budget is also *lean*
        assert cf.vmem_working_set() <= budget
        assert halo.read_amplification(cff.plan) <= 1.7
        assert cf.hbm_bytes_per_pixel() <= 2.7


def test_derive_strip_tile_narrow_dtypes_deepen_strips():
    """int8 scratch and a requantised output tile free VMEM: at the
    geometry the planner derives, the narrow plan holds less than the
    float32 one, and the derived narrow geometry is never shallower or
    leakier (the widened window and tap slices sit at int32 for both, so
    the planner may land on the same strip)."""
    budget = 2 ** 20
    s_f32, t_f32 = halo.derive_strip_tile(2160, 3840, 5, dtype=np.float32,
                                          vmem_budget=budget)
    rq8 = RequantSpec(multiplier=1, shift=8, dtype="int8")
    s_i8, t_i8 = halo.derive_strip_tile(
        2160, 3840, 5, dtype=np.int8, vmem_budget=budget, requant=rq8)
    plans = {}
    for key, (s, t, dt, rq) in {
            "f32": (s_f32, t_f32, np.float32, None),
            "i8": (s_i8, t_i8, np.int8, rq8),
            "i8_at_f32": (s_f32, t_f32, np.int8, rq8)}.items():
        plans[key] = halo.make_plan(2160, 3840, 5, BorderSpec("mirror"), s,
                                    t, dtype=dt, requant=rq)
    amp = {k: halo.read_amplification(p) for k, p in plans.items()}
    assert amp["i8"] <= amp["f32"]
    assert s_i8 * t_i8 >= s_f32 * t_f32      # freed bytes buy pixels/step
    assert (plan_vmem_working_set(plans["i8_at_f32"])
            < plan_vmem_working_set(plans["f32"]))
    # and both stay inside the budget they were derived from
    for key in ("f32", "i8"):
        assert plan_vmem_working_set(plans[key]) <= budget


def test_auto_streaming_executes_correctly(rng):
    """The auto-compiled streaming pipeline doesn't just get selected —
    it runs, and matches the oracle."""
    x = jnp.asarray(rng.standard_normal((64, 48)).astype(np.float32))
    k = jnp.asarray(filters.gaussian(5))
    budget = 24 * 1024                   # force the row-buffer decision
    spec = Filter2D(window=5)
    cf = spec.compile(x, "auto", vmem_budget=budget)
    assert cf.execution == "streaming"
    np.testing.assert_allclose(np.asarray(cf(x, k)),
                               np.asarray(filter2d(x, k)),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Spec/call validation
# ---------------------------------------------------------------------------


def test_spec_validation():
    with pytest.raises(ValueError, match="unknown form"):
        Filter2D(window=5, form="banana")
    with pytest.raises(ValueError, match="single-filter"):
        Filter2D(window=5, separable=True, num_filters=2)
    with pytest.raises(ValueError, match="storage contract"):
        Filter2D(window=5, dtype="int32")
    with pytest.raises(ValueError):      # requant needs a fixed-point dtype
        Filter2D(window=5, dtype="float32",
                 requant=RequantSpec(multiplier=1, shift=0))
    # policy strings normalise through BorderSpec
    assert Filter2D(window=3, border="zero").border == BorderSpec("zero")


def test_call_validation(rng):
    x = jnp.asarray(_frame(rng, np.float32))
    spec = Filter2D(window=5)
    cf = spec.compile(x, "core")
    with pytest.raises(ValueError, match="frame shape"):
        cf(jnp.zeros((8, 8), jnp.float32), jnp.asarray(filters.gaussian(5)))
    with pytest.raises(ValueError, match="coefficients of shape"):
        cf(x, jnp.asarray(filters.gaussian(3)))
    with pytest.raises(ValueError, match="no.*requant"):
        cf(x, jnp.asarray(filters.gaussian(5)), gains=(1, 0))
    with pytest.raises(ValueError, match="dtype"):
        spec.compile(jnp.zeros((4, 4), jnp.int8), "core")
    with pytest.raises(ValueError, match="needs a mesh"):
        spec.compile(x, "sharded")
    with pytest.raises(ValueError, match="single filters"):
        Filter2D(window=5, num_filters=2).compile(x, "xla")
    rq = RequantSpec(multiplier=1, shift=4, dtype="int8")
    cfi = Filter2D(window=5, dtype="int8", requant=rq).compile(
        (H, W), "core")
    with pytest.raises(ValueError, match="disagrees with the compiled"):
        cfi(jnp.zeros((H, W), jnp.int8), jnp.ones((5, 5), jnp.int32),
            gains=RequantSpec(multiplier=1, shift=4, dtype="int16"))


# ---------------------------------------------------------------------------
# The no-retrace contract holds with tracing ENABLED
# ---------------------------------------------------------------------------


def test_swaps_zero_recompiles_with_tracing_enabled(rng):
    """Observability must not perturb what it observes: with obs tracing
    on, coefficient / factor / gain swaps still pin cache_size() == 1,
    each pipeline emits exactly ONE compile event, and every post-warmup
    execute event reports a cache hit. (Fresh strip_h knobs throughout:
    the compile memo cache is process-wide, and a memo hit would
    legitimately emit no compile event.)"""
    from repro import obs
    obs.disable()
    obs.REGISTRY.reset()
    try:
        obs.enable()
        # coefficients
        x = jnp.asarray(_frame(rng, np.float32))
        cf = Filter2D(window=5).compile(x, "pallas", strip_h=16, tile_w=128)
        assert len(obs.events.events(kind="compile")) == 1
        cf(x, jnp.asarray(filters.gaussian(5)))
        assert cf.cache_size() == 1
        cf(x, jnp.asarray(filters.log_filter(5)))
        assert cf.cache_size() == 1, "coefficient swap retraced under obs"

        # separable factors
        sf = Filter2D(window=3, separable=True).compile(x, "pallas",
                                                        strip_h=16,
                                                        tile_w=128)
        assert len(obs.events.events(kind="compile")) == 2
        g = np.array([0.25, 0.5, 0.25], np.float32)
        sf(x, (g, g))
        sf(x, (np.full(3, 1 / 3, np.float32),) * 2)
        assert sf.cache_size() == 1, "factor swap retraced under obs"

        # requant gains
        xi = jnp.asarray(_frame(rng, np.int8))
        rq = RequantSpec(multiplier=3, shift=7, rounding="nearest",
                         dtype="int8")
        gf = Filter2D(window=5, dtype="int8",
                      requant=rq.gain_free()).compile(xi, "pallas",
                                                      strip_h=16,
                                                      tile_w=128)
        assert len(obs.events.events(kind="compile")) == 3
        ki = jnp.asarray(_kernel(rng, np.int8))
        gf(xi, ki, gains=rq)
        gf(xi, ki, gains=RequantSpec(multiplier=-5, shift=9,
                                     rounding="nearest", dtype="int8"))
        assert gf.cache_size() == 1, "gain swap retraced under obs"

        # still exactly one compile event per pipeline, and no execute
        # event after a pipeline's first reported a cache miss
        assert len(obs.events.events(kind="compile")) == 3
        seen = {}
        for e in obs.events.events(kind="execute"):
            if e.key in seen:
                assert e.cache_hit, f"{e.key}: swap call missed the cache"
                assert e.cache_size == 1
            seen[e.key] = e
    finally:
        obs.disable()
        obs.REGISTRY.reset()


# ---------------------------------------------------------------------------
# Gains kept on the device: one upload per distinct RequantSpec
# ---------------------------------------------------------------------------

GAIN_A = RequantSpec(multiplier=3, shift=7, rounding="nearest", dtype="int8")
GAIN_B = RequantSpec(multiplier=-5, shift=9, rounding="nearest", dtype="int8")
GAIN_OWN = RequantSpec(multiplier=7, shift=8, rounding="nearest",
                       dtype="int8")


def _fresh(spec, x, execution):
    """A pipeline built outside the compile memo, so its gains memo and
    counts start empty whatever other tests compiled."""
    return CompiledFilter(spec, tuple(x.shape), execution,
                          **_knobs(execution))


@pytest.mark.parametrize("execution", ("core",) + EXECUTORS)
def test_gain_memo_swap_sequence_is_bit_exact(execution, rng):
    """a, b, a, None, b: each distinct spec is uploaded once, a hit hands
    back the very array stored, the executable never grows, and every
    result equals both an unmemoized pipeline's (raw host tables) and
    the int64 reference."""
    x = jnp.asarray(_frame(rng, np.int8))
    k = jnp.asarray(_kernel(rng, np.int8))
    spec = Filter2D(window=5, dtype="int8", requant=GAIN_OWN)
    cf = _fresh(spec, x, execution)
    plain = _fresh(spec, x, execution)
    acc = np.asarray(filter2d(x, k))
    for gains in (GAIN_A, GAIN_B, GAIN_A, None, GAIN_B):
        rq = GAIN_OWN if gains is None else gains
        got = np.asarray(cf(x, k) if gains is None else cf(x, k, gains))
        assert cf.cache_size() == 1
        want = np.asarray(plain(x, k, np.asarray(rq.params(1), np.int32)))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, requantize_ref(acc, rq))
    assert cf.operand_stats() == {"gain_hits": 2, "gain_uploads": 3}
    assert plain.operand_stats() == {"gain_hits": 0, "gain_uploads": 0}
    first = cf._operands(x, k, GAIN_A)[2]
    assert cf._operands(x, k, GAIN_A)[2] is first
    assert cf._operands(x, k, None)[2] is cf._operands(x, k, GAIN_OWN)[2]
    assert cf.operand_stats()["gain_uploads"] == 3
    assert cf.cache_size() == 1


@pytest.mark.parametrize("bad", [
    RequantSpec(multiplier=3, shift=7, rounding="truncate", dtype="int8"),
    RequantSpec(multiplier=3, shift=7, rounding="nearest", dtype="int16"),
], ids=["rounding", "dtype"])
def test_gain_memo_never_stores_a_disagreeing_spec(bad, rng):
    """A spec whose rounding or storage dtype disagrees with the compiled
    epilogue raises on every call: the first failure stores nothing."""
    x = jnp.asarray(_frame(rng, np.int8))
    k = jnp.asarray(_kernel(rng, np.int8))
    cf = _fresh(Filter2D(window=5, dtype="int8", requant=GAIN_OWN), x,
                "core")
    for _ in range(2):
        with pytest.raises(ValueError, match="disagrees with the compiled"):
            cf(x, k, gains=bad)
    assert cf.operand_stats() == {"gain_hits": 0, "gain_uploads": 0}
    cf(x, k, gains=GAIN_A)
    assert cf.operand_stats() == {"gain_hits": 0, "gain_uploads": 1}


def test_gain_memo_evicts_the_least_recently_used(rng):
    """Past the fixed bound the least recently used spec is dropped, and
    uploaded again when it comes back; a recently used one survives."""
    from repro.core.pipeline import GAIN_MEMO_SIZE
    x = jnp.asarray(_frame(rng, np.int8))
    k = jnp.asarray(_kernel(rng, np.int8))
    cf = _fresh(Filter2D(window=5, dtype="int8", requant=GAIN_OWN), x,
                "core")
    specs = [RequantSpec(multiplier=m + 1, shift=7, rounding="nearest",
                         dtype="int8") for m in range(GAIN_MEMO_SIZE + 1)]
    for s in specs[:GAIN_MEMO_SIZE]:
        cf._operands(x, k, s)
    assert cf.operand_stats() == {"gain_hits": 0,
                                  "gain_uploads": GAIN_MEMO_SIZE}
    kept = cf._operands(x, k, specs[0])[2]         # now the most recent
    cf._operands(x, k, specs[-1])                  # evicts specs[1]
    assert cf.operand_stats() == {"gain_hits": 1,
                                  "gain_uploads": GAIN_MEMO_SIZE + 1}
    assert cf._operands(x, k, specs[0])[2] is kept
    cf._operands(x, k, specs[1])
    assert cf.operand_stats() == {"gain_hits": 2,
                                  "gain_uploads": GAIN_MEMO_SIZE + 2}
    np.testing.assert_array_equal(
        np.asarray(cf(x, k, specs[1])),
        requantize_ref(np.asarray(filter2d(x, k)), specs[1]))


def test_gain_memo_is_safe_under_concurrent_callers(rng):
    """More threads than cores share one pipeline, switching often: no
    call is lost from the counts, each spec is uploaded once while all
    fit, the memo never passes its bound, and every operand holds its
    own spec's gains."""
    import os
    import sys
    import threading
    from repro.core.pipeline import GAIN_MEMO_SIZE
    x = jnp.asarray(_frame(rng, np.int8))
    cf = _fresh(Filter2D(window=5, dtype="int8", requant=GAIN_OWN), x,
                "core")
    threads_n = (os.cpu_count() or 4) + 4
    calls = 200
    wrong = []

    def hammer(specs, seed):
        order = np.random.default_rng(seed).integers(0, len(specs), calls)
        for i in order:
            g = cf._gain_operand(specs[i])
            if np.asarray(g).tolist() != [list(specs[i].params(1)[0])]:
                wrong.append(specs[i])

    def run(specs):
        ts = [threading.Thread(target=hammer, args=(specs, t))
              for t in range(threads_n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        few = [RequantSpec(multiplier=m + 1, shift=5, rounding="nearest",
                           dtype="int8") for m in range(16)]
        run(few)
        assert cf.operand_stats() == {
            "gain_hits": threads_n * calls - 16, "gain_uploads": 16}
        many = [RequantSpec(multiplier=m + 1, shift=6, rounding="nearest",
                            dtype="int8")
                for m in range(GAIN_MEMO_SIZE + 16)]
        run(many)
    finally:
        sys.setswitchinterval(interval)
    st = cf.operand_stats()
    assert st["gain_hits"] + st["gain_uploads"] == 2 * threads_n * calls
    assert len(cf._gain_memo) == GAIN_MEMO_SIZE
    assert not wrong


def test_raw_gains_tables_bypass_the_memo(rng):
    """An int32 device table of shape (n, 2) passes through as it is;
    host pairs and tables keep their upload; neither is counted."""
    x = jnp.asarray(_frame(rng, np.int8))
    k = jnp.asarray(_kernel(rng, np.int8))
    cf = _fresh(Filter2D(window=5, dtype="int8", requant=GAIN_OWN), x,
                "core")
    table = jnp.asarray([[3, 7]], jnp.int32)
    assert cf._operands(x, k, table)[2] is table
    pair = cf._operands(x, k, (3, 7))[2]
    assert pair.shape == (1, 2) and pair.dtype == jnp.int32
    want = requantize_ref(np.asarray(filter2d(x, k)), GAIN_A)
    for gains in (table, (3, 7), np.asarray([[3, 7]])):
        np.testing.assert_array_equal(np.asarray(cf(x, k, gains)), want)
    assert cf.operand_stats() == {"gain_hits": 0, "gain_uploads": 0}


def test_gain_counters_record_only_while_recording(rng):
    """With nothing recording the calls touch no registry counter; while
    ``repro.obs`` is on, hits and uploads land in ``pipeline.gain_*``."""
    from repro import obs
    x = jnp.asarray(_frame(rng, np.int8))
    k = jnp.asarray(_kernel(rng, np.int8))
    cf = _fresh(Filter2D(window=5, dtype="int8", requant=GAIN_OWN), x,
                "core")
    obs.disable()
    obs.REGISTRY.reset()
    try:
        for gains in (GAIN_A, GAIN_A, GAIN_B):
            cf(x, k, gains)
        assert not [c for c in obs.REGISTRY.counters()
                    if c.startswith("pipeline.gain")]
        obs.enable()
        for gains in (GAIN_A, GAIN_B, None, None):
            cf(x, k, gains)
        counters = obs.REGISTRY.counters()
        assert counters["pipeline.gain_hits"] == 3
        assert counters["pipeline.gain_uploads"] == 1
    finally:
        obs.disable()
        obs.REGISTRY.reset()
    assert cf.operand_stats() == {"gain_hits": 4, "gain_uploads": 3}


def test_sharded_gains_stay_replicated_over_the_mesh():
    """On 4 virtual devices the sharded pipeline stores its gains operand
    replicated over the mesh, reuses it, and its output is unchanged
    (bit-exact with the single-device filter). Runs in a subprocess:
    the device count must be set before jax starts."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.filter2d import filter2d
    from repro.core.pipeline import Filter2D
    from repro.core.requant import RequantSpec
    assert len(jax.devices()) == 4
    mesh = jax.make_mesh((4,), ("data",))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(-20, 20, (1, 64, 40, 1)).astype(np.int8))
    k = jnp.asarray(rng.integers(-4, 5, (5, 5)).astype(np.int32))
    a = RequantSpec(multiplier=3, shift=7, rounding="nearest", dtype="int8")
    b = RequantSpec(multiplier=-5, shift=9, rounding="nearest", dtype="int8")
    cf = Filter2D(window=5, dtype="int8", requant=a).compile(
        x, "sharded", mesh=mesh)
    g = cf._operands(x, k, a)[2]
    assert g.sharding.is_equivalent_to(NamedSharding(mesh, P()), g.ndim)
    assert g.sharding.is_fully_replicated and len(g.devices()) == 4
    assert cf._operands(x, k, a)[2] is g
    for rq in (a, b, a, None):
        y = cf(x, k) if rq is None else cf(x, k, rq)
        ref = filter2d(x, k, requant=a if rq is None else rq)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))
    assert cf.cache_size() == 1
    assert cf.operand_stats()["gain_uploads"] == 2, cf.operand_stats()
    print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0 and "OK" in r.stdout, (
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}")
