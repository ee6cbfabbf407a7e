"""The direct form sums its w² taps in one pass: no patch tensor.

Every executor that runs the jnp direct form (``core``, the ``streaming``
strip scan per strip, ``sharded`` per shard on 4 virtual CPU devices) is
checked three ways:

* no equation of the compiled pipeline's jaxpr, sub-jaxprs of ``scan``,
  ``shard_map`` and ``pjit`` included, outputs a value with an axis of
  length w², and no ``concatenate`` takes w² operands;
* uint8 frames with the requant epilogue match the benchmark's plain
  numpy reference (``bench/reference.py``) bit-exactly under every
  same-size border policy, over more than one strip and more than one
  shard;
* float32 frames agree with the transposed form, and with the reference,
  within an absolute 1e-5;
* the taps are summed in passes of at most 32, each one fusion.

The sharded cases run in one subprocess (the device count is fixed when
JAX starts), which calls the same helpers and reports what they returned
as JSON.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.ir import iter_eqns
from repro.core.filter2d import _FORM_FNS
from repro.core.pipeline import Filter2D
from repro.core.requant import RequantSpec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import reference  # noqa: E402

N, H, W, WINDOW, STRIP = 4, 64, 256, 7, 16
TAPS = WINDOW * WINDOW
DTYPES = ("uint8", "float32")
POLICIES = ("mirror", "mirror_dup", "duplicate", "wrap", "constant")
GAIN_FREE = RequantSpec(1, 0, rounding="nearest", dtype="uint8")


def w2_values(jaxpr, taps=TAPS):
    """Every equation reachable from ``jaxpr`` that outputs a value with
    an axis of length ``taps``, or concatenates ``taps`` operands."""
    found = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        for v in eqn.outvars:
            if taps in getattr(v.aval, "shape", ()):
                found.append(f"{name} -> {v.aval.str_short()}")
        if name == "concatenate" and len(eqn.invars) == taps:
            found.append(f"concatenate of {taps} operands")
    return found


def frame_u8(seed):
    return np.random.default_rng(seed).integers(
        0, 256, (H, W)).astype(np.uint8)


def coeffs_u8(seed):
    """The paper cell's coefficients: uniform in [0, 16), centre + 1."""
    k = np.random.default_rng(seed).integers(
        0, 16, (WINDOW, WINDOW)).astype(np.int32)
    k[WINDOW // 2, WINDOW // 2] += 1
    return k


def frame_f32(seed):
    return np.random.default_rng(seed).random((H, W)).astype(np.float32)


def coeffs_f32(seed):
    return np.random.default_rng(seed).uniform(
        -1.0, 1.0, (WINDOW, WINDOW)).astype(np.float32)


def pipeline(execution, dtype, form="direct", policy="mirror", mesh=None):
    spec = Filter2D(window=WINDOW, form=form, border=policy, dtype=dtype,
                    requant=GAIN_FREE if dtype == "uint8" else None)
    knobs = {"strip_h": STRIP} if execution == "streaming" else {}
    cf = spec.compile((H, W), execution, mesh=mesh, **knobs)
    assert cf.execution == execution
    return cf


def w2_found(execution, dtype, mesh=None):
    """:func:`w2_values` of the compiled pipeline's whole program."""
    cf = pipeline(execution, dtype, mesh=mesh)
    if dtype == "uint8":
        args = (jnp.asarray(frame_u8(1)), coeffs_u8(0), GAIN_FREE)
    else:
        args = (jnp.asarray(frame_f32(1)), coeffs_f32(2), None)
    return w2_values(jax.make_jaxpr(cf._fn)(*cf._operands(*args)))


def requant_gap(execution, policy, mesh=None):
    """Largest gap between the uint8 requant pipeline and the reference."""
    x, k = frame_u8(3), coeffs_u8(4)
    m, s = reference.unity_gain(k, "uint8", "nearest")
    got = pipeline(execution, "uint8", policy=policy, mesh=mesh)(
        jnp.asarray(x), jnp.asarray(k),
        RequantSpec(m, s, rounding="nearest", dtype="uint8"))
    assert got.dtype == jnp.uint8
    cfg = {"border": policy,
           "requant": {"rounding": "nearest", "dtype": "uint8"}}
    return reference.max_abs_gap(np.asarray(got),
                                 reference.filter_frame(x, k, cfg, (m, s)))


def float_gaps(execution, mesh=None):
    """Largest gaps of the float32 direct form from the transposed form
    and from the reference (summed in float64)."""
    x, k = frame_f32(5), coeffs_f32(6)
    args = (jnp.asarray(x), jnp.asarray(k))
    a = np.asarray(pipeline(execution, "float32", mesh=mesh)(*args))
    b = pipeline(execution, "float32", form="transposed", mesh=mesh)(*args)
    return {"transposed": reference.max_abs_gap(a, np.asarray(b)),
            "reference": reference.max_abs_gap(
                a, reference.correlate(x, k, "mirror"))}


SCRIPT = textwrap.dedent("""
    import json, sys
    import jax
    sys.path[:0] = [{here!r}, {root!r}]
    import test_direct_form as t
    assert len(jax.devices()) == t.N
    mesh = jax.make_mesh((t.N,), ("data",))
    print(json.dumps({{
        "w2_found": {{d: t.w2_found("sharded", d, mesh) for d in t.DTYPES}},
        "requant_gap": {{p: t.requant_gap("sharded", p, mesh)
                        for p in t.POLICIES}},
        "float_gaps": t.float_gaps("sharded", mesh),
    }}))
""").format(here=HERE, root=ROOT)


@pytest.fixture(scope="module")
def sharded():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={N}",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("execution", ["core", "streaming", "sharded"])
def test_no_value_carries_a_w2_axis(execution, dtype, request):
    if execution == "sharded":
        found = request.getfixturevalue("sharded")["w2_found"][dtype]
    else:
        found = w2_found(execution, dtype)
    assert found == []


def test_the_check_sees_a_patch_tensor():
    """The walk finds a stacked patch tensor inside a scan body, and a
    concatenation of w² operands that leaves no w²-long axis."""
    def patches(x):
        def step(c, row):
            return c, jnp.stack([row * i for i in range(TAPS)], axis=-1)
        return jax.lax.scan(step, 0, x)[1]

    found = w2_values(jax.make_jaxpr(patches)(jnp.zeros((4, 8))))
    assert f"scan -> float32[4,8,{TAPS}]" in found
    assert f"concatenate -> float32[8,{TAPS}]" in found

    def rows(x):
        return jax.lax.concatenate([x * i for i in range(TAPS)], 0)

    assert w2_values(jax.make_jaxpr(rows)(jnp.zeros((2, 8)))) == [
        f"concatenate of {TAPS} operands"]


@pytest.mark.parametrize("w, passes", [(3, 1), (5, 1), (7, 2), (9, 3)])
def test_direct_sums_in_passes_of_at_most_32_taps(w, passes):
    """Each pass is one fusion holding at most 32 coefficient scalars
    (more, and XLA moves coefficients out as full-plane broadcasts); the
    passes change no value: the sum is the transposed form's, in order."""
    rng = np.random.default_rng(w)
    xp = jnp.asarray(rng.random((1, 20 + w - 1, 24 + w - 1, 1)),
                     jnp.float32)
    k = jnp.asarray(rng.uniform(-1, 1, (w, w)), jnp.float32)
    eqns = list(iter_eqns(jax.make_jaxpr(
        lambda a, b: _FORM_FNS["direct"](a, b, 20, 24))(xp, k)))
    barriers = [e for e in eqns if e.primitive.name == "optimization_barrier"]
    assert len(barriers) == passes - 1
    np.testing.assert_array_equal(
        np.asarray(_FORM_FNS["direct"](xp, k, 20, 24)),
        np.asarray(_FORM_FNS["transposed"](xp, k, 20, 24)))


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("execution", ["streaming", "sharded"])
def test_requant_pipeline_matches_the_reference(execution, policy, request):
    assert H // STRIP > 1 and H // N > 1
    if execution == "sharded":
        gap = request.getfixturevalue("sharded")["requant_gap"][policy]
    else:
        gap = requant_gap(execution, policy)
    assert gap == 0


@pytest.mark.parametrize("against", ["transposed", "reference"])
@pytest.mark.parametrize("execution", ["core", "streaming", "sharded"])
def test_float_direct_agrees(execution, against, request):
    """Frames from [0, 1), coefficients from [-1, 1): within 1e-5 of the
    transposed form and of the float64 reference."""
    if execution == "sharded":
        gaps = request.getfixturevalue("sharded")["float_gaps"]
    else:
        gaps = float_gaps(execution)
    assert gaps[against] <= 1e-5
