"""Multi-device tests (subprocess: host-platform device count must be set
before jax initialises, so each test runs its own python)."""
import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 4, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    for _attempt in range(3):
        r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                           capture_output=True, text=True, timeout=timeout,
                           env=env)
        if r.returncode == 0:
            break
        if r.returncode >= 0:          # real failure: don't mask it
            break
        # negative rc = signal (SIGABRT under suite-level memory pressure
        # when several jax processes coexist): retry, it's environmental
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_filter_halo_exchange():
    """Row-sharded frame + ppermute halo == single-device filter."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.filter2d import filter2d
    from repro.core.pipeline import Filter2D
    from repro.core.borders import BorderSpec
    from repro.core import filters
    mesh = jax.make_mesh((4,), ("data",))
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 64, 40, 3)).astype(np.float32)
    for pol in ("mirror", "duplicate", "constant"):
        k = jnp.asarray(filters.gaussian(5))
        ref = filter2d(jnp.asarray(x), k, border=BorderSpec(pol))
        cf = Filter2D(window=5, border=BorderSpec(pol)).compile(
            x.shape, "sharded", mesh=mesh)
        y = cf(jnp.asarray(x), k)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    print("OK")
    """)


def test_sharded_fixed_point_narrow_ring_and_requant():
    """Fixed-point shards exchange halos at *storage* width (the compiled
    HLO's collective-permutes run on s8, not s32) and the requantising
    epilogue applies per shard — bit-exact with the single-device path."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.filter2d import filter2d
    from repro.core.pipeline import Filter2D
    from repro.core.borders import BorderSpec
    from repro.core.requant import RequantSpec
    mesh = jax.make_mesh((4,), ("data",))
    rng = np.random.default_rng(3)
    x = rng.integers(-20, 20, (2, 64, 40, 3)).astype(np.int8)
    k = rng.integers(-4, 5, (3, 3)).astype(np.int32)
    rq = RequantSpec(multiplier=3, shift=6, rounding="nearest", dtype="int8")
    for pol in ("mirror", "wrap", "constant"):
        spec = BorderSpec(pol, 2.0)
        ref = filter2d(jnp.asarray(x), jnp.asarray(k), border=spec,
                       requant=rq)
        cf = Filter2D(window=3, border=spec, dtype="int8",
                      requant=rq).compile(x.shape, "sharded", mesh=mesh)
        y = cf(jnp.asarray(x), jnp.asarray(k))
        assert y.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))
    # wire dtype: the ring must carry storage-width halo rows
    cf = Filter2D(window=5, dtype="int8").compile((1, 64, 128, 1),
                                                  "sharded", mesh=mesh)
    fn = jax.jit(lambda a, b: cf(a, b))
    txt = fn.lower(jax.ShapeDtypeStruct((1, 64, 128, 1), jnp.int8),
                   jax.ShapeDtypeStruct((5, 5), jnp.int32)
                   ).compile().as_text()
    cp = [l for l in txt.splitlines() if "collective-permute(" in l]
    assert cp and all("s8" in l for l in cp), cp
    print("OK")
    """)


def test_compressed_dp_step_two_pods():
    """int8-EF hierarchical DP step runs on a (pod=2, data=2) mesh and the
    loss matches the uncompressed pjit step to quantisation tolerance."""
    _run("""
    import dataclasses, numpy as np, jax, jax.numpy as jnp
    from repro.configs.base import RunConfig, SHAPES, SINGLE_POD, TrainConfig
    from repro.configs.tiny import tiny_of
    from repro.models import registry
    from repro.optim import adamw_init
    from repro.training.dp_shardmap import (init_error_feedback,
                                            make_compressed_dp_step)
    from repro.training.step import make_train_step
    from repro.data import make_train_batch

    mc = tiny_of("yi_6b")
    sh = dataclasses.replace(SHAPES["train_4k"], seq_len=16, global_batch=8)
    rc = RunConfig(model=mc, shape=sh, mesh=SINGLE_POD,
                   train=TrainConfig(loss_chunk=16, remat_policy="none"))
    mesh = jax.make_mesh((2, 2), ("pod", "data"))
    bundle = registry.build(rc)
    params = bundle.init_params(jax.random.key(0))
    opt = adamw_init(params)
    err = init_error_feedback(params, mesh)
    step = make_compressed_dp_step(bundle, rc, mesh)
    batch = make_train_batch(rc, 0)
    p1, o1, err, m1 = step(params, opt, err, batch)

    ref_step = jax.jit(make_train_step(bundle, rc))
    p2, o2, m2 = ref_step(params, adamw_init(params), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    # updates agree to int8 tolerance
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d < 5e-3, d
    print("OK")
    """)


def test_tiny_dryrun_mesh_8dev():
    """The dry-run machinery (shardings + lower + compile) on a tiny config
    with a (2, 2, 2) pod mesh — the multi-pod path end to end."""
    _run("""
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs.base import (RunConfig, SHAPES, MeshConfig,
                                    TrainConfig)
    from repro.configs.tiny import tiny_of
    from repro.launch import dryrun as dr

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    mc = tiny_of("gemma3_4b")
    sh = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
    rc = RunConfig(model=mc, shape=sh, mesh=MeshConfig((2, 2, 2),
                   ("pod", "data", "model")),
                   train=TrainConfig(loss_chunk=32))
    lowered, ctx = dr.build_lowered(rc, mesh, "train")
    compiled = lowered.compile()
    assert dr.cost_analysis_dict(compiled).get("flops", 0) > 0
    # decode path too
    sh2 = dataclasses.replace(SHAPES["decode_32k"], seq_len=64,
                              global_batch=8)
    rc2 = dataclasses.replace(rc, shape=sh2)
    lowered2, _ = dr.build_lowered(rc2, mesh, "decode")
    lowered2.compile()
    print("OK")
    """, devices=8)


def test_collective_parser_sees_halo_permutes():
    """Roofline HLO parser finds the ppermute bytes of the halo exchange."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.pipeline import Filter2D
    from repro.core import filters
    from repro.launch.roofline import parse_collective_bytes
    mesh = jax.make_mesh((4,), ("data",))
    x = jax.ShapeDtypeStruct((1, 64, 128, 1), jnp.float32)
    k = jax.ShapeDtypeStruct((5, 5), jnp.float32)
    cf = Filter2D(window=5).compile(x.shape, "sharded", mesh=mesh)
    fn = jax.jit(lambda a, b: cf(a, b))
    txt = fn.lower(x, k).compile().as_text()
    coll = parse_collective_bytes(txt)
    assert coll.get("collective-permute", 0) > 0, coll
    print("OK", coll)
    """)
