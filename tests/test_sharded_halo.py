"""The sharded executor's halo exchange, counted and named, on 4 virtual
CPU devices.

One subprocess (the device count is fixed when JAX starts) builds the
pipelines and reports what it saw as JSON; each test checks one part:

* a recording sharded call adds ``2·n·r·W·itemsize`` to the counter
  ``pipeline.halo_bytes`` and 1 to ``pipeline.sharded_calls``, and the
  pipeline keeps that number as ``halo_bytes``;
* a call that is not recording, and a one-chip pipeline, add to neither;
* the compiled program's op metadata carries the ``repro.shard.halo``
  scope on both collective-permutes;
* a frame whose rows do not split over the mesh raises ``ValueError``
  when the pipeline is built.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

N, W, R = 4, 256, 3
WIDTHS = {"uint8": 1, "float32": 4}
BAD_ROWS = (66, 8)          # rows not divisible by 4; 2 rows a shard < r

SCRIPT = textwrap.dedent("""
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import obs
    from repro.core.pipeline import Filter2D
    from repro.core.requant import RequantSpec

    N, W, R, BAD_ROWS = {N}, {W}, {R}, {BAD_ROWS}
    assert len(jax.devices()) == N
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    KEYS = ("pipeline.halo_bytes", "pipeline.sharded_calls")
    out = {{}}

    def spec(dtype):
        rq = (RequantSpec(1, 0, rounding="nearest", dtype="uint8")
              if dtype == "uint8" else None)
        return Filter2D(window=2 * R + 1, dtype=dtype, requant=rq), rq

    def counted(cf, x, k, rq, recording):
        obs.REGISTRY.reset()
        if recording:
            obs.enable()
        try:
            cf(x, k) if rq is None else cf(x, k, rq)
        finally:
            obs.disable()
        c = obs.REGISTRY.counters()
        return {{key: c[key] for key in KEYS if key in c}}

    k = np.ones((2 * R + 1, 2 * R + 1), np.int32)
    for dtype in ("uint8", "float32"):
        f, rq = spec(dtype)
        cf = f.compile((64, W), "auto", mesh=mesh)
        x = jax.device_put(jnp.zeros((64, W), dtype), rows)
        kk = k if rq is not None else k.astype(np.float32)
        out[dtype] = {{
            "execution": cf.execution,
            "recording": counted(cf, x, kk, rq, True),
            "kept": cf.halo_bytes,
            "not_recording": counted(cf, x, kk, rq, False),
        }}
        if dtype == "uint8":
            txt = cf._fn.lower(*cf._operands(x, kk, rq)).compile().as_text()
            perms = [l for l in txt.splitlines()
                     if " collective-permute(" in l]
            out["permutes"] = len(perms)
            out["permutes_in_scope"] = sum("repro.shard.halo" in l
                                           for l in perms)
        one = f.compile((64, W), "auto")
        out[dtype]["one_chip"] = {{
            "execution": one.execution,
            "recording": counted(one, jnp.zeros((64, W), dtype), kk, rq,
                                 True)}}

    out["bad"] = {{}}
    for h in BAD_ROWS:
        try:
            spec("uint8")[0].compile((h, W), "auto", mesh=mesh)
            out["bad"][str(h)] = None
        except ValueError as e:
            out["bad"][str(h)] = str(e)
    print(json.dumps(out))
""").format(N=N, W=W, R=R, BAD_ROWS=BAD_ROWS)


@pytest.fixture(scope="module")
def seen():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={N}",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("dtype", sorted(WIDTHS))
def test_a_recording_sharded_call_counts_its_halo_bytes(seen, dtype):
    want = 2 * N * R * W * WIDTHS[dtype]
    assert seen[dtype]["execution"] == "sharded"
    assert seen[dtype]["recording"]["pipeline.halo_bytes"] == want
    assert seen[dtype]["kept"] == want


def test_float32_moves_four_times_the_uint8_halo(seen):
    assert (seen["float32"]["recording"]["pipeline.halo_bytes"]
            == 4 * seen["uint8"]["recording"]["pipeline.halo_bytes"])


@pytest.mark.parametrize("dtype", sorted(WIDTHS))
def test_a_recording_sharded_call_counts_one_sharded_call(seen, dtype):
    assert seen[dtype]["recording"]["pipeline.sharded_calls"] == 1


@pytest.mark.parametrize("dtype", sorted(WIDTHS))
def test_a_call_that_is_not_recording_counts_nothing(seen, dtype):
    assert seen[dtype]["not_recording"] == {}


@pytest.mark.parametrize("dtype", sorted(WIDTHS))
def test_a_one_chip_pipeline_never_counts_the_halo(seen, dtype):
    one = seen[dtype]["one_chip"]
    assert one["execution"] != "sharded"
    assert one["recording"] == {}


def test_the_exchange_is_named_in_the_compiled_program(seen):
    assert seen["permutes"] == 2
    assert seen["permutes_in_scope"] == 2


@pytest.mark.parametrize("rows", BAD_ROWS)
def test_rows_that_do_not_split_raise_when_the_pipeline_is_built(seen,
                                                                 rows):
    msg = seen["bad"][str(rows)]
    assert msg is not None
    assert f"{rows} rows" in msg and f"over {N} shards" in msg
    assert f"r={R}" in msg
