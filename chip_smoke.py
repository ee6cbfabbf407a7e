"""Smoke test of the filter system's main path on a TPU.

Drives ``Filter2D.compile`` -> ``CompiledFilter`` and ``FilterServeEngine``
at the paper's real frame sizes, in this one process, and checks every
output against the repo's ``core`` oracle. Frames and coefficients come
from ``--seed``; nothing is read from disk.

    python chip_smoke.py              # one chip: phases a-e
    python chip_smoke.py --chips 4    # the sharded executor on 4 chips only

Each phase prints one line (what it really ran, ``interpret``, the first
call's wall time with compilation in it, and max |delta| against the
oracle); the last line is the JSON verdict. Without a TPU it exits
non-zero before any phase runs. Any failed check raises, so the exit code
is non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.configs.spatial_filter_hd import CONFIG as HD  # noqa: E402
from repro.core import filters  # noqa: E402
from repro.core.border_spec import BorderSpec  # noqa: E402
from repro.core.pipeline import Filter2D  # noqa: E402
from repro.core.requant import RequantSpec  # noqa: E402
from repro.serving.engine import FilterServeEngine  # noqa: E402

HD_1080 = (1080, 1920)
UHD_2160 = (2160, 3840)
FLOAT_TOL = 3e-4            # the float parity tolerance of the test suite


def _u8_frame(rng, shape):
    return jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8))


def _u8_filter(rng, w=7):
    """Random non-negative 7x7 integer coefficients and their unity-gain
    uint8 requant scaler (the paper stream: 8-bit in, 8-bit out)."""
    k = rng.integers(0, 16, (w, w)).astype(np.int32)
    k[w // 2, w // 2] += 1                      # DC gain > 0
    return jnp.asarray(k), RequantSpec.unity_gain(k, "uint8")


def _paper_spec():
    return Filter2D(window=7, border=BorderSpec("mirror"), dtype="uint8",
                    requant=RequantSpec(1, 0, dtype="uint8").gain_free())


def _first_call(cf, *args):
    t0 = time.perf_counter()
    y = jax.block_until_ready(cf(*args))
    return y, time.perf_counter() - t0


def _oracle(spec, shape, *args):
    return spec.compile(shape, "core")(*args)


def _max_delta(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64)
                               - np.asarray(want, np.float64))))


def _report(phase, cf, seconds, delta, **extra):
    bits = [f"phase={phase}", f"executor={cf.execution}",
            f"regime={cf.regime}", f"interpret={cf.interpret}"]
    if cf.plan is not None and cf.execution == "pallas":
        bits.append(f"grid={cf.plan.rows.n}x{cf.plan.cols.n}"
                    f"(strip_h={cf.strip_h},tile_w={cf.tile_w})")
    bits += [f"compile_s={seconds:.3f}", f"max_abs_delta={delta}"]
    bits += [f"{k}={v}" for k, v in extra.items()]
    print(" ".join(bits), flush=True)


def _check(ok, message) -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(message)


def _check_chip_pipeline(cf, interpret):
    _check(cf.interpret is interpret,
           f"pipeline interpret={cf.interpret}, wanted {interpret}")


def phase_paper_stream(rng, shape=HD_1080, *, interpret=False,
                       vmem_budget=None):
    """(b) the paper stream: uint8, 7x7 runtime coefficients, mirror,
    requant to uint8, compiled on the Pallas kernel; bit-exact, and a
    coefficient + gain swap reuses the executable."""
    spec = _paper_spec()
    auto = spec.compile(shape, "auto", vmem_budget=vmem_budget,
                        interpret=interpret)
    _check_chip_pipeline(auto, interpret)
    print(f"phase=paper_stream_auto_choice executor={auto.execution} "
          f"regime={auto.regime} rule={auto.selection[0]}", flush=True)
    cf = spec.compile(shape, "pallas", vmem_budget=vmem_budget,
                      interpret=interpret)
    _check_chip_pipeline(cf, interpret)
    x = _u8_frame(rng, shape)
    k, rq = _u8_filter(rng)
    hlo = cf._fn.lower(x, k, cf._gain_operand(rq)).as_text()
    if not interpret:
        _check("tpu_custom_call" in hlo, "no Mosaic kernel in the HLO")
    y, seconds = _first_call(cf, x, k, rq)
    want = _oracle(spec, shape, x, k, rq)
    _check(y.dtype == jnp.uint8 and y.shape == want.shape,
           f"output {y.dtype}{y.shape}, oracle {want.dtype}{want.shape}")
    delta = _max_delta(y, want)
    _check(delta == 0, f"paper stream not bit-exact: max |delta| {delta}")
    size = cf.cache_size()
    k2, rq2 = _u8_filter(rng)
    y2 = jax.block_until_ready(cf(x, k2, rq2))
    _check(cf.cache_size() == size, "coefficient/gain swap recompiled")
    delta2 = _max_delta(y2, _oracle(spec, shape, x, k2, rq2))
    _check(delta2 == 0, f"swapped filter not bit-exact: {delta2}")
    _report("paper_stream", cf, seconds, max(delta, delta2),
            cache_size=cf.cache_size())
    return cf


def phase_stream_4k(rng, shape=UHD_2160, *, interpret=False,
                    vmem_budget=None):
    """(c) 2160p uint8 requant through the multi-strip, double-buffered
    stream regime; bit-exact."""
    spec = _paper_spec()
    cf = spec.compile(shape, "pallas", regime="stream",
                      vmem_budget=vmem_budget, interpret=interpret)
    _check_chip_pipeline(cf, interpret)
    _check(cf.plan.rows.n > 1 and cf.overlap, "not a multi-strip stream")
    x = _u8_frame(rng, shape)
    k, rq = _u8_filter(rng)
    y, seconds = _first_call(cf, x, k, rq)
    delta = _max_delta(y, _oracle(spec, shape, x, k, rq))
    _check(delta == 0, f"2160p stream not bit-exact: max |delta| {delta}")
    _report("stream_2160p", cf, seconds, delta)
    return cf


def phase_hd_float(rng, shape=(HD.image_h, HD.image_w), *, interpret=False,
                   vmem_budget=None):
    """(d) the configs/spatial_filter_hd.py float32 stream, through
    ``auto`` and through ``pallas``, within the suite's float tolerance."""
    spec = Filter2D(window=HD.filter_window, border=BorderSpec("mirror"),
                    dtype=HD.dtype)
    x = jnp.asarray(rng.random(shape, dtype=np.float32))
    k = jnp.asarray(filters.gaussian(HD.filter_window))
    with jax.default_matmul_precision("float32"):
        want = _oracle(spec, shape, x, k)
    out = []
    for execution in ("auto", "pallas"):
        cf = spec.compile(shape, execution, vmem_budget=vmem_budget,
                          interpret=interpret)
        _check_chip_pipeline(cf, interpret)
        y, seconds = _first_call(cf, x, k)
        delta = _max_delta(y, want)
        _check(np.allclose(np.asarray(y), np.asarray(want),
                           rtol=FLOAT_TOL, atol=FLOAT_TOL),
               f"{execution}: |delta| {delta}")
        _report(f"hd_float32_{execution}", cf, seconds, delta)
        out.append(cf)
    return out


def phase_served(rng, shape=HD_1080, *, interpret=False, requests=8,
                 batch_size=4, vmem_budget=None):
    """(e) FilterServeEngine on the Pallas kernel: two tenants with their
    own coefficients, every result bit-exact, no errors, and one compile
    per bucket."""
    spec = _paper_spec()
    tenants = {name: _u8_filter(rng) for name in ("tenant_a", "tenant_b")}
    frames = [_u8_frame(rng, shape) for _ in range(requests)]
    engine = FilterServeEngine(batch_size=batch_size, execution="pallas",
                               vmem_budget=vmem_budget, interpret=interpret)
    t0 = time.perf_counter()
    with engine:
        reqs = []
        for i, x in enumerate(frames):
            name = sorted(tenants)[i % 2]
            k, rq = tenants[name]
            reqs.append((engine.submit(x, k, spec=spec, gains=rq,
                                       tenant=name), x, k, rq))
        outs = [(r.result(timeout=900), x, k, rq) for r, x, k, rq in reqs]
        seconds = time.perf_counter() - t0
        buckets = {engine.bucket_key_for(spec, x.shape) for x in frames}
        pipes = list(engine._cache.values())
    stats = engine.stats()
    for cf in pipes:
        _check_chip_pipeline(cf, interpret)
        _check(cf.execution == "pallas", f"served on {cf.execution}")
    delta = 0.0
    for y, x, k, rq in outs:
        d = _max_delta(y, _oracle(spec, shape, x, k, rq))
        _check(d == 0, f"served request not bit-exact: max |delta| {d}")
        delta = max(delta, d)
    _check(stats["errors"] == 0, f"served errors: {stats}")
    _check(stats["completed"] == requests, f"not all served: {stats}")
    _check(stats["recompiles"] == len(buckets),
           f"{stats['recompiles']} compiles for {len(buckets)} bucket(s)")
    _report("served", pipes[0], seconds, delta, requests=requests,
            buckets=len(buckets), recompiles=stats["recompiles"],
            errors=stats["errors"], waves=stats["waves"])
    return stats


def phase_sharded(rng, devices, shape=UHD_2160, *, interpret=False):
    """The sharded executor over a 4-device mesh: 2160p uint8, mirror,
    requant; bit-exact against the single-device core oracle, and the
    output really spans every device of the mesh."""
    from jax.sharding import Mesh
    _check(len(devices) == 4, f"need 4 devices, got {devices}")
    mesh = Mesh(np.array(devices), ("data",))
    spec = _paper_spec()
    cf = spec.compile(shape, "sharded", mesh=mesh, interpret=interpret)
    _check_chip_pipeline(cf, interpret)
    x = _u8_frame(rng, shape)
    k, rq = _u8_filter(rng)
    y, seconds = _first_call(cf, x, k, rq)
    spread = len(y.sharding.device_set)
    _check(spread == 4, f"sharded output lives on {spread} device(s)")
    want = jax.device_put(_oracle(spec, shape, x, k, rq), devices[0])
    delta = _max_delta(y, want)
    _check(delta == 0, f"sharded output not bit-exact: max |delta| {delta}")
    _report("sharded_4chip", cf, seconds, delta, output_devices=spread)
    return cf


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded executor on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()                       # (a) a TPU, or stop
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"phase=device platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__} "
          f"compile_cache={compile_cache.enable()}", flush=True)

    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phase_sharded(rng, devices[:4])
    else:
        phase_paper_stream(rng)
        phase_stream_4k(rng)
        phase_hd_float(rng)
        phase_served(rng)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
