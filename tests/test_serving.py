"""FilterServeEngine scheduler semantics + real-pipeline parity.

The scheduler tests run against a *fake executor* injected through the
``compile_fn`` seam — they pin bucketing, batching, LRU eviction,
tenant isolation, shutdown and thread-safety without paying a single
real compile. The final tests run the real front door and pin the
acceptance invariant: after warmup, ``serve.recompiles == num_buckets``
(every post-warmup request is a cache hit) and engine results match the
direct ``CompiledFilter`` call bit-for-bit.
"""
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import filters
from repro.core.pipeline import (Filter2D, admit_batch, batched_shape,
                                 bucket_key, split_batch)
from repro.serving import FilterServeEngine


class FakeExecutor:
    """Stands in for a CompiledFilter: output = frame * coeffs.flat[0],
    so per-request results are distinguishable. Records every compile
    and every dispatch for the assertions."""

    def __init__(self, delay_s=0.0):
        self.compiles = []          # (spec, batched_shape) per compile
        self.calls = []             # coeffs scale per dispatch
        self.delay_s = delay_s

    def compile_fn(self, spec, shape):
        self.compiles.append((spec, shape))

        def pipe(x, coeffs, gains=None):
            if self.delay_s:
                time.sleep(self.delay_s)
            scale = float(np.asarray(coeffs).flat[0])
            self.calls.append(scale)
            return np.asarray(x) * scale

        return pipe


def frame(h, w, dtype=np.float32, seed=0):
    return (np.random.default_rng(seed)
            .integers(1, 9, (h, w)).astype(dtype))


SPEC3 = Filter2D(window=3)
SPEC5 = Filter2D(window=5)
K1 = np.full((3, 3), 2.0, np.float32)
K2 = np.full((3, 3), 5.0, np.float32)


# -- batch-admission helpers (the engine's substrate) -------------------------

def test_batched_shape_and_roundtrip():
    assert batched_shape((7, 9), 4) == (4, 7, 9, 1)
    assert batched_shape((7, 9, 3), 2) == (2, 7, 9, 3)
    with pytest.raises(ValueError):
        batched_shape((2, 7, 9, 3), 2)
    fs = [frame(5, 6, seed=i) for i in range(3)]
    x = admit_batch(fs, 4)
    assert x.shape == (4, 5, 6, 1)
    outs = split_batch(np.asarray(x), 3, 2)
    for f, o in zip(fs, outs):
        np.testing.assert_array_equal(np.asarray(o), f)
    np.testing.assert_array_equal(np.asarray(x)[3], 0)  # the pad plane


def test_admit_batch_rejects_mixed_geometry():
    with pytest.raises(ValueError):
        admit_batch([frame(5, 6), frame(6, 5)], 4)
    with pytest.raises(ValueError):
        admit_batch([frame(5, 6), frame(5, 6).astype(np.int8)], 4)
    with pytest.raises(ValueError):
        admit_batch([], 4)


def test_bucket_key_identity():
    k = bucket_key(SPEC3, (8, 8), batch=4)
    assert k == bucket_key(SPEC3, (8, 8), batch=4)          # stable
    assert k != bucket_key(SPEC3, (8, 9), batch=4)          # geometry
    assert k != bucket_key(SPEC5, (8, 8), batch=4)          # spec
    assert k != bucket_key(SPEC3, (8, 8), batch=8)          # batch size
    assert k != bucket_key(SPEC3, (8, 8), batch=4,
                           execution="core")                 # knobs


# -- scheduler semantics (fake executor) --------------------------------------

def test_bucketing_mixed_geometries():
    """Heterogeneous traffic compiles once per (spec, geometry) bucket
    and batches within buckets."""
    fx = FakeExecutor()
    with FilterServeEngine(batch_size=4, compile_fn=fx.compile_fn) as eng:
        reqs = []
        for _ in range(4):
            reqs.append(eng.submit(frame(8, 8), K1, spec=SPEC3))
            reqs.append(eng.submit(frame(6, 10), K1, spec=SPEC3))
            reqs.append(eng.submit(frame(8, 8), K1, spec=SPEC5))
        assert eng.drain(timeout=30)
        st = eng.stats()
    assert len(fx.compiles) == 3                  # 3 buckets, 1 compile each
    assert {s for _, s in fx.compiles} == {(4, 8, 8, 1), (4, 6, 10, 1)}
    assert st["recompiles"] == 3
    assert st["completed"] == 12
    # 4 same-signature requests per bucket, batch 4 -> 3 full waves is the
    # floor (the worker may dispatch early waves before the queue fills)
    assert 3 <= st["waves"] <= 12
    for r in reqs:
        np.testing.assert_allclose(r.result(timeout=5),
                                   np.asarray(r.frame) * 2.0)


def test_request_rank_and_pixels_restored():
    fx = FakeExecutor()
    with FilterServeEngine(batch_size=2, compile_fn=fx.compile_fn) as eng:
        f2 = frame(5, 7)
        f3 = np.stack([frame(5, 7, seed=s) for s in range(3)], -1)
        r2 = eng.submit(f2, K1, spec=SPEC3)
        r3 = eng.submit(f3, K1, spec=SPEC3)
        assert r2.result(timeout=10).shape == (5, 7)
        assert r3.result(timeout=10).shape == (5, 7, 3)
        assert r2.pixels == 35 and r3.pixels == 105
        assert r2.latency_s is not None and r2.latency_s >= 0


def test_lru_eviction_and_recompile_counting():
    """cache_slots=2 with 3 hot buckets: the cold bucket's return evicts
    and recompiles; cache_size() never exceeds the bound."""
    fx = FakeExecutor()
    geoms = [(8, 8), (6, 10), (12, 4)]
    with FilterServeEngine(batch_size=1, cache_slots=2,
                           compile_fn=fx.compile_fn) as eng:
        for h, w in geoms:                        # cold pass: 3 compiles
            eng.submit(frame(h, w), K1, spec=SPEC3).result(timeout=10)
        assert eng.cache_size() == 2              # bucket 0 evicted
        st = eng.stats()
        assert st["recompiles"] == 3 and st["evictions"] == 1
        # warm hits: the two resident buckets never recompile
        for h, w in geoms[1:]:
            eng.submit(frame(h, w), K1, spec=SPEC3).result(timeout=10)
        assert eng.stats()["recompiles"] == 3
        # the evicted bucket's return recompiles and evicts the new LRU
        eng.submit(frame(8, 8), K1, spec=SPEC3).result(timeout=10)
        st = eng.stats()
    assert st["recompiles"] == 4 and st["evictions"] == 2
    assert len(fx.compiles) == 4
    assert st["cache_hits"] == 2


def test_per_tenant_gain_isolation():
    """Tenants alternating through ONE bucket with different operands:
    one compile total — tenant A's swap never recompiles tenant B's
    bucket — and each tenant gets its own operands' results."""
    fx = FakeExecutor()
    with FilterServeEngine(batch_size=4, compile_fn=fx.compile_fn) as eng:
        ra, rb = [], []
        for i in range(6):
            ra.append(eng.submit(frame(8, 8, seed=i), K1, spec=SPEC3,
                                 tenant="a"))
            rb.append(eng.submit(frame(8, 8, seed=i), K2, spec=SPEC3,
                                 tenant="b"))
        assert eng.drain(timeout=30)
        st = eng.stats()
    assert len(fx.compiles) == 1 and st["recompiles"] == 1
    for r in ra:
        np.testing.assert_allclose(r.result(), np.asarray(r.frame) * 2.0)
    for r in rb:
        np.testing.assert_allclose(r.result(), np.asarray(r.frame) * 5.0)
    # no wave ever mixed the two operand sets
    assert set(fx.calls) == {2.0, 5.0}


def test_same_tenant_different_coeffs_split_waves():
    """Operand identity, not tenant name, gates wave membership — one
    tenant rotating coefficients still never recompiles."""
    fx = FakeExecutor()
    with FilterServeEngine(batch_size=4, compile_fn=fx.compile_fn) as eng:
        r1 = eng.submit(frame(8, 8), K1, spec=SPEC3, tenant="a")
        r2 = eng.submit(frame(8, 8), K2, spec=SPEC3, tenant="a")
        np.testing.assert_allclose(r1.result(timeout=10),
                                   np.asarray(r1.frame) * 2.0)
        np.testing.assert_allclose(r2.result(timeout=10),
                                   np.asarray(r2.frame) * 5.0)
        assert eng.stats()["recompiles"] == 1


def test_queue_drains_on_shutdown():
    fx = FakeExecutor(delay_s=0.01)
    eng = FilterServeEngine(batch_size=2, compile_fn=fx.compile_fn)
    reqs = [eng.submit(frame(8, 8, seed=i), K1, spec=SPEC3)
            for i in range(10)]
    eng.shutdown(drain=True)
    assert all(r.done() for r in reqs)
    assert eng.stats()["completed"] == 10
    with pytest.raises(RuntimeError):
        eng.submit(frame(8, 8), K1, spec=SPEC3)   # post-shutdown submit


def test_shutdown_without_drain_cancels_queued():
    fx = FakeExecutor(delay_s=0.05)
    eng = FilterServeEngine(batch_size=1, compile_fn=fx.compile_fn)
    reqs = [eng.submit(frame(8, 8, seed=i), K1, spec=SPEC3)
            for i in range(20)]
    eng.shutdown(drain=False)
    st = eng.stats()
    assert st["cancelled"] > 0
    assert st["completed"] + st["cancelled"] == 20
    cancelled = [r for r in reqs if r._error is not None]
    assert len(cancelled) == st["cancelled"]
    with pytest.raises(RuntimeError, match="shut down"):
        cancelled[0].result(timeout=1)


def test_executor_error_isolated_to_wave():
    """A failing dispatch fails its wave's requests (result() raises)
    without killing the worker — later requests still serve."""
    calls = {"n": 0}

    def compile_fn(spec, shape):
        def pipe(x, coeffs, gains=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("boom")
            return np.asarray(x)
        return pipe

    with FilterServeEngine(batch_size=1, compile_fn=compile_fn) as eng:
        bad = eng.submit(frame(8, 8), K1, spec=SPEC3)
        with pytest.raises(RuntimeError, match="boom"):
            bad.result(timeout=10)
        good = eng.submit(frame(8, 8), K1, spec=SPEC3)
        assert good.result(timeout=10).shape == (8, 8)
        st = eng.stats()
    assert st["errors"] == 1 and st["completed"] == 1


def test_submit_validation():
    fx = FakeExecutor()
    with FilterServeEngine(compile_fn=fx.compile_fn) as eng:
        with pytest.raises(TypeError, match="Filter2D"):
            eng.submit(frame(8, 8), K1, spec="w3")
        with pytest.raises(ValueError, match="\\[H, W\\]"):
            eng.submit(np.zeros((2, 8, 8, 1), np.float32), K1, spec=SPEC3)
        with pytest.raises(ValueError, match="dtype"):
            eng.submit(frame(8, 8, dtype=np.int8), K1, spec=SPEC3)
    with pytest.raises(ValueError):
        FilterServeEngine(batch_size=0)
    with pytest.raises(ValueError):
        FilterServeEngine(cache_slots=0)


def test_concurrent_submitters():
    """4 submitter threads × 25 requests race the worker; every request
    is served exactly once with its own tenant's scale."""
    fx = FakeExecutor()
    results = [[] for _ in range(4)]
    with FilterServeEngine(batch_size=4, compile_fn=fx.compile_fn) as eng:
        def submitter(t):
            k = np.full((3, 3), float(t + 2), np.float32)
            for i in range(25):
                results[t].append(
                    eng.submit(frame(8, 8, seed=i), k, spec=SPEC3,
                               tenant=f"t{t}"))
        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert eng.drain(timeout=60)
        st = eng.stats()
    assert st["requests"] == 100 and st["completed"] == 100
    assert st["recompiles"] == 1                  # one bucket for everyone
    for t in range(4):
        for r in results[t]:
            np.testing.assert_allclose(r.result(),
                                       np.asarray(r.frame) * (t + 2))


def test_copy_byte_counters_are_exact():
    """A 3-frame wave at batch 4: ``h2d_bytes`` counts the three host
    frames copied in, ``d2h_bytes`` the four planes copied out (the pad
    plane too). A frame already on the device is not copied in."""
    import jax.numpy as jnp

    gate, entered = threading.Event(), threading.Event()

    def compile_fn(spec, shape):
        def pipe(x, coeffs, gains=None):
            if float(np.asarray(coeffs).flat[0]) == 5.0:   # the blocker
                entered.set()
                assert gate.wait(30)
            return np.asarray(x) * 2
        return pipe

    blocker = frame(8, 8)                          # float32, 256 B
    with FilterServeEngine(batch_size=4, compile_fn=compile_fn) as eng:
        eng.submit(blocker, K2, spec=SPEC3)
        assert entered.wait(30)
        # the worker is held inside the blocker's dispatch: these three
        # queue up behind it and leave as one wave
        wave = [eng.submit(frame(6, 10, seed=i), K1, spec=SPEC3)
                for i in range(3)]
        gate.set()
        assert eng.drain(timeout=30)
        st = eng.stats()
        assert st["waves"] == 2 and st["padded_planes"] == 3 + 1
        assert st["h2d_bytes"] == blocker.nbytes + 3 * 6 * 10 * 4
        assert st["d2h_bytes"] == 4 * blocker.nbytes + 4 * 6 * 10 * 4
        for r in wave:
            np.testing.assert_array_equal(r.result(), np.asarray(r.frame) * 2)

        on_device = jnp.asarray(frame(6, 10))
        eng.submit(on_device, K1, spec=SPEC3).result(timeout=30)
        after = eng.stats()
    assert after["h2d_bytes"] == st["h2d_bytes"]
    assert after["d2h_bytes"] == st["d2h_bytes"] + 4 * 6 * 10 * 4


def test_wave_spans_carry_the_wave_and_its_requests(tmp_path):
    """Under a profiler session each wave's ``repro.serve.admit`` and
    ``repro.serve.copy_out`` spans are on the host plane with the wave's
    dispatch number and its requests' ids, one of each per wave."""
    import jax
    from jax.profiler import ProfileData

    obs.REGISTRY.reset()
    fx = FakeExecutor()
    with jax.profiler.trace(str(tmp_path)):
        with FilterServeEngine(batch_size=2,
                               compile_fn=fx.compile_fn) as eng:
            reqs = [eng.submit(frame(8, 8, seed=i), K1, spec=SPEC3)
                    for i in range(3)]
            assert eng.drain(timeout=30)
            waves = eng.stats()["waves"]
    [pb] = list(tmp_path.rglob("*.xplane.pb"))
    spans = {"repro.serve.admit": [], "repro.serve.copy_out": []}
    for p in ProfileData.from_file(str(pb)).planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(dict(e.stats))
    for name, metas in spans.items():
        assert sorted(m["wave"] for m in metas) == list(
            range(1, waves + 1)), name
        # a lone id reads back as an int
        rids = sorted(int(r) for m in metas
                      for r in str(m["requests"]).split())
        assert rids == sorted(r.rid for r in reqs), name
    counts = {n: h.count for n, h in obs.REGISTRY.histograms().items()}
    assert counts == {"span/repro.serve.admit": waves,
                      "span/repro.serve.copy_out": waves}
    obs.REGISTRY.reset()


def test_engine_off_means_no_registry_traffic():
    """With obs tracing off, serving leaves obs.REGISTRY untouched (the
    engine's always-on stats live in engine.stats() only)."""
    assert not obs.enabled()
    obs.REGISTRY.reset()
    fx = FakeExecutor()
    with FilterServeEngine(batch_size=2, compile_fn=fx.compile_fn) as eng:
        for i in range(4):
            eng.submit(frame(8, 8, seed=i), K1, spec=SPEC3)
        assert eng.drain(timeout=30)
    assert obs.REGISTRY.counters() == {}
    assert obs.REGISTRY.histograms() == {}


# -- real pipeline ------------------------------------------------------------

def test_real_pipeline_parity_and_warm_contract(rng):
    """The acceptance invariant, end to end on the real front door:
    after warmup every request is a cache hit — ``serve.recompiles``
    (obs.REGISTRY) == num_buckets — and batched-wave results match the
    direct CompiledFilter call."""
    f1 = rng.standard_normal((16, 20)).astype(np.float32)
    f2 = rng.standard_normal((12, 12)).astype(np.float32)
    g3, b3 = filters.gaussian(3), filters.box(3)
    obs.REGISTRY.reset()
    with obs.tracing():
        with FilterServeEngine(batch_size=3, execution="core") as eng:
            # warmup: one request per bucket
            eng.submit(f1, g3, spec=SPEC3, tenant="a")
            eng.submit(f2, g3, spec=SPEC3, tenant="a")
            assert eng.drain(timeout=60)
            num_buckets = eng.cache_size()
            assert num_buckets == 2
            # steady state: mixed tenants, both buckets, several waves
            reqs = []
            for i in range(9):
                fr, k, t = [(f1, g3, "a"), (f1, b3, "b"),
                            (f2, g3, "a")][i % 3]
                reqs.append(eng.submit(fr, k, spec=SPEC3, tenant=t))
            assert eng.drain(timeout=60)
            st = eng.stats()
            reg_recompiles = obs.REGISTRY.counter("serve.recompiles").value
            waves = obs.get_trace().events("serve_wave")
        assert st["recompiles"] == num_buckets
        assert reg_recompiles == num_buckets
        # every post-warmup wave was warm
        assert all(w.cache_hit for w in waves[num_buckets:])
        assert obs.REGISTRY.histogram("serve/request_us").summary()[
            "count"] == st["completed"]
        ref1g = np.asarray(SPEC3.compile(f1.shape, "core")(f1, g3))
        ref1b = np.asarray(SPEC3.compile(f1.shape, "core")(f1, b3))
        ref2g = np.asarray(SPEC3.compile(f2.shape, "core")(f2, g3))
        for i, r in enumerate(reqs):
            want = [ref1g, ref1b, ref2g][i % 3]
            np.testing.assert_allclose(r.result(timeout=10), want,
                                       atol=1e-5)
    obs.REGISTRY.reset()


def test_tenant_gains_upload_once_per_tenant(rng):
    """Three tenants, each with its own requant gains, over several waves
    of one bucket: the shared pipeline uploads each tenant's gains once
    and every result still equals the int64 reference."""
    import jax.numpy as jnp

    from repro.core.filter2d import filter2d
    from repro.core.requant import RequantSpec, requantize_ref
    spec = Filter2D(window=3, dtype="int8",
                    requant=RequantSpec(1, 0, rounding="nearest",
                                        dtype="int8"))
    gains = [RequantSpec(m, s, rounding="nearest", dtype="int8")
             for m, s in ((3, 5), (-7, 6), (11, 8))]
    k = rng.integers(-4, 5, (3, 3)).astype(np.int32)
    frames = [rng.integers(-20, 20, (10, 14)).astype(np.int8)
              for _ in range(4)]
    pipe = spec.compile(batched_shape((10, 14), 2), "core")
    before = pipe.operand_stats()["gain_uploads"]
    with FilterServeEngine(batch_size=2, execution="core") as eng:
        reqs = []
        for i in range(4):
            for t, g in enumerate(gains):
                reqs.append((eng.submit(frames[i], k, spec=spec, gains=g,
                                        tenant=f"t{t}"), i, g))
            assert eng.drain(timeout=60)
        st = eng.stats()
    assert st["recompiles"] == 1 and st["waves"] >= 4
    assert pipe.operand_stats()["gain_uploads"] - before == 3
    for r, i, g in reqs:
        acc = np.asarray(filter2d(jnp.asarray(frames[i]), jnp.asarray(k)))
        np.testing.assert_array_equal(r.result(timeout=10),
                                      requantize_ref(acc, g))


def test_bench_smoke_tiny(rng):
    """serving.bench end to end (tiny): rows in the BENCH_* schema, the
    aggregate row reports latency + sustained pixels/s, and the warm
    contract held (run_bench raises otherwise)."""
    from repro.serving import bench
    with obs.tracing():
        payload = bench.run_bench(duration_s=0.3, rate_rps=20.0,
                                  batch_size=2, execution="core", seed=1)
    assert payload["schema"] == "bench_trajectory_v1"
    agg = payload["rows"][0]
    assert agg["name"].startswith("serve/open_loop")
    assert agg["recompiles"] == agg["buckets"] == 3
    assert agg["pixels_per_s"] > 0 and agg["p99_us"] >= agg["p50_us"]
    buckets = [r for r in payload["rows"][1:]]
    assert len(buckets) == 3
    assert all("hbm_bytes_per_pixel" in r for r in buckets)
    assert any(r["dtype"] == "int8" for r in buckets)
    obs.REGISTRY.reset()
