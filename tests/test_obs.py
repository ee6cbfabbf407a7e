"""The observability subsystem (repro.obs): ring/sink/registry semantics,
event emission through the real pipeline, and explain()'s pin that every
byte figure IS the existing static accounting.

Acceptance pins:
  * default-off: no trace object, no events, no registry traffic — the
    hooks reduce to one attribute-test branch;
  * the ring is bounded (oldest dropped), the JSONL sink is complete and
    round-trips through ``json.loads``;
  * histogram percentiles agree with ``np.percentile`` (one estimator
    everywhere); instruments are thread-safe under concurrent writers;
  * plan / auto_select / compile / execute events carry exactly the
    decisions the pipeline made (winner == compiled geometry, fired rule
    == resolved executor, cache_hit flips on the first call only);
  * ``explain()`` numbers equal ``vmem_working_set()`` /
    ``hbm_bytes_per_pixel()`` / ``halo.read_amplification`` exactly.
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import filters
from repro.core.pipeline import Filter2D
from repro.kernels.filter2d import halo
from repro.obs.events import AutoSelectEvent, ExecuteEvent, Trace
from repro.obs.metrics import Histogram, Registry, percentiles


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with observability off and a clean
    registry — the module switch and REGISTRY are process-wide."""
    obs.disable()
    obs.REGISTRY.reset()
    yield
    obs.disable()
    obs.REGISTRY.reset()


def _ev(i=0):
    return ExecuteEvent(key=f"k{i}", wall_us=10.0 * (i + 1),
                        pixels_per_s=1e6, cache_hit=i > 0, cache_size=1)


# ---------------------------------------------------------------------------
# Ring buffer + JSONL sink
# ---------------------------------------------------------------------------


def test_default_off_no_trace_no_events():
    assert not obs.enabled()
    assert obs.get_trace() is None
    assert obs.events.events() == []          # module accessor: empty list
    obs.emit(_ev())                           # no-op, must not raise


def test_ring_bounded_oldest_dropped():
    trace = obs.enable(capacity=4)
    for i in range(10):
        trace.emit(_ev(i))
    evs = trace.events()
    assert len(evs) == 4
    assert [e.key for e in evs] == ["k6", "k7", "k8", "k9"]  # oldest first
    assert trace.emitted == 10                # total, not ring length
    recs = trace.records()
    assert [r["seq"] for r in recs] == [7, 8, 9, 10]


def test_kind_filter():
    trace = obs.enable()
    trace.emit(_ev())
    trace.emit(AutoSelectEvent(rule="pixel_cache", execution="pallas",
                               reason="fits", resident_vmem_bytes=1,
                               vmem_budget=2, has_mesh=False))
    assert len(trace.events(kind="execute")) == 1
    assert len(trace.events(kind="auto_select")) == 1
    assert len(trace.events()) == 2


def test_jsonl_sink_roundtrip(tmp_path):
    p = str(tmp_path / "trace.jsonl")
    with obs.tracing(capacity=2, jsonl=p) as trace:  # ring smaller than emits
        for i in range(6):
            trace.emit(_ev(i))
    lines = [json.loads(l) for l in open(p)]
    assert len(lines) == 6                    # the sink keeps everything
    assert [l["seq"] for l in lines] == list(range(1, 7))
    assert lines[0]["kind"] == "execute"
    assert lines[0]["key"] == "k0" and lines[0]["wall_us"] == 10.0


def test_enable_replaces_disable_clears():
    t1 = obs.enable()
    t2 = obs.enable()
    assert obs.get_trace() is t2 and t1 is not t2
    obs.disable()
    assert not obs.enabled()


def test_trace_thread_safety_smoke():
    trace = Trace(capacity=10_000)

    def writer(base):
        for i in range(250):
            trace.emit(_ev(base + i))

    threads = [threading.Thread(target=writer, args=(1000 * t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert trace.emitted == 1000
    assert len(trace.events()) == 1000
    assert sorted(r["seq"] for r in trace.records()) == list(range(1, 1001))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_histogram_percentiles_match_numpy():
    h = Histogram("t")
    samples = [float(v) for v in np.random.default_rng(0).integers(
        1, 1000, 200)]
    for s in samples:
        h.record(s)
    for q in (50.0, 90.0, 99.0):
        assert h.percentile(q) == pytest.approx(np.percentile(samples, q))
    s = h.summary()
    assert s["count"] == 200
    assert s["min"] == min(samples) and s["max"] == max(samples)
    assert s["mean"] == pytest.approx(np.mean(samples))
    assert s["p50"] == pytest.approx(np.percentile(samples, 50))


def test_percentiles_empty_is_nan():
    assert all(np.isnan(v) for v in percentiles([]))


def test_histogram_reservoir_bounds_percentile_window():
    h = Histogram("t", reservoir=10)
    for v in [1000.0] * 5 + [1.0] * 10:       # the 1000s age out
        h.record(v)
    assert h.count == 15                      # running count sees all
    assert h.percentile(99) == 1.0            # window sees the last 10


def test_registry_get_or_create_and_reset():
    r = Registry()
    assert r.counter("a") is r.counter("a")
    assert r.histogram("h") is r.histogram("h")
    r.counter("a").inc(3)
    assert r.counters() == {"a": 3}
    r.reset()
    assert r.counters() == {} and r.histograms() == {}


def test_registry_thread_safety_smoke():
    r = Registry()

    def worker():
        for _ in range(500):
            r.counter("hits").inc()
            r.histogram("lat").record(1.0)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.counter("hits").value == 2000
    assert r.histogram("lat").count == 2000


def test_registry_export_schema():
    r = Registry()
    r.counter("pipeline.calls").inc(2)
    for v in (10.0, 20.0, 30.0):
        r.histogram("call/x").record(v)
    out = r.export()
    assert out["schema"] == "obs_metrics_v1"
    by_name = {row["name"]: row for row in out["rows"]}
    assert by_name["counter/pipeline.calls"]["value"] == 2
    lat = by_name["latency/call/x"]
    # aligned with the BENCH_*.json row vocabulary (compare.py machinery)
    assert lat["us_per_call"] == lat["p50_us"] == 20.0
    assert {"p90_us", "p99_us", "mean_us", "max_us", "count"} <= set(lat)


# ---------------------------------------------------------------------------
# Events through the real pipeline
# ---------------------------------------------------------------------------

# geometry distinct from other test modules: the CompiledFilter memo cache
# is process-wide, so a reused (spec, shape, knobs) would skip compilation
# and emit no compile event
EH, EW = 48, 136


def _pipeline(window=5, **kw):
    spec = Filter2D(window=window)
    return spec, spec.compile((EH, EW), "pallas", regime="stream",
                              strip_h=12, tile_w=128, **kw)


def test_compile_and_execute_events(rng):
    obs.enable()
    spec, cf = _pipeline()
    comp = obs.events.events(kind="compile")
    assert len(comp) == 1
    ce = comp[0]
    assert ce.execution == "pallas" and ce.regime == "stream"
    assert ce.frame_shape == (EH, EW)
    assert (ce.strip_h, ce.tile_w) == (cf.strip_h, cf.tile_w)
    assert ce.vmem_working_set == cf.vmem_working_set()
    assert ce.hbm_bytes_per_pixel == pytest.approx(cf.hbm_bytes_per_pixel())
    assert ce.spec_hash == hash(spec)
    assert ce.wall_ms > 0

    x = jnp.asarray(rng.standard_normal((EH, EW)).astype(np.float32))
    k = jnp.asarray(filters.gaussian(5))
    cf(x, k)
    cf(x, k)
    exe = obs.events.events(kind="execute")
    assert len(exe) == 2
    assert exe[0].cache_hit is False          # first call compiles
    assert exe[1].cache_hit is True           # second hits the cache
    assert exe[0].cache_size == exe[1].cache_size == 1
    assert exe[1].wall_us > 0 and exe[1].pixels_per_s > 0
    counters = obs.REGISTRY.counters()
    assert counters["pipeline.compiles"] == 1
    assert counters["pipeline.calls"] == 2
    assert counters["pipeline.cache_hits"] == 1
    hists = obs.REGISTRY.histograms()
    assert set(hists) == {f"call/{cf._obs_key}", "span/repro.call.operands",
                          "span/repro.call.launch"}
    assert cf._obs_key.startswith("pallas/stream/")
    assert all(h.count == 2 for h in hists.values())


def test_auto_select_event_rules():
    obs.enable()
    spec = Filter2D(window=5)
    cf = spec.compile((EH, EW + 8), "auto")   # fits the default budget
    ev = obs.events.events(kind="auto_select")[-1]
    assert ev.rule == "pixel_cache" and cf.execution == "pallas"
    assert ev.execution == cf.execution
    assert ev.resident_vmem_bytes == cf.resident_vmem_bytes
    assert ev.resident_vmem_bytes <= ev.vmem_budget
    assert not ev.has_mesh

    cf2 = spec.compile((2048, 4104), "auto", vmem_budget=64 * 1024)
    ev2 = obs.events.events(kind="auto_select")[-1]
    assert ev2.rule == "row_buffer" and cf2.execution == "streaming"
    assert ev2.resident_vmem_bytes > ev2.vmem_budget

    # explicit executions emit no auto_select event
    n = len(obs.events.events(kind="auto_select"))
    spec.compile((EH, EW + 16), "core")
    assert len(obs.events.events(kind="auto_select")) == n


def test_plan_event_candidate_scan():
    obs.enable()
    spec = Filter2D(window=9, dtype="int8", num_filters=2)
    cf = spec.compile((1024, 4104), "auto", vmem_budget=4 * 2 ** 20)
    assert cf.execution == "pallas" and cf.regime == "stream"
    pe = obs.events.events(kind="plan")[-1]
    assert (pe.strip_h, pe.tile_w) == (cf.strip_h, cf.tile_w)
    assert pe.candidates                       # the full scan ran
    assert all(len(c) == 3 for c in pe.candidates)
    # the winner's amplification is within 2% of the scan minimum
    # (the widest-within-2% rule the why string states)
    amps = [a for _, _, a in pe.candidates]
    won = [a for t, s, a in pe.candidates
           if (s, t) == (pe.strip_h, pe.tile_w)]
    assert won and won[0] <= min(amps) * 1.02
    assert "2%" in pe.why


def test_plan_event_fixed_knob_paths():
    obs.enable()
    halo.derive_strip_tile(256, 512, 5, dtype=jnp.float32,
                           vmem_budget=1 << 20, strip_h=16, tile_w=128)
    pe = obs.events.events(kind="plan")[-1]
    assert (pe.strip_h, pe.tile_w) == (16, 128)
    assert pe.candidates == () and "fixed both" in pe.why


def test_events_are_jsonl_serialisable_end_to_end(tmp_path, rng):
    p = str(tmp_path / "obs.jsonl")
    with obs.tracing(jsonl=p):
        spec = Filter2D(window=5)
        cf = spec.compile((EH + 4, EW), "pallas", regime="stream",
                          strip_h=13, tile_w=128)
        x = jnp.asarray(rng.standard_normal((EH + 4, EW)).astype(
            np.float32))
        cf(x, jnp.asarray(filters.gaussian(5)))
    kinds = [json.loads(l)["kind"] for l in open(p)]
    assert kinds.count("compile") == 1 and kinds.count("execute") == 1


# ---------------------------------------------------------------------------
# explain() — numbers pinned to the existing accounting
# ---------------------------------------------------------------------------


def test_explain_dict_agrees_with_accounting_exactly():
    _, cf = _pipeline(overlap=True)
    d = cf.explain(as_dict=True)
    assert d["vmem"]["working_set_bytes"] == cf.vmem_working_set()
    assert d["vmem"]["budget_bytes"] == cf.vmem_budget
    assert d["vmem"]["resident_estimate_bytes"] == cf.resident_vmem_bytes
    assert d["hbm"]["bytes_per_pixel"] == cf.hbm_bytes_per_pixel()
    assert d["hbm"]["read_bytes_per_pixel"] == \
        halo.read_bytes_per_pixel(cf.plan)
    assert d["hbm"]["write_bytes_per_pixel"] == \
        halo.hbm_write_bytes_per_pixel(cf.plan)
    assert d["hbm"]["read_amplification"] == \
        halo.read_amplification(cf.plan)
    assert d["geometry"]["strips"] == cf.plan.rows.n
    assert d["geometry"]["tiles"] == cf.plan.cols.n
    assert d["execution"]["executor"] == cf.execution
    assert d["execution"]["rule"] == cf.selection[0]


def test_explain_roofline_from_shared_constants():
    """explain() states the roofline of the device it runs on, from the
    keyed peak table only: a device without published peaks (the CPU
    here) gets no prediction, and the v5e entry gives the two-ceiling
    rate for the same per-pixel costs."""
    _, cf = _pipeline()
    d = cf.explain(as_dict=True)
    roof = d["roofline"]
    w = cf.spec.window
    assert roof["flops_per_pixel"] == 2.0 * w * w          # direct, N=1
    assert roof["device_kind"] == jax.devices()[0].device_kind
    assert obs.roofline.peaks(roof["device_kind"]) is None
    assert roof["predicted_pixels_per_s"] is None and roof["bound"] is None
    assert "no roofline prediction" in cf.explain()
    v5e = obs.roofline.PEAKS["TPU v5 lite"]
    assert v5e == {"bf16_flops": 197e12, "int8_ops": 393e12,
                   "hbm_bw": 819e9}
    on = obs.roofline.predicted_pixel_rate(
        roof["flops_per_pixel"], d["hbm"]["bytes_per_pixel"], "TPU v5 lite")
    assert on["peak_flops"] == v5e["bf16_flops"]
    expect = min(v5e["bf16_flops"] / roof["flops_per_pixel"],
                 v5e["hbm_bw"] / d["hbm"]["bytes_per_pixel"])
    assert on["predicted_pixels_per_s"] == pytest.approx(expect)
    assert on["bound"] in ("compute", "memory")


def test_explain_text_report_and_repr():
    _, cf = _pipeline()
    text = cf.explain()
    assert "executor  pallas" in text
    assert "strips" in text and "tiles" in text
    assert "vmem" in text and "roofline" in text
    assert cf.selection[1].split("->")[0].strip()[:20] in text

    r = repr(cf)
    assert "execution='pallas'" in r
    assert "banks ext=" in r and "out=" in r   # the one-line summary
    assert f"{cf.plan.rows.n}x{cf.plan.cols.n} grid" in r


def test_explain_without_plan():
    spec = Filter2D(window=5)
    cf = spec.compile((EH, EW + 24), "core")
    d = cf.explain(as_dict=True)
    # core keeps an accounting-only plan when it can; either way the
    # report renders and the executor section is truthful
    assert d["execution"]["executor"] == "core"
    assert isinstance(cf.explain(), str)


# ---------------------------------------------------------------------------
# Zero-overhead-off + no cross-talk
# ---------------------------------------------------------------------------


def test_off_means_no_registry_traffic(rng, monkeypatch):
    """Off and with no profiler session, a call — the first one traces
    and compiles — opens no span: no annotation is built, no metadata
    formatted, nothing recorded."""
    def no_annotation(*a, **k):
        raise AssertionError("a span was opened with nothing recording")

    monkeypatch.setattr(obs.profiler, "TraceAnnotation", no_annotation)
    spec = Filter2D(window=5)
    cf = spec.compile((EH, EW + 32), "pallas", regime="stream",
                      strip_h=12, tile_w=128)
    x = jnp.asarray(rng.standard_normal((EH, EW + 32)).astype(np.float32))
    assert not obs.recording()
    cf(x, jnp.asarray(filters.gaussian(5)))
    cf(x, jnp.asarray(filters.gaussian(5)))
    assert cf.cache_size() == 1
    assert obs.REGISTRY.counters() == {}
    assert obs.REGISTRY.histograms() == {}
    assert obs.get_trace() is None


class _Unformattable:
    def __str__(self):
        raise AssertionError("span metadata formatted while off")

    __repr__ = __str__


def test_span_off_is_a_shared_no_op():
    with obs.span("repro.test.off", meta=_Unformattable()) as s:
        assert s is None
    assert obs.span("a") is obs.span("b")
    assert obs.REGISTRY.histograms() == {}


def test_span_records_its_duration_when_obs_is_on():
    obs.enable()
    assert obs.recording()
    for _ in range(3):
        with obs.span("repro.test.on", wave=1) as s:
            assert s is not None
            s.set(requests="1 2")
    [(name, h)] = list(obs.REGISTRY.histograms().items())
    assert name == "span/repro.test.on" and h.count == 3
    assert h.summary()["min"] >= 0


# ---------------------------------------------------------------------------
# Spans on the profiler's host plane
# ---------------------------------------------------------------------------


def test_call_spans_land_on_the_profiler_host_plane(tmp_path, rng):
    """With ``obs`` off, a profiler session alone makes the call's spans
    record: they sit on a ``/host:`` plane inside the session's window,
    on the trace's clock, and in ``span/<name>`` histograms."""
    from bench import trace_reduce

    spec = Filter2D(window=3, dtype="int8")
    cf = spec.compile((16, 24), "core")
    x = jnp.asarray(rng.integers(-8, 8, (16, 24)).astype(np.int8))
    k = jnp.ones((3, 3), jnp.int8)
    cf(x, k)                                   # compiled before the window
    assert not obs.recording()
    with jax.profiler.trace(str(tmp_path)):
        assert obs.recording()
        with jax.profiler.TraceAnnotation("bench.window"):
            jax.block_until_ready([cf(x, k) for _ in range(3)])
    assert not obs.recording()
    [pb] = list(tmp_path.rglob("*.xplane.pb"))
    trace = trace_reduce.load_xspace(str(pb), host_prefix="repro.")
    names = [n for _, _, n in trace.host]
    assert names.count("repro.call.operands") == 3
    assert names.count("repro.call.launch") == 3
    lo, hi = trace_reduce.load_xspace(str(pb)).window()
    assert all(lo <= s <= e <= hi for s, e, _ in trace.host)
    # leaves, one after the other: operands then launch, per call
    spans = sorted(trace.host)
    assert [n for _, _, n in spans] == ["repro.call.operands",
                                        "repro.call.launch"] * 3
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
    hists = obs.REGISTRY.histograms()
    assert {n: h.count for n, h in hists.items()} == {
        "span/repro.call.operands": 3, "span/repro.call.launch": 3}


def test_launch_span_marks_the_call_that_compiled(tmp_path, rng):
    from jax.profiler import ProfileData

    spec = Filter2D(window=3, dtype="int8")
    cf = spec.compile((16, 40), "core")
    x = jnp.asarray(rng.integers(-8, 8, (16, 40)).astype(np.int8))
    k = jnp.ones((3, 3), jnp.int8)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready([cf(x, k) for _ in range(2)])
    [pb] = list(tmp_path.rglob("*.xplane.pb"))
    launches = sorted(
        (e.start_ns, dict(e.stats))
        for p in ProfileData.from_file(str(pb)).planes
        if p.name.startswith("/host:") for line in p.lines
        for e in line.events if e.name == "repro.call.launch")
    assert [m.get("compiled") for _, m in launches] == [1, None]

