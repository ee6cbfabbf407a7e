"""The trace reduction, on small synthetic traces and on a short trace
recorded on a TPU v5e (``data/``)."""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = "jit(scoped)/repro.filter2d.streaming/while/body"


def _op(s, e, name="%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", tf=""):
    return (float(s), float(e), name, tf)


def _trace():
    d0 = tr.Device("/device:TPU:0", [
        _op(100, 200, "%while.1 = (s32[]) while((s32[]) %t)", SCOPED),
        _op(110, 130, tf=SCOPED), _op(140, 160, tf=SCOPED),
        _op(300, 320, "%convert.1 = s32[2]{0} convert(u8[2]{0} %a)"),
        _op(400, 450, "%collective-permute-start.1 = (u8[3]{0}, u8[3]{0}) "
                      "collective-permute-start(u8[3]{0} %s)", SCOPED),
        _op(440, 480, tf=SCOPED),
    ], [(100.0, 200.0, "jit_scoped(1)"), (300.0, 320.0, "jit_convert(2)"),
        (400.0, 480.0, "jit_scoped(1)"), (900.0, 1100.0, "jit_scoped(1)")])
    d1 = tr.Device("/device:TPU:1", [_op(100, 300)],
                   [(100.0, 300.0, "jit_other(3)")])
    host = [(50.0, 1000.0, "bench.window"), (60.0, 250.0, "bench.dispatch"),
            (230.0, 320.0, "bench.block"), (480.0, 990.0, "bench.block")]
    return tr.Trace([d0, d1], host)


def test_union_merges_overlaps_and_touching_spans():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert tr.union([]) == []


def test_busy_and_idle_share_are_clipped_to_the_window():
    t = _trace()
    w = t.window()
    assert w == (50.0, 1000.0)
    # device 0: [100,200] + [300,320] + [400,480] = 200 ns
    assert tr.busy_s(t.devices[0], w) == pytest.approx(200e-9)
    assert tr.busy_s(t.devices[0], (150.0, 310.0)) == pytest.approx(60e-9)
    idle = ((1 - 200 / 950) + (1 - 200 / 950)) / 2
    assert tr.idle_share(t, w) == pytest.approx(idle)


def test_filter_runs_are_the_scoped_programs_inside_the_window():
    t = _trace()
    d0 = t.devices[0]
    assert tr.filter_modules(d0) == {"jit_scoped(1)"}
    runs = tr.filter_runs(d0, t.window())
    assert [r[:2] for r in runs] == [(100.0, 200.0), (400.0, 480.0)]
    assert tr.filter_runs(t.devices[1], t.window()) == []


def test_collective_share_and_opcodes():
    t = _trace()
    assert tr.collective_share(t.devices[0], t.window()) == pytest.approx(
        50 / 200)
    assert tr.collective_share(t.devices[1], t.window()) is None
    assert tr.opcode("%copy-start = (s32[1,2]{1,0:T(1,128)}, u32[]{:S(2)})"
                     " copy-start(s32[1,2]{1,0} %a)") == "copy-start"
    assert tr.opcode("%all-reduce.3 = f32[] all-reduce(f32[] %x)") == \
        "all-reduce"


def test_self_time_takes_nested_ops_out_of_their_parent():
    t = _trace()
    selfs = dict(tr.self_times(t.devices[0].ops[:3]))
    assert selfs["repro.filter2d.streaming:while.1"] == 60.0
    assert selfs["repro.filter2d.streaming:fusion.1"] == 20.0


def test_breakdown_names_gaps_by_what_the_host_was_doing():
    t = _trace()
    b = tr.breakdown(t, t.window())
    ops = dict(b["device_ops"])
    assert ops["fusion.1"] == pytest.approx(200e-9)     # device 1, no scope
    assert ops["repro.filter2d.streaming:fusion.1"] == pytest.approx(80e-9)
    assert list(ops)[0] == "fusion.1"
    gaps = b["idle_gaps"]
    assert gaps[0] == ["bench.block", pytest.approx(520e-9)]
    # [200, 300] lies under both dispatch and block: the shorter names it
    assert gaps[1:] == [["bench.block", pytest.approx(100e-9)],
                        ["none", pytest.approx(80e-9)],
                        ["bench.dispatch", pytest.approx(50e-9)]]
    assert all(s >= 0 for _, s in gaps)


def test_trace_round_trips_through_its_json(tmp_path):
    t = _trace()
    p = str(tmp_path / "t.json.gz")
    t.save(p)
    assert tr.Trace.load(p) == t


# A trace recorded on one TPU v5e: three frames of the paper stream
# (1080p uint8, the jnp strip scan that ``auto`` picks), read by hand
# before the reduction was written against it.
PB = os.path.join(DATA, "paper_u8_1080p.stream.xplane.pb")
JSON = os.path.join(DATA, "paper_u8_1080p.stream.trace.json.gz")


def test_a_chip_trace_reads_into_the_recorded_reduction():
    assert tr.load_xspace(PB) == tr.Trace.load(JSON)


def test_the_chip_trace_reduces_to_its_recorded_numbers():
    from bench import generator, roofline
    from bench.metrics import device_idle_pct, filter_roofline
    t = tr.Trace.load(JSON)
    w = t.window()
    d, = t.devices
    assert d.name == "/device:TPU:0"
    assert tr.filter_modules(d) == {"jit_scoped(16112385189022924104)"}
    runs = tr.filter_runs(d, w)
    assert len(runs) == 2
    assert sum(e - s for s, e, _ in runs) == 696069.0
    assert tr.busy_s(d, w) == pytest.approx(910458e-9)
    assert tr.collective_share(d, w) is None
    ops = sum(1 for o in d.ops if tr.FILTER_SCOPE in o[3])
    assert ops == 282
    b = tr.breakdown(t, w)
    assert b["device_ops"][0][0] == \
        "repro.filter2d.streaming:multiply_reduce_fusion.3"
    assert {n for n, _ in b["idle_gaps"]} == {"bench.dispatch"}
    obs = generator.Observation(
        window_s=0.0, pixels_done=0.0, attempted=3, failed=0, trace=t,
        trace_window=w, device_kind="TPU v5 lite",
        work_per_call=roofline.filter_work(1080, 1920, 7, "uint8",
                                           "uint8"))
    assert filter_roofline.read(obs) == pytest.approx(
        100 * 2 * 4_147_200 / 819e9 / 696069e-9)
    assert device_idle_pct.read(obs) == pytest.approx(
        100 * (1 - 910458 / (w[1] - w[0])))
