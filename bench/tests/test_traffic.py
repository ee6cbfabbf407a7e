"""The load generator on the CPU, with no chip and no real executor: the
schedule repeats from a seed, the Zipf shares come out as set, and a stall
shows as latency counted from due time for the requests behind it."""
import time

import numpy as np
import pytest

from bench import generator, workload
from bench.metrics import common

TRAFFIC = {"loop": "open", "rate_fps": 200.0, "tenants": 8, "zipf_s": 1.0,
           "frame_pool": 16, "sample_outputs": 4, "probe_pixels": 8,
           "trace_seconds": 1.0}
BIG = 2 ** 31 + 977          # the driver's seeds pass 32 signed bits


def test_schedule_repeats_from_a_seed():
    a = workload.schedule(TRAFFIC, 5.0, workload.rngs(BIG)["schedule"])
    b = workload.schedule(TRAFFIC, 5.0, workload.rngs(BIG)["schedule"])
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    assert np.all(np.diff(a["due"]) >= 0) and a["due"].max() < 5.0


def test_seeds_change_the_order_not_the_work():
    a = workload.schedule(TRAFFIC, 5.0, workload.rngs(1)["schedule"])
    b = workload.schedule(TRAFFIC, 5.0, workload.rngs(2)["schedule"])
    assert len(a["due"]) == len(b["due"]) == 1000
    assert not np.array_equal(a["due"], b["due"])
    for key in ("tenant", "frame"):
        np.testing.assert_array_equal(np.bincount(a[key]),
                                      np.bincount(b[key]))


@pytest.mark.parametrize("n,k,s", [(1000, 8, 1.0), (37, 8, 1.0),
                                   (500, 3, 0.5)])
def test_zipf_shares_come_out_as_set(n, k, s):
    counts = workload.zipf_counts(n, k, s)
    p = 1.0 / np.arange(1, k + 1) ** s
    p /= p.sum()
    assert counts.sum() == n
    assert np.all(np.abs(counts - n * p) < 1.0)
    assert np.all(np.diff(counts) <= 0)


def test_tenant_draws_follow_the_zipf_counts():
    sch = workload.schedule(TRAFFIC, 5.0, workload.rngs(3)["schedule"])
    np.testing.assert_array_equal(np.bincount(sch["tenant"], minlength=8),
                                  workload.zipf_counts(1000, 8, 1.0))


def test_frames_and_coefficients_repeat_from_a_seed():
    cfg = {"height": 16, "width": 32, "dtype": "uint8", "window": 7,
           "requant": {"dtype": "uint8", "rounding": "nearest"},
           "coeffs": {"kind": "uniform_int", "low": 0, "high": 16,
                      "center_add": 1}}
    a = workload.coeff_sets(cfg, workload.rngs(BIG)["coeffs"], 3)
    b = workload.coeff_sets(cfg, workload.rngs(BIG)["coeffs"], 3)
    for (ka, ga), (kb, gb) in zip(a, b):
        np.testing.assert_array_equal(ka, kb)
        assert ga == gb
    np.testing.assert_array_equal(
        workload.host_frames(cfg, workload.rngs(BIG)["frames"], 2),
        workload.host_frames(cfg, workload.rngs(BIG)["frames"], 2))


class _StallingExecutor:
    """Stands in for a compiled filter: returns zeros at once, except on
    one call, which sleeps first."""

    def __init__(self, shape, stall_s):
        self.shape, self.stall_s = shape, stall_s
        self.calls, self.stall_call, self.stalled = 0, None, None

    def __call__(self, x, coeffs, gains=None):
        self.calls += 1
        if self.calls == self.stall_call:
            t0 = time.perf_counter()
            time.sleep(self.stall_s)
            self.stalled = (t0, time.perf_counter())
        return np.zeros(self.shape, np.uint8)


def test_a_stall_shows_as_latency_from_due_time(monkeypatch):
    import repro.serving.engine as engine_mod
    made = []

    class FakeEngine(engine_mod.FilterServeEngine):
        def __init__(self, **kw):
            def compile_fn(spec, shape):
                made.append(_StallingExecutor(shape, 0.3))
                return made[-1]
            super().__init__(compile_fn=compile_fn, **kw)

    monkeypatch.setattr(engine_mod, "FilterServeEngine", FakeEngine)
    cfg = {"height": 16, "width": 32, "dtype": "uint8", "window": 7,
           "border": "mirror",
           "requant": {"dtype": "uint8", "rounding": "nearest"},
           "coeffs": {"kind": "uniform_int", "low": 0, "high": 16,
                      "center_add": 1}}
    loop = generator.OpenLoop(cfg, dict(TRAFFIC), 11, devices=None)
    fake = made[0]
    fake.stall_call = fake.calls + 20
    obs = loop.window(1.0)
    loop.engine.shutdown()
    assert fake.stalled is not None
    s0, s1 = fake.stalled
    rec = obs.requests
    lat = common.served_latency_ms(obs) / 1e3
    np.testing.assert_allclose(lat, rec["done"] - rec["due"])
    behind = (rec["due"] >= s0) & (rec["due"] < s1 - 0.05)
    assert behind.sum() >= 10
    # every request due during the stall waits for its end, counted from
    # when it was due, although the generator submitted it on time
    assert np.all(lat[behind] >= (s1 - rec["due"][behind]) - 0.005)
    assert np.percentile(obs.late_s[behind], 95) < 0.05
    assert obs.failed == 0
