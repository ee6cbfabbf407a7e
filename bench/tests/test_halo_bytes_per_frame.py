"""The reader of the program's halo counters, on a hand-seeded registry
and on what the program counts on 4 virtual CPU devices: bytes per
sharded call, and None where the program counted nothing (as a program
without the counters does)."""
import json
import os
import subprocess
import sys

import pytest

from bench import generator, workload
from bench.metrics import halo_bytes_per_frame
from repro import obs

ROOT = workload.ROOT
CELL = "uhd_u8_2160p_x4.stream"
SHARDED = ("collective_pct.sharded", "halo_bytes_per_frame.sharded",
           "filter_roofline.sharded", "device_idle_pct.sharded",
           "call_launch_us.sharded")


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.disable()
    obs.REGISTRY.reset()
    yield
    obs.REGISTRY.reset()


def _obs():
    return generator.Observation(window_s=1.0, pixels_done=0.0, attempted=0,
                                 failed=0)


def test_reads_none_without_the_counters():
    obs.REGISTRY.counter("pipeline.calls").inc(5)
    assert halo_bytes_per_frame.read(_obs()) is None
    assert halo_bytes_per_frame.describe(_obs()) == "no counters"
    # reading creates no counter
    assert set(obs.REGISTRY.counters()) == {"pipeline.calls"}


@pytest.mark.parametrize("calls,nbytes,want", [
    (1990, 1990 * 92160, 92160.0), (3, 3 * 6144, 6144.0), (1, 0, 0.0)])
def test_reads_the_bytes_per_sharded_call(calls, nbytes, want):
    obs.REGISTRY.counter("pipeline.sharded_calls").inc(calls)
    if nbytes:
        obs.REGISTRY.counter("pipeline.halo_bytes").inc(nbytes)
    assert halo_bytes_per_frame.read(_obs()) == pytest.approx(want)
    assert halo_bytes_per_frame.describe(_obs()) == (
        f"sharded_calls={calls} halo_bytes={nbytes}")


PROGRAM = r"""
import os, sys, json
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
import jax
from bench import generator, workload
from bench.metrics import halo_bytes_per_frame
from repro import obs
cfg = dict(workload.load_json(os.path.join({root!r}, "bench", "configs",
                                           "uhd_u8_2160p_x4.json")),
           height=64, width=256)
tr = dict(workload.traffic("stream"), ring_frames=2)
loop = generator.ClosedLoop(cfg, tr, 5, jax.devices()[:4])
obs.REGISTRY.reset()
obs.enable()
n, _ = loop._run(frames=3, keep=False)
obs.disable()
print(json.dumps({{"calls": n,
                   "read": halo_bytes_per_frame.read(None)}}))
"""


def test_reads_what_the_program_counts_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", PROGRAM.format(root=ROOT)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["calls"] == 3
    assert res["read"] == 2 * 4 * 3 * 256 * 1     # 2·n·r·W·itemsize


@pytest.mark.parametrize("name", SHARDED)
def test_the_metric_is_declared_for_the_sharded_cell(name):
    (m,) = [m for m in workload.benchmark()["per_layer"]
            if m["name"] == name]
    assert m["moves"] == "mpix_s" and m["workloads"] == [CELL]


def test_the_cell_runs_the_config_on_four_chips():
    bench = workload.benchmark()
    w = workload.cell(bench, CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "uhd_u8_2160p_x4", "stream", 4)
    cfg = workload.config(bench, w["config"])
    assert (cfg["height"], cfg["width"], cfg["dtype"]) == (2160, 3840,
                                                           "uint8")
