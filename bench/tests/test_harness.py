"""A whole run of each cell's loop on the CPU at a small size, past the
harness's look for a chip: sound, it comes out correct; with the control
(the reference one precision step down) in the program's place, or with
the timed path broken underneath, ``correct`` comes out false."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, reference, workload

ROOT = workload.ROOT
SMALL = {"height": 64, "width": 256}
BENCH = workload.benchmark()


def _files(cell):
    """A cell's configuration and traffic, read from their files by the
    cell's name (``<config>.<traffic>``), cut to a small frame."""
    config, traffic = cell.split(".")
    cfg = workload.load_json(os.path.join(ROOT, "bench", "configs",
                                          f"{config}.json"))
    return dict(cfg, **SMALL), dict(workload.traffic(traffic))


def _run(cell, seconds=0.3, rate=None, seed=2 ** 31 + 5):
    cfg, tr = _files(cell)
    if rate is not None:
        tr["rate_fps"] = rate
    import jax
    with open(os.devnull, "w") as log:
        return harness.run_loaded(BENCH, cell, cfg, tr, seed, seconds, False,
                                  jax.devices()[:1], log=log)


def _patch_call(monkeypatch, fn):
    """Put ``fn(self, frame, coeffs, gains)`` in the compiled filter's
    place: the timed path then runs it for every frame or wave."""
    from repro.core.pipeline import CompiledFilter
    monkeypatch.setattr(CompiledFilter, "__call__", fn)


def _control(self, frame, coeffs, gains=None):
    import jax.numpy as jnp
    cfg = {"border": self.spec.border.policy,
           "requant": (None if self.spec.requant is None else
                       {"rounding": self.spec.requant.rounding,
                        "dtype": self.spec.requant.dtype})}
    g = None if gains is None else (gains.multiplier, gains.shift)
    x, k = np.asarray(frame), np.asarray(coeffs)
    planes = x.reshape((-1,) + x.shape[-3:-1]) if x.ndim == 4 else x[None]
    out = np.stack([reference.control(p, k, cfg, g) for p in planes])
    return jnp.asarray(out.reshape(x.shape))


STREAMS = ("paper_u8_1080p.stream", "hd_f32_1080p.stream")
SERVED = "paper_u8_1080p.served"


@pytest.mark.parametrize("cell", STREAMS + (SERVED,))
def test_a_sound_run_is_correct_and_well_formed(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    entries = harness.metrics_for(BENCH, cell, traced=False)
    assert set(res["metrics"]) == {m["name"] for m in entries}
    assert res["failed"] == 0 and res["attempted"] > 0
    json.dumps(res, allow_nan=False)


@pytest.mark.parametrize("cell", STREAMS + (SERVED,))
def test_the_control_in_the_programs_place_is_not_correct(cell,
                                                          monkeypatch):
    _patch_call(monkeypatch, _control)
    res = _run(cell)
    assert not res["correct"]
    assert res["checks"]["max_abs_gap"]["value"] > \
        res["checks"]["max_abs_gap"]["limit"]


def _altered(self, frame, coeffs, gains=None):
    """The right answer with one pixel changed where it is produced."""
    y = _original(self, frame, coeffs, gains)
    return y.at[..., 5, 7].add(1) if y.ndim == 2 else \
        y.at[..., 5, 7, :].add(1)


def _unchanged(self, frame, coeffs, gains=None):
    """A step that hands its input back unfiltered."""
    return frame


_original = None


@pytest.mark.parametrize("fault", ("altered", "unchanged"))
@pytest.mark.parametrize("cell", STREAMS + (SERVED,))
def test_a_broken_filter_is_not_correct(cell, fault, monkeypatch):
    global _original
    from repro.core.pipeline import CompiledFilter
    _original = CompiledFilter.__call__
    _patch_call(monkeypatch, _altered if fault == "altered" else _unchanged)
    assert not _run(cell)["correct"]


def test_half_of_each_wave_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    import repro.serving.engine as engine_mod
    real = engine_mod.admit_batch

    def half(frames, batch):
        x = real(frames, batch)
        keep = (len(frames) + 1) // 2
        return x.at[keep:].set(jnp.zeros_like(x[keep:]))

    monkeypatch.setattr(engine_mod, "admit_batch", half)
    res = _run(SERVED, seconds=0.4, rate=600.0)
    assert not res["correct"]
    assert res["checks"]["unserved"]["value"] == 0
    assert res["checks"]["max_abs_gap"]["value"] > 0


SHARDED = r"""
import os, sys, json, jax
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
from bench import harness, workload
import repro.core.distributed as dist
if {broken}:
    # the exchange between chips left out: each shard keeps its own rows
    class _Lax:
        def __getattr__(self, name):
            return getattr(jax.lax, name)
        @staticmethod
        def ppermute(x, axis_name, perm):
            return x
    class _Jax:
        lax = _Lax()
        def __getattr__(self, name):
            return getattr(jax, name)
    dist.jax = _Jax()
bench = workload.benchmark()
cell = "uhd_u8_2160p_x4.stream"
cfg = dict(workload.load_json(os.path.join({root!r}, "bench", "configs",
                                           "uhd_u8_2160p_x4.json")),
           height=64, width=256)
tr = workload.traffic("stream")
with open(os.devnull, "w") as log:
    res = harness.run_loaded(bench, cell, cfg, tr, 9, 0.3, False,
                             jax.devices()[:4], log=log)
print(json.dumps({{"correct": res["correct"],
                   "devices": res["device"]["count"]}}))
"""


@pytest.mark.parametrize("broken", (False, True))
def test_the_sharded_halo_exchange_is_checked(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", SHARDED.format(root=ROOT, broken=broken)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["correct"] is (not broken)


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "paper_u8_1080p.stream", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
