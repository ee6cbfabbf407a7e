"""chip_smoke.py's phases, rehearsed on the CPU at small sizes.

The script itself refuses to run without a TPU; these tests import its
phase functions and drive them in Pallas interpret mode, so the smoke's
logic (what it compiles, what it compares, what it asserts) stays tested
in tier-1. The sharded phase needs four devices, so it runs in a child
process on four virtual CPU devices.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load()


def test_paper_stream_phase(capsys):
    cf = smoke.phase_paper_stream(np.random.default_rng(0), (64, 256),
                                  interpret=True)
    assert cf.execution == "pallas" and cf.interpret is True
    out = capsys.readouterr().out
    assert "phase=paper_stream " in out and "max_abs_delta=0.0" in out


def test_stream_phase_runs_multi_strip(capsys):
    cf = smoke.phase_stream_4k(np.random.default_rng(1), (96, 256),
                               interpret=True, vmem_budget=2 * 2 ** 20)
    assert cf.regime == "stream" and cf.plan.rows.n > 1
    assert "phase=stream_2160p" in capsys.readouterr().out


def test_hd_float_phase_auto_and_pallas(capsys):
    cfs = smoke.phase_hd_float(np.random.default_rng(2), (64, 256),
                               interpret=True)
    assert [cf.execution for cf in cfs][1] == "pallas"
    out = capsys.readouterr().out
    assert "hd_float32_auto" in out and "hd_float32_pallas" in out


def test_served_phase(capsys):
    stats = smoke.phase_served(np.random.default_rng(3), (48, 128),
                               interpret=True, requests=8, batch_size=4)
    assert stats["errors"] == 0 and stats["recompiles"] == 1
    assert "phase=served" in capsys.readouterr().out


def test_sharded_phase_on_four_virtual_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = textwrap.dedent(f"""
        import importlib.util, numpy as np, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.phase_sharded(np.random.default_rng(4), jax.devices()[:4],
                          (64, 96))
    """)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "output_devices=4" in r.stdout and "max_abs_delta=0.0" in r.stdout


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_script_alone_fails_without_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(lone)], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_compile_cache_dir_follows_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV)
        got = compile_cache.enable()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
