"""``BENCHMARK.json`` against the benchmark's contract, and the harness
against the rule that it is driven by data alone."""
import importlib
import os
import re

import pytest

from bench import generator, workload

BENCH = workload.benchmark()
ROOT = workload.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_command_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    cmd = BENCH["command"]
    assert len(cmd) <= 32
    files = [w for w in cmd if "/" in w]
    assert files and all(any(f.startswith(p + "/") for p in BENCH["paths"])
                         for f in files)
    assert all(os.path.exists(os.path.join(ROOT, f)) for f in files)


def test_run_seconds_fits_the_full_check():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_name_their_files():
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert c["file"].startswith("bench/")
        cfg = workload.config(BENCH, c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["limits"]) >= {"max_abs_gap", "malformed"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == names
    assert len({c["file"] for c in BENCH["configs"]}) == len(names)


def test_workloads_name_their_traffic_and_chips():
    seen = set()
    four = 0
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert workload.config(BENCH, w["config"])["chips"] == w["chips"]
        assert workload.traffic(w["traffic"])["loop"] in generator.LOOPS
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_metrics_have_readers_and_every_cell_reports_enough():
    from bench import harness
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert callable(importlib.import_module(
            f"bench.metrics.{m['name'].split('.')[0]}").read)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for cell in m.get("workloads", []):
            assert m["moves"] in {x["name"] for x in harness.metrics_for(
                BENCH, cell, traced=False)}
    for w in BENCH["workloads"]:
        got = {m["name"] for m in harness.metrics_for(BENCH, w["name"],
                                                      False)}
        assert "setup_s" in got and len(got) >= 2
        assert harness.metrics_for(BENCH, w["name"], True)


@pytest.mark.parametrize("module", ("run.py", "harness.py",
                                    "generator.py"))
def test_the_harness_names_no_cell_config_traffic_or_metric(module):
    with open(os.path.join(ROOT, "bench", module)) as fh:
        src = fh.read()
    words = ([w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    for w in words:
        assert not re.search(rf"(?<![\w.]){re.escape(w)}(?![\w.])", src), w
    for t in {w["traffic"] for w in BENCH["workloads"]}:
        assert f'"{t}"' not in src and f"'{t}'" not in src, t
