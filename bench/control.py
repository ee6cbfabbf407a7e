"""Readings that set the limits of ``correct``: the program's gap on many
seeds and the control's gap on the same inputs, in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 2

For each seed it sets the cell up, runs a short window at the cell's own
load, and compares what the window produced with the reference (the
program's reading). It then puts the control (``reference.control``: the
filter one precision step below the configuration's) in the program's
place on the same sampled inputs and compares that with the reference
(the control's reading). One JSON line per seed, then a summary. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import generator, harness, reference, workload  # noqa: E402


def control_gap(cfg: dict, inputs) -> float:
    gap = 0.0
    for frame, k, g in inputs:
        want = reference.filter_frame(frame, k, cfg, g)
        gap = max(gap, reference.max_abs_gap(
            reference.control(frame, k, cfg, g), want))
    return gap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also get a control reading")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    import jax
    harness.enable_compile_cache(ROOT)
    bench = workload.benchmark(ROOT)
    entry = workload.cell(bench, args.workload)
    cfg = workload.config(bench, entry["config"])
    tr = workload.traffic(entry["traffic"])
    devices = jax.devices()[:entry["chips"]]
    program, control = [], []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        loop = generator.LOOPS[tr["loop"]](cfg, tr, seed, devices)
        obs = loop.window(args.seconds)
        found = loop.check()
        line = {"seed": seed, "attempted": obs.attempted,
                "failed": obs.failed, "program": found}
        program.append(found["max_abs_gap"])
        if n < args.control_seeds:
            line["control_max_abs_gap"] = control_gap(cfg, loop.inputs)
            control.append(line["control_max_abs_gap"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload,
                      "device": devices[0].device_kind,
                      "program_max_abs_gap_max": max(program),
                      "program_readings": program,
                      "control_max_abs_gap_min": min(control, default=None),
                      "control_readings": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
