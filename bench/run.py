"""Run one benchmark cell once, on the chips of the machine it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics come from
``BENCHMARK.json`` at the root of the checkout. Frames, coefficients and
arrivals come from ``--seed``. Without a TPU, or with fewer chips than
the cell asks for, it exits with code 2 and prints no result. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end ones, or with ``--trace 1`` the
per-layer ones), ``device`` and, last, ``checks``: each number compared
with the reference beside its limit. The same checks are the last lines
of standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, workload  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = workload.benchmark(ROOT)
    chips = workload.cell(bench, args.workload)["chips"]
    import jax
    harness.enable_compile_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX sees {devices[0].platform}); nothing "
              "was run", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"jax={jax.__version__}", file=sys.stderr, flush=True)
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), devices[:chips])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
