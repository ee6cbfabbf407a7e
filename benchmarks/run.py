"""Benchmark harness: one module per paper table (+ LM roofline summary).

  PYTHONPATH=src python -m benchmarks.run [--only <substr>] [--smoke]
                                          [--json BENCH_out.json]

``--smoke`` is the CI mode: filter-path modules only, reduced timing
iterations — a fast end-to-end exercise of every bench code path on the
CPU-interpret backend. Prints ``name,us_per_call,derived`` CSV.

``--json PATH`` additionally writes a machine-readable trajectory record:
every CSV row parsed into ``{"name", "us_per_call", <derived metrics>}``
(numbers as numbers), plus run metadata — the ``BENCH_*.json`` artifact CI
uploads so throughput can be tracked across commits instead of eyeballed
in logs. The per-row byte metrics the CI gate diffs (``benchmarks/
compare.py``, median-of-N windowed baseline) are all analytic, derived
from the static halo plan: ``hbm_read_bytes_per_pixel`` (read
amplification × storage width), ``hbm_write_bytes_per_pixel`` (output
width — 1 byte for the requantised int8 lanes, 4 for the wide
accumulator) and their round-trip sum ``hbm_bytes_per_pixel``, so a
datapath widening on either side of the stream is a one-commit-visible
regression.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time


def _parse_derived(derived: str):
    out = {}
    for item in derived.split(";"):
        if not item or "=" not in item:
            continue
        key, val = item.split("=", 1)
        try:
            out[key] = float(val)
        except ValueError:
            out[key] = val
    return out


def _row_record(line: str):
    name, us, derived = line.split(",", 2)
    try:
        rec = {"name": name, "us_per_call": float(us)}
    except ValueError:
        return {"name": name, "error": derived or us}
    rec.update(_parse_derived(derived))
    return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_*.json trajectory record here")
    ap.add_argument("--obs-jsonl", default=None, metavar="PATH",
                    help="enable repro.obs tracing for the whole run and "
                         "stream every event (plan/auto_select/compile/"
                         "execute) to this JSONL file; the metrics-registry "
                         "export rides into the --json payload as "
                         "'obs_metrics'")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()

    from benchmarks import common
    if args.smoke:
        common.SMOKE = True

    obs = None
    if args.obs_jsonl:
        from repro import obs
        obs.enable(jsonl=args.obs_jsonl)

    from benchmarks import (bench_border_overhead, bench_filter_forms,
                            bench_hls_comparison, bench_lm_roofline,
                            bench_pipeline, bench_throughput)
    modules = [
        ("filter_forms", bench_filter_forms),
        ("border_overhead", bench_border_overhead),
        ("pipeline", bench_pipeline),
        ("hls_comparison", bench_hls_comparison),
        ("throughput", bench_throughput),
        ("lm_roofline", bench_lm_roofline),
    ]
    if args.smoke:
        modules = [m for m in modules
                   if m[0] in ("filter_forms", "border_overhead",
                               "pipeline", "throughput")]
    print("name,us_per_call,derived")
    failures = 0
    records = []
    for name, mod in modules:
        if args.only and args.only not in name:
            continue
        try:
            for line in mod.run():
                print(line)
                records.append(_row_record(line))
        except Exception as e:  # noqa: BLE001
            failures += 1
            line = f"{name},-1,ERROR={type(e).__name__}:{e}"
            print(line)
            records.append({"name": name, "error": f"{type(e).__name__}:{e}"})

    if args.json:
        import jax
        payload = {
            "schema": "bench_trajectory_v1",
            "created_unix": time.time(),
            "smoke": args.smoke,
            "only": args.only,
            "backend": jax.default_backend(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "failures": failures,
            "rows": records,
        }
        if obs is not None:
            payload["obs_metrics"] = obs.REGISTRY.export()
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"# wrote {len(records)} records -> {args.json}",
              file=sys.stderr)

    if obs is not None:
        n = obs.get_trace().emitted
        obs.disable()          # flushes + closes the JSONL sink
        print(f"# wrote {n} obs events -> {args.obs_jsonl}",
              file=sys.stderr)

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
