"""Find the served cell's knee: one set-up, then one open-loop window per
offered rate, each printed as a row.

    python bench/knee.py --workload <served cell> --rates 100,200 --seconds 8

A rate is sustained when the backlog (requests due but not yet served)
does not grow over the window: the row gives it at each quarter of the
window, the share of the offered frames completed inside it, and the
median and 95th percentile of due → result latency in each half. The
served traffic's rate is then set to four fifths of the highest
sustained rate, as a number in its traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import generator, harness, workload  # noqa: E402


def backlog(rec: dict, t: float) -> int:
    due = rec["due"] <= t
    done = rec["ok"] & (rec["done"] <= t)
    return int(due.sum() - (due & done).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import jax
    harness.enable_compile_cache(ROOT)
    bench = workload.benchmark(ROOT)
    entry = workload.cell(bench, args.workload)
    cfg = workload.config(bench, entry["config"])
    tr = dict(workload.traffic(entry["traffic"]))
    loop = generator.LOOPS[tr["loop"]](cfg, tr, args.seed,
                                       jax.devices()[:entry["chips"]])
    for rate in (float(r) for r in args.rates.split(",")):
        loop.tr["rate_fps"] = rate
        obs = loop.window(args.seconds)
        rec = obs.requests
        t0 = rec["due"].min() - 1e-9
        lat = np.where(rec["ok"], rec["done"] - rec["due"], np.inf) * 1e3
        half = rec["due"] < t0 + args.seconds / 2
        print(json.dumps({
            "rate_fps": rate, "requests": obs.attempted,
            "failed": obs.failed,
            "completed_share": obs.pixels_done
            / (cfg["height"] * cfg["width"] * max(obs.attempted, 1)),
            "backlog_quarters": [backlog(rec, t0 + q * args.seconds / 4)
                                 for q in (1, 2, 3, 4)],
            "p50_ms_halves": [float(np.median(lat[half])),
                              float(np.median(lat[~half]))],
            "p95_ms_halves": [float(np.percentile(lat[half], 95)),
                              float(np.percentile(lat[~half], 95))],
            "wave_frames": obs.engine["completed"]
            / max(obs.engine["waves"], 1),
            "late_p95_ms": float(np.percentile(obs.late_s, 95) * 1e3)}),
            flush=True)
    found = loop.check()
    print(json.dumps({"check_last_window": found}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
