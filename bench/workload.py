"""Everything a run makes from ``--seed``: frames, coefficient sets, the
open-loop schedule and the samples the check compares.

One seed gives the same inputs in every run. Each use draws from its own
child of ``numpy.random.SeedSequence(seed)``, so adding a draw to one use
does not move another's. Seeds of any size are taken; JAX's key gets a
32-bit word drawn from the seed.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STREAMS = ("coeffs", "frames", "schedule", "sample", "probes", "jax")


def rngs(seed: int) -> Dict[str, np.random.Generator]:
    kids = np.random.SeedSequence(int(seed)).spawn(len(STREAMS))
    return {name: np.random.default_rng(k) for name, k in zip(STREAMS, kids)}


def jax_seed(seed: int) -> int:
    return int(rngs(seed)["jax"].integers(0, 2 ** 31 - 1))


# -- the benchmark's files ---------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "bench", "traffic", f"{name}.json"))


# -- data --------------------------------------------------------------------

def gaussian(w: int) -> np.ndarray:
    """The w×w Gaussian with OpenCV's default sigma for that size
    (``getGaussianKernel``: 0.3·((w−1)/2 − 1) + 0.8), normalised."""
    sigma = 0.3 * ((w - 1) * 0.5 - 1) + 0.8
    ax = np.arange(w, dtype=np.float64) - (w - 1) / 2
    g = np.exp(-(ax ** 2) / (2 * sigma ** 2))
    k = np.outer(g, g)
    return (k / k.sum()).astype(np.float32)


def coeff_sets(cfg: dict, rng: np.random.Generator, n: int
               ) -> List[Tuple[np.ndarray, Optional[Tuple[int, int]]]]:
    """``n`` runtime coefficient sets, each with its unity-gain scaler
    where the configuration requantises."""
    spec, w = cfg["coeffs"], cfg["window"]
    out = []
    for i in range(n):
        if spec["kind"] == "uniform_int":
            k = rng.integers(spec["low"], spec["high"], (w, w)).astype(
                np.int32)
            k[w // 2, w // 2] += spec.get("center_add", 0)
        elif spec["kind"] == "normal":
            if i == 0 and spec.get("first") == "gaussian":
                k = gaussian(w)
            else:
                k = (rng.standard_normal((w, w)) * spec["scale"]).astype(
                    np.float32)
        else:
            raise ValueError(f"unknown coefficient kind {spec['kind']!r}")
        rq = cfg.get("requant")
        gains = (reference.unity_gain(k, cfg["dtype"], rq["rounding"])
                 if rq else None)
        out.append((k, gains))
    return out


def host_frames(cfg: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    shape = (n, cfg["height"], cfg["width"])
    if reference.is_integer(cfg["dtype"]):
        info = np.iinfo(np.dtype(cfg["dtype"]))
        return rng.integers(info.min, int(info.max) + 1, shape,
                            dtype=cfg["dtype"])
    return rng.random(shape, dtype=np.float32)


def device_frames(cfg: dict, seed: int, n: int, sharding=None) -> list:
    """``n`` frames made on the device in one jitted call, each its own
    array (placed by ``sharding`` when given)."""
    import jax
    import jax.numpy as jnp

    shape = (n, cfg["height"], cfg["width"])
    dtype = jnp.dtype(cfg["dtype"])

    def make(key):
        if reference.is_integer(cfg["dtype"]):
            info = jnp.iinfo(dtype)
            x = jax.random.randint(key, shape, int(info.min),
                                   int(info.max) + 1, jnp.int32)
            x = x.astype(dtype)
        else:
            x = jax.random.uniform(key, shape, dtype)
        return tuple(x[i] for i in range(n))

    out = None if sharding is None else (sharding,) * n
    return list(jax.jit(make, out_shardings=out)(
        jax.random.key(jax_seed(seed))))


# -- open-loop traffic ----------------------------------------------------

def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of ``n`` requests go to each of ``k`` tenants under
    Zipf(s) popularity (rank i ∝ 1/i^s), by largest remainder."""
    p = 1.0 / np.arange(1, k + 1) ** s
    p /= p.sum()
    raw = n * p
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def schedule(tr: dict, seconds: float, rng: np.random.Generator
             ) -> Dict[str, np.ndarray]:
    """The open-loop schedule of one window: due times (s from the start),
    tenant and pool frame of each request.

    The count is fixed, ``round(rate · seconds)``, and so are the tenants'
    shares (Zipf by largest remainder) and each pool frame's uses: a seed
    changes only when each request is due and in what order they come.
    Due times are sorted uniform draws, which is a Poisson process
    conditioned on its count.
    """
    n = int(round(tr["rate_fps"] * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    tenants = np.repeat(np.arange(tr["tenants"]),
                        zipf_counts(n, tr["tenants"], tr["zipf_s"]))
    rng.shuffle(tenants)
    frames = np.arange(n) % tr["frame_pool"]
    rng.shuffle(frames)
    return {"due": due, "tenant": tenants, "frame": frames}


def sample(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """``k`` of ``n`` indices, sorted, with the last one always in."""
    if n <= k:
        return np.arange(n)
    picked = rng.choice(n - 1, size=k - 1, replace=False)
    return np.sort(np.append(picked, n - 1))


def probes(rng: np.random.Generator, n: int, cfg: dict, per: int
           ) -> Tuple[np.ndarray, np.ndarray]:
    """``per`` pixel positions for each of ``n`` outputs: a quarter in
    the border bands, where the border policy acts, the rest anywhere."""
    H, W, r = cfg["height"], cfg["width"], cfg["window"] // 2
    rows = rng.integers(0, H, (n, per))
    cols = rng.integers(0, W, (n, per))
    edge = max(per // 4, 1)
    band = rng.integers(0, 2 * r, (n, edge))
    band = np.where(band < r, band, band - r + max(H - r, 0))
    rows[:, :edge] = np.clip(band, 0, H - 1)
    cband = rng.integers(0, 2 * r, (n, edge))
    cband = np.where(cband < r, cband, cband - r + max(W - r, 0))
    cols[:, edge:2 * edge] = np.clip(cband, 0, W - 1)
    return rows, cols

