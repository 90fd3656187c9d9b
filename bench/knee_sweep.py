"""Sweep the offered rate of an open-loop serving cell, once, to find its
knee: the highest rate at which the backlog does not grow.

    python3 bench/knee_sweep.py --workload cdn_ogb_1e6.serve --seed 7 \\
        --seconds 10 --rates 100 150 200 250 300

One process sets the cell up once, then offers each rate for ``--seconds``
and prints, per rate, the latency percentiles and the mean latency of the
first and the last quarter of the decisions: a last quarter that waits far
longer than the first is a backlog that grows.  The cell's mix file then
fixes its rate at about four fifths of the knee; the benchmark's runs never
search for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def sweep(workload: str, seed: int, seconds: float, rates, root: Path = ROOT):
    from bench import drivers, harness

    _, cfg, mix = harness.cell_files(harness.load_manifest(root), workload, root)
    if mix["mode"] != "serve":
        raise ValueError(f"{workload} is not an open-loop serving cell")
    drv = drivers.DRIVERS["serve"](cfg, mix, seed)
    drv.setup()
    for rate in rates:
        drv.rate = float(rate)
        s = drv.window(seconds)
        lat = s.latencies_ms
        q = max(1, len(lat) // 4)
        yield {
            "rate_per_s": float(rate),
            "decisions": int(s.attempted),
            "served": int(s.attempted - s.failed),
            "p50_ms": float(np.quantile(lat, 0.5, method="inverted_cdf")),
            "p95_ms": float(np.quantile(lat, 0.95, method="inverted_cdf")),
            "p99_ms": float(np.quantile(lat, 0.99, method="inverted_cdf")),
            "first_quarter_mean_ms": float(np.mean(lat[:q])),
            "last_quarter_mean_ms": float(np.mean(lat[-q:])),
            "served_per_s": float((s.attempted - s.failed) / s.seconds),
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from repro import compile_cache

    if jax.devices()[0].platform == "cpu":
        print("knee_sweep: JAX finds no accelerator", file=sys.stderr)
        return 2
    compile_cache.enable()
    for row in sweep(args.workload, args.seed, args.seconds, args.rates):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
