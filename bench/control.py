"""Readings that the limits of the comparison are set from, for one cell.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... [--control-seeds 3]

For each seed, in one process: the program through the cell's set-up and
a short window, as a run drives it, then its numbers against the float64
reference (the lower readings); for the first ``--control-seeds`` seeds
also the control, the same reference computed in bfloat16 (the precision
below the configuration's float32) in the program's place, against the
float64 one (the upper readings).  Prints one JSON line per seed.  Runs on
the chip only, like ``bench/run.py``; the benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload: str, seed: int, control: bool, window_s: float,
             root: Path = ROOT) -> dict:
    import ml_dtypes

    import jax

    from bench import check, harness, traffic

    cell, cfg, mix = harness.cell_files(harness.load_manifest(root), workload, root)
    ref = harness.load_module(Path(root) / "bench" / "reference" / f"{cfg['reference']}.py",
                              f"bench.reference.{cfg['reference']}")
    devices = jax.devices()[: int(cell["chips"])]
    drv = harness.load_mode(mix["mode"], root).Driver(cfg, mix, seed, devices)
    drv.setup()
    drv.window(window_s)
    ids, got = drv.checked()
    drv.release()
    del drv
    gc.collect()
    policy_seed = traffic.seeds(seed)[1]
    t0 = time.perf_counter()
    want = ref.replay(ids, cfg, policy_seed)
    out = {"seed": seed, "windows": len(ids), "reference_s": time.perf_counter() - t0,
           "program": check.compare(got, want, cfg)}
    if control:
        t0 = time.perf_counter()
        low = ref.replay(ids, cfg, policy_seed, dtype=ml_dtypes.bfloat16)
        out["control_s"] = time.perf_counter() - t0
        out["control"] = check.compare(low, want, cfg)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--window", type=float, default=1.0,
                    help="seconds of the short window after set-up")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from repro import compile_cache

    if jax.devices()[0].platform == "cpu":
        print("control: JAX finds no accelerator", file=sys.stderr)
        return 2
    compile_cache.enable()
    for i, seed in enumerate(args.seeds):
        r = readings(args.workload, seed, i < args.control_seeds, args.window)
        print(json.dumps({"workload": args.workload, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
