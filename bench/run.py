"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics with the profiler
off; ``--trace 1`` traces a window of at most three seconds and reports the
per-layer metrics.  The last line of standard output is one JSON object;
the numbers compared with the plain reference, each with its limit, are
the last lines of standard error and the result's last key.  The run exits
non-zero, and prints no result, when JAX finds no accelerator or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the persistent compile cache lives at a fixed path inside the checkout,
    # so that every run of a cell after its first loads what the first compiled
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime's logs go inside the checkout too, not to a shared /tmp path
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))

    from bench import harness

    cell = harness.find(harness.load_manifest()["workloads"], args.workload, "workload")
    import jax

    from repro import compile_cache

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("bench: JAX finds no accelerator; the benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    compile_cache.enable()
    print(f"bench: {args.workload} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind} ({devices[0].platform})", file=sys.stderr, flush=True)
    harness.execute(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
