"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus readable summaries) and
writes JSON to benchmarks/results/.  REPRO_BENCH_SCALE=full for paper-scale
runs; default sizes finish in minutes on one CPU core.

  python -m benchmarks.run            # all figures
  python -m benchmarks.run fig2 fig9  # a subset
  python -m benchmarks.run --list     # print registered suite names
"""

from __future__ import annotations

import sys
import traceback

from repro import compile_cache

from . import (
    complexity_scaling,
    engines_throughput,
    kernel_sweeps,
    fig2_adversarial,
    fig3_sensitivity_short,
    fig4_sensitivity_long,
    fig7_8_traces,
    fig9_occupancy,
    fig10_batched,
    fig11_locality,
    fleet_scale,
    serving_slo,
    sized_cdn,
    stream_scale,
)

SUITES = {
    "fig2": fig2_adversarial.main,
    "fig3": fig3_sensitivity_short.main,
    "fig4": fig4_sensitivity_long.main,
    "fig7_8": fig7_8_traces.main,
    "fig9": fig9_occupancy.main,
    "fig10": fig10_batched.main,
    "fig11": fig11_locality.main,
    "complexity": complexity_scaling.main,
    "kernels": kernel_sweeps.main,
    "engines": engines_throughput.main,
    "serving": serving_slo.main,
    "sized": sized_cdn.main,
    "stream": stream_scale.main,
    "fleet": fleet_scale.main,
}


def _roofline():
    # imported lazily: needs dry-run artifacts to exist
    from . import roofline

    return roofline.main()


SUITES["roofline"] = _roofline


def main() -> None:
    if any(a in ("--list", "-l") for a in sys.argv[1:]):
        for name in sorted(SUITES):
            print(name)
        return
    wanted = sys.argv[1:] or list(SUITES)
    # a typo'd suite name must fail the run, not silently skip the suite
    unknown = [n for n in wanted if n not in SUITES]
    if unknown:
        print(f"unknown suites {unknown}; available: {sorted(SUITES)}")
        raise SystemExit(2)
    compile_cache.enable()
    failures = []
    for name in wanted:
        print(f"\n=== {name} " + "=" * (70 - len(name)))
        try:
            SUITES[name]()
        except Exception as e:  # reprolint: allow(broad-except) recorded; exits 1 below
            failures.append((name, e))
            traceback.print_exc()
    if failures:
        print("\nFAILED suites:", [n for n, _ in failures])
        raise SystemExit(1)
    print("\nall benchmark suites passed")


if __name__ == "__main__":
    main()
