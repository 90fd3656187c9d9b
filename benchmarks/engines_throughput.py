"""Baseline-engine replay throughput — the apples-to-apples speed ledger.

Replays a fig7_8-class trace (zipf 0.9, N=20k, C=N/20) of T=1e6 requests
through every registered policy engine (LRU/FIFO/LFU/FTPL automata, the OMD
mirror-descent engine, the OGB scan/tree replays, and the sized engines —
GDS on the min-pair tree and the size-aware ``ogb_sized`` tree) via the one
unified ``api.run`` path, on whatever backend JAX picks (CPU in CI).  The acceptance
bar is **< 15 us/request for every policy** — the bound that makes the
paper-scale (T=2e7) comparison runs feasible.  A short host-side LRU run
gives the baseline of the speedup column.

Writes ``benchmarks/results/engines_throughput.json`` and the tracked
top-level ``BENCH_engines.json`` so the perf trajectory is visible PR over
PR.

Also exercises the unified sweep layer: one (capacities x seeds) LRU grid
must cost close to a single replay, not |grid| replays.
"""

from __future__ import annotations

import json
import os

import numpy as np

import jax

from repro.cachesim.api import policy_def, run, sweep
from repro.cachesim.simulator import simulate
from repro.cachesim.traces import zipf
from repro.core.policies import make_policy

from .common import check_finite, csv_row, save_json, scale

BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_engines.json",
)

US_PER_REQUEST_BUDGET = 15.0


def main() -> dict:
    N = 20_000
    C = N // 20
    T = scale(1_000_000, 1_000_000)  # the acceptance bar is defined at T=1e6
    B = 1000
    trace = zipf(N, T, alpha=0.9, seed=21)
    out = {
        "N": N,
        "C": C,
        "T": T,
        "backend": jax.default_backend(),
        "budget_us_per_request": US_PER_REQUEST_BUDGET,
        "engines": {},
    }

    # heterogeneous-size rows: slab sizes anti-correlated with popularity
    # (the sized_cdn regime); ogb_sized takes the equivalent byte budget
    slabs = np.asarray([1.0, 4.0, 16.0, 64.0])
    sizes = slabs[np.minimum(np.arange(N) * len(slabs) // N, len(slabs) - 1)]
    cap_bytes = int(round(C * float(sizes.mean())))

    for kind in (
        "lru", "fifo", "lfu", "ftpl", "omd", "ogb", "ogb_tree",
        "gds", "ogb_sized",
    ):
        pd = policy_def(kind)
        sized = kind in ("gds", "ogb_sized")
        window = B if pd.fractional else max(T // 100, 1)
        r = run(
            pd, trace, N, cap_bytes if kind == "ogb_sized" else C,
            window=window, horizon=T, track_opt=False,
            sizes=sizes if sized else None,
        )
        out["engines"][r.name] = {
            "us_per_request": r.us_per_request,
            "hit_ratio": r.hit_ratio,
        }
        if sized:
            out["engines"][r.name]["byte_hit_ratio"] = r.byte_hit_ratio
        csv_row(
            f"engines/{r.name}", r.us_per_request, f"hit_ratio={r.hit_ratio:.4f}"
        )

    # host-side reference point (short run; the engines replace this loop)
    t_host = min(T, 100_000)
    host = simulate(make_policy("lru", N, C), trace[:t_host], record_cum=False)
    out["host_lru_us_per_request"] = host.us_per_request
    out["lru_speedup_vs_host"] = (
        host.us_per_request / out["engines"]["LRU"]["us_per_request"]
    )
    csv_row("engines/host_LRU", host.us_per_request, f"T={t_host}")
    # the prefix-tree LRU engine must beat the host loop outright — a
    # regression below 1x means the O(log) reuse-distance path broke
    assert out["lru_speedup_vs_host"] >= 1.0, out["lru_speedup_vs_host"]

    # vmapped sweep amortization: a 6-combo LRU grid in one dispatch
    sweep_t = min(T, 200_000)
    sw = sweep(
        policy_def("lru"),
        trace[:sweep_t],
        N,
        capacities=[C // 4, C // 2, C],
        seeds=(0, 1),
        window=max(sweep_t // 20, 1),
        track_opt=False,
    )
    single = run(
        policy_def("lru"), trace[:sweep_t], N, C,
        window=max(sweep_t // 20, 1), track_opt=False,
    )
    out["sweep"] = {
        "combos": len(sw.combos),
        "us_per_request_total": 1e6 * sw.wall_seconds / sw.T,
        "amortization_vs_serial": (
            len(sw.combos)
            * single.wall_seconds
            / max(sw.wall_seconds, 1e-12)
        ),
        "hit_ratios": {
            f"C={c['capacity']}/seed={c['seed']}": float(h)
            for c, h in zip(sw.combos, sw.hit_ratios)
        },
    }
    print(
        f"sweep: {len(sw.combos)} combos in {sw.wall_seconds:.2f}s "
        f"({out['sweep']['amortization_vs_serial']:.2f}x vs serial replays)"
    )

    for name, row in out["engines"].items():
        print(
            f"{name:>6}: {row['us_per_request']:8.3f} us/req   "
            f"hit={row['hit_ratio']:.4f}"
        )
        assert row["us_per_request"] < US_PER_REQUEST_BUDGET, (
            name,
            row["us_per_request"],
        )
    check_finite(out)
    save_json("engines_throughput", out)
    with open(BENCH_JSON, "w") as f:
        json.dump(out, f, indent=2, default=float)
    print(f"wrote {BENCH_JSON}")
    return out


if __name__ == "__main__":
    main()
