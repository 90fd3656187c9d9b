"""Paper Fig. 2 — adversarial round-robin trace, via the scan engines.

Claim reproduced: recency/frequency policies collapse (linear regret) while
gradient policies track OPT = C/N.  Every policy now runs device-resident
(:mod:`repro.cachesim.engines`, through :mod:`repro.cachesim.api`) via the
``fig2_adversarial`` scenario; the host-side OGB_cl(B=1) footnote-3 check
stays on the slow oracle path at quick scale only."""

from __future__ import annotations

from repro.cachesim.scenarios import get_scenario, run_scenario
from repro.cachesim.simulator import simulate
from repro.core.ogb_classic import OGBClassic

from .common import SCALE, check_finite, csv_row, save_json


def main() -> dict:
    scale = "full" if SCALE == "full" else "quick"
    sc = get_scenario("fig2_adversarial")
    N, T, C = sc.dims(scale)
    trace = sc.make_trace(scale)  # generated once, shared by every driver
    res = run_scenario("fig2_adversarial", scale=scale, trace=trace)
    opt_ratio = C / N

    rows = {
        name: dict(row) for name, row in res.rows.items()
    }
    if scale == "quick":
        # footnote 3: OGB == OGB_cl for B=1 — host oracle, toy scale only
        r = simulate(
            OGBClassic(N, C, horizon=T, batch_size=1),
            trace,
            window=max(T // 20, 1),
            record_cum=False,
        )
        rows["OGB_cl(B=1)"] = {
            "hit_ratio": r.hit_ratio,
            "us_per_request": r.us_per_request,
        }
    for name, row in rows.items():
        csv_row(
            f"fig2/{name}",
            row.get("us_per_request", 0.0),
            f"hit_ratio={row['hit_ratio']:.4f}",
        )

    print(f"\nFig2 adversarial N={N} C={C} T={T} (OPT={opt_ratio:.3f}):")
    for k, v in rows.items():
        print(f"  {k:>12}: hit={v['hit_ratio']:.4f}")
    # assertions mirroring the figure
    assert rows["OGB"]["hit_ratio"] > 0.7 * opt_ratio
    assert rows["OMD"]["hit_ratio"] > 0.7 * opt_ratio
    assert rows["LRU"]["hit_ratio"] < 0.2 * opt_ratio
    assert rows["LFU"]["hit_ratio"] < 0.2 * opt_ratio
    payload = {"N": N, "C": C, "T": T, "rows": rows}
    check_finite(payload)
    save_json("fig2_adversarial", payload)
    return rows


if __name__ == "__main__":
    main()
