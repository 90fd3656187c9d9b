"""Bring-up run of the OGB caching system on a TPU.

    python chip_smoke.py             # replay, catalog, stream, serve: one chip
    python chip_smoke.py --chips 4   # only the tenant-sharded fleet: four chips

Drives the main path through the entry points a user calls (``repro.run``,
``repro.run_stream``, the continuous serving loop and ``repro.run_fleet``)
at the full scale of the ``fig8_cdn`` scenario, the paper's CDN figure:
N = 1e6 items, capacity C = 5e4, zipf(0.9) requests, T = 2e7, window 1000,
trace drawn from ``--seed``.  A second catalog of N = 1e7 (C = 5e5) runs the
O(log N) engine ten times wider.  Each phase checks what comes out against
a reference: the host policies of ``repro.core``, the float64 projection
oracle, the dense engines, or the same tenants replayed on one chip.

Each phase prints one line: its name, sizes, compile seconds (JAX's trace,
lowering and compile time, or the load time of cached executables), wall
seconds and its checks.  The last line of stdout is one JSON object naming
the device.  The script exits non-zero, and prints no result, when JAX
finds no TPU or any check fails.  It runs every phase in this one process.

The phases are plain functions of their sizes, so tests call them at tiny
sizes on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
from jax.sharding import AxisType, Mesh  # noqa: E402

from benchmarks.serving_slo import (  # noqa: E402
    expert_cache_track,
    stream_window_track,
)
from repro import (  # noqa: E402
    fit_profile,
    get_scenario,
    make_policy,
    make_trace,
    policy_def,
    run,
    run_fleet,
    run_stream,
    simulate,
    synthesize_chunks,
)
from repro import compile_cache  # noqa: E402
from repro.configs.base import get_arch  # noqa: E402
from repro.core.projection import project_capped_simplex  # noqa: E402
from repro.core.regret import best_static_hits  # noqa: E402

SCENARIO = get_scenario("fig8_cdn")
N, T, C = SCENARIO.dims("full")
ALPHA = dict(SCENARIO.trace_kw)["alpha"]
WINDOW = SCENARIO.batch
#: ogb_tree vs dense ogb hit ratio, the bound of the differential test
TREE_TOL = 5e-3
#: relative tolerance of the float32 device rewards against float64
ORACLE_RTOL = 1e-4
#: the MoE whose (layer, expert) pairs the serve phase's expert cache holds
SERVE_ARCH = "kimi-k2-1t-a32b"


class CheckFailed(AssertionError):
    """A phase's output disagreed with its reference."""


def check(ok, what: str) -> str:
    if not ok:
        raise CheckFailed(what)
    return what


def _report(name: str, sizes: dict, clock, wall: float, checks, **values):
    fields = [name]
    fields += [f"{k}={v}" for k, v in sizes.items()]
    fields += [
        f"compile_s={clock.seconds:.3f}",
        f"cache_hits={clock.cache_hits}",
        f"wall_s={wall:.3f}",
    ]
    fields += [f"{k}={v}" for k, v in values.items()]
    fields.append("checks_ok=" + ";".join(checks))
    print(" ".join(fields), flush=True)


def _trace(n: int, t: int, seed: int) -> np.ndarray:
    return make_trace(SCENARIO.trace, n, t, seed=seed, alpha=ALPHA)


def _oracle_rewards(trace, n, c, eta, window, windows) -> np.ndarray:
    """Per-window fractional rewards of OGB in float64 on the host: the
    gradient step and the exact capped-simplex projection of core/."""
    f = np.full(n, c / n)
    out = np.empty(windows)
    for w in range(windows):
        ids = trace[w * window:(w + 1) * window]
        out[w] = f[ids].sum()
        f = project_capped_simplex(f + eta * np.bincount(ids, minlength=n), c)
    return out


def phase_replay(n=N, c=C, t=T, window=WINDOW, seed=SCENARIO.trace_seed,
                 lru_host_t=200_000, lru_dense_t=1_000_000,
                 oracle_windows=50) -> dict:
    """``repro.run`` over one trace with dense ``ogb``, ``ogb_tree`` and the
    tree ``lru``, each checked against its reference."""
    t0 = time.perf_counter()
    with compile_cache.CompileClock() as clock:
        trace = _trace(n, t, seed)
        opt = best_static_hits(trace, c) / t
        res = {
            kind: run(policy_def(kind), trace, n, c, window=window,
                      track_opt=False, keep_carry=False)
            for kind in ("ogb", "ogb_tree", "lru")
        }
        checks = []
        host = simulate(make_policy("lru", n, c), trace[:lru_host_t],
                        window=window, record_cum=False)
        m = lru_host_t // window
        checks.append(check(
            np.array_equal(np.rint(host.windowed * window)[:m],
                           res["lru"].hits[:m]),
            f"lru=host_lru[:{lru_host_t}]",
        ))
        m = lru_dense_t // window
        dense = run(policy_def("lru", impl="dense"), trace[:lru_dense_t], n,
                    c, window=window, track_opt=False, keep_carry=False)
        checks.append(check(
            np.array_equal(dense.hits, res["lru"].hits[:m]),
            f"lru_tree=lru_dense[:{lru_dense_t}]",
        ))
        ref = _oracle_rewards(trace, n, c, res["ogb"].extras["eta"], window,
                              oracle_windows)
        got = res["ogb"].reward[:oracle_windows]
        rel = float(np.max(np.abs(got - ref) / np.abs(ref)))
        checks.append(check(
            rel <= ORACLE_RTOL,
            f"ogb_reward~f64[:{oracle_windows}w](rel={rel:.3g})",
        ))
        gap = abs(res["ogb_tree"].hit_ratio - res["ogb"].hit_ratio)
        checks.append(check(gap <= TREE_TOL,
                            f"|ogb_tree-ogb|<={TREE_TOL}(gap={gap:.3g})"))
        checks.append(check(
            all(r.hit_ratio < opt for r in res.values()), "all<opt"
        ))
    _report(
        "replay", dict(N=n, C=c, T=t, window=window), clock,
        time.perf_counter() - t0, checks,
        opt=opt, **{k: r.hit_ratio for k, r in res.items()},
        **{f"{k}_run_s": r.wall_seconds for k, r in res.items()},
    )
    return {"opt": opt, **{k: r.hit_ratio for k, r in res.items()}}


def phase_catalog(n=10 * N, c=10 * C, t=T, dense_t=2_000_000, window=WINDOW,
                  seed=SCENARIO.trace_seed) -> dict:
    """``ogb_tree`` at a catalog ten times wider, checked against dense
    ``ogb`` (at the same eta) over a prefix."""
    t0 = time.perf_counter()
    with compile_cache.CompileClock() as clock:
        trace = _trace(n, t, seed)
        tree = run(policy_def("ogb_tree"), trace, n, c, window=window,
                   track_opt=False, keep_carry=False)
        dense = run(policy_def("ogb"), trace[:dense_t], n, c, window=window,
                    eta=tree.extras["eta"], track_opt=False,
                    keep_carry=False)
        m = dense_t // window
        prefix = float(tree.hits[:m].sum()) / (m * window)
        gap = abs(prefix - dense.hit_ratio)
        checks = [
            check(np.all(np.isfinite(tree.reward)), "finite"),
            check(gap <= TREE_TOL,
                  f"|ogb_tree-ogb|[:{dense_t}]<={TREE_TOL}(gap={gap:.3g})"),
        ]
    _report(
        "catalog", dict(N=n, C=c, T=t, window=window), clock,
        time.perf_counter() - t0, checks,
        ogb_tree=tree.hit_ratio, ogb_tree_run_s=tree.wall_seconds,
        ogb_prefix=dense.hit_ratio, ogb_tree_prefix=prefix,
    )
    return {"ogb_tree": tree.hit_ratio, "gap": gap}


def phase_stream(n=N, c=C, t=T // 2, window=WINDOW, opt_window=1_000_000,
                 profile_t=200_000, seed=SCENARIO.trace_seed) -> dict:
    """``run_stream`` of ``ogb_tree`` over synthesized chunks, async
    (prefetch=2) against synchronous (prefetch=0): bit for bit the same."""
    t0 = time.perf_counter()
    with compile_cache.CompileClock() as clock:
        profile = fit_profile(_trace(n, profile_t, seed))
        res = {}
        for prefetch in (2, 0):
            chunks = synthesize_chunks(profile, t, catalog=n, seed=seed)
            res[prefetch] = run_stream(
                policy_def("ogb_tree"), chunks, n, c, window=window,
                horizon=t, opt_window=opt_window, prefetch=prefetch,
            )
        a, b = res[2], res[0]
        leaves_a, leaves_b = jax.tree.leaves(a.carry), jax.tree.leaves(b.carry)
        checks = [
            check(a.T == b.T == (t // window) * window, "T"),
            check(np.array_equal(a.hits, b.hits), "hits:prefetch2=prefetch0"),
            check(
                len(leaves_a) == len(leaves_b) and all(
                    np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(leaves_a, leaves_b)
                ),
                "carry:prefetch2=prefetch0",
            ),
            check(float(a.hits.sum()) <= a.dynamic_opt_total, "hits<=dyn_opt"),
        ]
    _report(
        "stream", dict(N=n, C=c, T=t, window=window, opt_window=opt_window),
        clock, time.perf_counter() - t0, checks,
        ogb_tree=a.hit_ratio, dyn_opt=a.dynamic_opt_total / a.T,
        prefetch2_s=a.wall_seconds, prefetch0_s=b.wall_seconds,
        segments=a.n_segments,
    )
    return {"hits": a.hits, "prefetch2_s": a.wall_seconds}


def phase_serve(n=N, c=C, window=WINDOW, requests=500, layers=None,
                experts=None, seed=0) -> dict:
    """The continuous serving loop, open-loop at 70% of the capacity
    measured here: ``requests`` expert-cache decisions over a real MoE's
    (layer, expert) pairs and ``requests`` resumable ``run(carry=...)``
    windows over the catalog."""
    if layers is None or experts is None:
        arch = get_arch(SERVE_ARCH)
        layers, experts = arch.n_layers, arch.n_experts
    t0 = time.perf_counter()
    with compile_cache.CompileClock() as clock:
        e_slo, e_rate, ec = expert_cache_track(layers, experts, requests,
                                               seed)
        w_slo, w_rate = stream_window_track(n, c, window, requests, seed + 1)
        checks = []
        for name, slo in (("expert_cache", e_slo), ("stream_window", w_slo)):
            lat = slo.latencies_ms
            checks.append(check(
                slo.requests == requests and len(lat) == requests
                and bool(np.all(np.isfinite(lat) & (lat >= 0))),
                f"{name}:all_{requests}_answered",
            ))
        checks.append(check(0.0 <= ec.mean_hit_ratio <= 1.0,
                            "expert_hit_ratio_in_[0,1]"))
    _report(
        "serve", dict(N=n, C=c, window=window, requests=requests,
                      layers=layers, experts=experts),
        clock, time.perf_counter() - t0, checks,
        expert_p50_ms=e_slo.p50_ms, expert_p99_ms=e_slo.p99_ms,
        expert_rate=e_rate, window_p50_ms=w_slo.p50_ms,
        window_p99_ms=w_slo.p99_ms, window_rate=w_rate,
    )
    return {"expert_cache": e_slo, "stream_window": w_slo}


def _bytes_by_device(carry, devices) -> list:
    """Bytes of the carry's shards that each device holds."""
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(carry):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return [held[d] for d in devices]


def phase_fleet(devices, tenants=2048, n=N, c=C, window=WINDOW, windows=10,
                seed=SCENARIO.trace_seed) -> dict:
    """``run_fleet`` with the tenant axis sharded over ``devices``, each
    slice of tenants checked against the same tenants replayed alone on
    that slice's device (the slices at once): ``lru`` exactly, ``ogb``
    rewards within rtol 1e-5."""
    k = len(devices)
    if tenants % k:
        raise ValueError(f"{tenants} tenants do not split over {k} devices")
    mesh = Mesh(np.asarray(devices), ("data",), axis_types=(AxisType.Auto,))
    per = tenants // k
    t0 = time.perf_counter()
    values, checks = {}, []

    def alone(pd, q):
        """Slice ``q`` of the tenants replayed by itself on device ``q``."""
        lo, hi = q * per, (q + 1) * per
        with jax.default_device(devices[q]):
            return run_fleet(pd, traces[lo:hi], n, c, window=window,
                             seeds=np.arange(lo, hi), track_opt=False,
                             keep_carry=False)

    with compile_cache.CompileClock() as clock:
        traces = _trace(n, tenants * windows * window, seed).reshape(
            tenants, -1
        )
        for kind in ("ogb", "lru"):
            pd = policy_def(kind)
            sharded = run_fleet(pd, traces, n, c, window=window,
                                track_opt=False, mesh=mesh)
            held = _bytes_by_device(sharded.carry, devices)
            in_use = [
                (d.memory_stats() or {}).get("bytes_in_use") for d in devices
            ]
            sharded.carry = None  # free the devices for the references
            checks.append(check(
                min(held) > 0 and max(held) == min(held),
                f"{kind}:carry_spread",
            ))
            values[f"{kind}_carry_bytes_per_device"] = held
            values[f"{kind}_bytes_in_use"] = in_use
            values[f"{kind}_sharded_s"] = sharded.wall_seconds
            # the k references run at once, one per device
            with ThreadPoolExecutor(k) as pool:
                refs = list(pool.map(lambda q: alone(pd, q), range(k)))
            values[f"{kind}_alone_s"] = max(r.wall_seconds for r in refs)
            for q, ref in enumerate(refs):
                lo, hi = q * per, (q + 1) * per
                if kind == "lru":
                    ok = np.array_equal(ref.hits, sharded.hits[lo:hi])
                else:
                    ok = np.allclose(ref.reward, sharded.reward[lo:hi],
                                     rtol=1e-5, atol=0.0)
                checks.append(check(ok, f"{kind}:tenants[{lo}:{hi}]=alone"))
            values[f"{kind}_hit_ratio"] = sharded.hit_ratio
    _report(
        "fleet", dict(devices=k, tenants=tenants, N=n, C=c, window=window,
                      windows=windows),
        clock, time.perf_counter() - t0, checks, **values,
    )
    return values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tenant-sharded fleet, on 4 chips")
    ap.add_argument("--seed", type=int, default=SCENARIO.trace_seed,
                    help="seed of the generated traces")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: JAX finds no TPU (platform "
            f"{devices[0].platform!r}); this check runs on the chip only",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {cache_dir}", flush=True)
    try:
        if args.chips == 4:
            phase_fleet(devices[:4], seed=args.seed)
        else:
            phase_replay(seed=args.seed)
            phase_catalog(seed=args.seed)
            phase_stream(seed=args.seed)
            phase_serve(seed=args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
