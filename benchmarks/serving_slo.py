"""Continuous-serving latency SLO — the "heavy traffic" artifact.

Every other suite replays a dead trace and reports amortized us/request;
this one measures what serving actually pays: **per-decision latency
under sustained open-loop arrivals**.  Two decision paths are driven
through :class:`repro.serve.engine.ContinuousServingLoop`:

* ``expert_cache`` — one :class:`~repro.serve.expert_cache.OGBExpertCache`
  decision per arriving routed-count vector (the MoE serving hot path);
* ``stream_window`` — one resumable ``api.run(carry=...)`` window per
  arriving id batch (the paper's B-batched online decision, as a serving
  step instead of a replay chunk).

Arrivals are open-loop at ~70% of the measured offline capacity, so the
p99 includes real queueing delay without saturating; each track reports
p50/p99/mean decision latency and sustained requests/sec.

The second half pins the async streaming pipeline's win: the
``stream_scale`` quick shape replayed through ``run_stream`` with
``prefetch=0`` (synchronous) vs ``prefetch=2`` (double-buffered), with
the :class:`~repro.cachesim.results.StreamResult` timing split showing
the ingest/device overlap and a bit-exactness check on the hits.  The
acceptance assert is **async throughput >= synchronous** (the device no
longer waits for host ingest) — on multi-core hosts; a single-CPU host
has no second core to overlap into, so there the floor degrades to a
bounded-overhead check (``SINGLE_CORE_FLOOR``) and the recorded
``cpu_count`` says why.

Writes ``benchmarks/results/serving_slo.json`` and the tracked top-level
``BENCH_serving.json``.

Scales (``REPRO_BENCH_SCALE``): ``mini`` (CI smoke, seconds), ``quick``
(default, ~1 min), ``full`` (a few minutes).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import jax

from repro.cachesim import api
from repro.cachesim.tracelab import fit_profile, run_stream, synthesize_chunks
from repro.cachesim.traces import make_trace
from repro.serve.engine import ContinuousServingLoop
from repro.serve.expert_cache import ExpertCacheConfig, OGBExpertCache

from .common import SCALE, check_finite, csv_row, save_json

BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serving.json",
)

#: fraction of measured offline capacity offered as the open-loop rate —
#: high enough that queueing is real, low enough that p99 is an SLO and
#: not a saturation artifact
LOAD_FACTOR = 0.7

#: per-scale knobs: serving decisions per track, expert-cache geometry,
#: and the streaming shape.  The quick stream shape matches
#: ``stream_scale`` (N=100k, C=2k) — the acceptance criterion is defined
#: there; the stream window is 250 so the device scan is a real fraction
#: of the pipeline (at window=1000 ingest is ~95% of the wall and there
#: is nothing left to overlap); mini tolerates CI-runner noise.
CONFIGS = {
    "mini": {
        "serve_steps": 200,
        "layers": 2,
        "experts": 32,
        "window": 500,
        "stream": dict(
            n=20_000, c=1_000, t=100_000, window=250, repeats=2,
            min_speedup=0.85,
        ),
    },
    "quick": {
        "serve_steps": 1_000,
        "layers": 4,
        "experts": 64,
        "window": 1_000,
        "stream": dict(
            n=100_000, c=2_000, t=1_000_000, window=250, repeats=3,
            min_speedup=1.0,
        ),
    },
    "full": {
        "serve_steps": 5_000,
        "layers": 8,
        "experts": 64,
        "window": 1_000,
        "stream": dict(
            n=100_000, c=2_000, t=2_000_000, window=250, repeats=3,
            min_speedup=1.0,
        ),
    },
}

SEGMENT_LEN = 50_000

#: overlap needs a second core: on a single-CPU host the ingest thread,
#: the XLA compute pool, and the main loop time-slice one core, so total
#: work is conserved and the pipeline can only break even.  There the
#: assert degrades to "the pipeline overhead stays bounded".
SINGLE_CORE_FLOOR = 0.85


def _slo_row(name: str, slo, rate: float, extra=None) -> dict:
    row = {
        "track": name,
        "offered_rate": rate,
        "requests": slo.requests,
        "req_per_sec": slo.req_per_sec,
        "p50_ms": slo.p50_ms,
        "p99_ms": slo.p99_ms,
        "mean_ms": slo.mean_ms,
        "max_ms": slo.max_ms,
        "backlog_max": slo.backlog_max,
    }
    if extra:
        row.update(extra)
    csv_row(
        f"serving/{name}",
        1e3 * slo.mean_ms,
        f"p50={slo.p50_ms:.3f}ms p99={slo.p99_ms:.3f}ms "
        f"sustained={slo.req_per_sec:.0f}/s offered={rate:.0f}/s",
    )
    # keeping up at 70% load is the point of an SLO: a server that falls
    # behind an offered rate below its measured capacity has no SLO at all
    assert slo.req_per_sec > 0.5 * rate, (name, slo.req_per_sec, rate)
    return row


def expert_cache_track(layers: int, experts: int, steps: int, seed: int = 0):
    """Serve ``steps`` routed-count vectors through one
    :class:`~repro.serve.expert_cache.OGBExpertCache` decision each,
    open-loop at ``LOAD_FACTOR`` of the capacity measured here.

    Returns ``(slo, offered_rate, cache)``."""
    ec = OGBExpertCache(
        ExpertCacheConfig(
            n_layers=layers,
            n_experts=experts,
            resident_fraction=0.25,
            horizon_steps=steps,
            bytes_per_expert=64 << 20,  # a 64MB expert: swap traffic in bytes
        ),
        seed=seed,
    )
    rng = np.random.default_rng(seed)
    shape = (layers, experts)
    # pre-generated routed-count vectors: payload synthesis must not
    # pollute the decision latency
    payloads = [
        rng.poisson(5.0, shape).astype(np.float32) for _ in range(steps)
    ]
    for p in payloads[:20]:  # warmup: compile + residency steady-state
        ec.step(p)
    t0 = time.perf_counter()
    for p in payloads[:50]:
        ec.step(p)
    per_step = (time.perf_counter() - t0) / 50
    rate = LOAD_FACTOR / per_step

    loop = ContinuousServingLoop(lambda batch: ec.step(batch[0]))
    return loop.run(payloads, rate), rate, ec


def stream_window_track(n: int, c: int, window: int, steps: int,
                        seed: int = 1):
    """Serve ``steps`` id batches of ``window`` zipf(0.9) requests through
    one resumable dense-OGB ``api.run(carry=...)`` window each, open-loop
    at ``LOAD_FACTOR`` of the capacity measured here.

    Returns ``(slo, offered_rate)``."""
    pd = api.policy_def("ogb")
    horizon = steps * window
    rng = np.random.default_rng(seed)
    zipf_p = 1.0 / np.arange(1, n + 1) ** 0.9
    zipf_p /= zipf_p.sum()
    payloads = [
        rng.choice(n, size=window, p=zipf_p).astype(np.int64)
        for _ in range(steps)
    ]

    state = {"carry": None}

    def decide(batch):
        ids = batch[0]
        if state["carry"] is None:
            res = api.run(
                pd, ids, n, c, window=window, horizon=horizon,
                track_opt=False,
            )
        else:
            res = api.run(
                pd, ids, capacity=c, carry=state["carry"], window=window,
                track_opt=False,
            )
        state["carry"] = res.carry

    for p in payloads[:10]:  # warmup: compile
        decide([p])
    t0 = time.perf_counter()
    for p in payloads[:20]:
        decide([p])
    per_step = (time.perf_counter() - t0) / 20
    rate = LOAD_FACTOR / per_step

    state["carry"] = None  # fresh policy for the measured run
    return ContinuousServingLoop(decide).run(payloads, rate), rate


def _expert_cache_slo(cfg: dict) -> dict:
    slo, rate, ec = expert_cache_track(
        cfg["layers"], cfg["experts"], cfg["serve_steps"]
    )
    swap_bytes = (ec.swapped_in + ec.swapped_out) * ec.cfg.bytes_per_expert
    return _slo_row(
        "expert_cache",
        slo,
        rate,
        extra={
            "mean_hit_ratio": ec.mean_hit_ratio,
            "swapped_in": ec.swapped_in,
            "swapped_out": ec.swapped_out,
            "swap_gb_total": swap_bytes / 1e9,
        },
    )


def _stream_window_slo(cfg: dict, n: int, c: int) -> dict:
    slo, rate = stream_window_track(
        n, c, cfg["window"], cfg["serve_steps"]
    )
    return _slo_row(
        "stream_window", slo, rate,
        extra={"requests_per_decision": cfg["window"]},
    )


def _async_vs_sync(
    n: int, c: int, t: int, window: int, repeats: int, min_speedup: float
):
    """run_stream prefetch=2 vs prefetch=0 on the stream_scale shape:
    bit-exact results, async throughput at or above synchronous (on hosts
    with a core to overlap into; see SINGLE_CORE_FLOOR)."""
    source = make_trace(
        "bursty", min(n, 20_000), 200_000, seed=17,
        burst_fraction=0.5, burst_len_mean=8.0, burst_span=60,
    )
    profile = fit_profile(source)
    pd = api.policy_def("ogb")

    def one(prefetch: int):
        chunks = synthesize_chunks(
            profile, t, catalog=n, seed=5, chunk_size=65_536
        )
        return run_stream(
            pd, chunks, n, c, window=window, horizon=t,
            segment_len=SEGMENT_LEN, keep_carry=False, prefetch=prefetch,
        )

    one(0)  # warmup: compile both segment shapes
    best = {}
    sample = {}
    for prefetch in (0, 2):
        walls = []
        for _ in range(repeats):
            res = one(prefetch)
            walls.append(res.wall_seconds)
            sample[prefetch] = res
        best[prefetch] = min(walls)

    # the pipeline must not change the replayed dynamics, only the clock
    np.testing.assert_array_equal(sample[0].hits, sample[2].hits)
    np.testing.assert_array_equal(sample[0].reward, sample[2].reward)

    speedup = best[0] / best[2]
    rows = {}
    for prefetch in (0, 2):
        r = sample[prefetch]
        rows[f"prefetch_{prefetch}"] = {
            "wall_seconds": best[prefetch],
            "req_per_sec": t / best[prefetch],
            "us_per_request": 1e6 * best[prefetch] / t,
            "ingest_seconds": r.ingest_seconds,
            "device_seconds": r.device_seconds,
            "host_seconds": r.host_seconds,
        }
        csv_row(
            f"serving/stream_prefetch={prefetch}",
            1e6 * best[prefetch] / t,
            f"T={t} {t / best[prefetch]:.0f}req/s "
            f"ing={r.ingest_seconds:.2f}s dev={r.device_seconds:.2f}s",
        )
    cores = os.cpu_count() or 1
    floor = min_speedup if cores > 1 else min(min_speedup, SINGLE_CORE_FLOOR)
    print(
        f"async speedup {speedup:.3f}x over synchronous at "
        f"(N={n}, C={c}, T={t}, window={window}) — floor {floor:.2f}x"
        + ("" if cores > 1 else f" (single-core host: overhead bound only)")
    )
    assert speedup >= floor, (
        f"async run_stream is slower than synchronous: {speedup:.3f}x "
        f"(best async {best[2]:.3f}s vs sync {best[0]:.3f}s, "
        f"{cores} cores, floor {floor:.2f}x)"
    )
    rows["speedup"] = speedup
    rows["cpu_count"] = cores
    rows["speedup_floor"] = floor
    return rows


def main() -> dict:
    scale_name = SCALE if SCALE in CONFIGS else "quick"
    cfg = CONFIGS[scale_name]
    stream = cfg["stream"]

    out = {
        "scale": scale_name,
        "backend": jax.default_backend(),
        "load_factor": LOAD_FACTOR,
        "slo": [
            _expert_cache_slo(cfg),
            _stream_window_slo(cfg, min(stream["n"], 20_000), stream["c"]),
        ],
        "stream": _async_vs_sync(**stream),
    }

    check_finite(out)
    save_json("serving_slo", out)
    with open(BENCH_JSON, "w") as f:
        json.dump(out, f, indent=2, default=float)
    print(f"wrote {BENCH_JSON}")
    return out


if __name__ == "__main__":
    main()
