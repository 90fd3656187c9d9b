"""Shared result types for the unified run/sweep engine.

One home for the host-side views every execution path returns:

* :class:`RunResult` — one policy replayed over one trace (any kind: the
  fractional gradient policies and the discrete automata share it).
* :class:`SweepResult` — a stacked (capacities x seeds x etas) grid run in
  one vmapped dispatch.
* :class:`StreamResult` — a :class:`RunResult` accumulated out-of-core by
  :func:`repro.cachesim.tracelab.stream.run_stream`, extended with the
  windowed time-varying-OPT ("dynamic regret") accounting.
* :class:`HitStatsMixin` — the single implementation of ``hit_ratio`` and
  ``us_per_request``, also mixed into the per-request simulator's
  :class:`repro.cachesim.simulator.SimResult`.

Field conventions: per-chunk arrays are shaped ``(M,)`` (runs) or ``(R, M)``
(sweeps, one row per combo); ``reward`` is the fractional pre-update reward
(equal to ``hits`` for the integral automata), ``aux`` holds the per-chunk
projection threshold (tau for OGB, lambda for OMD, 0 for automata).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np


def find_combo(combos: "List[Dict[str, float]]", **match) -> int:
    """Row index of the sweep combo matching all given key/values."""
    for r, combo in enumerate(combos):
        if all(combo.get(k) == v for k, v in match.items()):
            return r
    raise KeyError(f"no combo matching {match}")


class HitStatsMixin:
    """The one implementation of the scalar throughput/quality ratios."""

    @property
    def hit_ratio(self) -> float:
        return float(np.sum(self.hits)) / max(self.T, 1)

    @property
    def byte_hit_ratio(self) -> float:
        """Bytes served from cache over bytes requested (sized runs).

        Falls back to the object hit ratio for unsized runs (every object
        one byte), so callers can read it unconditionally."""
        bh = getattr(self, "byte_hits", None)
        bt = float(getattr(self, "bytes_total", 0.0) or 0.0)
        if bh is None or bt <= 0.0:
            return self.hit_ratio
        return float(np.sum(bh)) / bt

    @property
    def us_per_request(self) -> float:
        return 1e6 * self.wall_seconds / max(self.T, 1)


@dataclass
class RunResult(HitStatsMixin):
    """Host-side view of one policy replay (single final fetch).

    ``carry`` is the final device carry — pass it back to
    :func:`repro.cachesim.api.run` to resume the replay on the next trace
    chunk (the streaming contract; note the carry is *donated* on resume,
    so hand it off rather than keeping references).
    """

    name: str
    kind: str
    T: int  # requests actually replayed (num_chunks * window)
    window: int  # requests per chunk (the OGB/OMD update batch B)
    capacity: int
    reward: np.ndarray  # (M,) per-chunk fractional reward (== hits if integral)
    hits: np.ndarray  # (M,) per-chunk integral hits
    aux: np.ndarray  # (M,) per-chunk projection threshold (tau / lambda)
    occupancy: np.ndarray  # (M,) per-chunk cached mass / item count
    opt_hits: float = 0.0  # hindsight static-OPT reward over the replayed prefix
    carry: Any = None  # final device carry (resumable)
    wall_seconds: float = 0.0
    extras: Dict[str, float] = field(default_factory=dict)
    byte_hits: Optional[np.ndarray] = None  # (M,) per-chunk byte hits (sized)
    bytes_total: float = 0.0  # total bytes requested (sized runs, else 0)

    @property
    def frac_hit_ratio(self) -> float:
        return float(self.reward.sum()) / max(self.T, 1)

    @property
    def regret(self) -> float:
        """Hindsight regret of the fractional (OCO) reward."""
        return self.opt_hits - float(self.reward.sum())

    @property
    def integral_regret(self) -> float:
        return self.opt_hits - float(self.hits.sum())

    def windowed_hit_ratio(self, window: int) -> np.ndarray:
        """Hit ratio per non-overlapping window (rounded to whole chunks)."""
        per = max(window // self.window, 1)
        m = (len(self.hits) // per) * per
        if m == 0:
            return np.array([self.hit_ratio])
        return self.hits[:m].reshape(-1, per).sum(axis=1) / (per * self.window)

    def windowed_frac_ratio(self, window: int) -> np.ndarray:
        per = max(window // self.window, 1)
        m = (len(self.reward) // per) * per
        if m == 0:
            return np.array([self.frac_hit_ratio])
        return self.reward[:m].reshape(-1, per).sum(axis=1) / (
            per * self.window
        )


@dataclass
class StreamResult(RunResult):
    """A :class:`RunResult` accumulated out-of-core by
    :func:`repro.cachesim.tracelab.stream.run_stream`.

    Per-chunk arrays are concatenated across stream segments (so every
    inherited windowed/ratio view works unchanged); on top of them the
    stream tracks the **time-varying OPT proxy**: ``dyn_opt_hits[k]`` is
    the hindsight-optimal static allocation recomputed for the ``k``-th
    ``dyn_opt_window``-request window alone (the final window may be a
    shorter remainder — see :attr:`dyn_opt_lens` — so together the windows
    cover every replayed request).  Summed, that is the comparator of the
    *dynamic* regret notion (an adversary allowed to re-pick its cache
    every window) — a strictly harder bar than the static OPT in
    ``opt_hits``.

    **Timing split:** ``wall_seconds`` stays the total wall clock of the
    stream (back-compat).  ``ingest_seconds`` is time spent inside the
    chunk source and ``host_seconds`` the main thread's dynamic-OPT
    accounting and folding of results.  On the synchronous path
    (``prefetch=0``) they sum to at most ``wall_seconds``; on the async
    pipeline they overlap the device.  The dispatch and the wait for the
    device are the ``repro.run.dispatch`` and ``repro.stream.wait_device``
    spans of a profiler trace.
    """

    dyn_opt_hits: Optional[np.ndarray] = None  # (K,) per-window OPT hits
    dyn_opt_window: int = 0  # requests per dynamic-OPT window (0 = off)
    n_segments: int = 0  # device dispatches the stream took
    t_dropped: int = 0  # trailing requests short of one window, not replayed
    ingest_seconds: float = 0.0  # time waiting on the chunk source
    host_seconds: float = 0.0  # dynamic-OPT accounting + folding results
    prefetch: int = 0  # pipeline depth the stream ran with (0 = synchronous)

    @property
    def dyn_opt_lens(self) -> np.ndarray:
        """Requests covered by each dynamic-OPT window.

        All windows are ``dyn_opt_window`` long except the last, which
        covers the replayed remainder (the flush that keeps
        ``sum(dyn_opt_lens) == T``)."""
        if self.dyn_opt_hits is None:
            raise ValueError("run_stream(..., opt_window=...) was not set")
        k = len(self.dyn_opt_hits)
        lens = np.full(k, self.dyn_opt_window, np.int64)
        if k:
            lens[-1] = self.T - (k - 1) * self.dyn_opt_window
        return lens

    @property
    def dynamic_opt_total(self) -> float:
        """Total hits of the per-window re-optimized comparator."""
        if self.dyn_opt_hits is None:
            raise ValueError("run_stream(..., opt_window=...) was not set")
        return float(np.sum(self.dyn_opt_hits))

    @property
    def dynamic_regret(self) -> float:
        """Fractional-reward regret vs the time-varying OPT proxy, over the
        prefix the dynamic windows cover (== every replayed request)."""
        total = self.dynamic_opt_total  # raises cleanly when not tracked
        covered = int(self.dyn_opt_lens.sum())
        chunks = covered // max(self.window, 1)
        return total - float(self.reward[:chunks].sum())

    def dyn_opt_ratio(self) -> np.ndarray:
        """Per-window hit ratio of the time-varying OPT proxy."""
        lens = self.dyn_opt_lens  # raises cleanly when not tracked
        return self.dyn_opt_hits / np.maximum(lens, 1)


@dataclass
class SweepResult:
    """Stacked replays over a parameter grid (single vmapped dispatch).

    ``combos[r]`` names row ``r``: always ``capacity`` and ``seed``, plus
    ``eta`` for the fractional policies; :meth:`row` looks rows up by any
    subset of those keys.
    """

    kind: str
    combos: List[Dict[str, float]]
    T: int
    window: int
    reward: np.ndarray  # (R, M)
    hits: np.ndarray  # (R, M)
    aux: np.ndarray  # (R, M)
    occupancy: np.ndarray  # (R, M)
    opt_hits: np.ndarray  # (R,) hindsight static-OPT per combo (host-side)
    wall_seconds: float = 0.0
    byte_hits: Optional[np.ndarray] = None  # (R, M) per-chunk byte hits
    bytes_total: float = 0.0  # total bytes requested (sized runs, else 0)

    @property
    def byte_hit_ratios(self) -> np.ndarray:
        """Per-combo byte hit ratio (falls back to object ratio unsized)."""
        if self.byte_hits is None or self.bytes_total <= 0.0:
            return self.hit_ratios
        return self.byte_hits.sum(axis=1) / self.bytes_total

    @property
    def hit_ratios(self) -> np.ndarray:
        return self.hits.sum(axis=1) / max(self.T, 1)

    @property
    def frac_hit_ratios(self) -> np.ndarray:
        return self.reward.sum(axis=1) / max(self.T, 1)

    @property
    def regrets(self) -> np.ndarray:
        return self.opt_hits - self.reward.sum(axis=1)

    def row(self, **match) -> int:
        return find_combo(self.combos, **match)


@dataclass
class FleetResult:
    """Host-side view of one multi-tenant fleet replay.

    E independent per-tenant caches stepped in lockstep by one vmapped,
    donated-carry scan (``api._fleet_jit``): every per-chunk observable
    gains a leading tenant axis, so ``reward``/``hits``/``aux``/
    ``occupancy`` are ``(E, M)`` and the scalar ratios aggregate over the
    whole fleet.  ``T`` is the number of requests replayed *per tenant*
    (the fleet steps in lockstep, so it is shared); ``carry`` is the
    final tenant-stacked carry — pass it back to ``run_fleet(carry=...)``
    to resume every tenant mid-stream in one call.
    """

    name: str
    kind: str
    n_tenants: int
    T: int  # requests replayed PER TENANT (num_chunks * window)
    window: int
    capacities: np.ndarray  # (E,)
    seeds: np.ndarray  # (E,) (-1 on resumed runs: seeds live in the carry)
    etas: Optional[np.ndarray]  # (E,) resolved per-tenant eta, fractional only
    reward: np.ndarray  # (E, M)
    hits: np.ndarray  # (E, M)
    aux: np.ndarray  # (E, M)
    occupancy: np.ndarray  # (E, M)
    opt_hits: np.ndarray  # (E,) per-tenant hindsight static OPT (0 if untracked)
    carry: Any = None  # final tenant-stacked device carry (resumable)
    wall_seconds: float = 0.0
    byte_hits: Optional[np.ndarray] = None  # (E, M) sized runs only
    bytes_total: Optional[np.ndarray] = None  # (E,) bytes requested per tenant
    n_segments: int = 1  # dispatches (1 for in-memory run_fleet)
    t_dropped: int = 0  # unreplayed tail requests across the fleet (stream)
    prefetch: int = 0

    @property
    def total_requests(self) -> int:
        """Requests replayed across the whole fleet (E * T)."""
        return self.n_tenants * self.T

    @property
    def tenant_hit_ratios(self) -> np.ndarray:
        """(E,) integral hit ratio of each tenant."""
        return self.hits.sum(axis=1) / max(self.T, 1)

    @property
    def tenant_frac_ratios(self) -> np.ndarray:
        """(E,) fractional (OCO) reward ratio of each tenant."""
        return self.reward.sum(axis=1) / max(self.T, 1)

    @property
    def regrets(self) -> np.ndarray:
        """(E,) per-tenant hindsight regret of the fractional reward."""
        return self.opt_hits - self.reward.sum(axis=1)

    @property
    def hit_ratio(self) -> float:
        """Aggregate hit ratio over every request the fleet served."""
        return float(self.hits.sum()) / max(self.total_requests, 1)

    @property
    def hit_ratio_mean(self) -> float:
        return float(self.tenant_hit_ratios.mean())

    @property
    def hit_ratio_p5(self) -> float:
        """5th-percentile tenant hit ratio — the tail tenants SLOs live on."""
        return float(np.percentile(self.tenant_hit_ratios, 5.0))

    @property
    def hit_ratio_p95(self) -> float:
        return float(np.percentile(self.tenant_hit_ratios, 95.0))

    @property
    def byte_hit_ratio(self) -> float:
        """Fleet-aggregate byte hit ratio (object ratio when unsized)."""
        if self.byte_hits is None or self.bytes_total is None:
            return self.hit_ratio
        bt = float(np.sum(self.bytes_total))
        if bt <= 0.0:
            return self.hit_ratio
        return float(np.sum(self.byte_hits)) / bt

    @property
    def us_per_request(self) -> float:
        """Aggregate dispatch cost per request across the fleet."""
        return 1e6 * self.wall_seconds / max(self.total_requests, 1)

    @property
    def requests_per_second(self) -> float:
        return self.total_requests / max(self.wall_seconds, 1e-12)


@dataclass
class EdgeFleetResult:
    """Two-level edge->origin replay: E edge caches, one shared origin.

    ``edges`` is the fleet replay of the per-edge request streams;
    ``origin`` is the streamed replay of the deterministic interleave of
    every edge miss (arrival-position major, edge index minor).
    ``origin_requests`` counts every edge miss handed to the origin tier —
    the origin replays its window-aligned prefix of them (its ``T``).
    """

    edges: "FleetResult"
    origin: Any  # StreamResult of the origin cache over the miss stream
    origin_requests: int

    @property
    def edge_hit_ratio(self) -> float:
        return self.edges.hit_ratio

    @property
    def origin_hit_ratio(self) -> float:
        return self.origin.hit_ratio

    @property
    def end_to_end_hit_ratio(self) -> float:
        """Requests served by either tier over all edge-arriving requests."""
        total = self.edges.total_requests
        return float(self.edges.hits.sum() + self.origin.hits.sum()) / max(
            total, 1
        )
