"""Device-resident whole-trace OGB_cl replay — one ``lax.scan``, zero host syncs.

The per-batch driver (``for batch: ogb_batch_update(...)``) pays a Python
dispatch + host round-trip per batch and a cold ~50-sweep bisection per
projection — at paper scale (millions of requests over million-item catalogs)
the harness reintroduces exactly the per-step overhead the paper's O(log N)
policy removes.  This module owns the *raw* OGB_cl scan step (gradient
scatter-add + warm-started capped-simplex projection) and the low-level
whole-trace ``make_replay_fn`` builder used by the throughput benchmark.

The projection is *warm-started*: with a feasible pre-step state the per-chunk
threshold provably lies in [0, eta * B], and the previous chunk's tau seeds a
bracketed-Newton root-find (:func:`repro.jaxcache.fractional.
capped_simplex_project_warm`) that needs single-digit catalog sweeps instead
of ~50 cold bisection sweeps.

The public entry points (``replay_trace`` / ``sweep_replay``) are deprecated
thin wrappers over the unified policy engine — use
:func:`repro.cachesim.api.run` / :func:`repro.cachesim.api.sweep` with
``policy_def("ogb")`` instead; the OGB policy is registered there through the
same step built here, so the replayed dynamics are identical.
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.cachesim.results import RunResult, SweepResult
from repro.jaxcache.fractional import (
    DEFAULT_BISECT_ITERS,
    DEFAULT_WARM_SWEEPS,
    capped_simplex_project,
    capped_simplex_project_warm,
    madow_sample_jax,
    permanent_random_numbers,
    warm_bracket_hi,
)

#: legacy names — the five result dataclasses are unified in
#: :mod:`repro.cachesim.results`
ReplayMetrics = RunResult
ReplaySweepResult = SweepResult


def sampling_keys(seed: int, catalog_size: int, sample: str) -> tuple:
    """Seed-derived ``(p, k_u)``: the permanent random numbers for Poisson
    sampling (size-0 when unused) and the key that drives Madow offsets.
    THE one seed derivation — both the unified api carries and the legacy
    per-trace arrays build on it, so the Poisson stream cannot desync
    between the two paths (the goldens pin it)."""
    k_p, k_u = jax.random.split(jax.random.key(seed))
    p = (
        permanent_random_numbers(k_p, catalog_size)
        if sample == "poisson"
        else jnp.zeros((0,), jnp.float32)
    )
    return p, k_u


#: sampling modes that draw a per-chunk Madow offset u from the carried key
MADOW_SAMPLES = ("madow", "madow_tree")


def sample_chunk_metrics(sample: str, capacity, f, ids, p, u):
    """(reward, hits, occupancy) for one request chunk at the pre-update
    state ``f`` (OCO order).  The one definition of the Poisson / Madow /
    fractional hit-accounting conventions, shared by the OGB and OMD scan
    engines so they cannot drift.

    ``madow_tree`` is the O(C log N) form of ``madow``: the same systematic
    sample drawn by prefix-tree descent
    (:func:`repro.kernels.prefix_tree.madow_sample_tree`) instead of an
    O(N) cumsum + mask — an equally valid draw from the same marginals, but
    not the bit-identical sample set (float32 tree sums associate
    differently), so the committed goldens stay on ``madow``."""
    fi = f[ids]
    reward = jnp.sum(fi)
    if sample == "poisson":
        # hits only need the requested coordinates: B-sized gathers, not an
        # N-sized mask; occupancy is the one remaining catalog pass
        hits = jnp.sum((fi >= p[ids]).astype(jnp.int32))
        occ = jnp.sum((f >= p).astype(jnp.float32))
    elif sample == "madow":
        cached = madow_sample_jax(f, u, capacity)
        hits = jnp.sum(cached[ids].astype(jnp.int32))
        occ = jnp.sum(cached.astype(jnp.float32))
    elif sample == "madow_tree":
        from repro.kernels.prefix_tree import madow_sample_tree

        sel = madow_sample_tree(f, u, capacity)  # (C,) ascending leaf ids
        pos = jnp.searchsorted(sel, ids)
        cached = sel[jnp.minimum(pos, capacity - 1)] == ids
        hits = jnp.sum(cached.astype(jnp.int32))
        occ = jnp.float32(capacity)
    else:
        hits = jnp.zeros((), jnp.int32)
        occ = jnp.sum(f)
    return reward, hits, occ


def opt_hits_by_combo(
    trace_prefix: np.ndarray, combos: "List[Dict[str, float]]"
) -> np.ndarray:
    """Hindsight static-OPT per combo, computed host-side once per capacity
    (OPT depends only on the trace histogram and C)."""
    from repro.core.regret import best_static_hits

    opt_by_c = {
        c: float(best_static_hits(trace_prefix, c))
        for c in set(int(combo["capacity"]) for combo in combos)
    }
    return np.asarray([opt_by_c[int(c["capacity"])] for c in combos])


class ReplayCarry(NamedTuple):
    """Scan carry: donated, lives on device for the whole replay."""

    f: jax.Array  # (N,) float32 fractional state
    tau: jax.Array  # () float32 previous chunk's projection threshold
    counts: jax.Array  # (N,) float32 whole-trace histogram (hindsight OPT)

    @staticmethod
    def create(catalog_size: int, capacity: int) -> "ReplayCarry":
        return ReplayCarry(
            f=jnp.full(catalog_size, capacity / catalog_size, jnp.float32),
            tau=jnp.zeros((), jnp.float32),
            counts=jnp.zeros(catalog_size, jnp.float32),
        )


def _make_ogb_step(
    sample: str,
    projection: str,
    sweeps: int,
    iters: int,
    track_opt: bool,
    madow_capacity: Optional[int] = None,
):
    """The per-chunk OGB_cl update, with *traced* eta and capacity.

    Shared by :func:`make_replay_fn` (capacity baked in as a constant) and
    the unified policy engine (:mod:`repro.cachesim.api`, capacity vmapped
    over a grid).  The chunk size B is read off ``ids.shape`` (static under
    scan); ``madow_capacity`` must be the static C when ``sample == "madow"``
    (Madow needs a static sample count).
    """
    if sample not in ("poisson", "madow", "madow_tree", "none"):
        raise ValueError(f"unknown sample mode {sample!r}")
    if projection not in ("warm", "bisect"):
        raise ValueError(f"unknown projection mode {projection!r}")
    if sample in MADOW_SAMPLES and madow_capacity is None:
        raise ValueError("madow sampling needs a static capacity")

    def step(eta, p, cap, carry, xs):
        f, tau_prev, counts_tot = carry
        ids, u = xs
        # named scopes (ogb/<phase>) let a device trace split the step
        with jax.named_scope("ogb/sample"):
            reward, hits, occ = sample_chunk_metrics(
                sample, madow_capacity, f, ids, p, u
            )
        # gradient step as a B-element scatter-add (duplicates accumulate);
        # avoids materializing a dense (N,) counts histogram per chunk
        with jax.named_scope("ogb/gradient"):
            y = f.at[ids].add(eta)
        with jax.named_scope("ogb/project"):
            if projection == "warm":
                hi = warm_bracket_hi(eta * jnp.float32(ids.shape[0]))
                f_new, tau = capped_simplex_project_warm(
                    y, cap, jnp.float32(0.0), hi, tau_prev, sweeps
                )
            else:
                f_new, tau = capped_simplex_project(y, cap, iters)
        if track_opt:
            counts_tot = counts_tot.at[ids].add(1.0)
        return (
            ReplayCarry(f_new, tau, counts_tot),
            (reward, hits, tau, occ),
        )

    return step


@functools.lru_cache(maxsize=64)
def make_replay_fn(
    catalog_size: int,
    capacity: int,
    batch: int,
    sample: str = "poisson",
    projection: str = "warm",
    sweeps: int = DEFAULT_WARM_SWEEPS,
    iters: int = DEFAULT_BISECT_ITERS,
    track_opt: bool = True,
):
    """Build the jitted whole-trace replay.

    Returns ``replay(carry, chunks, eta, p, us) -> (carry', opt_hits, ys)``
    where ``chunks`` is (M, B) int32, ``p`` the (N,) permanent random numbers
    (Poisson sampling), ``us`` the (M,) Madow offsets (pass size-0 arrays for
    the unused one) and ``ys`` stacks per-chunk (reward, hits, tau,
    occupancy).  The carry is donated: call with a fresh ``ReplayCarry``.

    Memoized on its (hashable) configuration so repeat calls — e.g. the
    throughput benchmark's repeated timings — reuse the same jitted function
    and hence XLA's compilation cache instead of re-tracing every time.
    """
    cap_f = float(capacity)
    step = _make_ogb_step(
        sample, projection, sweeps, iters, track_opt,
        madow_capacity=capacity,
    )

    def replay(carry, chunks, eta, p, us):
        m = chunks.shape[0]
        if us.shape[0] != m:
            us = jnp.zeros((m,), jnp.float32)
        carry, ys = jax.lax.scan(
            lambda c, x: step(eta, p, jnp.float32(cap_f), c, x),
            carry,
            (chunks, us),
        )
        if track_opt:
            opt = jnp.sum(jax.lax.top_k(carry.counts, capacity)[0])
        else:
            opt = jnp.zeros((), jnp.float32)
        return carry, opt, ys

    return jax.jit(replay, donate_argnums=(0,))


def replay_trace(
    trace: np.ndarray,
    catalog_size: int,
    capacity: int,
    batch: int,
    eta: Optional[float] = None,
    sample: str = "poisson",
    projection: str = "warm",
    sweeps: int = DEFAULT_WARM_SWEEPS,
    iters: int = DEFAULT_BISECT_ITERS,
    seed: int = 0,
    track_opt: bool = True,
    keep_final_f: bool = False,
    name: str = "OGB_scan",
) -> RunResult:
    """Replay a whole trace through the scan-compiled OGB_cl engine.

    .. deprecated::
        Use ``api.run(api.policy_def("ogb", ...), trace, N, C, window=batch)``
        (:mod:`repro.cachesim.api`).  This wrapper forwards there and keeps
        the legacy signature/result shape.  Poisson and fractional replays
        are numerically identical to the pre-unification engine; under
        ``sample="madow"`` the per-chunk offsets are now counter-derived
        from the carried key (the streaming-resume requirement), so madow
        hit *samples* come from a different — equally valid — random stream.
    """
    warnings.warn(
        "replay_trace is deprecated; use repro.cachesim.api.run("
        "policy_def('ogb'), ...) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.cachesim import api

    opts = dict(sample=sample, projection=projection, sweeps=sweeps, iters=iters)
    if sample == "madow":
        opts["madow_capacity"] = int(capacity)
    res = api.run(
        api.policy_def("ogb", **opts),
        trace,
        catalog_size,
        capacity,
        window=batch,
        eta=eta,
        seed=seed,
        track_opt=track_opt,
        keep_carry=keep_final_f,  # legacy footprint: final state is opt-in
        name=name,
    )
    res.extras["sweeps"] = float(sweeps)
    return res


def sweep_replay(
    trace: np.ndarray,
    catalog_size: int,
    capacities: Sequence[int],
    etas: Sequence[Optional[float]] = (None,),
    seeds: Sequence[int] = (0,),
    batch: int = 1000,
    sample: str = "poisson",
    projection: str = "warm",
    sweeps: int = DEFAULT_WARM_SWEEPS,
    iters: int = DEFAULT_BISECT_ITERS,
    track_opt: bool = True,
) -> SweepResult:
    """Run the whole (seeds x etas x capacities) OGB grid in one dispatch.

    .. deprecated::
        Use ``api.sweep(api.policy_def("ogb", ...), trace, N, capacities,
        etas=..., seeds=..., window=batch)`` (:mod:`repro.cachesim.api`).
    """
    warnings.warn(
        "sweep_replay is deprecated; use repro.cachesim.api.sweep("
        "policy_def('ogb'), ...) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.cachesim import api

    opts = dict(sample=sample, projection=projection, sweeps=sweeps, iters=iters)
    if sample == "madow":
        if len(set(int(c) for c in capacities)) > 1:
            raise ValueError(
                "madow sweeps need a single capacity (static sample count); "
                "use sample='poisson' for capacity grids"
            )
        opts["madow_capacity"] = int(capacities[0])
    return api.sweep(
        api.policy_def("ogb", **opts),
        trace,
        catalog_size,
        capacities,
        etas=etas,
        seeds=seeds,
        window=batch,
        track_opt=track_opt,
    )
