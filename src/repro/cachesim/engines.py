"""Device-resident dense policy engines: the fractional steps and the
classic policies as scan automata.

Each engine here owns its carry and its per-chunk step; the execution layer
lives in :mod:`repro.cachesim.api`, where every kind is registered as a
:class:`~repro.cachesim.api.PolicyDef` and replayed/swept by the one
generic engine (the tree engines of :mod:`repro.cachesim.tree_engines`
follow the same seam).

* **OGB** — the paper's gradient step (a B-element scatter-add) and the
  capped-simplex projection, warm-started: with a feasible pre-step state
  the per-chunk threshold provably lies in ``[0, eta * B]``, and the
  previous chunk's tau seeds a bracketed-Newton root-find
  (:func:`repro.jaxcache.fractional.capped_simplex_project_warm`) that
  needs single-digit catalog sweeps instead of ~50 cold bisection sweeps.
* **OMD** — negative-entropy mirror descent (multiplicative weights with a
  KL projection onto the capped simplex), sharing the warm-bracket idea:
  after the log-weight step the threshold provably lies in ``[0, eta * B]``,
  and a few safeguarded Newton sweeps replace a cold bisection.
* **LRU / FIFO** — fixed-size slot arrays ``(slots, stamps)``: membership is a
  C-wide compare, the victim is ``argmin(stamps)`` (last-use time for LRU,
  insertion time for FIFO).  Bit-exact vs the OrderedDict policies: the hit
  sequence depends only on the membership set, which is fully determined by
  the timestamp map.
* **LFU** — perfect-frequency counters over the catalog plus slot arrays with
  the Python policy's exact ``(freq, tick)`` eviction key and "admit only if
  the newcomer's frequency beats the victim's" rule, via a two-stage argmin
  (min frequency, then min tick).
* **FTPL** — perturbed counters ``count + noise`` with top-C membership
  maintained by single-swap eviction.  The noise is the *same float32 grid*
  the host policy uses (:func:`repro.core.ftpl.ftpl_noise`), and scores are
  float32 IEEE adds on both sides, so agreement is bit-exact, not approximate.

The fractional engines share one sampling convention
(:func:`sampling_keys`, :func:`sample_chunk_metrics`): the seed derivation
of the Poisson permanent random numbers and the Madow key, and the
per-chunk reward / hits / occupancy accounting at the pre-update state.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.ftpl import ftpl_initial_top_c, ftpl_noise, theoretical_zeta
from repro.jaxcache.fractional import (
    capped_simplex_project,
    capped_simplex_project_warm,
    madow_sample_jax,
    permanent_random_numbers,
    warm_bracket_hi,
)

_I32_MAX = np.int32(np.iinfo(np.int32).max)
_I32_MIN = np.int32(np.iinfo(np.int32).min)

#: kinds compiled by this module as discrete slot automata
ENGINE_KINDS = ("lru", "fifo", "lfu", "ftpl")
DEFAULT_OMD_SWEEPS = 10

# ---------------------------------------------------------------------------
# carries — NamedTuples of fixed-shape device arrays
# ---------------------------------------------------------------------------
class SlotCarry(NamedTuple):
    """LRU / FIFO state: C slots with an eviction timestamp each.

    Slot ids: ``-1`` empty (fillable), ``-2`` inactive (capacity padding for
    vmapped sweeps over capacities; never matched, never evicted into).
    """

    slots: jax.Array  # (K,) int32 item ids
    stamps: jax.Array  # (K,) int32; empty = -1, inactive = INT32_MAX
    t: jax.Array  # () int32 request clock


class LFUCarry(NamedTuple):
    slots: jax.Array  # (K,) int32 item ids (-1 empty, -2 inactive)
    ticks: jax.Array  # (K,) int32 tie-break clock; inactive = INT32_MAX
    counts: jax.Array  # (N,) int32 perfect-LFU counters
    t: jax.Array  # () int32


class FTPLCarry(NamedTuple):
    slots: jax.Array  # (K,) int32 item ids (-2 inactive; always C cached)
    counts: jax.Array  # (N,) int32 request counters
    noise: jax.Array  # (N,) float32 one-shot perturbation (constant)


class OGBCarry(NamedTuple):
    """OGB_cl state with its per-combo parameters as traced leaves."""

    f: jax.Array  # (N,) float32 fractional state
    tau: jax.Array  # () float32 previous chunk's projection threshold
    eta: jax.Array  # () float32 learning rate
    cap: jax.Array  # () float32 capacity
    p: jax.Array  # (N,) permanent random numbers (poisson) or (0,)
    u_key: jax.Array  # (2,) uint32 key data for per-chunk Madow offsets
    t: jax.Array  # () int32 chunk counter


class OMDCarry(NamedTuple):
    """OMD log-weight state with its per-combo parameters as traced leaves.

    The weights are renormalized every chunk, so f = min(1, exp(w)) is
    always feasible."""

    f: jax.Array  # (N,) float32 fractional state
    w: jax.Array  # (N,) float32 log-weights (renormalized every chunk)
    lam: jax.Array  # () float32 last KL-projection threshold
    eta: jax.Array  # () float32
    cap: jax.Array  # () float32
    p: jax.Array  # (N,) or (0,)
    u_key: jax.Array  # (2,) uint32
    t: jax.Array  # () int32


def _padded(active: np.ndarray, n_slots: int, inactive_val: int) -> jnp.ndarray:
    pad = n_slots - len(active)
    if pad < 0:
        raise ValueError(f"n_slots {n_slots} < capacity {len(active)}")
    return jnp.asarray(
        np.concatenate([active, np.full(pad, inactive_val, active.dtype)])
    )


def init_engine_carry(
    kind: str,
    catalog_size: int,
    capacity: int,
    *,
    n_slots: Optional[int] = None,
    seed: int = 0,
    zeta: Optional[float] = None,
    horizon: Optional[int] = None,
):
    """Build the initial carry for one automaton.

    ``n_slots`` > capacity pads with inactive slots so carries for different
    capacities share a shape (the vmapped-sweep requirement).
    """
    K = int(n_slots) if n_slots else int(capacity)
    C = int(capacity)
    if kind in ("lru", "fifo"):
        return SlotCarry(
            slots=_padded(np.full(C, -1, np.int32), K, -2),
            stamps=_padded(np.full(C, -1, np.int32), K, _I32_MAX),
            t=jnp.zeros((), jnp.int32),
        )
    if kind == "lfu":
        return LFUCarry(
            slots=_padded(np.full(C, -1, np.int32), K, -2),
            ticks=_padded(np.full(C, -1, np.int32), K, _I32_MAX),
            counts=jnp.zeros(catalog_size, jnp.int32),
            t=jnp.zeros((), jnp.int32),
        )
    if kind == "ftpl":
        if zeta is None:
            if horizon is None:
                raise ValueError("ftpl needs zeta or horizon")
            zeta = theoretical_zeta(C, catalog_size, horizon)
        noise = ftpl_noise(catalog_size, zeta, seed=seed)
        top = ftpl_initial_top_c(noise, C).astype(np.int32)
        return FTPLCarry(
            slots=_padded(top, K, -2),
            counts=jnp.zeros(catalog_size, jnp.int32),
            noise=jnp.asarray(noise),
        )
    raise ValueError(f"unknown engine kind {kind!r} (have {ENGINE_KINDS})")


# ---------------------------------------------------------------------------
# per-request steps — each mirrors its core/policies.py counterpart exactly
# ---------------------------------------------------------------------------
def _lru_step(carry: SlotCarry, j):
    slots, stamps, t = carry
    match = slots == j
    hit = jnp.any(match)
    # one fused pass: a matching slot outranks every timestamp, so argmin is
    # the hit slot on a hit and the oldest (or first empty) slot on a miss
    idx = jnp.argmin(jnp.where(match, _I32_MIN, stamps))
    slots = slots.at[idx].set(j)  # no-op on hit (slot already holds j)
    stamps = stamps.at[idx].set(t)  # refresh-on-hit == LRU
    return SlotCarry(slots, stamps, t + 1), hit


def _fifo_step(carry: SlotCarry, j):
    slots, stamps, t = carry
    match = slots == j
    hit = jnp.any(match)
    idx = jnp.argmin(jnp.where(match, _I32_MIN, stamps))
    # FIFO never refreshes: on a hit both writes are no-ops
    slots = slots.at[idx].set(j)
    stamps = stamps.at[idx].set(jnp.where(hit, stamps[idx], t))
    return SlotCarry(slots, stamps, t + 1), hit


def _lfu_step(carry: LFUCarry, j):
    slots, ticks, counts, t = carry
    counts = counts.at[j].add(1)
    f = counts[j]
    match = slots == j
    hit = jnp.any(match)
    # per-slot eviction key (freq, tick): empty slots (-1) sort below any real
    # frequency >= 1 so they fill first; inactive slots (-2) sort above all
    sf = jnp.where(
        slots >= 0,
        counts[jnp.maximum(slots, 0)],
        jnp.where(slots == -1, jnp.int32(-1), _I32_MAX),
    )
    minf = jnp.min(sf)
    victim = jnp.argmin(jnp.where(sf == minf, ticks, _I32_MAX))
    idx = jnp.where(hit, jnp.argmax(match), victim)
    # admission: the newcomer must match the victim's frequency (policies.LFU)
    write = jnp.logical_or(hit, f >= minf)
    slots = slots.at[idx].set(jnp.where(write, j, slots[idx]))
    ticks = ticks.at[idx].set(jnp.where(write, t, ticks[idx]))
    return LFUCarry(slots, ticks, counts, t + 1), hit


def _ftpl_step(carry: FTPLCarry, j):
    slots, counts, noise = carry
    counts = counts.at[j].add(1)
    s = counts[j].astype(jnp.float32) + noise[j]
    match = slots == j
    hit = jnp.any(match)
    si = jnp.maximum(slots, 0)
    sscore = jnp.where(
        slots >= 0, counts[si].astype(jnp.float32) + noise[si], jnp.inf
    )
    mins = jnp.min(sscore)
    # ties break by item id, matching the host policy's (score, item) store
    victim = jnp.argmin(jnp.where(sscore == mins, slots, _I32_MAX))
    swap = jnp.logical_and(~hit, s > mins)  # strict >, like the host policy
    slots = slots.at[victim].set(jnp.where(swap, j, slots[victim]))
    return FTPLCarry(slots, counts, noise), hit


def _occ_slots(carry) -> jax.Array:
    return jnp.sum((carry.slots >= 0).astype(jnp.int32))


_STEPS = {
    "lru": _lru_step,
    "fifo": _fifo_step,
    "lfu": _lfu_step,
    "ftpl": _ftpl_step,
}


# ---------------------------------------------------------------------------
# fractional engines: the shared sampling convention
# ---------------------------------------------------------------------------
#: sampling modes that draw a per-chunk Madow offset u from the carried key
MADOW_SAMPLES = ("madow", "madow_tree")


def sampling_keys(seed: int, catalog_size: int, sample: str) -> tuple:
    """Seed-derived ``(p, k_u)``: the permanent random numbers for Poisson
    sampling (size-0 when unused) and the key that drives Madow offsets.
    THE one seed derivation — every fractional carry (dense and tree)
    builds on it, so the Poisson stream cannot desync between engines (the
    goldens pin it)."""
    k_p, k_u = jax.random.split(jax.random.key(seed))
    p = (
        permanent_random_numbers(k_p, catalog_size)
        if sample == "poisson"
        else jnp.zeros((0,), jnp.float32)
    )
    return p, k_u


def _chunk_u(sample: str, u_key: jax.Array, t: jax.Array) -> jax.Array:
    """Per-chunk Madow offset, derived from the carried key + chunk counter
    (counter-mode so streamed/resumed runs draw the same sequence)."""
    if sample not in MADOW_SAMPLES:
        return jnp.zeros((), jnp.float32)
    k = jax.random.fold_in(jax.random.wrap_key_data(u_key), t)
    return jax.random.uniform(k, (), jnp.float32)


def sample_chunk_metrics(sample: str, capacity, f, ids, p, u):
    """(reward, hits, occupancy) for one request chunk at the pre-update
    state ``f`` (OCO order).  The one definition of the Poisson / Madow /
    fractional hit-accounting conventions, shared by the OGB and OMD
    chunks so they cannot drift.

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


def _check_sample(sample: str, madow_capacity: Optional[int]) -> None:
    if sample not in ("poisson", "madow", "madow_tree", "none"):
        raise ValueError(f"unknown sample mode {sample!r}")
    if sample in MADOW_SAMPLES and madow_capacity is None:
        raise ValueError("madow sampling needs a static capacity")


# ---------------------------------------------------------------------------
# OGB — gradient step + warm-started capped-simplex projection
# ---------------------------------------------------------------------------
def make_ogb_chunk(
    sample: str,
    projection: str,
    sweeps: int,
    iters: int,
    madow_capacity: Optional[int] = None,
):
    """Per-chunk OGB_cl step ``(carry, ids) -> (carry, (reward, hits, tau,
    occ))`` over an :class:`OGBCarry`; advances ``carry.t``.

    eta and capacity are traced carry leaves, so one compiled step serves
    a single replay and a vmapped grid.  The chunk size B is read off
    ``ids.shape`` (static under scan); ``madow_capacity`` must be the
    static C under Madow sampling (it needs a static sample count).
    ``projection="bisect"`` is the cold bisection (the tests' reference).
    """
    _check_sample(sample, madow_capacity)
    if projection not in ("warm", "bisect"):
        raise ValueError(f"unknown projection mode {projection!r}")

    def chunk(carry: OGBCarry, ids):
        u = _chunk_u(sample, carry.u_key, carry.t)
        # named scopes (ogb/<phase>) let a device trace split the step
        with jax.named_scope("ogb/sample"):
            reward, hits, occ = sample_chunk_metrics(
                sample, madow_capacity, carry.f, ids, carry.p, u
            )
        # gradient step as a B-element scatter-add (duplicates accumulate);
        # avoids materializing a dense (N,) counts histogram per chunk
        with jax.named_scope("ogb/gradient"):
            y = carry.f.at[ids].add(carry.eta)
        with jax.named_scope("ogb/project"):
            if projection == "warm":
                hi = warm_bracket_hi(carry.eta * jnp.float32(ids.shape[0]))
                f, tau = capped_simplex_project_warm(
                    y, carry.cap, jnp.float32(0.0), hi, carry.tau, sweeps
                )
            else:
                f, tau = capped_simplex_project(y, carry.cap, iters)
        carry = carry._replace(f=f, tau=tau, t=carry.t + 1)
        return carry, (reward, hits, tau, occ)

    return chunk


# ---------------------------------------------------------------------------
# OMD — mirror-descent fractional step (multiplicative analogue of OGB)
# ---------------------------------------------------------------------------
def _omd_project(w, cap, hi, sweeps):
    """Safeguarded-Newton KL threshold: lam with sum min(1, e^(w-lam)) = C.

    For feasible pre-step weights the root provably lies in [0, hi] where hi
    covers the added gradient mass eta*B (same invariant as
    ``warm_bracket_hi``): weights only grew, so mass(0) >= C, and every
    log-weight grew by at most eta*B, so mass(eta*B) <= C.  g is convex and
    decreasing, so Newton from the mass-excess side converges monotonically;
    the bisection midpoint safeguards the other side.
    """
    cap = jnp.float32(cap)

    def body(_, c):
        lo, hi, t = c
        e = jnp.exp(w - t)
        fcur = jnp.minimum(1.0, e)
        mass = jnp.sum(fcur)
        interior = jnp.sum(jnp.where(e < 1.0, e, 0.0))
        too_much = mass >= cap
        lo = jnp.where(too_much, t, lo)
        hi = jnp.where(too_much, hi, t)
        t_newton = t + (mass - cap) / jnp.maximum(interior, 1e-12)
        t_mid = 0.5 * (lo + hi)
        ok = jnp.logical_and(t_newton >= lo, t_newton <= hi)
        return lo, hi, jnp.where(ok, t_newton, t_mid)

    lo0 = jnp.float32(0.0)
    _lo, _hi, lam = jax.lax.fori_loop(
        0, sweeps, body, (lo0, jnp.float32(hi), lo0)
    )
    return lam


def make_omd_chunk(
    sample: str,
    sweeps: int = DEFAULT_OMD_SWEEPS,
    madow_capacity: Optional[int] = None,
):
    """Per-chunk OMD step ``(carry, ids) -> (carry, (reward, hits, lam,
    occ))`` over an :class:`OMDCarry` — the mirror-descent counterpart of
    :func:`make_ogb_chunk` (same contract); advances ``carry.t``."""
    _check_sample(sample, madow_capacity)

    def chunk(carry: OMDCarry, ids):
        u = _chunk_u(sample, carry.u_key, carry.t)
        reward, hits, occ = sample_chunk_metrics(
            sample, madow_capacity, carry.f, ids, carry.p, u
        )
        eta = carry.eta
        w = carry.w.at[ids].add(eta)
        lam = _omd_project(
            w, carry.cap, warm_bracket_hi(eta * jnp.float32(ids.shape[0])),
            sweeps,
        )
        w = w - lam  # renormalize: f = min(1, e^w) stays threshold-free
        f = jnp.minimum(1.0, jnp.exp(w))
        carry = carry._replace(f=f, w=w, lam=lam, t=carry.t + 1)
        return carry, (reward, hits, lam, occ)

    return chunk
