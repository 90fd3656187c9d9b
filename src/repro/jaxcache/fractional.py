"""Batched fractional OGB_cl in pure JAX — the TPU data-plane form.

Per batch of B requests over a catalog of N items (paper Eq. 2 / §5.3):

    counts = histogram(request_ids)           # the summed gradient
    y      = f + eta * counts                 # ascent step
    tau    = root of sum(clip(y - tau, 0, 1)) = C     (capped-simplex proj.)
    f'     = clip(y - tau, 0, 1)

Everything is element-wise over the catalog except the scalar root-find, which
is K bisection iterations each needing one global sum — this is the structure
the Pallas kernel (repro.kernels.capped_simplex) fuses and the shard_map
version (repro.jaxcache.sharded) distributes with one psum per iteration.

`jnp.float32` is sufficient: tau only needs ~1e-7 relative accuracy for the
sampling decisions downstream (validated against the float64 numpy oracle).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

DEFAULT_BISECT_ITERS = 50
DEFAULT_WARM_SWEEPS = 5


def request_counts(ids: jax.Array, catalog_size: int) -> jax.Array:
    """Histogram of request ids — the batch gradient (one-hot sum)."""
    return jnp.zeros(catalog_size, jnp.float32).at[ids].add(1.0)


def warm_bracket_hi(step_mass) -> jax.Array:
    """Upper bracket for the warm projection of y = f + (gradient step).

    ``step_mass`` is the total gradient mass added this step (eta * B for a
    B-request batch, or eta * sum(counts) in general).  For a *feasible*
    pre-step f the threshold satisfies 0 <= tau <= step_mass; the small
    relative + absolute slack absorbs float32 rounding of the mass sums.
    This is the single definition of that invariant — every warm path
    (scan replay, per-batch, sharded, Pallas) must use it.
    """
    return jnp.float32(step_mass) * (1.0 + 1e-5) + 1e-7


def capped_simplex_project(
    y: jax.Array,
    capacity: float,
    iters: int = DEFAULT_BISECT_ITERS,
    lo: Optional[jax.Array] = None,
    hi: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Bisection projection onto {f in [0,1]^N : sum f = C}. Returns (f, tau).

    ``lo``/``hi`` override the cold bracket [min(y)-1, max(y)].  When the step
    comes from an OGB update (y = f + eta*counts with f already feasible) the
    threshold provably lies in [0, eta*sum(counts)], a far tighter bracket.
    """
    if lo is None:
        lo = jnp.min(y) - 1.0
    if hi is None:
        hi = jnp.max(y)
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        mass = jnp.sum(jnp.clip(y - mid, 0.0, 1.0))
        too_much = mass >= capacity
        return jnp.where(too_much, mid, lo), jnp.where(too_much, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    tau = 0.5 * (lo + hi)
    return jnp.clip(y - tau, 0.0, 1.0), tau


_TILE = 1024  # float32 elements in one (8, 128) TPU tile
_LANES = 128
_MAX_ROWS = 1024  # most terms in one partial sum of a sweep


def _sweep_layout(y: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Split a 1-D y into (G, R, 128) blocks of whole tiles and a < 1024 tail.

    R is the largest multiple of 8 up to _MAX_ROWS that divides the blocks'
    rows, so the split depends on len(y) alone; G = 0 when len(y) < 1024."""
    tiles = y.shape[0] // _TILE
    rows = 8 * max(k for k in range(1, _MAX_ROWS // 8 + 1) if tiles % k == 0)
    main = tiles * _TILE
    return y[:main].reshape(-1, rows, _LANES), y[main:]


def _mass_count(z: jax.Array, t: jax.Array, axis: int):
    """(mass, interior count) of clip(z - t, 0, 1), reduced over ``axis``.

    One variadic reduce yields both, so a sweep reads z once."""
    clipped = jnp.clip(z - t, 0.0, 1.0)
    interior = jnp.logical_and(clipped > 0.0, clipped < 1.0).astype(jnp.float32)
    return jax.lax.reduce(
        (clipped, interior),
        (jnp.float32(0.0), jnp.float32(0.0)),
        lambda a, b: (a[0] + b[0], a[1] + b[1]),
        (axis,),
    )


def capped_simplex_project_warm(
    y: jax.Array,
    capacity: float,
    lo: jax.Array,
    hi: jax.Array,
    tau0: jax.Array,
    sweeps: int = DEFAULT_WARM_SWEEPS,
) -> Tuple[jax.Array, jax.Array]:
    """Warm-started projection: bracketed Newton on the piecewise-linear g.

    g(tau) = sum(clip(y - tau, 0, 1)) is non-increasing and piecewise linear
    with slope -#{i : 0 < y_i - tau < 1}.  Each sweep evaluates (mass,
    interior count) in one catalog pass, shrinks the bracket, and proposes the
    Newton point ``tau + (g - C) / count`` (exact whenever the remaining
    bracket contains no clip breakpoint), safeguarded by the bisection
    midpoint.  ``sweeps`` single-digit passes match ~50 cold bisection sweeps.

    Requires a valid bracket g(lo) >= C >= g(hi); for an OGB step
    (y = f + eta*counts, f feasible) lo=0, hi=eta*sum(counts) always works,
    and ``tau0`` = previous step's tau is an excellent seed because the
    cumulative threshold rho_t = sum_s tau_s drifts slowly (it is monotone
    non-decreasing, with per-step increment tau_t in that same bracket).
    """
    cap = jnp.float32(capacity)
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    t = jnp.clip(jnp.asarray(tau0, jnp.float32), lo, hi)

    # Each sweep reads y lane-dense.  On the TPU a 1-D float32 array lies in
    # tiles of 1024 elements, one (8, 128) vreg each, so the whole tiles of y
    # view as (G, R, 128) blocks with every lane holding a catalog item: a
    # sweep reads each element once, at 4 bytes, and reducing over R adds
    # across vregs on the VPU.  The (G, 128) partials hold at most _MAX_ROWS
    # terms each before the pairwise jnp.sum, which bounds the float32 error
    # on every backend.  The blocks do not change between sweeps, so XLA
    # slices them out of y once, before the loop.
    blocks, tail = _sweep_layout(y.ravel())

    def body(_, carry):
        lo, hi, t = carry
        pm, pc = _mass_count(blocks, t, axis=1)
        tm, tc = _mass_count(tail, t, axis=0)
        mass = jnp.sum(pm) + tm
        cnt = jnp.sum(pc) + tc
        too_much = mass >= cap
        lo = jnp.where(too_much, t, lo)
        hi = jnp.where(too_much, hi, t)
        t_newton = t + (mass - cap) / jnp.maximum(cnt, 1.0)
        t_mid = 0.5 * (lo + hi)
        ok = jnp.logical_and(cnt > 0.0, jnp.logical_and(t_newton >= lo, t_newton <= hi))
        return lo, hi, jnp.where(ok, t_newton, t_mid)

    _lo, _hi, tau = jax.lax.fori_loop(0, sweeps, body, (lo, hi, t))
    return jnp.clip(y - tau, 0.0, 1.0), tau


class FractionalState(NamedTuple):
    """Catalog-wide fractional cache state (the data-plane state)."""

    f: jax.Array  # (N,) float32, in the capped simplex
    step: jax.Array  # () int32

    @staticmethod
    def create(catalog_size: int, capacity: int) -> "FractionalState":
        f0 = jnp.full(catalog_size, capacity / catalog_size, jnp.float32)
        return FractionalState(f=f0, step=jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, static_argnames=("capacity", "iters"))
def ogb_batch_update(
    state: FractionalState,
    request_ids: jax.Array,  # (B,) int32
    eta: jax.Array,
    capacity: int,
    iters: int = DEFAULT_BISECT_ITERS,
) -> Tuple[FractionalState, jax.Array]:
    """One batched OGB_cl step. Returns (new_state, fractional_reward).

    Reward is sum_t f[r_t] evaluated at the *pre-update* state (OCO order).
    """
    reward = jnp.sum(state.f[request_ids])
    counts = request_counts(request_ids, state.f.shape[0])
    y = state.f + eta * counts
    f_new, _tau = capped_simplex_project(y, float(capacity), iters)
    return FractionalState(f=f_new, step=state.step + 1), reward


@functools.partial(jax.jit, static_argnames=("capacity", "sweeps"))
def ogb_batch_update_warm(
    state: FractionalState,
    request_ids: jax.Array,  # (B,) int32
    eta: jax.Array,
    capacity: int,
    tau_prev: jax.Array,
    sweeps: int = DEFAULT_WARM_SWEEPS,
) -> Tuple[FractionalState, jax.Array, jax.Array]:
    """`ogb_batch_update` with the warm-started projection.

    Returns (new_state, fractional_reward, tau) so the caller can thread tau
    into the next step.  Because ``state.f`` is feasible, the new threshold
    lies in [0, eta * B] — the provable warm bracket (see
    :func:`capped_simplex_project_warm`).
    """
    reward = jnp.sum(state.f[request_ids])
    counts = request_counts(request_ids, state.f.shape[0])
    y = state.f + eta * counts
    hi = warm_bracket_hi(eta * jnp.float32(request_ids.shape[0]))
    f_new, tau = capped_simplex_project_warm(
        y, float(capacity), jnp.float32(0.0), hi, tau_prev, sweeps
    )
    return FractionalState(f=f_new, step=state.step + 1), reward, tau


@functools.partial(jax.jit, static_argnames=("capacity",))
def poisson_sample(
    f: jax.Array, p: jax.Array, capacity: int
) -> jax.Array:
    """Coordinated Poisson sample: x_i = (f_i >= p_i); E[sum x] = C."""
    del capacity  # soft constraint: capacity is implied by sum(f)
    return (f >= p).astype(jnp.bool_)


def permanent_random_numbers(key: jax.Array, catalog_size: int) -> jax.Array:
    """The p_i of §5.1 (drawn once; may be re-drawn periodically)."""
    return jax.random.uniform(key, (catalog_size,), jnp.float32)


@functools.partial(jax.jit, static_argnames=("capacity",))
def madow_sample_jax(f: jax.Array, u: jax.Array, capacity: int) -> jax.Array:
    """Madow systematic sampling in JAX: exactly C items, P(i) = f_i.

    Returns a bool mask. Used by the hard-capacity serving configurations.
    """
    cum = jnp.cumsum(f)
    # item i selected iff some threshold u+k falls in (cum[i-1], cum[i]]
    lower = jnp.concatenate([jnp.zeros(1, f.dtype), cum[:-1]])
    # number of thresholds <= x is floor(x - u) + 1 for x >= u
    n_below = lambda x: jnp.floor(x - u + 1.0)
    sel = n_below(cum) - n_below(lower)
    return sel >= 1.0


def fractional_hit_ratio(
    state: FractionalState, request_ids: jax.Array
) -> jax.Array:
    return jnp.mean(state.f[request_ids])
