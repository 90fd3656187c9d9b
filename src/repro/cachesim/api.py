"""One optax-style policy protocol behind a single run/sweep engine.

The paper's thesis is that gradient-based caching (OGB) and its no-regret
cousins (OMD, FTPL) are interchangeable points in one online-optimization
design space.  This module makes the code say so: every policy — fractional
gradient policies and discrete slot automata alike — is a

    :class:`PolicyDef`:
        ``init(catalog_size, capacity, *, seed, eta, horizon, n_slots)
        -> carry``          (a pytree ``NamedTuple`` of device arrays)
        ``step(carry, request_ids) -> (carry, StepOut)``   (pure, scannable)

and exactly one execution layer drives them all:

* :func:`run` — a single donated-carry ``lax.scan`` over the chunked trace.
  Resumable: it accepts and returns the carry, so a trace can be streamed
  chunk by chunk (the serving integration uses the same contract one step
  at a time).
* :func:`sweep` — one ``vmap``-ped dispatch over a (capacities x seeds x
  etas) grid of stacked carries, capacity-padded for the automata.

Adding a policy, a sweep axis, or a serving integration is one
registration (:func:`register_policy_def`) — not a fourth execution stack.
All per-combo parameters (eta, capacity, sampling randomness) live *in the
carry* as traced arrays, which is what makes one compiled step serve both
the single replay and the whole grid.

Hindsight static-OPT is computed host-side from the trace histogram (exact
int64, cheaper than carrying per-combo count arrays on device).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.cachesim import engines as _engines
from repro.cachesim import tree_engines as _tree_engines
from repro.cachesim.replay import (
    _make_ogb_step,
    opt_hits_by_combo,
    sampling_keys,
)
from repro.cachesim.results import RunResult, SweepResult
from repro.core.ogb import theoretical_eta
from repro.core.omd import theoretical_eta_omd
from repro.core.policies import ENGINE_DEFS, register_engine_def
from repro.core.regret import best_static_hits
from repro.kernels.capped_simplex.ops import weighted_simplex_project
from repro.jaxcache.fractional import (
    DEFAULT_BISECT_ITERS,
    DEFAULT_WARM_SWEEPS,
    capped_simplex_project,
    permanent_random_numbers,
    poisson_sample,
)

__all__ = [
    "PolicyDef",
    "StepOut",
    "RunResult",
    "SweepResult",
    "policy_def",
    "policy_def_kinds",
    "register_policy_def",
    "run",
    "sweep",
]


class StepOut(NamedTuple):
    """Per-chunk observables every policy step emits.

    ``reward`` is the *pre-update* fractional reward (OCO order) — equal to
    ``hits`` for the integral automata; ``aux`` is the projection threshold
    (tau for OGB, lambda for OMD, 0 for automata).  ``byte_hits`` is the
    size-weighted hit mass of sized runs; the default ``None`` is an empty
    pytree node, so unsized steps/carries are structurally unchanged and
    every existing golden stays bit-exact."""

    reward: jax.Array  # () float32
    hits: jax.Array  # () int32
    aux: jax.Array  # () float32
    occupancy: jax.Array  # () float32
    byte_hits: Any = None  # () float32 for sized runs, else None


@dataclass(frozen=True)
class PolicyDef:
    """An optax-style ``(init, step)`` caching policy.

    ``init`` builds the carry — a pytree ``NamedTuple`` holding the policy
    state *and* its traced parameters (eta, capacity, sampling randomness),
    so ``step`` is a pure function of ``(carry, request_ids)`` and a stack
    of carries vmaps into a parameter sweep.  ``default_eta`` resolves
    ``eta=None`` at :func:`run`/:func:`sweep` time from
    ``(catalog_size, capacity, horizon, window)``.
    """

    kind: str
    name: str  # display name used in result rows ("OGB", "LRU", ...)
    init: Callable[..., Any]
    step: Callable[[Any, jax.Array], Tuple[Any, StepOut]]
    fractional: bool = False
    default_eta: Optional[Callable[[int, int, int, int], float]] = None
    #: step consumes request-id chunks (False for gradient-vector flavors
    #: like ogb_grad, which stream dense per-item weights instead and are
    #: excluded from trace replays/scenario sweeps)
    trace_driven: bool = True


# ---------------------------------------------------------------------------
# registry — backed by the core policy table (core/policies.ENGINE_DEFS)
# ---------------------------------------------------------------------------
def register_policy_def(kind: str, factory: Callable[..., PolicyDef]) -> None:
    """Register a :class:`PolicyDef` factory under a kind string.

    ``factory(**static_options) -> PolicyDef``; static options are things
    that change the compiled step (sample mode, projection flavor, sweep
    counts) as opposed to traced parameters, which belong in the carry.
    """
    register_engine_def(kind, factory)


def policy_def_kinds() -> tuple:
    """All registered device-engine kind strings."""
    return tuple(ENGINE_DEFS)


@functools.lru_cache(maxsize=None)
def _cached_def(kind: str, options: tuple) -> PolicyDef:
    return ENGINE_DEFS[kind](**dict(options))


def policy_def(kind: str, **options) -> PolicyDef:
    """Resolve a registered kind to a (memoized) :class:`PolicyDef`.

    Memoization matters: the returned def's ``step`` identity keys the
    compiled-executable cache, so repeat calls reuse compilations.
    """
    kind = kind.lower()
    if kind not in ENGINE_DEFS:
        raise KeyError(
            f"unknown policy kind {kind!r}; registered: {sorted(ENGINE_DEFS)}"
        )
    return _cached_def(kind, tuple(sorted(options.items())))


# ---------------------------------------------------------------------------
# the one execution layer
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _scan_jit(step):
    def run_fn(carry, chunks):
        return jax.lax.scan(step, carry, chunks)

    return jax.jit(run_fn, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _sweep_jit(step):
    def one(carry, chunks):
        return jax.lax.scan(step, carry, chunks)

    return jax.jit(jax.vmap(one, in_axes=(0, None)), donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _fleet_jit(step):
    """Tenant-vmapped scan: stacked carries (E, ...) x chunks (E, M, W).

    Unlike :func:`_sweep_jit` (one shared trace fanned over combos), every
    tenant replays its *own* chunk stream — ``in_axes=(0, 0)``.  Memoized so
    the jitted wrapper's identity keys the executable cache."""

    def one(carry, chunks):
        return jax.lax.scan(step, carry, chunks)

    return jax.jit(jax.vmap(one, in_axes=(0, 0)), donate_argnums=(0,))


_EXEC_CACHE: dict = {}

#: observers notified once per executable-cache miss (see
#: repro.analysis.recompile.track_compiles); each gets a small info dict
_COMPILE_LISTENERS: list = []


def add_compile_listener(cb) -> None:
    """Subscribe ``cb(info: dict)`` to executable-cache misses."""
    _COMPILE_LISTENERS.append(cb)


def remove_compile_listener(cb) -> None:
    try:
        _COMPILE_LISTENERS.remove(cb)
    except ValueError:
        pass


def clear_executable_cache() -> None:
    """Drop every memoized compiled executable (tests use this to measure
    cold-path compile counts deterministically).  The jitted wrappers in
    ``_scan_jit``/``_sweep_jit``/``_fleet_jit`` stay cached, so step
    identities — and therefore cache keys — remain stable."""
    _EXEC_CACHE.clear()


def _compiled(jitted, carry, chunks):
    """AOT-compiled executable, memoized on (step, carry/chunk shapes and
    placements).

    ``jit.lower().compile()`` bypasses jit's own call cache, so without this
    every :func:`run` would recompile; with it, repeated runs of the same
    shapes (goldens, parity tests, benchmark repeats) compile once.  An
    executable is bound to the devices it was compiled for, so the
    shardings are part of the key: the same shapes on another device, or
    sharded over a mesh, get their own executable."""
    key = (
        id(jitted),  # _scan_jit/_sweep_jit are memoized, so ids are stable
        chunks.shape,
        chunks.sharding,
        jax.tree.structure(carry),
        tuple(
            (x.shape, str(x.dtype), getattr(x, "sharding", None))
            for x in jax.tree.leaves(carry)
        ),
    )
    if key not in _EXEC_CACHE:
        _EXEC_CACHE[key] = jitted.lower(carry, chunks).compile()
        if _COMPILE_LISTENERS:
            info = {
                "name": getattr(
                    getattr(jitted, "__wrapped__", jitted),
                    "__name__",
                    "<jit>",
                ),
                "chunks_shape": tuple(chunks.shape),
                "n_carry_leaves": len(jax.tree.leaves(carry)),
            }
            for cb in list(_COMPILE_LISTENERS):
                cb(info)
    return _EXEC_CACHE[key]


def _chunked(trace: np.ndarray, window: int):
    trace = np.asarray(trace)
    m = len(trace) // window
    if m == 0:
        raise ValueError(
            f"trace shorter than one window ({len(trace)} < {window})"
        )
    t_used = m * window
    return (
        jnp.asarray(trace[:t_used].reshape(m, window), jnp.int32),
        trace[:t_used],
        t_used,
    )


def run(
    pd: PolicyDef,
    trace: np.ndarray,
    catalog_size: Optional[int] = None,
    capacity: Optional[int] = None,
    *,
    window: int = 1000,
    carry: Any = None,
    seed: int = 0,
    eta: Optional[float] = None,
    horizon: Optional[int] = None,
    n_slots: Optional[int] = None,
    sizes: Optional[np.ndarray] = None,
    costs: Optional[np.ndarray] = None,
    track_opt: bool = True,
    keep_carry: bool = True,
    name: Optional[str] = None,
    block: bool = True,
    **init_kw,
) -> RunResult:
    """Replay a whole trace through one policy: a single donated-carry scan.

    The trace is reshaped into ``(T // window, window)`` chunks (a trailing
    partial chunk is dropped); ``window`` is the OGB/OMD update batch B and
    the hit-accounting granularity for the automata.  ``eta=None`` resolves
    through ``pd.default_eta`` for the replayed horizon.

    **Streaming contract:** pass ``carry=result.carry`` from a previous call
    to resume exactly where it left off — two chunked runs replay the same
    dynamics as one full run, bit for bit.  The carry is *donated* to the
    device computation, so hand it off (references kept to a resumed-from
    carry are invalidated).  When resuming, ``catalog_size`` is not needed;
    ``capacity`` is still used for OPT/bookkeeping, and the init-time
    parameters (``seed``/``eta``/``horizon``/...) must not be passed — the
    carry already holds them.  Pass ``keep_carry=False`` when the result is
    only read for metrics: the final carry is several (N,)-sized device
    arrays, and dropping it releases that memory immediately (results
    accumulated in a sweep loop otherwise pin it for their lifetime).

    **Sized runs:** pass per-item ``sizes`` (bytes) to thread the paper's
    cost-aware setting through: sized policies (``ogb_sized``, ``gds``)
    shape their decisions with them, the automata account size-weighted
    (byte) hits, and the result gains ``byte_hits``/``bytes_total`` so
    ``byte_hit_ratio`` reflects bytes served from cache.  ``costs``
    overrides the per-item miss costs (default: the sizes).  On resume
    the carry already holds the policy-side sizes; ``sizes`` may still be
    passed for the host-side byte accounting.

    **Non-blocking dispatch:** ``block=False`` returns as soon as the scan
    is *dispatched* — the result's ``reward``/``hits``/``aux``/
    ``occupancy``/``byte_hits`` (and the carry) are still device arrays
    backed by in-flight computation, and ``wall_seconds`` measures only
    the dispatch.  Call ``jax.block_until_ready`` (then ``np.asarray``)
    at the consume point.  The async streaming pipeline
    (:func:`repro.cachesim.tracelab.stream.run_stream`) uses this to
    overlap host ingest with device replay; the returned carry can be fed
    straight back into the next ``run`` — JAX chains the dispatches.
    """
    chunks, trace_used, t_used = _chunked(trace, window)
    extras = {}
    if carry is None:
        if catalog_size is None or capacity is None:
            raise ValueError("run() needs catalog_size and capacity (or carry=)")
        if eta is None and pd.default_eta is not None:
            eta = pd.default_eta(
                int(catalog_size), int(capacity), t_used, window
            )
        sized_kw = {}
        if sizes is not None:
            sized_kw["sizes"] = np.asarray(sizes)
        if costs is not None:
            sized_kw["costs"] = np.asarray(costs)
        carry = pd.init(
            int(catalog_size),
            int(capacity),
            seed=seed,
            eta=eta,
            horizon=int(horizon) if horizon is not None else t_used,
            n_slots=n_slots,
            **sized_kw,
            **init_kw,
        )
        if eta is not None:
            extras["eta"] = float(eta)
    elif (
        eta is not None
        or horizon is not None
        or n_slots is not None
        or seed != 0
        or costs is not None
        or any(v is not None for v in init_kw.values())
    ):
        # a resumed run takes every policy parameter from the carry; a
        # silently-ignored eta or seed would mislabel sweep results
        # (sizes= stays allowed: it only drives host-side byte accounting)
        raise ValueError(
            "run(carry=...) resumes with the carry's parameters; do not "
            "pass seed/eta/horizon/n_slots/costs/init kwargs alongside a "
            "carry"
        )
    compiled = _compiled(_scan_jit(pd.step), carry, chunks)
    t0 = time.perf_counter()
    carry, out = compiled(carry, chunks)
    if block:
        jax.block_until_ready((carry, out))
    wall = time.perf_counter() - t0
    opt = (
        float(best_static_hits(trace_used, int(capacity)))
        if (track_opt and capacity is not None)
        else 0.0
    )
    bytes_total = 0.0
    if sizes is not None:
        bytes_total = float(
            np.sum(np.asarray(sizes, np.float64)[trace_used])
        )
    if block:
        reward = np.asarray(out.reward, np.float64)
        hits = np.asarray(out.hits, np.int64)
        aux = np.asarray(out.aux, np.float64)
        occupancy = np.asarray(out.occupancy, np.float64)
        byte_hits = (
            np.asarray(out.byte_hits, np.float64)
            if out.byte_hits is not None
            else None
        )
    else:
        # in-flight device arrays: np.asarray here would silently block
        reward, hits, aux, occupancy = (
            out.reward, out.hits, out.aux, out.occupancy
        )
        byte_hits = out.byte_hits
    return RunResult(
        name=name or pd.name,
        kind=pd.kind,
        T=t_used,
        window=window,
        capacity=int(capacity) if capacity is not None else -1,
        reward=reward,
        hits=hits,
        aux=aux,
        occupancy=occupancy,
        opt_hits=opt,
        carry=carry if keep_carry else None,
        wall_seconds=wall,
        extras=extras,
        byte_hits=byte_hits,
        bytes_total=bytes_total,
    )


def sweep(
    pd: PolicyDef,
    trace: np.ndarray,
    catalog_size: int,
    capacities: Sequence[int],
    *,
    etas: Sequence[Optional[float]] = (None,),
    seeds: Sequence[int] = (0,),
    window: int = 1000,
    horizon: Optional[int] = None,
    sizes: Optional[np.ndarray] = None,
    costs: Optional[np.ndarray] = None,
    track_opt: bool = True,
    **init_kw,
) -> SweepResult:
    """Run a whole (seeds x etas x capacities) grid in one vmapped dispatch.

    One carry per combo is built by ``pd.init`` (automata are padded to
    ``max(capacities)`` slots so the stacked carries share a shape), the
    stack is ``vmap``-ed over with the trace broadcast, and the entire grid
    costs one compile + one device round-trip.  ``eta=None`` entries resolve
    to ``pd.default_eta`` for that combo's capacity, so default-tuned sweep
    rows reproduce default-tuned single runs exactly.  OPT is computed
    host-side per capacity (it depends only on the trace histogram).
    """
    chunks, trace_used, t_used = _chunked(trace, window)
    if horizon is None:
        horizon = t_used
    n_slots = int(max(capacities))
    sized_kw = {}
    if sizes is not None:
        sized_kw["sizes"] = np.asarray(sizes)
    if costs is not None:
        sized_kw["costs"] = np.asarray(costs)
    combos, carries = [], []
    for s in seeds:
        for eta in etas:
            for C in capacities:
                e = eta
                if e is None and pd.default_eta is not None:
                    e = pd.default_eta(
                        int(catalog_size), int(C), t_used, window
                    )
                combo = {"capacity": int(C), "seed": int(s)}
                if pd.fractional and e is not None:
                    # ogb_sized resolves eta=None inside init (it needs the
                    # sizes); its default-tuned combos just omit the key
                    combo["eta"] = float(e)
                combos.append(combo)
                carries.append(
                    pd.init(
                        int(catalog_size),
                        int(C),
                        seed=int(s),
                        eta=e,
                        horizon=int(horizon),
                        n_slots=n_slots,
                        **sized_kw,
                        **init_kw,
                    )
                )
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *carries)
    compiled = _compiled(_sweep_jit(pd.step), stacked, chunks)
    t0 = time.perf_counter()
    _carry, out = compiled(stacked, chunks)
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    opt = (
        opt_hits_by_combo(trace_used, combos)
        if track_opt
        else np.zeros(len(combos))
    )
    bytes_total = 0.0
    if sizes is not None:
        bytes_total = float(
            np.sum(np.asarray(sizes, np.float64)[trace_used])
        )
    return SweepResult(
        kind=pd.kind,
        combos=combos,
        T=t_used,
        window=window,
        reward=np.asarray(out.reward, np.float64),
        hits=np.asarray(out.hits, np.int64),
        aux=np.asarray(out.aux, np.float64),
        occupancy=np.asarray(out.occupancy, np.float64),
        opt_hits=opt,
        wall_seconds=wall,
        byte_hits=(
            np.asarray(out.byte_hits, np.float64)
            if out.byte_hits is not None
            else None
        ),
        bytes_total=bytes_total,
    )


# ---------------------------------------------------------------------------
# carries for the fractional policies (state + traced params + sampling rng)
# ---------------------------------------------------------------------------
class OGBCarry(NamedTuple):
    """OGB_cl state with its per-combo parameters as traced leaves."""

    f: jax.Array  # (N,) float32 fractional state
    tau: jax.Array  # () float32 previous chunk's projection threshold
    eta: jax.Array  # () float32 learning rate
    cap: jax.Array  # () float32 capacity
    p: jax.Array  # (N,) permanent random numbers (poisson) or (0,)
    u_key: jax.Array  # (2,) uint32 key data for per-chunk Madow offsets
    t: jax.Array  # () int32 chunk counter


class SizedAutomatonCarry(NamedTuple):
    """A discrete automaton carry paired with per-item byte sizes.

    The inner automaton is size-blind (its decisions are unchanged by
    construction — same hit flags as the unsized carry); the sizes only
    weight the hit accounting, turning ``StepOut.byte_hits`` on.  The
    wrapper changes the carry pytree structure, so sized and unsized runs
    compile separately and unsized goldens stay bit-exact."""

    inner: Any  # the unchanged automaton carry (tree or dense)
    szs: jax.Array  # (N,) float32 per-item sizes (bytes)


def _sizes_array(sizes, catalog_size: int) -> jnp.ndarray:
    s = np.asarray(sizes, np.float32)
    if s.shape != (int(catalog_size),):
        raise ValueError(
            f"sizes must be a ({catalog_size},) array, got {s.shape}"
        )
    if not (np.all(np.isfinite(s)) and float(s.min()) > 0.0):
        raise ValueError("sizes must be finite and > 0")
    return jnp.asarray(s)


class SizedOGBScanCarry(NamedTuple):
    """Dense (scan-flavor) sized-OGB state: exact per-item sizes, O(N)
    weighted projection per chunk.  The differential oracle for the
    O(K log N) tree flavor.  Sizes/costs are normalized by their mean
    (``sref``) so uniform sizes reduce to the unit OGB dynamics at the
    same eta; byte outputs are scaled back by ``sref``."""

    f: jax.Array  # (N,) float32 projected fractional state
    tau: jax.Array  # () float32 last weighted-projection threshold
    eta: jax.Array  # () float32
    cap: jax.Array  # () float32 capacity in normalized bytes
    s: jax.Array  # (N,) float32 normalized exact per-item sizes
    wts: jax.Array  # (N,) float32 normalized gradient weights (costs)
    sref: jax.Array  # () float32 bytes per normalized size unit
    p: jax.Array  # (N,) float32 permanent random numbers, or (0,)
    t: jax.Array  # () int32 chunk counter


class OMDApiCarry(NamedTuple):
    """OMD log-weight state with its per-combo parameters as traced leaves."""

    f: jax.Array  # (N,) float32 fractional state
    w: jax.Array  # (N,) float32 log-weights (renormalized every chunk)
    lam: jax.Array  # () float32 last KL-projection threshold
    eta: jax.Array  # () float32
    cap: jax.Array  # () float32
    p: jax.Array  # (N,) or (0,)
    u_key: jax.Array  # (2,) uint32
    t: jax.Array  # () int32


def _sampling_init(seed: int, catalog_size: int, sample: str):
    """(p, u_key): the shared seed derivation
    (:func:`repro.cachesim.replay.sampling_keys`), with the Madow key as
    raw key data so it stacks/donates like any other carry leaf."""
    p, k_u = sampling_keys(seed, catalog_size, sample)
    return p, jax.random.key_data(k_u)


def _chunk_u(sample: str, u_key: jax.Array, t: jax.Array) -> jax.Array:
    """Per-chunk Madow offset, derived from the carried key + chunk counter
    (counter-mode so streamed/resumed runs draw the same sequence)."""
    if sample not in ("madow", "madow_tree"):
        return jnp.zeros((), jnp.float32)
    k = jax.random.fold_in(jax.random.wrap_key_data(u_key), t)
    return jax.random.uniform(k, (), jnp.float32)


_EMPTY_COUNTS = None  # lazily-created (0,) placeholder for untracked OPT


def _empty_counts():
    global _EMPTY_COUNTS
    if _EMPTY_COUNTS is None:
        _EMPTY_COUNTS = jnp.zeros((0,), jnp.float32)
    return _EMPTY_COUNTS


# ---------------------------------------------------------------------------
# policy registrations
# ---------------------------------------------------------------------------
def _ogb_def(
    sample: str = "poisson",
    projection: str = "warm",
    sweeps: int = DEFAULT_WARM_SWEEPS,
    iters: int = DEFAULT_BISECT_ITERS,
    madow_capacity: Optional[int] = None,
) -> PolicyDef:
    raw = _make_ogb_step(
        sample, projection, sweeps, iters, track_opt=False,
        madow_capacity=madow_capacity,
    )

    def init(catalog_size, capacity, *, seed=0, eta=None, horizon=None,
             n_slots=None, sizes=None, costs=None):
        if sizes is not None or costs is not None:
            raise ValueError(
                "ogb is unit-size; use policy_def('ogb_sized') for "
                "per-item sizes/costs"
            )
        if eta is None:
            raise ValueError("ogb init needs eta (run() resolves eta=None)")
        if sample in ("madow", "madow_tree") and int(madow_capacity) != int(
            capacity
        ):
            raise ValueError(
                f"madow needs a static capacity: policy_def('ogb', "
                f"sample={sample!r}, madow_capacity={capacity}) "
                f"(got {madow_capacity})"
            )
        p, u_key = _sampling_init(seed, catalog_size, sample)
        return OGBCarry(
            f=jnp.full(catalog_size, capacity / catalog_size, jnp.float32),
            tau=jnp.zeros((), jnp.float32),
            eta=jnp.float32(eta),
            cap=jnp.float32(capacity),
            p=p,
            u_key=u_key,
            t=jnp.zeros((), jnp.int32),
        )

    def step(carry, ids):
        u = _chunk_u(sample, carry.u_key, carry.t)
        state = (carry.f, carry.tau, _empty_counts())
        (f, tau, _), (reward, hits, tau_o, occ) = raw(
            carry.eta, carry.p, carry.cap, state, (ids, u)
        )
        carry = carry._replace(f=f, tau=tau, t=carry.t + 1)
        return carry, StepOut(reward, hits, tau_o, occ)

    return PolicyDef(
        kind="ogb",
        name="OGB",
        init=init,
        step=step,
        fractional=True,
        # Theorem 3.1 tuning at B=1, matching the legacy replay default
        default_eta=lambda N, C, T, W: theoretical_eta(C, N, T, 1),
    )


def _omd_def(
    sample: str = "poisson",
    sweeps: int = _engines.DEFAULT_OMD_SWEEPS,
    madow_capacity: Optional[int] = None,
) -> PolicyDef:
    raw = _engines._make_omd_step(
        sample, sweeps, track_opt=False, madow_capacity=madow_capacity
    )

    def init(catalog_size, capacity, *, seed=0, eta=None, horizon=None,
             n_slots=None, sizes=None, costs=None):
        if sizes is not None or costs is not None:
            raise ValueError(
                "omd is unit-size; use policy_def('ogb_sized') for "
                "per-item sizes/costs"
            )
        if eta is None:
            raise ValueError("omd init needs eta (run() resolves eta=None)")
        if sample in ("madow", "madow_tree") and int(madow_capacity) != int(
            capacity
        ):
            raise ValueError(
                f"madow needs a static capacity: policy_def('omd', "
                f"sample={sample!r}, madow_capacity={capacity}) "
                f"(got {madow_capacity})"
            )
        p, u_key = _sampling_init(seed, catalog_size, sample)
        f0 = capacity / catalog_size
        return OMDApiCarry(
            f=jnp.full(catalog_size, f0, jnp.float32),
            w=jnp.full(catalog_size, float(np.log(f0)), jnp.float32),
            lam=jnp.zeros((), jnp.float32),
            eta=jnp.float32(eta),
            cap=jnp.float32(capacity),
            p=p,
            u_key=u_key,
            t=jnp.zeros((), jnp.int32),
        )

    def step(carry, ids):
        u = _chunk_u(sample, carry.u_key, carry.t)
        state = (carry.f, carry.w, carry.lam, _empty_counts())
        (f, w, lam, _), (reward, hits, lam_o, occ) = raw(
            carry.eta, carry.p, carry.cap, state, (ids, u)
        )
        carry = carry._replace(f=f, w=w, lam=lam, t=carry.t + 1)
        return carry, StepOut(reward, hits, lam_o, occ)

    return PolicyDef(
        kind="omd",
        name="OMD",
        init=init,
        step=step,
        fractional=True,
        # Si Salem et al. tuning at the replay batch size (legacy default)
        default_eta=lambda N, C, T, W: theoretical_eta_omd(C, N, T, W),
    )


def _ogb_tree_def(
    sample: str = "poisson",
    buckets: int = _tree_engines.OGB_TREE_BUCKETS,
    radix: int = _tree_engines.OGB_TREE_RADIX,
    iters: int = _tree_engines.OGB_TREE_ITERS,
    batch_hint: int = 4096,
) -> PolicyDef:
    """Lazy bucketized OGB: O(B log V) per chunk instead of O(N).

    Same gradient step and hit accounting as ``ogb``; the per-chunk
    capped-simplex projection is replaced by a scalar threshold solve over
    a V-bucket histogram of the accumulated values, so per-chunk work no
    longer scales with the catalog.  Hit ratios track the dense ``ogb``
    within the histogram quantization (see the differential test); use
    ``ogb`` when bit-exact projections matter.  ``sample`` is limited to
    ``"poisson"``/``"none"`` — Madow needs the full fractional vector.
    """
    if sample not in ("poisson", "none"):
        raise ValueError(
            f"ogb_tree supports sample='poisson'|'none' (got {sample!r}); "
            "use policy_def('ogb', sample='madow_tree', ...) for Madow"
        )

    def init(catalog_size, capacity, *, seed=0, eta=None, horizon=None,
             n_slots=None, sizes=None, costs=None):
        if sizes is not None or costs is not None:
            raise ValueError(
                "ogb_tree is unit-size; use policy_def('ogb_sized', "
                "flavor='tree') for per-item sizes/costs"
            )
        if eta is None:
            raise ValueError(
                "ogb_tree init needs eta (run() resolves eta=None)"
            )
        return _tree_engines.init_ogb_tree_carry(
            catalog_size,
            capacity,
            eta=eta,
            seed=seed,
            sample=sample,
            buckets=buckets,
            radix=radix,
            batch_hint=batch_hint,
        )

    def step(carry, ids):
        chunk = _tree_engines.make_ogb_tree_chunk(
            carry.y.shape[0], buckets, radix, sample, iters
        )
        carry, (reward, hits, dtau, occ) = chunk(carry, ids)
        return carry, StepOut(reward, hits, dtau, occ)

    return PolicyDef(
        kind="ogb_tree",
        name="OGB_tree",
        init=init,
        step=step,
        fractional=True,
        default_eta=lambda N, C, T, W: theoretical_eta(C, N, T, 1),
    )


def _automaton_def(
    kind: str,
    zeta: Optional[float] = None,
    impl: Optional[str] = None,
) -> PolicyDef:
    """Discrete automaton PolicyDef.

    ``impl`` selects the engine implementation: ``"tree"`` (the default for
    lru/lfu/ftpl) runs the O(log) prefix-tree engines of
    :mod:`repro.cachesim.tree_engines`; ``"dense"`` is the O(C)-per-request
    slot automaton — kept as an escape hatch and as the differential-test
    oracle.  Both produce bit-identical hit sequences; only the carry
    layout differs.  FIFO has no tree form (insertion order is not a reuse
    distance) and always runs dense.

    Sized runs: ``init(..., sizes=...)`` wraps the unchanged carry in a
    :class:`SizedAutomatonCarry` — the automaton stays size-blind (identical
    decisions, slot-based capacity), but every hit is also weighted by the
    requested item's bytes so the result carries ``byte_hits``.  ``costs``
    are rejected — these automata have no cost model (use ``gds``).
    """
    if impl is None:
        impl = "tree" if kind in _tree_engines.TREE_ENGINE_KINDS else "dense"
    def_zeta = zeta

    def _reject_costs(costs):
        if costs is not None:
            raise ValueError(
                f"{kind} has no miss-cost model (costs= unsupported); "
                "use policy_def('gds') or policy_def('ogb_sized')"
            )

    if impl == "tree":
        if kind not in _tree_engines.TREE_ENGINE_KINDS:
            raise ValueError(f"no tree engine for kind {kind!r}")

        def init(catalog_size, capacity, *, seed=0, eta=None, horizon=None,
                 n_slots=None, zeta=None, ring=None, sizes=None, costs=None):
            _reject_costs(costs)
            inner = _tree_engines.init_tree_engine_carry(
                kind,
                catalog_size,
                capacity,
                n_slots=n_slots,
                seed=seed,
                zeta=zeta if zeta is not None else def_zeta,
                horizon=horizon,
                ring=ring,
            )
            if sizes is None:
                return inner
            return SizedAutomatonCarry(
                inner, _sizes_array(sizes, catalog_size)
            )

        def step(carry, ids):
            # static geometry comes from the (traced) carry's shapes, so
            # one PolicyDef serves every catalog/window combination
            sized = isinstance(carry, SizedAutomatonCarry)
            inner = carry.inner if sized else carry
            chunk = _tree_engines.make_tree_chunk(
                kind, inner, return_flags=sized
            )
            inner, (hits, occ) = chunk(inner, ids)
            if not sized:
                return inner, StepOut(
                    hits.astype(jnp.float32),
                    hits,
                    jnp.zeros((), jnp.float32),
                    occ.astype(jnp.float32),
                )
            flags = hits  # (window,) per-request, aligned with ids
            hits = jnp.sum(flags.astype(jnp.int32))
            byte_hits = jnp.sum(jnp.where(flags, carry.szs[ids], 0.0))
            return SizedAutomatonCarry(inner, carry.szs), StepOut(
                hits.astype(jnp.float32),
                hits,
                jnp.zeros((), jnp.float32),
                occ.astype(jnp.float32),
                byte_hits,
            )

        return PolicyDef(kind=kind, name=kind.upper(), init=init, step=step)

    if impl != "dense":
        raise ValueError(f"unknown automaton impl {impl!r}")
    raw = _engines._STEPS[kind]

    def init(catalog_size, capacity, *, seed=0, eta=None, horizon=None,
             n_slots=None, zeta=None, sizes=None, costs=None):
        _reject_costs(costs)
        inner = _engines.init_engine_carry(
            kind,
            catalog_size,
            capacity,
            n_slots=n_slots,
            seed=seed,
            zeta=zeta if zeta is not None else def_zeta,
            horizon=horizon,
        )
        if sizes is None:
            return inner
        return SizedAutomatonCarry(inner, _sizes_array(sizes, catalog_size))

    def step(carry, ids):
        sized = isinstance(carry, SizedAutomatonCarry)
        inner = carry.inner if sized else carry
        inner, hitflags = jax.lax.scan(raw, inner, ids)
        hits = jnp.sum(hitflags.astype(jnp.int32))
        occ = _engines._occ_slots(inner).astype(jnp.float32)
        if not sized:
            return inner, StepOut(
                hits.astype(jnp.float32),
                hits,
                jnp.zeros((), jnp.float32),
                occ,
            )
        byte_hits = jnp.sum(jnp.where(hitflags, carry.szs[ids], 0.0))
        return SizedAutomatonCarry(inner, carry.szs), StepOut(
            hits.astype(jnp.float32),
            hits,
            jnp.zeros((), jnp.float32),
            occ,
            byte_hits,
        )

    return PolicyDef(kind=kind, name=kind.upper(), init=init, step=step)


def _ogb_grad_def(iters: int = DEFAULT_BISECT_ITERS) -> PolicyDef:
    """OGB on dense gradient vectors — the serving-side flavor.

    ``step(carry, grad)`` takes a raw per-item weight vector (e.g. routed
    token counts per MoE expert), normalizes it to unit mass, and performs
    one fractional OGB update.  ``StepOut.reward`` is the weighted resident
    hit mass (pre-update, under the carried Poisson sample) and ``hits``
    the *count* of requested items resident at decision time — the same
    "hits mean hits" convention every other kind follows.  Swap-in/out
    telemetry (the paper's O(changed-mass) coordination claim) is *not* a
    hit count and is derived by the consumer from the residency-mask diff
    (:class:`repro.serve.expert_cache.OGBExpertCache` streams this one
    step at a time via the carry contract and diffs
    :func:`~repro.jaxcache.fractional.poisson_sample` masks)."""

    def init(catalog_size, capacity, *, seed=0, eta=None, horizon=None,
             n_slots=None, sizes=None, costs=None):
        if sizes is not None or costs is not None:
            raise ValueError("ogb_grad is unit-size (weights ride the "
                             "gradient vector); sizes/costs unsupported")
        if eta is None:
            raise ValueError("ogb_grad init needs eta")
        # legacy expert-cache stream: p drawn straight from key(seed)
        p = permanent_random_numbers(jax.random.key(seed), catalog_size)
        return OGBCarry(
            f=jnp.full(catalog_size, capacity / catalog_size, jnp.float32),
            tau=jnp.zeros((), jnp.float32),
            eta=jnp.float32(eta),
            cap=jnp.float32(capacity),
            p=p,
            u_key=jax.random.key_data(jax.random.key(seed)),
            t=jnp.zeros((), jnp.int32),
        )

    def step(carry, grad):
        total = jnp.sum(grad)
        norm = grad / jnp.maximum(total, 1.0)  # unit-mass per-step gradient
        resident = poisson_sample(carry.f, carry.p, 0)
        reward = jnp.sum(norm * resident.astype(jnp.float32))
        hits = jnp.sum(
            jnp.logical_and(grad > 0, resident).astype(jnp.int32)
        )
        y = carry.f + carry.eta * norm
        f_new, tau = capped_simplex_project(y, carry.cap, iters)
        resident_new = poisson_sample(f_new, carry.p, 0)
        carry = carry._replace(f=f_new, tau=tau, t=carry.t + 1)
        return carry, StepOut(
            reward,
            hits,
            tau,
            jnp.sum(resident_new.astype(jnp.float32)),
        )

    return PolicyDef(kind="ogb_grad", name="OGB_grad", init=init, step=step,
                     fractional=True, trace_driven=False)


def _gds_def() -> PolicyDef:
    """GreedyDual-Size: the classical size/cost-aware automaton baseline.

    Runs on the min-pair eviction trees (O(log C) per request) with
    size-normalized keys H_i = L + cost_i / size_i — differential-tested
    against the host ``core.policies.GDS`` oracle.  Unit sizes/costs
    reduce it to an LRU-like automaton (every H increment equal).  Always
    emits ``byte_hits`` (== hits when unit-size)."""

    def init(catalog_size, capacity, *, seed=0, eta=None, horizon=None,
             n_slots=None, sizes=None, costs=None):
        return _tree_engines.init_tree_gds_carry(
            int(catalog_size),
            int(capacity),
            n_slots,
            sizes=sizes,
            costs=costs,
        )

    def step(carry, ids):
        chunk = _tree_engines.make_tree_chunk("gds", carry,
                                              return_flags=True)
        carry, (flags, occ) = chunk(carry, ids)
        hits = jnp.sum(flags.astype(jnp.int32))
        byte_hits = jnp.sum(jnp.where(flags, carry.szs[ids], 0.0))
        return carry, StepOut(
            hits.astype(jnp.float32),
            hits,
            jnp.zeros((), jnp.float32),
            occ.astype(jnp.float32),
            byte_hits,
        )

    return PolicyDef(kind="gds", name="GDS", init=init, step=step)


def _ogb_sized_def(
    flavor: str = "tree",
    sample: str = "poisson",
    classes: int = _tree_engines.SIZED_OGB_CLASSES,
    buckets: int = _tree_engines.OGB_TREE_BUCKETS,
    radix: int = _tree_engines.OGB_TREE_RADIX,
    iters: int = _tree_engines.OGB_TREE_ITERS,
    proj_iters: int = DEFAULT_BISECT_ITERS,
    batch_hint: int = 4096,
) -> PolicyDef:
    """Size-aware OGB over the knapsack-relaxed feasible set (paper §8).

    ``flavor="tree"`` is the O(K * B log V) per-size-class lazy bucketized
    form; ``flavor="scan"`` is the dense O(N)-per-chunk form with *exact*
    per-item sizes and a full weighted bisection projection — the
    differential oracle for the tree flavor (both are property-tested
    against the float64 ``core.ogb_sized`` oracle).  ``init`` requires
    per-item ``sizes`` (pass ``run(..., sizes=...)``); ``costs`` default
    to the sizes (byte-weighted rewards).  ``eta=None`` resolves to the
    Theorem 3.1 rate at the byte capacity expressed in mean-object units
    — the natural reduction of the unit tuning to heterogeneous sizes.
    """
    if flavor not in ("tree", "scan"):
        raise ValueError(f"ogb_sized flavor must be 'tree'|'scan': {flavor!r}")
    if sample not in ("poisson", "none"):
        raise ValueError(
            f"ogb_sized supports sample='poisson'|'none' (got {sample!r})"
        )

    def init(catalog_size, capacity, *, seed=0, eta=None, horizon=None,
             n_slots=None, sizes=None, costs=None):
        if sizes is None:
            raise ValueError(
                "ogb_sized init needs per-item sizes: run(..., sizes=...)"
            )
        n = int(catalog_size)
        s64 = np.asarray(sizes, np.float64)
        if s64.shape != (n,):
            raise ValueError(f"sizes must be a ({n},) array: {s64.shape}")
        if eta is None:
            # Theorem 3.1 tuning with the capacity in mean-object units
            c_eq = float(capacity) / float(np.mean(s64))
            eta = theoretical_eta(c_eq, n, int(horizon or 1), 1)
        if flavor == "tree":
            return _tree_engines.init_sized_ogb_tree_carry(
                n,
                float(capacity),
                sizes=s64,
                costs=costs,
                eta=float(eta),
                seed=seed,
                sample=sample,
                classes=classes,
                buckets=buckets,
                radix=radix,
                batch_hint=batch_hint,
            )
        # scan flavor: exact sizes, same mean-size normalization
        if not (np.all(np.isfinite(s64)) and float(s64.min()) > 0.0):
            raise ValueError("sizes must be finite and > 0")
        sref = float(np.mean(s64))
        s_n = s64 / sref
        if costs is None:
            w = s_n.copy()
        else:
            w = np.asarray(costs, np.float64) / sref
            if w.shape != (n,):
                raise ValueError(f"costs must be a ({n},) array")
            if not (np.all(np.isfinite(w)) and w.min() > 0.0):
                raise ValueError("costs must be finite and > 0")
        cap_n = float(capacity) / sref
        total_s = float(np.sum(s_n))
        if cap_n >= total_s:
            raise ValueError(
                f"capacity {capacity} holds the whole catalog; caching is "
                "trivial"
            )
        f0 = cap_n / total_s
        p, _ = _sampling_init(seed, n, sample)
        return SizedOGBScanCarry(
            f=jnp.full(n, f0, jnp.float32),
            tau=jnp.zeros((), jnp.float32),
            eta=jnp.float32(eta),
            cap=jnp.float32(cap_n),
            s=jnp.asarray(s_n, jnp.float32),
            wts=jnp.asarray(w, jnp.float32),
            sref=jnp.float32(sref),
            p=p,
            t=jnp.zeros((), jnp.int32),
        )

    if flavor == "tree":

        def step(carry, ids):
            chunk = _tree_engines.make_sized_ogb_tree_chunk(
                carry.y.shape[0], carry.s.shape[0], buckets, radix,
                sample, iters,
            )
            carry, (reward, hits, byte_hits, drho, occ) = chunk(carry, ids)
            return carry, StepOut(
                reward * carry.sref, hits, drho, occ, byte_hits
            )

    else:

        def step(carry, ids):
            f, s, wts, p, sref = carry.f, carry.s, carry.wts, carry.p, \
                carry.sref
            sj = s[ids]
            wj = wts[ids]
            fi = f[ids]
            reward = jnp.sum(wj * fi)  # pre-update (OCO order)
            if sample == "poisson":
                hflag = fi >= p[ids]
                hits = jnp.sum(hflag.astype(jnp.int32))
                byte_hits = jnp.sum(jnp.where(hflag, sj, 0.0)) * sref
                occ = jnp.sum(
                    jnp.where(f >= p, s, 0.0)
                ) * sref
            else:
                hits = jnp.zeros((), jnp.int32)
                byte_hits = jnp.zeros((), jnp.float32)
                occ = carry.cap * sref
            y = f.at[ids].add(carry.eta * wj)
            f_new, tau = weighted_simplex_project(
                y, s, carry.cap, proj_iters
            )
            carry = carry._replace(f=f_new, tau=tau, t=carry.t + 1)
            return carry, StepOut(
                reward * sref, hits, tau, occ, byte_hits
            )

    return PolicyDef(
        kind="ogb_sized",
        name=f"OGB_sized_{flavor}",
        init=init,
        step=step,
        fractional=True,
    )


register_policy_def("ogb", _ogb_def)
register_policy_def("ogb_tree", _ogb_tree_def)
register_policy_def("omd", _omd_def)
register_policy_def("ogb_grad", _ogb_grad_def)
register_policy_def("gds", _gds_def)
register_policy_def("ogb_sized", _ogb_sized_def)
for _kind in _engines.ENGINE_KINDS:
    register_policy_def(_kind, functools.partial(_automaton_def, _kind))
