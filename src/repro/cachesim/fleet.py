"""Multi-tenant cache fleets: thousands of independent caches, one dispatch.

The ROADMAP north-star is heavy traffic from millions of users; this module
is the layer that claim stands on.  :func:`run_fleet` stacks E per-tenant
carries (heterogeneous capacity / eta / seed via the carried-params
contract, capacity-padded like ``api.sweep``) along a leading tenant axis
and steps the whole fleet in a single vmapped, donated-carry ``lax.scan``
— unlike ``sweep`` every tenant replays its *own* request stream
(``in_axes=(0, 0)``).  The tenant axis shards over the ``data`` mesh axis
through :mod:`repro.dist.sharding` when a mesh is active.

:func:`run_fleet_stream` feeds the same dispatch from per-tenant chunk
iterators (e.g. ``tracelab.tenant_streams``) in fixed memory, with the
async double-buffered prefetch pipeline of ``tracelab.run_stream``.

:func:`run_edge_fleet` is the two-level CDN setting of "Learning to Cache
With No Regrets" collapsed to one shared parent: E edge caches replay
their streams with per-request hit flags, and the deterministic interleave
of their misses (arrival-position major, edge index minor) becomes the
origin cache's request stream.
"""

# the ingest thread is the sole writer of the stream-position counters
# reprolint: thread-owned(t_ingested, t_dropped)

from __future__ import annotations

import functools
import queue
import threading
import time
from collections import deque
from typing import Any, Iterable, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import AxisType

from repro.core.regret import best_static_hits
from repro.dist import sharding as _sharding

from . import api
from . import engines as _engines
from . import tree_engines as _tree_engines
from .results import EdgeFleetResult, FleetResult
from .scenarios import get_edge_fleet_scenario
from .tracelab import stream as _stream

#: per-tenant requests per streamed dispatch (window-aligned down)
DEFAULT_FLEET_SEGMENT = 16_384


# ---------------------------------------------------------------------------
# per-tenant parameter plumbing
# ---------------------------------------------------------------------------


def _tenant_array(value, n_tenants: int, name: str, dtype=np.int64) -> np.ndarray:
    """Normalize a scalar or length-E sequence to an (E,) host array."""
    arr = np.asarray(value)
    if arr.ndim == 0:
        arr = np.full(n_tenants, arr.item())
    if arr.shape != (n_tenants,):
        raise ValueError(
            f"{name} must be a scalar or a length-{n_tenants} sequence, "
            f"got shape {arr.shape}"
        )
    return arr.astype(dtype)


def _tenant_etas(etas, n_tenants: int) -> list:
    if etas is None or isinstance(etas, (int, float)):
        return [etas] * n_tenants
    out = list(etas)
    if len(out) != n_tenants:
        raise ValueError(
            f"etas must be a scalar or length-{n_tenants} (got {len(out)})"
        )
    return out


def _tenant_chunks(traces, window: int):
    """(E, M, W) int32 device chunks + (E, t_used) host ids + t_used.

    ``traces`` is an (E, T) array or a list of equal-length 1-D arrays —
    the fleet steps in lockstep, so ragged tenants must be truncated by the
    caller (or streamed via :func:`run_fleet_stream`, which truncates to
    the shortest window-aligned tenant automatically)."""
    if isinstance(traces, np.ndarray) and traces.ndim == 2:
        rows = [np.asarray(traces[e]).ravel() for e in range(traces.shape[0])]
    else:
        rows = [np.asarray(t).ravel() for t in traces]
    if not rows:
        raise ValueError("run_fleet needs at least one tenant trace")
    t_len = len(rows[0])
    if any(len(r) != t_len for r in rows):
        raise ValueError(
            "all tenant traces must have equal length (the fleet steps in "
            "lockstep); stream ragged tenants through run_fleet_stream"
        )
    m = t_len // window
    if m == 0:
        raise ValueError(
            f"tenant traces shorter than one window ({t_len} < {window})"
        )
    t_used = m * window
    used = np.stack([r[:t_used] for r in rows])
    chunks = jnp.asarray(used.reshape(len(rows), m, window), jnp.int32)
    return chunks, used, t_used


def _build_fleet_carries(
    pd: "api.PolicyDef",
    catalog_size: int,
    caps: np.ndarray,
    seeds: np.ndarray,
    eta_list: list,
    horizons: np.ndarray,
    window: int,
    n_slots: int,
    sizes,
    costs,
    init_kw: dict,
    sharding=None,
):
    """Stacked tenant carries + the per-tenant resolved etas.

    ``eta=None`` tenants resolve ``pd.default_eta`` at **their own**
    horizon — a tenant replaying a T/E slice of a fleet workload needs the
    Theorem-3.1 rate at T/E, not at the fleet-aggregate T (which is what a
    naive ``sweep()``-style resolution at the full trace horizon would
    give it).  With a tenant ``sharding`` the stack is built block by
    block on the devices that hold it (:func:`_stack_on_devices`)."""
    resolved = []
    for t in range(len(caps)):
        e = eta_list[t]
        if e is None and pd.default_eta is not None:
            e = pd.default_eta(
                int(catalog_size), int(caps[t]), int(horizons[t]), window
            )
        resolved.append(e)

    def init(t):
        return pd.init(
            int(catalog_size),
            int(caps[t]),
            seed=int(seeds[t]),
            eta=resolved[t],
            horizon=int(horizons[t]),
            n_slots=n_slots,
            sizes=sizes,
            costs=costs,
            **init_kw,
        )

    def stack(tenants):
        # rows are written in place into one preallocated stack, so the
        # build never holds a second copy of the fleet's carries
        first = init(tenants[0])
        out = jax.tree.map(
            lambda x: jnp.zeros((len(tenants),) + x.shape, x.dtype), first
        )
        for i, t in enumerate(tenants):
            out = _set_row(out, first if i == 0 else init(t), i)
        return out

    if sharding is None:
        stacked = stack(range(len(caps)))
    else:
        stacked = _stack_on_devices(stack, len(caps), sharding)
    if any(r is not None for r in resolved):
        etas_out = np.array(
            [np.nan if r is None else float(r) for r in resolved]
        )
    else:
        etas_out = None
    return stacked, etas_out


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_row(stacked, row, i):
    """``stacked[i] = row`` for every leaf, in place (the stack is donated)."""
    return jax.tree.map(lambda s, r: s.at[i].set(r), stacked, row)


def _stack_on_devices(stack, n_tenants: int, sharding):
    """Tenant-sharded stacked carries, each block built on its own device.

    ``stack(tenants)`` stacks the carries of a range of tenants on the
    default device.  Building every block under ``jax.default_device`` of
    the device that holds it means a fleet whose carries exceed one
    device's memory never passes through a single device."""
    blocks: dict = {}
    shards = []
    for dev, idx in sharding.devices_indices_map((n_tenants,)).items():
        lo, hi, _ = idx[0].indices(n_tenants)
        if (lo, hi) not in blocks:
            with jax.default_device(dev):
                blocks[(lo, hi)] = stack(range(lo, hi))
        shards.append(jax.device_put(blocks[(lo, hi)], dev))
    treedef = jax.tree.structure(shards[0])
    leaves = [
        jax.make_array_from_single_device_arrays(
            (n_tenants,) + col[0].shape[1:], sharding, list(col)
        )
        for col in zip(*(jax.tree.leaves(sh) for sh in shards))
    ]
    return jax.tree.unflatten(treedef, leaves)


def _reject_resume_kwargs(seeds, etas, horizons, n_slots, costs, init_kw):
    if (
        seeds is not None
        or etas is not None
        or horizons is not None
        or n_slots is not None
        or costs is not None
        or init_kw
    ):
        raise ValueError(
            "run_fleet(carry=...) resumes with the stacked carry's own "
            "parameters; do not pass seeds/etas/horizons/n_slots/costs/"
            "init kwargs alongside a carry"
        )


def _tenant_sharding(n_tenants: int, mesh, rules):
    """The tenant axis over the mesh's data axis, or None with no live mesh.

    Every fleet array (carry leaves and the (E, M, W) chunk block) leads
    with the tenant axis, mapped through the ``"tenants"`` logical axis of
    :func:`repro.dist.sharding.default_rules`, so one sharding places them
    all; a tenant count the data axis does not divide is replicated
    (``logical_to_spec`` drops the axis), so oddball fleets still run.
    The mesh's axes are taken as Auto: the vmapped scans leave the
    partitioning of their intermediates to the compiler, which Explicit
    axes (the ``jax.make_mesh`` default) do not allow."""
    if mesh is None:
        mesh = _sharding.current_mesh()
        rules = rules if rules is not None else _sharding.current_rules()
    if mesh is None:
        return None
    if rules is None:
        rules = _sharding.default_rules()
    mesh = mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    return _sharding.named_sharding(
        mesh, ("tenants",), rules=rules, shape=(n_tenants,)
    )


def _opt_from_counts(counts: np.ndarray, capacity: int) -> float:
    if len(counts) <= capacity:
        return float(counts.sum())
    top = np.partition(counts, len(counts) - capacity)[len(counts) - capacity:]
    return float(top.sum())


# ---------------------------------------------------------------------------
# in-memory fleet replay
# ---------------------------------------------------------------------------


def run_fleet(
    pd: "api.PolicyDef",
    traces,
    catalog_size: Optional[int] = None,
    capacities=None,
    *,
    window: int = 1000,
    carry: Any = None,
    seeds=None,
    etas=None,
    horizons=None,
    n_slots: Optional[int] = None,
    sizes: Optional[np.ndarray] = None,
    costs: Optional[np.ndarray] = None,
    track_opt: bool = True,
    keep_carry: bool = True,
    name: Optional[str] = None,
    mesh=None,
    rules=None,
    **init_kw,
) -> FleetResult:
    """Replay E per-tenant traces through E independent caches in one dispatch.

    ``traces`` is an (E, T) array (or list of equal-length 1-D arrays); row
    ``e`` is tenant ``e``'s own request stream.  Per-tenant knobs
    (``capacities``, ``seeds``, ``etas``, ``horizons``) each accept a scalar
    or a length-E sequence; carries are padded to ``n_slots =
    max(capacities)`` exactly like ``api.sweep`` so heterogeneous
    capacities stack.  ``etas=None`` resolves ``pd.default_eta`` *per
    tenant at that tenant's horizon* (default: its own replayed length).

    Resume by passing the previous result's tenant-stacked ``carry=``
    (donated — hand it off, don't keep references).  With a live mesh
    (``mesh=`` or an ambient ``dist.sharding.use_sharding``), the tenant
    axis shards over the mesh's data axis.

    **Tracing:** under ``jax.profiler.trace`` the call shows as one
    ``repro.fleet`` span (arguments ``tenants``, ``windows`` per tenant and
    ``bytes_in``) tiled by ``repro.fleet.upload`` (the host stack of the
    ids and, on a mesh, their ``device_put`` onto the tenant sharding: two
    spans, around ``init`` on a fresh call)/``init``/``dispatch``
    (``repro.run.compile`` nested on a cache miss)/``wait``/``readback``/
    ``opt``.
    """
    if not pd.trace_driven:
        raise ValueError(
            f"policy kind {pd.kind!r} is not trace-driven; the fleet "
            "replays per-tenant request streams"
        )
    # the span's arguments, read off the traces before the stack it times
    n_rows = len(traces)
    m = np.size(traces[0]) // window if n_rows else 0
    with TraceAnnotation(
        "repro.fleet", tenants=n_rows, windows=m, bytes_in=4 * n_rows * m * window
    ):
        with TraceAnnotation("repro.fleet.upload"):
            chunks, used, t_used = _tenant_chunks(traces, window)
        n_tenants = chunks.shape[0]
        sharding = _tenant_sharding(n_tenants, mesh, rules)

        if carry is None:
            if catalog_size is None or capacities is None:
                raise ValueError(
                    "run_fleet() needs catalog_size and capacities (or carry=)"
                )
            caps = _tenant_array(capacities, n_tenants, "capacities")
            seed_arr = _tenant_array(
                seeds if seeds is not None else np.arange(n_tenants),
                n_tenants,
                "seeds",
            )
            hor = _tenant_array(
                horizons if horizons is not None else t_used,
                n_tenants,
                "horizons",
            )
            eta_list = _tenant_etas(etas, n_tenants)
            slots = int(n_slots) if n_slots is not None else int(caps.max())
            with TraceAnnotation("repro.fleet.init"):
                stacked, etas_out = _build_fleet_carries(
                    pd, catalog_size, caps, seed_arr, eta_list, hor, window,
                    slots, sizes, costs, init_kw, sharding,
                )
        else:
            _reject_resume_kwargs(seeds, etas, horizons, n_slots, costs, init_kw)
            stacked = carry
            lead = {int(np.shape(x)[0]) for x in jax.tree.leaves(carry)}
            if lead != {n_tenants}:
                raise ValueError(
                    f"carry tenant axis {sorted(lead)} does not match "
                    f"{n_tenants} tenant traces"
                )
            caps = (
                _tenant_array(capacities, n_tenants, "capacities")
                if capacities is not None
                else np.full(n_tenants, -1)
            )
            seed_arr = np.full(n_tenants, -1)
            etas_out = None

        jitted = api._fleet_jit(pd.step)
        if sharding is not None:
            with TraceAnnotation("repro.fleet.upload"):
                stacked, chunks = jax.device_put((stacked, chunks), sharding)
        t0 = time.perf_counter()
        with TraceAnnotation("repro.fleet.dispatch"):
            final, out = api._compiled(jitted, stacked, chunks)(stacked, chunks)
        with TraceAnnotation("repro.fleet.wait"):
            jax.block_until_ready(out)
        wall = time.perf_counter() - t0

        if track_opt and caps.min() >= 0:
            with TraceAnnotation("repro.fleet.opt"):
                opt = np.array(
                    [
                        float(best_static_hits(used[e], int(caps[e])))
                        for e in range(n_tenants)
                    ]
                )
        else:
            opt = np.zeros(n_tenants)

        bytes_total = None
        if sizes is not None:
            bytes_total = np.asarray(sizes, np.float64)[used].sum(axis=1)

        with TraceAnnotation("repro.fleet.readback"):
            reward = np.asarray(out.reward, np.float64)
            hits = np.asarray(out.hits, np.int64)
            aux = np.asarray(out.aux, np.float64)
            occupancy = np.asarray(out.occupancy, np.float64)
            byte_hits = (
                np.asarray(out.byte_hits, np.float64)
                if out.byte_hits is not None
                else None
            )

    return FleetResult(
        name=name or pd.name,
        kind=pd.kind,
        n_tenants=n_tenants,
        T=t_used,
        window=window,
        capacities=caps,
        seeds=seed_arr,
        etas=etas_out,
        reward=reward,
        hits=hits,
        aux=aux,
        occupancy=occupancy,
        opt_hits=opt,
        carry=final if keep_carry else None,
        wall_seconds=wall,
        byte_hits=byte_hits,
        bytes_total=bytes_total,
    )


# ---------------------------------------------------------------------------
# streamed fleet replay (fixed memory, async prefetch)
# ---------------------------------------------------------------------------


class _FleetState:
    """Accumulators shared by the sync and async fleet-stream drivers.

    The ingest-side counters (``t_ingested``, ``t_dropped``) are written
    only by whichever thread runs the segment assembly; the replay-side
    accumulators only by the main thread."""

    def __init__(self):
        self.reward: list = []
        self.hits: list = []
        self.aux: list = []
        self.occupancy: list = []
        self.byte_hits: list = []
        self.n_segments = 0
        self.t_used = 0  # per tenant
        self.t_ingested = 0  # across the fleet
        self.t_dropped = 0
        self.counts: Optional[np.ndarray] = None  # (E, N) when track_opt
        self.bytes_total: Optional[np.ndarray] = None


def _assemble_fleet_segments(
    sources: list,
    segment_len: int,
    window: int,
    catalog_size: Optional[int],
    st: _FleetState,
):
    """Lockstep (E, segment_len) blocks from E independent chunk iterators.

    Each tenant's source is buffered until every tenant can cover a full
    segment; when any source runs dry the whole fleet is truncated to the
    longest window-aligned length *every* tenant can still cover (the
    lockstep analogue of ``run_stream``'s window-aligned tail), and the
    unreplayable remainder is counted in ``t_dropped``."""
    its = [_stream._as_chunks(s) for s in sources]
    n = len(its)
    bufs: list = [[] for _ in range(n)]
    buffered = [0] * n
    done = [False] * n

    def _pull(e: int) -> None:
        try:
            chunk = next(its[e])
        except StopIteration:
            done[e] = True
            return
        except Exception as err:  # reprolint: allow(broad-except) wrapped as _SourceError
            raise _stream._SourceError(err) from err
        chunk = np.asarray(chunk, dtype=np.int64).ravel()
        if chunk.size == 0:
            return
        if catalog_size is not None:
            cmin, cmax = int(chunk.min()), int(chunk.max())
            if cmin < 0 or cmax >= catalog_size:
                raise ValueError(
                    f"tenant {e} ids out of range [0, {catalog_size}): "
                    f"saw [{cmin}, {cmax}]"
                )
        st.t_ingested += chunk.size
        bufs[e].append(chunk)
        buffered[e] += chunk.size

    def _take(e: int, k: int) -> np.ndarray:
        merged = np.concatenate(bufs[e]) if len(bufs[e]) > 1 else bufs[e][0]
        rest = merged[k:]
        bufs[e][:] = [rest] if rest.size else []
        buffered[e] = int(rest.size)
        return merged[:k]

    while True:
        for e in range(n):
            while buffered[e] < segment_len and not done[e]:
                _pull(e)
        if all(b >= segment_len for b in buffered):
            yield np.stack([_take(e, segment_len) for e in range(n)])
            continue
        # tail: some tenant ran dry below one segment.  Pull the others up
        # to the best window-aligned target the dry tenants still allow.
        target = min(buffered[e] for e in range(n) if done[e])
        target = (target // window) * window
        for e in range(n):
            while buffered[e] < target and not done[e]:
                _pull(e)
        aligned = (min(buffered) // window) * window
        st.t_dropped = int(sum(buffered) - aligned * n)
        if aligned:
            yield np.stack([_take(e, aligned) for e in range(n)])
        return


def run_fleet_stream(
    pd: "api.PolicyDef",
    sources: Sequence[Union[np.ndarray, Iterable[np.ndarray]]],
    catalog_size: Optional[int] = None,
    capacities=None,
    *,
    window: int = 1000,
    segment_len: Optional[int] = None,
    carry: Any = None,
    seeds=None,
    etas=None,
    horizons=None,
    n_slots: Optional[int] = None,
    sizes: Optional[np.ndarray] = None,
    costs: Optional[np.ndarray] = None,
    track_opt: bool = False,
    keep_carry: bool = True,
    name: Optional[str] = None,
    prefetch: Optional[int] = None,
) -> FleetResult:
    """Stream E per-tenant chunk iterators through the fleet in fixed memory.

    ``sources[e]`` yields tenant ``e``'s request-id chunks (any sizes —
    they are re-batched into lockstep ``(E, segment_len)`` blocks); use
    ``tracelab.tenant_streams`` for stats-matched synthetic tenants.  With
    ``prefetch > 0`` (default ``REPRO_STREAM_PREFETCH``) a daemon thread
    ingests and assembles segments while the device steps the previous
    ones — the same async double-buffered pipeline as
    ``tracelab.run_stream``, with non-blocking dispatch and at most
    ``prefetch`` segments in flight.

    Fresh fleets need ``horizons`` (the planned per-tenant stream length)
    so each tenant's ``eta=None`` resolves the Theorem-3.1 rate at its own
    horizon — a stream cannot infer its length up front.  ``track_opt``
    accumulates per-tenant request histograms at ingest and reports
    hindsight static OPT (off by default: it is O(E*N) host memory).

    On a source failure mid-stream the in-flight device work is drained
    and a :class:`~repro.cachesim.tracelab.stream.StreamFault` is raised
    whose ``partial`` holds the replayed-prefix :class:`FleetResult`
    (resumable via its ``carry``).
    """
    if window <= 0:
        raise ValueError(f"window must be positive (got {window})")
    sources = list(sources)
    n_tenants = len(sources)
    if n_tenants == 0:
        raise ValueError("run_fleet_stream needs at least one tenant source")
    if segment_len is None:
        segment_len = max(window, (DEFAULT_FLEET_SEGMENT // window) * window)
    else:
        segment_len = max(window, (int(segment_len) // window) * window)
    if prefetch is None:
        prefetch = _stream._default_prefetch()
    prefetch = max(0, int(prefetch))

    if carry is None:
        if catalog_size is None or capacities is None:
            raise ValueError(
                "run_fleet_stream() needs catalog_size and capacities "
                "(or carry=)"
            )
        if horizons is None:
            raise ValueError(
                "run_fleet_stream() needs horizons= (planned per-tenant "
                "stream length) for fresh fleets: per-tenant eta "
                "resolution cannot infer a stream's length"
            )
        caps = _tenant_array(capacities, n_tenants, "capacities")
        seed_arr = _tenant_array(
            seeds if seeds is not None else np.arange(n_tenants),
            n_tenants,
            "seeds",
        )
        hor = _tenant_array(horizons, n_tenants, "horizons")
        eta_list = _tenant_etas(etas, n_tenants)
        slots = int(n_slots) if n_slots is not None else int(caps.max())
        stacked, etas_out = _build_fleet_carries(
            pd, catalog_size, caps, seed_arr, eta_list, hor, window, slots,
            sizes, costs, {},
        )
    else:
        _reject_resume_kwargs(seeds, etas, horizons, n_slots, costs, {})
        stacked = carry
        caps = (
            _tenant_array(capacities, n_tenants, "capacities")
            if capacities is not None
            else np.full(n_tenants, -1)
        )
        seed_arr = np.full(n_tenants, -1)
        etas_out = None

    st = _FleetState()
    if track_opt:
        if catalog_size is None or caps.min() < 0:
            raise ValueError("track_opt=True needs catalog_size and capacities")
        st.counts = np.zeros((n_tenants, int(catalog_size)), np.int64)
    sizes_np = None
    if sizes is not None:
        sizes_np = np.asarray(sizes, np.float64)
        st.bytes_total = np.zeros(n_tenants, np.float64)

    jitted = api._fleet_jit(pd.step)
    t0_wall = time.perf_counter()

    def _dispatch(seg: np.ndarray, block: bool):
        """One fleet scan over an (E, seg_len) lockstep block."""
        nonlocal stacked
        chunks = jnp.asarray(
            seg.reshape(n_tenants, -1, window), jnp.int32
        )
        compiled = api._compiled(jitted, stacked, chunks)
        stacked, out = compiled(stacked, chunks)
        if block:
            jax.block_until_ready(out)
        return out, seg.shape[1]

    def _host_pass(seg: np.ndarray) -> None:
        """Per-tenant OPT histograms / byte accounting (host-only, so it
        overlaps the device scan in the async pipeline)."""
        if st.counts is None and sizes_np is None:
            return
        for e in range(n_tenants):
            if st.counts is not None:
                st.counts[e] += np.bincount(
                    seg[e], minlength=st.counts.shape[1]
                )
            if sizes_np is not None:
                st.bytes_total[e] += float(sizes_np[seg[e]].sum())

    def _consume(item) -> None:
        out, t_seg = item
        jax.block_until_ready((out.reward, out.hits, out.aux, out.occupancy))
        st.reward.append(np.asarray(out.reward, np.float64))
        st.hits.append(np.asarray(out.hits, np.int64))
        st.aux.append(np.asarray(out.aux, np.float64))
        st.occupancy.append(np.asarray(out.occupancy, np.float64))
        if out.byte_hits is not None:
            st.byte_hits.append(np.asarray(out.byte_hits, np.float64))
        st.n_segments += 1
        st.t_used += t_seg

    def _result() -> FleetResult:
        if st.counts is not None:
            opt = np.array(
                [
                    _opt_from_counts(st.counts[e], int(caps[e]))
                    for e in range(n_tenants)
                ]
            )
        else:
            opt = np.zeros(n_tenants)
        return FleetResult(
            name=name or pd.name,
            kind=pd.kind,
            n_tenants=n_tenants,
            T=st.t_used,
            window=window,
            capacities=caps,
            seeds=seed_arr,
            etas=etas_out,
            reward=np.concatenate(st.reward, axis=1),
            hits=np.concatenate(st.hits, axis=1),
            aux=np.concatenate(st.aux, axis=1),
            occupancy=np.concatenate(st.occupancy, axis=1),
            opt_hits=opt,
            carry=stacked if keep_carry else None,
            wall_seconds=time.perf_counter() - t0_wall,
            byte_hits=(
                np.concatenate(st.byte_hits, axis=1)
                if len(st.byte_hits) == st.n_segments and st.n_segments
                else None
            ),
            bytes_total=st.bytes_total,
            n_segments=st.n_segments,
            t_dropped=st.t_dropped,
            prefetch=prefetch,
        )

    def _fault(err: "_stream._SourceError", pending=None) -> "_stream.StreamFault":
        for res in pending or ():
            _consume(res)
        partial = _result() if st.t_used else None
        return _stream.StreamFault(
            f"tenant chunk source failed after {st.t_ingested} ingested / "
            f"{st.t_used} per-tenant replayed requests "
            f"({st.n_segments} segments): {err.cause!r}",
            t_ingested=st.t_ingested,
            t_replayed=st.t_used * n_tenants,
            n_segments=st.n_segments,
            partial=partial,
        )

    if prefetch == 0:
        segs = _assemble_fleet_segments(
            sources, segment_len, window, catalog_size, st
        )
        while True:
            try:
                seg = next(segs)
            except StopIteration:
                break
            except _stream._SourceError as e:
                raise _fault(e) from e.cause
            res = _dispatch(seg, block=True)
            _host_pass(seg)
            _consume(res)
    else:
        q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        stop = threading.Event()

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def _ingest():
            try:
                for seg in _assemble_fleet_segments(
                    sources, segment_len, window, catalog_size, st
                ):
                    if not _put(seg):
                        return
                _put(_stream._DONE)
            except BaseException as e:  # reprolint: allow(broad-except) forwarded; classified by main
                _put(e)

        worker = threading.Thread(
            target=_ingest, name="run_fleet_stream-ingest", daemon=True
        )
        worker.start()
        pending: deque = deque()
        try:
            while True:
                item = q.get()
                if item is _stream._DONE:
                    break
                if isinstance(item, _stream._SourceError):
                    raise _fault(item, pending) from item.cause
                if isinstance(item, BaseException):
                    for res in pending:
                        _consume(res)
                    pending.clear()
                    raise item
                res = _dispatch(item, block=False)
                pending.append(res)
                _host_pass(item)
                while len(pending) > prefetch:
                    _consume(pending.popleft())
            while pending:
                _consume(pending.popleft())
        finally:
            stop.set()
            worker.join(timeout=5.0)

    if st.t_used == 0:
        raise ValueError(
            f"tenant streams shorter than one window "
            f"({st.t_dropped} buffered across {n_tenants} tenants < "
            f"{window} per tenant)"
        )
    return _result()


# ---------------------------------------------------------------------------
# two-level edge -> origin fleet
# ---------------------------------------------------------------------------

#: kinds whose per-request hit flags the edge tier can expose
FLAG_KINDS = ("ogb", "omd", "lru", "lfu", "ftpl", "fifo", "gds")


@functools.lru_cache(maxsize=None)
def _flags_policy(kind: str):
    """(pd, flags_step) for the edge tier.

    ``flags_step(carry, ids) -> (carry, (StepOut, flags))`` mirrors the
    kind's registered step bit-exactly and additionally emits the
    (window,) per-request hit flags whose complement is the origin's
    request stream.  Memoized so the step identity keys the executable
    cache like every registered step."""
    pd = api.policy_def(kind)
    if kind in ("ogb", "omd"):
        # Poisson accounting: a request hits iff f[id] >= p[id] at the
        # pre-update state — the same convention as sample_chunk_metrics,
        # so sum(flags) == StepOut.hits by construction.
        def step(carry, ids):
            flags = carry.f[ids] >= carry.p[ids]
            carry, out = pd.step(carry, ids)
            return carry, (out, flags)

    elif kind in _tree_engines.TREE_ENGINE_KINDS or kind == "gds":

        def step(carry, ids):
            chunk = _tree_engines.make_tree_chunk(kind, carry,
                                                  return_flags=True)
            carry, (flags, occ) = chunk(carry, ids)
            hits = jnp.sum(flags.astype(jnp.int32))
            out = api.StepOut(
                hits.astype(jnp.float32),
                hits,
                jnp.zeros((), jnp.float32),
                occ.astype(jnp.float32),
                (
                    jnp.sum(jnp.where(flags, carry.szs[ids], 0.0))
                    if kind == "gds"
                    else None
                ),
            )
            return carry, (out, flags)

    elif kind == "fifo":
        raw = _engines._STEPS[kind]

        def step(carry, ids):
            carry, flags = jax.lax.scan(raw, carry, ids)
            hits = jnp.sum(flags.astype(jnp.int32))
            out = api.StepOut(
                hits.astype(jnp.float32),
                hits,
                jnp.zeros((), jnp.float32),
                _engines._occ_slots(carry).astype(jnp.float32),
            )
            return carry, (out, flags)

    else:
        raise ValueError(
            f"edge tier needs per-request hit flags; kind {kind!r} has "
            f"none (supported: {FLAG_KINDS})"
        )
    return pd, step


def run_edge_fleet(
    edge_kind: str,
    origin_kind: str,
    traces,
    catalog_size: int,
    edge_capacities,
    origin_capacity: int,
    *,
    window: int = 500,
    origin_window: Optional[int] = None,
    seeds=None,
    edge_etas=None,
    origin_eta: Optional[float] = None,
    origin_seed: int = 0,
    track_opt: bool = True,
    prefetch: Optional[int] = None,
    name: Optional[str] = None,
) -> EdgeFleetResult:
    """Two-level replay: E edge caches in front of one shared origin cache.

    Phase 1 replays every edge's own trace through the fleet dispatch with
    per-request hit flags.  Phase 2 interleaves the edge *misses*
    deterministically — arrival position major, edge index minor, the
    round-robin order a synchronous fleet would present to its parent —
    and streams them through the origin cache via ``tracelab.run_stream``
    (async prefetch path).  Regret accounting is per tenant at the edge
    and hindsight-static at the origin.
    """
    chunks, used, t_used = _tenant_chunks(traces, window)
    n_edges = chunks.shape[0]
    pd_edge, flags_step = _flags_policy(edge_kind)

    caps = _tenant_array(edge_capacities, n_edges, "edge_capacities")
    seed_arr = _tenant_array(
        seeds if seeds is not None else np.arange(n_edges), n_edges, "seeds"
    )
    hor = np.full(n_edges, t_used)
    eta_list = _tenant_etas(edge_etas, n_edges)
    stacked, etas_out = _build_fleet_carries(
        pd_edge, catalog_size, caps, seed_arr, eta_list, hor, window,
        int(caps.max()), None, None, {},
    )

    jitted = api._fleet_jit(flags_step)
    t0 = time.perf_counter()
    compiled = api._compiled(jitted, stacked, chunks)
    final, (out, flags) = compiled(stacked, chunks)
    jax.block_until_ready(flags)
    edge_wall = time.perf_counter() - t0

    if track_opt:
        opt = np.array(
            [
                float(best_static_hits(used[e], int(caps[e])))
                for e in range(n_edges)
            ]
        )
    else:
        opt = np.zeros(n_edges)

    edges = FleetResult(
        name=f"{name or 'edge_fleet'}/{pd_edge.name}",
        kind=pd_edge.kind,
        n_tenants=n_edges,
        T=t_used,
        window=window,
        capacities=caps,
        seeds=seed_arr,
        etas=etas_out,
        reward=np.asarray(out.reward, np.float64),
        hits=np.asarray(out.hits, np.int64),
        aux=np.asarray(out.aux, np.float64),
        occupancy=np.asarray(out.occupancy, np.float64),
        opt_hits=opt,
        carry=final,
        wall_seconds=edge_wall,
        byte_hits=(
            np.asarray(out.byte_hits, np.float64)
            if out.byte_hits is not None
            else None
        ),
    )

    # ---- phase 2: the miss interleave becomes the origin's stream --------
    flags_np = np.asarray(flags, bool)  # (E, M, W)
    ids_np = used.reshape(n_edges, -1, window)
    n_chunks = ids_np.shape[1]
    total_misses = int((~flags_np).sum())
    ow = int(origin_window) if origin_window is not None else window
    if total_misses < ow:
        raise ValueError(
            f"edge misses ({total_misses}) shorter than one origin window "
            f"({ow}); lower origin_window or raise the edge load"
        )

    def _miss_chunks():
        # arrival-position major, edge minor: transpose each (E, W) chunk
        # to (W, E) before masking, so simultaneous arrivals interleave
        # round-robin across edges — deterministic, replayable
        for k in range(n_chunks):
            miss = ~flags_np[:, k, :]
            yield ids_np[:, k, :].T[miss.T]

    pd_origin = api.policy_def(origin_kind)
    origin = _stream.run_stream(
        pd_origin,
        _miss_chunks(),
        catalog_size,
        int(origin_capacity),
        window=ow,
        seed=origin_seed,
        eta=origin_eta,
        horizon=total_misses,
        keep_carry=False,
        prefetch=prefetch,
        name=f"{name or 'edge_fleet'}/origin-{pd_origin.name}",
    )
    if track_opt:
        miss_trace = np.concatenate(list(_miss_chunks()))[: origin.T]
        origin.opt_hits = float(
            best_static_hits(miss_trace, int(origin_capacity))
        )
    return EdgeFleetResult(
        edges=edges, origin=origin, origin_requests=total_misses
    )


def run_edge_fleet_scenario(
    name: str,
    scale: str = "quick",
    *,
    prefetch: Optional[int] = None,
    track_opt: bool = True,
) -> EdgeFleetResult:
    """Run a registered ``EDGE_FLEET_SCENARIOS`` entry at the given scale."""
    sc = get_edge_fleet_scenario(name)
    n_edges, catalog, t_edge, c_edge, c_origin = sc.dims(scale)
    traces = sc.make_edge_traces(scale)
    del n_edges, t_edge  # encoded in the traces' shape
    return run_edge_fleet(
        sc.edge_policy,
        sc.origin_policy,
        traces,
        catalog,
        c_edge,
        c_origin,
        window=sc.window,
        prefetch=prefetch,
        track_opt=track_opt,
        name=sc.name,
    )
