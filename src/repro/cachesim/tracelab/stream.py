"""Out-of-core streaming replay: any PolicyDef over any chunk iterator.

:func:`run_stream` is the third execution surface next to ``api.run`` and
``api.sweep`` — except it is *not* a third engine: it re-batches an
arbitrary chunk iterator (a trace-file loader, a catalog remapper, the
workload synthesizer, a live request tap) into fixed-shape segments and
replays each one through the resumable ``api.run(carry=...)`` contract.
Peak memory is O(segment + policy state), independent of the trace
length, and the replayed dynamics are **bit-exact** equal to a one-shot
in-memory ``api.run`` over the concatenated trace — whatever the incoming
chunking (PR-4's streaming tests are the foundation; the tracelab
differential sweep extends them to the ingestion path).

Fixed-shape segments matter: ``api.run`` memoizes compiled executables on
the chunk shape, so a multi-gigabyte stream costs two compilations (the
steady-state segment and the tail), not one per chunk.

**The async double-buffered pipeline (default).**  The synchronous loop —
load a chunk, step the device, repeat — leaves the device idle during
host I/O and the host idle during device replay.  With ``prefetch >= 1``
the stream runs as a pipeline instead:

* a background ingest thread pulls chunks from the source and re-batches
  them into segments, keeping up to ``prefetch`` assembled segments ahead
  of the device;
* the main thread dispatches segment ``k`` *without blocking*
  (``api.run(block=False)`` — the carry chains through JAX's async
  dispatch), then runs the host-side dynamic-OPT/stats pass for segment
  ``k`` while the device scans it and the ingest thread reads ``k+1``;
* ``jax.block_until_ready`` happens only at the consume point, when a
  segment's results are folded into the accumulators.

The pipeline is **bit-exact** with the synchronous path — same segment
re-batching, same carry chain, same dynamic-OPT windows — only the
:class:`~repro.cachesim.results.StreamResult` timing split
(``ingest_seconds`` / ``host_seconds``) tells them apart.  ``prefetch=0``
falls back to the fully synchronous loop.

Under ``jax.profiler.trace`` the call shows as one ``repro.stream`` span
on the main thread, holding ``repro.stream.queue_wait`` (waiting for the
ingest thread; argument ``depth``, the queue's depth on entry),
``repro.stream.dyn_opt``, ``repro.stream.consume`` (with
``repro.stream.wait_device`` nested) and each segment's ``repro.run.*``
spans; the thread that assembles segments records
``repro.stream.source`` (time inside the chunk source) and
``repro.stream.validate`` (the id-range check and re-batching).

When the chunk source *raises* mid-stream, the pipeline degrades
gracefully: in-flight device work is drained, accumulated results are
packaged (resumable carry included), and a :class:`StreamFault` pinning
the stream position — requests ingested, requests replayed, segments
dispatched — is raised from the original error.  A source that merely
*stalls* just idles the pipeline: the device drains its queue and the
stream resumes when chunks flow again.
"""

from __future__ import annotations

# the ingest thread is the SOLE writer of these _StreamState counters;
# the main thread reads them only after joining (single-writer contract)
# reprolint: thread-owned(t_ingested, ingest_seconds, t_dropped)

import os
import queue
import threading
import time
from collections import deque
from typing import Any, Iterable, Iterator, Optional, Union

import numpy as np

import jax
from jax.profiler import TraceAnnotation

from repro.cachesim import api
from repro.cachesim.results import StreamResult
from repro.core.regret import best_static_hits

#: default steady-state segment length (requests per device dispatch)
DEFAULT_SEGMENT = 131_072

#: default pipeline depth (segments assembled/dispatched ahead of the
#: consume point); override per call with ``prefetch=`` or process-wide
#: with ``REPRO_STREAM_PREFETCH`` (0 = synchronous)
DEFAULT_PREFETCH = 2


class StreamFault(RuntimeError):
    """The chunk source failed mid-stream.

    Raised by :func:`run_stream` *after* the in-flight device work has
    been drained, so the attributes pin the exact stream position:

    - ``t_ingested``: requests successfully pulled from the source,
    - ``t_replayed``: requests whose segments were dispatched and drained,
    - ``n_segments``: device dispatches completed,
    - ``partial``: a :class:`~repro.cachesim.results.StreamResult` over the
      replayed prefix (resumable via its ``carry``), or ``None`` when the
      fault hit before one full window replayed.

    The original source exception is chained as ``__cause__``.
    """

    def __init__(
        self,
        message: str,
        *,
        t_ingested: int = 0,
        t_replayed: int = 0,
        n_segments: int = 0,
        partial: Optional[StreamResult] = None,
    ):
        super().__init__(message)
        self.t_ingested = int(t_ingested)
        self.t_replayed = int(t_replayed)
        self.n_segments = int(n_segments)
        self.partial = partial


class _SourceError(Exception):
    """Internal marker: the *source iterator* raised (vs our own
    validation, which must surface unwrapped)."""

    def __init__(self, cause: BaseException):
        super().__init__(str(cause))
        self.cause = cause


_DONE = object()  # ingest-thread sentinel: source exhausted


def _as_chunks(
    chunks: Union[np.ndarray, Iterable[np.ndarray]],
) -> Iterator[np.ndarray]:
    if isinstance(chunks, np.ndarray):
        yield chunks
        return
    for c in chunks:
        yield np.asarray(c)


def _default_prefetch() -> int:
    return int(os.environ.get("REPRO_STREAM_PREFETCH", DEFAULT_PREFETCH))


class _StreamState:
    """Mutable accumulators shared by the sync and async drivers.

    The ingest-side counters (``t_ingested``, ``ingest_seconds``,
    ``t_dropped``) are written only by whichever thread runs the segment
    assembly; the replay-side accumulators only by the main thread."""

    def __init__(self):
        self.reward, self.hits, self.aux, self.occupancy = [], [], [], []
        self.byte_hits: list = []
        self.bytes_total = 0.0
        self.dyn_opt: list = []
        self.opt_buf: list = []
        self.opt_buffered = 0
        self.n_segments = 0
        self.t_used = 0
        self.t_ingested = 0
        self.t_dropped = 0
        self.extras: dict = {}
        self.ingest_seconds = 0.0
        self.host_seconds = 0.0


def _assemble_segments(
    source,
    segment_len: int,
    window: int,
    catalog_size: Optional[int],
    st: _StreamState,
) -> Iterator[np.ndarray]:
    """Re-batch raw source chunks into window-aligned segments.

    Yields steady-state ``segment_len`` segments, then one final
    window-aligned tail (``st.t_dropped`` records the sub-window
    remainder).  Time spent *inside the source* accrues to
    ``st.ingest_seconds``; source exceptions are wrapped in
    :class:`_SourceError` so the driver can tell a failing loader apart
    from a validation bug."""
    it = _as_chunks(source)
    buf: list = []
    buffered = 0
    while True:
        with TraceAnnotation("repro.stream.source"):
            t0 = time.perf_counter()
            try:
                chunk = next(it)
            except StopIteration:
                st.ingest_seconds += time.perf_counter() - t0
                break
            except Exception as e:  # the source failed, not us
                st.ingest_seconds += time.perf_counter() - t0
                raise _SourceError(e) from e
            st.ingest_seconds += time.perf_counter() - t0
        # full segments are cut inside the span and yielded after it, so
        # the span never stays open while the consumer holds the generator
        ready = []
        with TraceAnnotation("repro.stream.validate"):
            chunk = np.asarray(chunk, dtype=np.int64).ravel()
            if chunk.size == 0:
                continue
            if catalog_size is not None and not (
                0 <= int(chunk.min()) and int(chunk.max()) < catalog_size
            ):
                # an out-of-range dense id would be silently clamped by the
                # device gather (aliasing item N-1) — corrupt results, no
                # error
                raise ValueError(
                    f"stream ids must be dense in [0, {catalog_size}): got "
                    f"[{int(chunk.min())}, {int(chunk.max())}] — route raw "
                    "traces through CatalogRemap (with "
                    "max_items=catalog_size) first"
                )
            st.t_ingested += chunk.size
            buf.append(chunk)
            buffered += chunk.size
            while buffered >= segment_len:
                merged = np.concatenate(buf) if len(buf) > 1 else buf[0]
                ready.append(merged[:segment_len])
                rest = merged[segment_len:]
                buf = [rest] if rest.size else []
                buffered = rest.size
        yield from ready
    # tail: whole windows replay as one final (differently shaped) segment
    if buffered:
        merged = np.concatenate(buf) if len(buf) > 1 else buf[0]
        aligned = (buffered // window) * window
        st.t_dropped = buffered - aligned
        if aligned:
            yield merged[:aligned]


def run_stream(
    pd: "api.PolicyDef",
    chunks: Union[np.ndarray, Iterable[np.ndarray]],
    catalog_size: Optional[int] = None,
    capacity: Optional[int] = None,
    *,
    window: int = 1000,
    segment_len: Optional[int] = None,
    carry: Any = None,
    seed: int = 0,
    eta: Optional[float] = None,
    horizon: Optional[int] = None,
    n_slots: Optional[int] = None,
    sizes: Optional[np.ndarray] = None,
    costs: Optional[np.ndarray] = None,
    opt_window: Optional[int] = None,
    keep_carry: bool = True,
    name: Optional[str] = None,
    prefetch: Optional[int] = None,
) -> StreamResult:
    """Replay a chunk iterator through one policy in fixed memory.

    ``chunks`` yields 1-D int arrays of dense ids in ``[0, catalog_size)``
    (route raw traces through
    :class:`~repro.cachesim.tracelab.catalog.CatalogRemap` first).  They
    are re-buffered into ``segment_len``-request segments (rounded down to
    a multiple of ``window``; the incoming chunking never changes the
    replayed dynamics) and each segment resumes the previous one's carry
    via ``api.run(carry=...)``.  A trailing remainder shorter than one
    ``window`` is dropped — exactly like the one-shot ``api.run`` — and
    reported as ``t_dropped``.

    ``horizon`` is the *planned* total stream length and is required on a
    fresh (non-resumed) stream: it seeds horizon-tuned policies (FTPL's
    noise scale, OGB/OMD's ``eta=None`` resolution via ``pd.default_eta``)
    and a stream cannot know its own length up front.  For bit-exact
    parity with a one-shot ``api.run`` over the same trace, pass the same
    ``horizon``/``eta``/``seed``.

    ``opt_window`` (a multiple of ``window``; rounded up) additionally
    computes the hindsight-optimal *per-window* static allocation on the
    host while the stream passes by — the time-varying comparator behind
    :attr:`~repro.cachesim.results.StreamResult.dynamic_regret`.  The
    final window covers the replayed remainder (shorter than
    ``opt_window`` when the stream length is not a multiple), so the
    windows together cover every replayed request.

    ``prefetch`` sets the pipeline depth: with the default (``2``, or the
    ``REPRO_STREAM_PREFETCH`` env var) a background thread ingests and
    assembles up to ``prefetch`` segments ahead while the device scans
    and the host runs the dynamic-OPT pass — the async double-buffered
    mode.  ``prefetch=0`` is the fully synchronous fallback (load, step,
    repeat).  Both modes produce **bit-identical** results; only the
    :class:`~repro.cachesim.results.StreamResult` timing split differs.
    If the chunk source raises mid-stream, in-flight work is drained and
    a :class:`StreamFault` (with the stream position and a resumable
    ``partial`` result) is raised from the source error.

    Pass ``carry=`` to resume a previous stream's final carry; as with
    ``api.run``, the carry holds every policy parameter, so
    ``seed``/``eta``/``horizon``/``n_slots``/``costs`` must not be
    re-passed (``sizes`` may be: it also drives the host-side byte
    accounting).

    ``sizes``/``costs`` are per-*item* arrays passed through to
    ``api.run`` — sized policies shape decisions with them and results
    gain ``byte_hits``/``bytes_total`` (ingest per-request sizes with
    ``open_trace(..., with_sizes=True)`` + ``CatalogRemap.item_sizes``).
    """
    if window <= 0:
        raise ValueError(f"window must be positive, got {window}")
    if segment_len is None:
        segment_len = max(window, (DEFAULT_SEGMENT // window) * window)
    else:
        segment_len = max(window, (int(segment_len) // window) * window)
    if opt_window is not None:
        if capacity is None:
            raise ValueError("opt_window needs capacity")
        opt_window = max(1, -(-int(opt_window) // window)) * window
    if prefetch is None:
        prefetch = _default_prefetch()
    prefetch = max(0, int(prefetch))

    resumed = carry is not None
    if not resumed:
        if catalog_size is None or capacity is None:
            raise ValueError(
                "run_stream() needs catalog_size and capacity (or carry=)"
            )
        if horizon is None:
            # a one-shot api.run can default horizon to the trace length; a
            # stream cannot know its own length, and letting horizon-tuned
            # policies (FTPL's noise scale, eta=None resolution) silently
            # tune to the *first segment* length would break the bit-exact
            # parity with the one-shot replay
            raise ValueError(
                "run_stream() needs horizon= (the planned total stream "
                "length): a stream cannot infer it, and horizon-tuned "
                "policies would otherwise mis-tune to the first segment"
            )
        if eta is None and pd.default_eta is not None:
            eta = pd.default_eta(
                int(catalog_size), int(capacity), int(horizon), window
            )
    elif (
        eta is not None
        or horizon is not None
        or n_slots is not None
        or seed != 0
        or costs is not None
    ):
        raise ValueError(
            "run_stream(carry=...) resumes with the carry's parameters; do "
            "not pass seed/eta/horizon/n_slots/costs alongside a carry"
        )

    st = _StreamState()
    t0_wall = time.perf_counter()

    def _dispatch(seg: np.ndarray, block: bool):
        """One ``api.run`` over a segment (first call initializes)."""
        nonlocal carry
        run_kw = dict(
            window=window, track_opt=False, name=name, sizes=sizes,
            block=block,
        )
        if carry is None:
            res = api.run(
                pd, seg, catalog_size, capacity, seed=seed, eta=eta,
                horizon=horizon, n_slots=n_slots, costs=costs, **run_kw,
            )
            st.extras.update(res.extras)
        else:
            res = api.run(pd, seg, capacity=capacity, carry=carry, **run_kw)
        carry = res.carry
        return res

    def _host_pass(seg: np.ndarray):
        """Dynamic-OPT accounting over a segment's ids (host-only: it needs
        the request ids, not the device results — which is what lets it
        overlap the device scan in the async pipeline)."""
        if opt_window is None:
            return
        t0 = time.perf_counter()
        with TraceAnnotation("repro.stream.dyn_opt"):
            st.opt_buf.append(seg)
            st.opt_buffered += len(seg)
            while st.opt_buffered >= opt_window:
                merged = (
                    np.concatenate(st.opt_buf)
                    if len(st.opt_buf) > 1
                    else st.opt_buf[0]
                )
                st.dyn_opt.append(float(
                    best_static_hits(merged[:opt_window], int(capacity))
                ))
                rest = merged[opt_window:]
                st.opt_buf[:] = [rest] if rest.size else []
                st.opt_buffered = rest.size
        st.host_seconds += time.perf_counter() - t0

    def _consume(res):
        """Fold one segment's (possibly in-flight) results into the
        accumulators — the only place the pipeline blocks on the device."""
        with TraceAnnotation("repro.stream.consume"):
            with TraceAnnotation("repro.stream.wait_device"):
                jax.block_until_ready(
                    (res.reward, res.hits, res.aux, res.occupancy)
                )
            t0 = time.perf_counter()
            st.reward.append(np.asarray(res.reward, np.float64))
            st.hits.append(np.asarray(res.hits, np.int64))
            st.aux.append(np.asarray(res.aux, np.float64))
            st.occupancy.append(np.asarray(res.occupancy, np.float64))
            if res.byte_hits is not None:
                st.byte_hits.append(np.asarray(res.byte_hits, np.float64))
            st.bytes_total += res.bytes_total
            st.n_segments += 1
            st.t_used += res.T
            st.host_seconds += time.perf_counter() - t0

    def _flush_dyn_opt_tail():
        """The replayed remainder shorter than one opt_window still gets a
        (final, shorter) dynamic-OPT window — without it the end of every
        stream would be invisible to the dynamic-regret comparator."""
        if opt_window is None or not st.opt_buffered:
            return
        t0 = time.perf_counter()
        with TraceAnnotation("repro.stream.dyn_opt"):
            merged = (
                np.concatenate(st.opt_buf)
                if len(st.opt_buf) > 1
                else st.opt_buf[0]
            )
            st.dyn_opt.append(float(best_static_hits(merged, int(capacity))))
            st.opt_buf.clear()
            st.opt_buffered = 0
        st.host_seconds += time.perf_counter() - t0

    def _result() -> StreamResult:
        return StreamResult(
            name=name or pd.name,
            kind=pd.kind,
            T=st.t_used,
            window=window,
            capacity=int(capacity) if capacity is not None else -1,
            reward=np.concatenate(st.reward),
            hits=np.concatenate(st.hits),
            aux=np.concatenate(st.aux),
            occupancy=np.concatenate(st.occupancy),
            opt_hits=0.0,
            carry=carry if keep_carry else None,
            wall_seconds=time.perf_counter() - t0_wall,
            extras=st.extras,
            byte_hits=(
                np.concatenate(st.byte_hits)
                if len(st.byte_hits) == st.n_segments and st.n_segments
                else None
            ),
            bytes_total=st.bytes_total,
            dyn_opt_hits=(
                np.asarray(st.dyn_opt, np.float64)
                if opt_window is not None
                else None
            ),
            dyn_opt_window=opt_window or 0,
            n_segments=st.n_segments,
            t_dropped=st.t_dropped,
            ingest_seconds=st.ingest_seconds,
            host_seconds=st.host_seconds,
            prefetch=prefetch,
        )

    def _fault(err: _SourceError, pending=None) -> StreamFault:
        """Drain in-flight work, package the replayed prefix, and build the
        position-pinned fault to raise from the source error."""
        for res in pending or ():
            _consume(res)
        _flush_dyn_opt_tail()
        partial = _result() if st.t_used else None
        return StreamFault(
            f"chunk source failed after {st.t_ingested} ingested / "
            f"{st.t_used} replayed requests "
            f"({st.n_segments} segments): {err.cause!r}",
            t_ingested=st.t_ingested,
            t_replayed=st.t_used,
            n_segments=st.n_segments,
            partial=partial,
        )

    with TraceAnnotation("repro.stream"):
        if prefetch == 0:
            # ---- synchronous fallback: load, step, repeat ----------------
            segs = _assemble_segments(
                chunks, segment_len, window, catalog_size, st
            )
            while True:
                try:
                    seg = next(segs)
                except StopIteration:
                    break
                except _SourceError as e:
                    raise _fault(e) from e.cause
                res = _dispatch(seg, block=True)
                _host_pass(seg)
                _consume(res)
        else:
            # ---- async double-buffered pipeline --------------------------
            q: "queue.Queue" = queue.Queue(maxsize=prefetch)
            stop = threading.Event()

            def _put(item) -> bool:
                # bounded put that aborts when the consumer has bailed, so the
                # ingest thread can never hang on a dead pipeline
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        return True
                    except queue.Full:
                        continue
                return False

            def _ingest():
                try:
                    for seg in _assemble_segments(
                        chunks, segment_len, window, catalog_size, st
                    ):
                        if not _put(seg):
                            return
                    _put(_DONE)
                except BaseException as e:  # reprolint: allow(broad-except) forwarded; classified by main
                    _put(e)  # (source fault vs validation error)

            worker = threading.Thread(
                target=_ingest, name="run_stream-ingest", daemon=True
            )
            worker.start()
            pending: deque = deque()  # dispatched, not yet consumed
            try:
                while True:
                    with TraceAnnotation("repro.stream.queue_wait",
                                         depth=q.qsize()):
                        item = q.get()
                    if item is _DONE:
                        break
                    if isinstance(item, _SourceError):
                        raise _fault(item, pending) from item.cause
                    if isinstance(item, BaseException):
                        for res in pending:  # drain before re-raising
                            _consume(res)
                        pending.clear()
                        raise item
                    res = _dispatch(item, block=False)
                    pending.append(res)
                    # overlaps the device scan just dispatched
                    _host_pass(item)
                    while len(pending) > prefetch:
                        _consume(pending.popleft())
                while pending:
                    _consume(pending.popleft())
            finally:
                stop.set()
                worker.join(timeout=5.0)

        _flush_dyn_opt_tail()

        if st.t_used == 0:
            raise ValueError(
                f"stream shorter than one window ({st.t_dropped} < {window})"
            )

        return _result()
