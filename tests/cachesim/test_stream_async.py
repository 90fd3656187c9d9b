"""The async double-buffered pipeline: bit-exactness, faults, timing.

``run_stream``'s async mode (background ingest + non-blocking dispatch +
overlapped host OPT pass) must be a pure *scheduling* change: for every
trace-driven PolicyDef, over ragged prime-sized chunks, the async replay
equals the synchronous one bit for bit — hits, fractional reward, aux,
occupancy, dynamic-OPT windows, and every leaf of the final carry.  On
top of the differential sweep: the fault path (a loader that raises
mid-stream drains in-flight work and surfaces a position-pinned
:class:`StreamFault` with a *resumable* partial result), the stall path
(a slow source only idles the pipeline), and the split timing fields.
"""

import time

import numpy as np
import pytest

import jax

from repro.cachesim import api
from repro.cachesim.results import StreamResult
from repro.cachesim.tracelab import StreamFault, run_stream
from repro.cachesim.traces import zipf
from repro.core.regret import best_static_hits

STREAM_KINDS = tuple(
    k for k in api.policy_def_kinds() if api.policy_def(k).trace_driven
)

N, C, T = 311, 23, 6400
WINDOW = 16


def _kind_kwargs(kind):
    kw = {"eta": 0.03} if api.policy_def(kind).fractional else {}
    if kind == "ogb_sized":
        kw["sizes"] = np.asarray([1.0, 2.0, 4.0, 8.0])[np.arange(N) % 4]
    return kw


def _ragged(trace, size=997):
    return (trace[i : i + size] for i in range(0, len(trace), size))


@pytest.mark.parametrize("kind", STREAM_KINDS)
def test_async_bit_exact_vs_sync(kind):
    """prefetch=2 == prefetch=0 over ragged prime chunks, every kind."""
    trace = zipf(N, T, alpha=0.9, seed=3)
    pd = api.policy_def(kind)
    kw = _kind_kwargs(kind)
    runs = {}
    for prefetch in (0, 2):
        runs[prefetch] = run_stream(
            pd, _ragged(trace), N, C, window=WINDOW, seed=0, horizon=T,
            segment_len=2048, opt_window=704, prefetch=prefetch, **kw,
        )
    sync, asy = runs[0], runs[2]
    assert asy.prefetch == 2 and sync.prefetch == 0
    assert asy.T == sync.T and asy.n_segments == sync.n_segments
    np.testing.assert_array_equal(asy.hits, sync.hits)
    np.testing.assert_array_equal(asy.reward, sync.reward)
    np.testing.assert_array_equal(asy.aux, sync.aux)
    np.testing.assert_array_equal(asy.occupancy, sync.occupancy)
    np.testing.assert_array_equal(asy.dyn_opt_hits, sync.dyn_opt_hits)
    if sync.byte_hits is not None:
        np.testing.assert_array_equal(asy.byte_hits, sync.byte_hits)
    for a, b in zip(jax.tree.leaves(asy.carry), jax.tree.leaves(sync.carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("prefetch", (0, 1, 2, 4))
def test_prefetch_depths_agree(prefetch):
    """Any pipeline depth replays the same dynamics (lfu as the automaton
    witness; the full kind sweep above covers depth 0 vs 2)."""
    trace = zipf(N, T, alpha=0.9, seed=7)
    sr = run_stream(
        api.policy_def("lfu"), _ragged(trace, 1013), N, C, window=WINDOW,
        horizon=T, segment_len=1024, prefetch=prefetch,
    )
    ref = api.run(
        api.policy_def("lfu"), trace, N, C, window=WINDOW, horizon=T,
        track_opt=False,
    )
    np.testing.assert_array_equal(sr.hits, ref.hits)
    np.testing.assert_array_equal(sr.reward, ref.reward)


@pytest.mark.parametrize("prefetch", (0, 2))
def test_source_fault_drains_and_pins_position(prefetch):
    """A loader that raises mid-stream: in-flight segments are drained,
    the StreamFault pins the position, and the partial result resumes
    bit-exactly into the rest of the trace."""
    trace = zipf(N, T, alpha=0.9, seed=11)
    cut = 4096  # fault lands exactly at a segment boundary

    def faulty():
        yield trace[:2048]
        yield trace[2048:cut]
        raise OSError("disk vanished")

    pd = api.policy_def("lru")
    with pytest.raises(StreamFault) as ei:
        run_stream(
            pd, faulty(), N, C, window=WINDOW, horizon=T,
            segment_len=2048, prefetch=prefetch,
        )
    fault = ei.value
    assert isinstance(fault.__cause__, OSError)
    assert fault.t_ingested == cut
    assert fault.t_replayed == cut  # both in-flight segments drained
    assert fault.n_segments == 2
    partial = fault.partial
    assert isinstance(partial, StreamResult)
    assert partial.T == cut and partial.prefetch == prefetch

    # the drained prefix + a resumed stream == the uninterrupted replay
    rest = run_stream(
        pd, trace[cut:], capacity=C, carry=partial.carry, window=WINDOW,
        segment_len=2048, prefetch=prefetch,
    )
    full = run_stream(
        pd, trace, N, C, window=WINDOW, horizon=T, segment_len=2048,
        prefetch=prefetch,
    )
    np.testing.assert_array_equal(
        np.concatenate([partial.hits, rest.hits]), full.hits
    )
    for a, b in zip(jax.tree.leaves(rest.carry), jax.tree.leaves(full.carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_source_fault_before_first_window():
    """A fault before one full window replays: no partial, position 0."""

    def dead():
        raise RuntimeError("no data")
        yield  # pragma: no cover

    with pytest.raises(StreamFault) as ei:
        run_stream(
            api.policy_def("lru"), dead(), N, C, window=WINDOW, horizon=T,
        )
    assert ei.value.partial is None
    assert ei.value.t_replayed == 0 and ei.value.t_ingested == 0


def test_slow_source_stalls_gracefully():
    """A stalling chunk source just idles the pipeline — results are
    unchanged and the stall shows up as ingest time, not an error."""
    trace = zipf(N, 3200, alpha=0.9, seed=13)

    def slow():
        for i in range(0, 3200, 800):
            time.sleep(0.02)
            yield trace[i : i + 800]

    sr = run_stream(
        api.policy_def("ogb"), slow(), N, C, window=WINDOW, horizon=3200,
        segment_len=1024, eta=0.03, prefetch=2,
    )
    ref = run_stream(
        api.policy_def("ogb"), trace, N, C, window=WINDOW, horizon=3200,
        segment_len=1024, eta=0.03, prefetch=0,
    )
    np.testing.assert_array_equal(sr.hits, ref.hits)
    np.testing.assert_array_equal(sr.reward, ref.reward)
    assert sr.ingest_seconds > 0.05  # the four sleeps landed on the clock


def test_validation_error_is_not_wrapped():
    """Out-of-range ids are a caller bug, not a source fault: the async
    path must surface the same ValueError the sync path raises."""
    trace = zipf(N, 2000, seed=2)
    bad = trace.copy()
    bad[777] = N + 500
    for prefetch in (0, 2):
        with pytest.raises(ValueError, match=r"dense in \[0"):
            run_stream(
                api.policy_def("lru"), bad, N, C, window=WINDOW,
                horizon=2000, prefetch=prefetch,
            )


def test_timing_split_components():
    """wall_seconds stays the total; the component clocks are populated
    and non-negative in both modes."""
    trace = zipf(N, T, alpha=0.9, seed=5)
    for prefetch in (0, 2):
        sr = run_stream(
            api.policy_def("lfu"), _ragged(trace), N, C, window=WINDOW,
            horizon=T, segment_len=2048, opt_window=640, prefetch=prefetch,
        )
        assert sr.wall_seconds > 0
        assert sr.ingest_seconds >= 0 and sr.host_seconds >= 0
        if prefetch == 0:
            # synchronous: the components fit inside the wall clock
            total = sr.ingest_seconds + sr.host_seconds
            assert total <= sr.wall_seconds + 0.05


def test_dyn_opt_tail_flush_covers_every_replayed_request():
    """Regression (the residual dynamic-OPT buffer was dropped): windows
    now cover all t_used requests, the final shorter window included."""
    t = 5000  # 312 windows of 16 + 8 dropped; opt_window 704 leaves a tail
    trace = zipf(N, t, alpha=0.8, seed=9)
    sr = run_stream(
        api.policy_def("lfu"), trace, N, C, window=WINDOW, horizon=t,
        opt_window=704, segment_len=1024,
    )
    assert sr.t_dropped == t % WINDOW
    lens = sr.dyn_opt_lens
    assert int(lens.sum()) == sr.T  # full coverage, nothing discarded
    assert (lens[:-1] == sr.dyn_opt_window).all()
    assert 0 < lens[-1] <= sr.dyn_opt_window
    # each window (the partial tail included) is exactly the hindsight
    # static OPT of its own slice
    edges = np.concatenate([[0], np.cumsum(lens)])
    for k in range(len(lens)):
        blk = trace[edges[k] : edges[k + 1]]
        assert sr.dyn_opt_hits[k] == float(best_static_hits(blk, C))
    # dynamic_regret now compares over the whole replayed prefix
    assert sr.dynamic_regret == pytest.approx(
        sr.dynamic_opt_total - float(sr.reward.sum())
    )
    np.testing.assert_allclose(
        sr.dyn_opt_ratio(), sr.dyn_opt_hits / lens
    )


def test_opt_window_longer_than_stream_still_covered():
    """opt_window > T used to yield an empty comparator; now the whole
    (short) stream is one flushed window."""
    t = 1600
    trace = zipf(N, t, alpha=0.9, seed=21)
    sr = run_stream(
        api.policy_def("fifo"), trace, N, C, window=WINDOW, horizon=t,
        opt_window=10 * t,
    )
    assert len(sr.dyn_opt_hits) == 1
    assert sr.dyn_opt_hits[0] == float(best_static_hits(trace, C))
    assert int(sr.dyn_opt_lens.sum()) == sr.T


def test_fault_partial_preserves_dyn_opt_coverage():
    """The drained partial result's dynamic-OPT windows cover its own
    replayed prefix (the flush also runs on the fault path)."""
    trace = zipf(N, T, alpha=0.9, seed=15)

    def faulty():
        yield trace[:3000]
        raise RuntimeError("gone")

    with pytest.raises(StreamFault) as ei:
        run_stream(
            api.policy_def("lru"), faulty(), N, C, window=WINDOW,
            horizon=T, segment_len=1024, opt_window=704, prefetch=2,
        )
    partial = ei.value.partial
    assert partial is not None
    assert int(partial.dyn_opt_lens.sum()) == partial.T


def test_prefetch_env_default(monkeypatch):
    """REPRO_STREAM_PREFETCH is the process-wide fallback knob."""
    trace = zipf(N, 2000, seed=4)
    monkeypatch.setenv("REPRO_STREAM_PREFETCH", "0")
    sr = run_stream(
        api.policy_def("lru"), trace, N, C, window=WINDOW, horizon=2000
    )
    assert sr.prefetch == 0
    monkeypatch.setenv("REPRO_STREAM_PREFETCH", "3")
    sr = run_stream(
        api.policy_def("lru"), trace, N, C, window=WINDOW, horizon=2000
    )
    assert sr.prefetch == 3
