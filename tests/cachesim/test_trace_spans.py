"""The program's own spans and named scopes, read back from CPU traces.

``api.run``, ``run_fleet`` and ``run_stream`` write
``jax.profiler.TraceAnnotation`` spans under ``repro.*`` names; the OGB
engines trace their phases under ``jax.named_scope`` paths
(``ogb_tree/<phase>``, ``ogb/<phase>``) that reach the executables' HLO
``op_name`` metadata.  Each test records a
trace on the CPU and checks the spans it holds, and how they nest.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.cachesim import api
from repro.cachesim.fleet import run_fleet
from repro.cachesim.tracelab import run_stream
from repro.cachesim.traces import zipf

N, C, WINDOW = 400, 20, 50
RUN_CHILDREN = ("repro.run.upload", "repro.run.init", "repro.run.lookup",
                "repro.run.dispatch", "repro.run.wait", "repro.run.readback",
                "repro.run.opt")
FLEET_CHILDREN = ("repro.fleet.upload", "repro.fleet.init", "repro.fleet.dispatch",
                  "repro.fleet.wait", "repro.fleet.readback", "repro.fleet.opt")


def _spans(trace_dir):
    """[(name, start, end, thread line, stats)] of every ``repro.*`` span."""
    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for li, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, (plane.name, li),
                     dict(e.stats))
                    for e in line.events if e.name.startswith("repro.")]
    return out


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _spans(tmp_path)


def _parent(spans, child):
    """The innermost span of the same thread that encloses ``child``."""
    around = [s for s in spans if s is not child and s[3] == child[3]
              and s[1] <= child[1] and child[2] <= s[2]]
    return min(around, key=lambda s: s[2] - s[1])[0] if around else None


def test_run_spans_tile_the_call(tmp_path):
    pd = api.policy_def("ogb_tree")
    trace = zipf(N, 20 * WINDOW + 7, alpha=0.9, seed=1)
    api.clear_executable_cache()
    first = []
    spans = _traced(tmp_path, lambda: first.append(
        api.run(pd, trace, N, C, window=WINDOW)))
    names = [s[0] for s in spans]
    assert names.count("repro.run") == 1
    assert set(names) == {"repro.run", "repro.run.compile", *RUN_CHILDREN}
    (top,) = [s for s in spans if s[0] == "repro.run"]
    assert top[4] == {"windows": 20, "bytes_in": 4 * 20 * WINDOW}
    for s in spans:
        if s[0] in RUN_CHILDREN:
            assert _parent(spans, s) == "repro.run"
    (compile_,) = [s for s in spans if s[0] == "repro.run.compile"]
    assert _parent(spans, compile_) == "repro.run.lookup"

    # a resumed call on a compiled shape neither initializes nor compiles,
    # and tracks no OPT when told not to
    resumed = _traced(tmp_path / "resumed", lambda: api.run(
        pd, trace, capacity=C, carry=first[0].carry, window=WINDOW, track_opt=False))
    assert {s[0] for s in resumed} == {"repro.run"} | (
        set(RUN_CHILDREN) - {"repro.run.init", "repro.run.opt"})


def test_fleet_spans_tile_the_call(tmp_path):
    pd = api.policy_def("ogb")
    tenants, windows = 3, 6
    traces = np.stack([zipf(N, windows * WINDOW + 7, alpha=0.9, seed=10 + e)
                       for e in range(tenants)])
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    api.clear_executable_cache()
    first = []
    fresh = _traced(tmp_path, lambda: first.append(
        run_fleet(pd, traces, N, C, window=WINDOW, mesh=mesh)))
    resumed = _traced(tmp_path / "resumed", lambda: run_fleet(
        pd, traces, window=WINDOW, carry=first[0].carry, track_opt=False, mesh=mesh))
    for spans in (fresh, resumed):
        (top,) = [s for s in spans if s[0] == "repro.fleet"]
        assert top[4] == {"tenants": tenants, "windows": windows,
                          "bytes_in": 4 * tenants * windows * WINDOW}
        for s in spans:
            if s[0] in FLEET_CHILDREN:
                assert _parent(spans, s) == "repro.fleet", s[0]
        # the host stack of the ids, then their copy onto the tenant sharding
        assert [s[0] for s in spans].count("repro.fleet.upload") == 2
    assert {s[0] for s in fresh} == {"repro.fleet", "repro.run.compile", *FLEET_CHILDREN}
    (compile_,) = [s for s in fresh if s[0] == "repro.run.compile"]
    assert _parent(fresh, compile_) == "repro.fleet.dispatch"
    # a resumed call on a compiled shape neither initializes nor compiles,
    # and tracks no OPT when told not to
    assert {s[0] for s in resumed} == {"repro.fleet"} | (
        set(FLEET_CHILDREN) - {"repro.fleet.init", "repro.fleet.opt"})


def test_run_without_blocking_neither_waits_nor_reads_back(tmp_path):
    pd = api.policy_def("lru")
    trace = zipf(N, 10 * WINDOW, alpha=0.9, seed=2)
    res = []
    spans = _traced(tmp_path, lambda: res.append(
        api.run(pd, trace, N, C, window=WINDOW, block=False, track_opt=False)))
    jax.block_until_ready(res[0].hits)
    names = {s[0] for s in spans}
    assert "repro.run.dispatch" in names
    assert not names & {"repro.run.wait", "repro.run.readback", "repro.run.opt"}


@pytest.mark.parametrize("prefetch", [0, 2])
def test_stream_spans_nest_as_stated(tmp_path, prefetch):
    trace = zipf(N, 40 * WINDOW + 13, alpha=0.9, seed=3)
    chunks = (trace[i:i + 333] for i in range(0, len(trace), 333))
    res = []
    spans = _traced(tmp_path, lambda: res.append(run_stream(
        api.policy_def("ogb"), chunks, N, C, window=WINDOW, horizon=len(trace),
        segment_len=7 * WINDOW, opt_window=10 * WINDOW, prefetch=prefetch)))
    segments = res[0].n_segments
    assert segments == 6  # five of 7 windows and a tail of 5
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    (top,) = by["repro.stream"]
    main = top[3]
    for name in ("repro.stream.dyn_opt", "repro.stream.consume", "repro.run"):
        assert by[name] and all(s[3] == main and _parent(spans, s) == "repro.stream"
                                for s in by[name]), name
    assert all(_parent(spans, s) == "repro.stream.consume"
               for s in by["repro.stream.wait_device"])
    assert len(by["repro.stream.wait_device"]) == len(by["repro.stream.consume"]) == segments
    # every segment's api.run call nests its own spans
    assert len(by["repro.run"]) == segments
    assert all(_parent(spans, s) == "repro.run" for s in by["repro.run.dispatch"])
    ingest = {s[3] for s in by["repro.stream.source"] + by["repro.stream.validate"]}
    if prefetch:
        assert all(_parent(spans, s) == "repro.stream" for s in by["repro.stream.queue_wait"])
        assert all("depth" in s[4] for s in by["repro.stream.queue_wait"])
        # the ingest thread assembles segments on a line of its own
        assert main not in ingest
    else:
        assert "repro.stream.queue_wait" not in by
        assert ingest == {main}
        assert all(_parent(spans, s) == "repro.stream"
                   for s in by["repro.stream.source"] + by["repro.stream.validate"])


@pytest.mark.parametrize("kind, scopes", [
    ("ogb_tree", ["metrics", "dedup", "gradient", "update", "solve", "reanchor"]),
    ("ogb", ["sample", "gradient", "project"]),
])
def test_engine_phases_reach_the_hlo_op_names(kind, scopes):
    api.clear_executable_cache()
    api.run(api.policy_def(kind), zipf(N, 4 * WINDOW, seed=4), N, C, window=WINDOW,
            track_opt=False)
    (text,) = api.cached_executable_texts()
    found = set(re.findall(rf'op_name="[^"]*/{kind}/(\w+)[/"]', text))
    assert found == set(scopes)

