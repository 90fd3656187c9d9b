"""The reduction from a profiler trace to busy time, spans and idle gaps.

On planes built by hand, where every number can be worked out, and on
recorded traces of the serving cell under ``data/`` with their numbers
pinned, so that every PR computes them the same way.
"""

from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"
#: traces of the serving cell recorded with ``--trace 1``: ``serve_cpu`` on
#: the CPU backend at a tiny size (the tests' fallback path)
RECORDED = ["serve_cpu"]


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _planes():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            _ev("bench.run_call", 0, 100),
            _ev("other", 5, 10),
            _ev("bench.readback", 100, 20),
            _ev("bench.run_call", 150, 50),
        ]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_run_fn", 10, 180)]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 10, 30),   # 10-40
            _ev("fusion.2", 30, 20),   # 30-50, overlaps the first
            _ev("scatter", 90, 40),    # 90-130, crosses into readback
            _ev("fusion.1", 160, 60),  # 160-220, runs past the window
        ]),
    ])
    return [host, dev]


def test_busy_spans_ops_and_idle_are_worked_out():
    red = trace_reduce.reduce_planes(_planes())
    # window 0-200; busy 10-50, 90-130, 160-200 = 40 + 40 + 40
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(120e-9)
    call = red["spans"]["bench.run_call"]
    assert call["count"] == 2
    assert call["seconds"] == pytest.approx(150e-9)
    # busy in 0-100 is 40 + 10, in 150-200 is 40
    assert call["busy_s"] == pytest.approx(90e-9)
    assert red["spans"]["bench.readback"]["busy_s"] == pytest.approx(20e-9)
    ops = dict(red["top_ops"])
    assert ops["fusion.1"] == pytest.approx(70e-9) and ops["scatter"] == pytest.approx(40e-9)
    idle = dict(red["idle_by_span"])
    assert idle["bench.run_call"] == pytest.approx(60e-9)
    assert "bench.readback" not in idle  # the device was busy all through it
    # 120-150 has no span; the scatter runs in 120-130 of it
    assert idle[trace_reduce.NO_SPAN] == pytest.approx(20e-9)
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"])


def test_a_trace_without_spans_or_ops_is_an_error():
    planes = _planes()
    with pytest.raises(ValueError, match="no host span"):
        trace_reduce.reduce_planes([planes[1]])
    with pytest.raises(ValueError, match="no device"):
        trace_reduce.reduce_planes([planes[0]])


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace_reduces_to_its_pinned_numbers(name):
    red = trace_reduce.reduce_file(DATA / f"{name}.xplane.pb")
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    call = red["spans"]["bench.run_call"]
    assert call["count"] > 10 and 0 < call["busy_s"] < call["seconds"]
    assert sum(v for _, v in red["idle_by_span"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    pinned = (DATA / f"{name}.expected.txt").read_text().split()
    assert [float(x) for x in pinned] == pytest.approx(
        [red["window_s"], red["busy_s"], call["seconds"], call["busy_s"]], rel=1e-12)
