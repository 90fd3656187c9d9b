"""The reduction of the program's own spans and named scopes.

On planes and HLO text built by hand, where every number can be worked
out, and on traces recorded on the CPU under ``data/``: ``tree_cpu``,
``dense_cpu`` and ``stream_cpu`` are ``--trace 1`` runs of the three
cells at a catalog of 1e4, capacity 500 and segments of two windows (the
stream in chunks of 1500 ids, its dynamic OPT every 4000), each beside the
HLO text of the executable it ran (``.hlo.json.gz``) and the windows it
replayed (``.json``).
"""

from __future__ import annotations

import gzip
import json
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import jax

from bench import harness, program_trace, trace_reduce

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
RECORDED = ["tree_cpu", "dense_cpu", "stream_cpu"]
NEW_METRICS = ["run_upload_ms_per_call.replay", "run_readback_ms_per_call.replay",
               "ogb_tree_solve_us_per_window", "ogb_tree_update_us_per_window",
               "dense_project_us_per_window", "stream_ingest_wait_share"]


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur), stats=[])


def _planes():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            _ev("bench.run_call", 0, 100),
            _ev("repro.run", 5, 90),
            _ev("repro.run.upload", 5, 20),
            _ev("repro.run.wait", 30, 50),
            _ev("repro.run.readback", 80, 10),
            _ev("bench.run_call", 150, 50),
            _ev("repro.run", 150, 50),
        ]),
        NS(name="ingest", events=[
            _ev("repro.stream.validate", 10, 10),  # begun after upload: owns 10-20
            _ev("repro.stream.source", 120, 20),
        ]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            _ev("%while.1 = (s32[], f32[]) while(%t.1), body=%body.2", 30, 50),
            _ev("%fusion.1 = f32[] fusion(%p.1), kind=kLoop", 30, 20),
            _ev("fusion.2", 50, 20),
            _ev("fusion.3", 85, 10),
        ]),
    ])
    return [host, dev]


HLO = """HloModule jit_run_fn, entry_computation_layout={(f32[])->f32[]}

%body.2 (p.1: (s32[], f32[])) -> (s32[], f32[]) {
  %p.1 = (s32[], f32[]) parameter(0)
  %fusion.1 = f32[] fusion(%p.1), kind=kLoop, calls=%fc.1, metadata={op_name="jit(run_fn)/while/body/ogb_tree/solve/while/body/mul"}
  %fusion.2 = f32[] fusion(%fusion.1), kind=kLoop, calls=%fc.2
  ROOT %copy.3 = f32[] copy(%p.1)
}

ENTRY %main.9 (a.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %while.1 = (s32[], f32[]) while(%t.1), condition=%cond.1, body=%body.2, metadata={op_name="jit(run_fn)/while/body/ogb_tree/solve/while"}
  ROOT %fusion.3 = f32[] fusion(%while.1), kind=kLoop, calls=%fc.3, metadata={op_name="jit(run_fn)/while/body/add"}
}
"""


def test_hlo_text_gives_each_instruction_its_scope():
    scopes = program_trace.hlo_scopes(HLO)
    assert scopes["fusion.1"] == "ogb_tree/solve"  # its own op_name
    assert scopes["fusion.2"] == "ogb_tree/solve"  # none: its operand's
    assert scopes["copy.3"] == "ogb_tree/solve"  # none anywhere: its while's
    assert scopes["while.1"] == "ogb_tree/solve"
    assert scopes["fusion.3"] is None  # named, but under no engine scope
    assert program_trace.scope_of("jit(f)/cond/branch_1_fun/ogb_tree/reanchor/add") \
        == "ogb_tree/reanchor"
    assert program_trace.scope_of("jit(f)/while/body/ogb/project/while/mul") == "ogb/project"
    assert program_trace.scope_of("jit(f)/while/body/ogb") is None


def test_maps_of_other_executables_are_not_mixed_in():
    tree = {"fusion.1": "ogb_tree/solve", "fusion.9": "ogb_tree/update"}
    dense = {"fusion.1": "ogb/project"}
    assert program_trace._pick([tree, dense], {"fusion.1", "fusion.9"}) == tree
    # a name two equally good maps place apart gets no scope
    assert program_trace._pick([tree, dense], {"fusion.1"})["fusion.1"] is None
    assert program_trace._pick([], {"fusion.1"}) == {}


def test_spans_idle_and_scopes_are_worked_out():
    red = program_trace.reduce_planes(_planes(), [program_trace.hlo_scopes(HLO)])
    spans = red["program_spans"]
    run = spans["repro.run"]
    assert run["count"] == 2
    assert run["seconds"] == pytest.approx(140e-9)
    # 5-95 less its children (20 + 50 + 10), and 150-200 whole
    assert run["self_s"] == pytest.approx(60e-9)
    # busy 30-80 and 85-95
    assert run["busy_s"] == pytest.approx(60e-9)
    assert spans["repro.run.wait"]["busy_s"] == pytest.approx(50e-9)
    assert spans["repro.run.readback"]["busy_s"] == pytest.approx(5e-9)
    assert spans["repro.run.upload"]["self_s"] == pytest.approx(20e-9)
    assert spans["repro.stream.source"]["seconds"] == pytest.approx(20e-9)

    idle = dict(red["idle_by_program_span"])
    assert idle == pytest.approx({
        program_trace.NO_PROGRAM_SPAN: 40e-9,  # 0-5, 95-120, 140-150
        "repro.run.upload": 10e-9,  # 5-10 and 20-25
        "repro.stream.validate": 10e-9,  # 10-20, begun last
        "repro.run": 55e-9,  # 25-30 and 150-200
        "repro.run.readback": 5e-9,  # 80-85
        "repro.stream.source": 20e-9,
    })
    base = trace_reduce.reduce_planes(_planes())
    assert sum(idle.values()) == pytest.approx(base["window_s"] - base["busy_s"])

    # the while enclosing fusion.1 and fusion.2 is counted once, as them
    assert red["scopes"] == pytest.approx({"ogb_tree/solve": 40e-9,
                                           program_trace.NO_SCOPE: 10e-9})


def test_without_hlo_text_every_op_is_unscoped():
    red = program_trace.reduce_planes(_planes())
    assert red["scopes"] == pytest.approx({program_trace.NO_SCOPE: 50e-9})


def _recorded(name):
    with gzip.open(DATA / f"{name}.xplane.pb.gz", "rb") as f:
        raw = f.read()
    with gzip.open(DATA / f"{name}.hlo.json.gz", "rt") as f:
        texts = json.load(f)
    return raw, texts, json.loads((DATA / f"{name}.json").read_text())


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_step_time_sits_under_named_scopes(name):
    raw, texts, _ = _recorded(name)
    planes = list(jax.profiler.ProfileData.from_serialized_xspace(raw).planes)
    red = program_trace.reduce_planes(planes, [program_trace.hlo_scopes(t) for t in texts])
    scopes = red["scopes"]
    named = sum(v for k, v in scopes.items() if k != program_trace.NO_SCOPE)
    assert named >= 0.9 * sum(scopes.values())
    root = "ogb_tree/" if name == "tree_cpu" else "ogb/"
    assert all(k.startswith(root) for k in scopes if k != program_trace.NO_SCOPE)
    base = trace_reduce.reduce_planes(planes)
    idle = sum(v for _, v in red["idle_by_program_span"])
    assert idle == pytest.approx(base["window_s"] - base["busy_s"], rel=1e-9)
    assert red["program_spans"]


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_traces_give_every_new_metric(tmp_path, monkeypatch, name):
    raw, texts, meta = _recorded(name)
    cell = meta["cell"]
    trace_dir = tmp_path / ".bench_out" / "trace" / cell
    trace_dir.mkdir(parents=True)
    (trace_dir / "run.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(program_trace, "program_hlo", lambda: texts)
    shutil.copytree(REPO / "bench" / "metrics", tmp_path / "bench" / "metrics")
    ctx = {"trace": trace_reduce.reduce_dir(trace_dir), "cell": {"name": cell},
           "stats": NS(windows=meta["windows"])}
    man = harness.load_manifest(REPO)
    wanted = [m["name"] for m in harness.metrics_of(man, cell, "per_layer")
              if m["name"] in NEW_METRICS]
    assert wanted
    for metric in wanted:
        reader = harness.load_module(tmp_path / "bench" / "metrics" / f"{metric}.py",
                                     f"bench_metric_{metric}")
        value = reader.read(ctx)
        assert value is not None and value > 0, metric
    assert json.loads((trace_dir / "program_trace.json").read_text())["scopes"]
    assert reader.read({**ctx, "trace": None}) is None


def test_a_program_without_spans_or_hlo_text_reads_as_no_metric(tmp_path, monkeypatch):
    """The trace of a program that writes no ``repro.*`` span and gives no
    HLO text (``serve_cpu`` was recorded so) yields no new metric, and no
    error."""
    cell = "cdn_ogb_1e6.zipf"
    trace_dir = tmp_path / ".bench_out" / "trace" / cell
    trace_dir.mkdir(parents=True)
    shutil.copy(DATA / "serve_cpu.xplane.pb", trace_dir)
    from repro.cachesim import api

    monkeypatch.delattr(api, "cached_executable_texts")
    assert program_trace.program_hlo() == []
    shutil.copytree(REPO / "bench" / "metrics", tmp_path / "bench" / "metrics")
    ctx = {"trace": trace_reduce.reduce_dir(trace_dir), "cell": {"name": cell},
           "stats": NS(windows=10)}
    for metric in NEW_METRICS:
        reader = harness.load_module(tmp_path / "bench" / "metrics" / f"{metric}.py",
                                     f"bench_metric_{metric}")
        assert reader.read(ctx) is None, metric
