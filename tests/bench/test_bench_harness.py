"""The benchmark harness end to end on the CPU at tiny sizes.

Every cell runs through ``bench.harness.execute`` (the whole of a run but
the look for a chip), with the profiler off and on; the entry script
refuses to run without an accelerator or without the program; a new
configuration, mix and metric are found by name as new files; and a run
whose timed path is broken underneath comes out not correct.
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import drivers, harness

REPO = Path(__file__).resolve().parents[2]
CELLS = ["cdn_ogb_tree_1e6.zipf", "cdn_ogb_1e6.zipf", "cdn_ogb_1e6.serve", "cdn_ogb_1e6.stream"]


def _execute(root, workload, trace=False, seconds=0.3, seed=2**31 + 17):
    out, err = io.StringIO(), io.StringIO()
    res = harness.execute(workload, seed, seconds, trace, t_start=time.perf_counter(),
                          root=root, out=out, err=err)
    return res, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_runs_and_is_correct(tiny_root, workload, trace):
    res, out, err = _execute(tiny_root, workload, trace)
    line = json.loads(out.strip().splitlines()[-1])
    assert line == json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    man = json.loads((tiny_root / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in harness.metrics_of(man, workload, kind)}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 or "idle" in k for k, v in line["metrics"].items())
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert "setup_s" in line["metrics"]
    # the numbers compared are the last lines of standard error
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1] for t in tail] == list(line["checks"])


def test_runs_of_one_seed_offer_the_same_traffic(tiny_root):
    a = drivers.traffic.seeds(2**31 + 5)
    b = drivers.traffic.seeds(2**31 + 5)
    assert a[1] == b[1]
    assert np.array_equal(a[0].random(8), b[0].random(8))
    assert drivers.traffic.seeds(2**31 + 6)[1] != a[1]
    g1 = drivers.traffic.exp_gaps(1000, 200.0, drivers.traffic.seeds(1)[0])
    g2 = drivers.traffic.exp_gaps(1000, 200.0, drivers.traffic.seeds(2)[0])
    assert not np.array_equal(g1, g2)
    assert np.allclose(np.sort(g1), np.sort(g2)) and abs(g1.sum() - 5.0) < 0.01


def _run_script(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cdn_ogb_1e6.zipf", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_without_an_accelerator_fails_and_prints_no_result():
    r = _run_script(REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_script(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_found_by_name(tiny_root):
    before = _digest(tiny_root / "bench")
    cfg = json.loads((tiny_root / "bench/configs/cdn_ogb_1e6.json").read_text())
    cfg.update(name="small_ogb", catalog_size=5000, capacity=250)
    (tiny_root / "bench/configs/small_ogb.json").write_text(json.dumps(cfg))
    (tiny_root / "bench/traffic/flat.json").write_text(json.dumps(
        {"mode": "replay", "alpha": 0.5, "ring_segments": 3, "setup_segments": 1}))
    (tiny_root / "bench/metrics/windows_replayed.py").write_text(
        "def read(ctx):\n    return ctx['stats'].windows\n")
    man = json.loads((tiny_root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "small_ogb", "source": "test", "reduced": [], "why": "test",
                           "file": "bench/configs/small_ogb.json"})
    man["workloads"].append({"name": "small_ogb.flat", "config": "small_ogb",
                             "traffic": "flat", "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "windows_replayed", "unit": "windows", "better": "higher",
                              "bound": 0.1, "source": "host_clock",
                              "workloads": ["small_ogb.flat"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(man))
    res, _, _ = _execute(tiny_root, "small_ogb.flat")
    assert res["correct"] is True
    assert res["metrics"]["windows_replayed"]["value"] > 0
    assert "requests_per_s" not in res["metrics"]  # its entry names the cells it is for
    after = _digest(tiny_root / "bench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {"configs/small_ogb.json", "traffic/flat.json",
                                        "metrics/windows_replayed.py"}


def _fault(name):
    """A step broken underneath the timed path, the way a wrong change
    would break it."""

    def wrap(orig):
        def step(carry, ids):
            if name == "state_unchanged":
                _, out = orig(carry, ids)
                return carry, out
            if name == "half_batch":
                new, out = orig(carry, ids[: ids.shape[0] // 2])
                return new, out._replace(reward=out.reward * 2, hits=out.hits * 2)
            new, out = orig(carry, ids)
            return new, out._replace(hits=out.hits + 1)

        return step

    return wrap


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload, fault):
    real = drivers.policy_def

    def broken(kind, **kw):
        pd = real(kind, **kw)
        return dataclasses.replace(pd, step=_fault(fault)(pd.step))

    monkeypatch.setattr(drivers, "policy_def", broken)
    res, _, _ = _execute(tiny_root, workload)
    assert res["correct"] is False, res["checks"]


def test_a_compile_inside_the_window_is_not_correct(tiny_root, monkeypatch):
    real = drivers.Replay.window

    def window(self, seconds):
        import jax
        import jax.numpy as jnp

        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
        return real(self, seconds)

    monkeypatch.setattr(drivers.Replay, "window", window)
    res, _, _ = _execute(tiny_root, "cdn_ogb_1e6.zipf")
    assert res["correct"] is False
    assert res["checks"]["window_compiles"]["value"] >= 1
