"""The committed four-chip fleet cell, ``cdn_ogb_fleet_2048.fleet``, on the
CPU at a tiny size, its reference, and its roofline reader.

``fleet_root`` is ``tiny_root`` with the committed cell cut further, as
that fixture cuts every configuration: 4 tenants (checked: ``[0, 3]``),
a segment of 8 windows per tenant, one chip, and the committed mix and
reference in place; ``fleet_step_roofline`` is left out there, as
``dense_step_roofline`` is, since the CPU has no entry in the table of
peaks.
"""

from __future__ import annotations

import dataclasses
import json
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import ml_dtypes
import numpy as np
import pytest
from test_bench_harness import _execute, _fault

from bench import check, drivers, harness, traffic
from repro import policy_def, run_fleet

REPO = Path(__file__).resolve().parents[2]
CELL = "cdn_ogb_fleet_2048.fleet"
CONFIG = "cdn_ogb_fleet_2048"


@pytest.fixture
def fleet_root(tiny_root):
    man = json.loads((tiny_root / "BENCHMARK.json").read_text())
    harness.find(man["workloads"], CELL, "workload")["chips"] = 1
    man["per_layer"] = [m for m in man["per_layer"] if m["name"] != "fleet_step_roofline"]
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(man))
    path = tiny_root / harness.find(man["configs"], CONFIG, "config")["file"]
    cfg = json.loads(path.read_text())
    cfg.update(tenants=4, segment=8 * cfg["window"], checked_tenants=[0, 3])
    path.write_text(json.dumps(cfg))
    for part in ("traffic/fleet.json", "reference/ogb_fleet.py"):
        shutil.copy(REPO / "bench" / part, tiny_root / "bench" / part)
    return tiny_root


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_committed_fleet_cell_runs_and_is_correct(fleet_root, trace):
    res, _, err = _execute(fleet_root, CELL, trace)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    man = json.loads((fleet_root / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    assert set(res["metrics"]) == {m["name"] for m in harness.metrics_of(man, CELL, kind)}
    assert all(v["value"] > 0 or "idle" in k for k, v in res["metrics"].items())
    if not trace:
        assert "setup_s" in res["metrics"]
    # 2 checked tenants x 2 set-up segments of 8 windows
    assert "reference: 32 windows" in err


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_a_broken_fleet_step_is_not_correct(fleet_root, monkeypatch, fault):
    real = drivers.policy_def

    def broken(kind, **kw):
        pd = real(kind, **kw)
        return dataclasses.replace(pd, step=_fault(fault)(pd.step))

    monkeypatch.setattr(drivers, "policy_def", broken)
    res, _, _ = _execute(fleet_root, CELL)
    assert res["correct"] is False, res["checks"]


def test_committed_fleet_checks_the_ends_of_each_chip_slice(fleet_root):
    fleet = harness.load_mode("fleet", REPO)
    cfg = json.loads((REPO / "bench" / "configs" / f"{CONFIG}.json").read_text())
    cell = harness.find(harness.load_manifest(REPO)["workloads"], CELL, "workload")
    assert cfg["checked_tenants"] == fleet.default_checked(cfg["tenants"], cell["chips"])
    _, tiny, _ = harness.cell_files(harness.load_manifest(fleet_root), CELL, fleet_root)
    assert tiny["checked_tenants"] == fleet.default_checked(tiny["tenants"], 1) == [0, 3]


def test_manifest_holds_the_fleet_cell_on_four_chips():
    man = harness.load_manifest(REPO)
    cells = man["workloads"]
    assert all(w["chips"] in (1, 4) for w in cells)
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert four == [CELL] and len(four) <= max(1, len(cells) // 2)
    _, cfg, mix = harness.cell_files(man, CELL, REPO)
    assert mix["mode"] == "fleet" and cfg["name"] == CONFIG
    assert harness.find(man["configs"], CONFIG, "config")["reduced"] == []
    assert (REPO / "bench" / "reference" / f"{cfg['reference']}.py").is_file()
    # each tenant is the single cache of cdn_ogb_1e6, held to the same limits
    single = json.loads((REPO / "bench" / "configs" / "cdn_ogb_1e6.json").read_text())
    for key in ("policy", "catalog_size", "capacity", "window", "horizon", "dtype", "sampling",
                "occupancy_quantile", "limits"):
        assert cfg[key] == single[key], key
    assert {m["name"] for m in harness.metrics_of(man, CELL, "end_to_end")} == {
        "requests_per_s", "setup_s"}
    per_layer = harness.metrics_of(man, CELL, "per_layer")
    assert {m["name"] for m in per_layer} == {
        "device_idle_share.replay", "device_us_per_window", "api_host_ms_per_call.replay",
        "dense_project_us_per_window", "fleet_upload_ms_per_call",
        "fleet_readback_ms_per_call", "fleet_step_roofline"}
    for m in per_layer:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] == "requests_per_s"


@pytest.fixture(scope="module")
def readings():
    """The fleet at a tenth of the catalog, 4 tenants, two resumed calls of
    40 windows each, against the float64 reference and its bfloat16
    control on the checked tenants."""
    cfg = json.loads((REPO / "bench" / "configs" / f"{CONFIG}.json").read_text())
    cfg.update(catalog_size=cfg["catalog_size"] // 10, capacity=cfg["capacity"] // 10,
               tenants=4, checked_tenants=[0, 3])
    ref = harness.load_module(REPO / "bench" / "reference" / f"{cfg['reference']}.py",
                              f"bench.reference.{cfg['reference']}")
    rng, policy_seed = traffic.seeds(2**31 + 103)
    n, c, b, e = cfg["catalog_size"], cfg["capacity"], cfg["window"], cfg["tenants"]
    cdf = traffic.zipf_cdf(n, 0.9)
    ids = np.stack([traffic.zipf_ids(cdf, 80 * b, g) for g in rng.spawn(e)])
    pd = policy_def(cfg["policy"])
    eta = pd.default_eta(n, c, cfg["horizon"], b)
    first = run_fleet(pd, ids[:, :40 * b], n, [c] * e, window=b,
                      seeds=[(policy_seed + t) % 2**31 for t in range(e)], etas=[eta] * e,
                      track_opt=False)
    second = run_fleet(pd, ids[:, 40 * b:], window=b, carry=first.carry, track_opt=False)
    kept = cfg["checked_tenants"]
    got = {k: np.concatenate([getattr(first, k), getattr(second, k)], axis=1)[kept].reshape(-1)
           for k in ("reward", "hits", "occupancy")}
    windows = ids[kept].reshape(-1, b)
    want = ref.replay(windows, cfg, policy_seed)
    low = ref.replay(windows, cfg, policy_seed, dtype=ml_dtypes.bfloat16)
    return cfg, got, want, low


def _verdict(got, want, cfg):
    numbers = check.compare(got, want, cfg)
    numbers["window_compiles"] = 0.0
    return check.judge(numbers, cfg["limits"])


def test_fleet_passes_every_limit_against_float64(readings):
    cfg, got, want, _ = readings
    verdict = _verdict(got, want, cfg)
    assert verdict["correct"], verdict["checks"]


def test_fleet_bfloat16_control_fails_a_limit(readings):
    cfg, _, want, low = readings
    verdict = _verdict(low, want, cfg)
    assert not verdict["correct"], verdict["checks"]


def _roofline_ctx(busy_s, windows, chips):
    return {"trace": {"busy_s": busy_s}, "stats": NS(windows=windows),
            "cfg": {"catalog_size": 1_000_000, "window": 1000}, "cell": {"chips": chips},
            "device_kind": "TPU v5 lite"}


def test_fleet_step_roofline_spreads_the_peak_over_the_cells_chips():
    fleet = harness.load_module(REPO / "bench" / "metrics" / "fleet_step_roofline.py",
                                "bench_metric_fleet_step_roofline")
    dense = harness.load_module(REPO / "bench" / "metrics" / "dense_step_roofline.py",
                                "bench_metric_dense_step_roofline")
    one = _roofline_ctx(2.8, 61_440, 1)
    assert fleet.read(one) == pytest.approx(dense.read(one), rel=1e-12)
    # 8.004e6 B at 819e9 B/s is 9.77 us of least time a tenant-window
    assert fleet.read(one) == pytest.approx(100 * 61_440 * 8.004e6 / 819e9 / 2.8, rel=1e-12)
    assert fleet.read(_roofline_ctx(2.8, 61_440, 4)) == pytest.approx(fleet.read(one) / 4,
                                                                      rel=1e-12)
    assert fleet.read(_roofline_ctx(0.0, 61_440, 4)) is None
    assert fleet.read(_roofline_ctx(2.8, 0, 4)) is None
