"""The least-bytes function, the table of peaks, and the manifest's shape."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench import roofline, traffic

REPO = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_dense_step_bytes_counts_the_state_twice_and_the_ids_once():
    assert roofline.dense_step_bytes(1_000_000, 1000) == 8_004_000
    assert roofline.dense_step_bytes(10, 1) == 84


def test_peaks_of_v5e_and_an_unknown_device_is_an_error():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in json.loads(roofline.PEAKS.read_text())["source"]
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("cpu")


def test_manifest_names_files_and_metrics_fit_together():
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    assert man["command"] == ["python3", "bench/run.py"]
    configs = {c["name"]: c for c in man["configs"]}
    metrics = {m["name"]: m for m in man["end_to_end"] + man["per_layer"]}
    cells = [w["name"] for w in man["workloads"]]
    assert len(cells) == len(set(cells)) and len(metrics) == len(man["end_to_end"]) + len(
        man["per_layer"])
    for name in list(configs) + cells + list(metrics):
        assert NAME.match(name), name
    for c in configs.values():
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (REPO / "bench" / "reference" / f"{cfg['reference']}.py").is_file()
        assert set(cfg["limits"]) == {"reward_gap", "hit_count_gap", "occupancy_gap",
                                      "window_compiles"}
    for w in man["workloads"]:
        assert w["config"] in configs and w["chips"] == 1
        traffic.load_mix(REPO / "bench" / "traffic" / f"{w['traffic']}.json")
        reported = [m for m in metrics.values() if w["name"] in m.get("workloads", [w["name"]])]
        kinds = {m["name"] for m in reported}
        assert "setup_s" in kinds and len(kinds & {m["name"] for m in man["end_to_end"]}) >= 2
        assert kinds & {m["name"] for m in man["per_layer"]}
    for m in metrics.values():
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in man["per_layer"]:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in man["end_to_end"]}
        moved = metrics[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for m in man["end_to_end"]:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25


def test_manifest_entries_have_exactly_their_keys_and_short_lines():
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }
    for part, want in keys.items():
        for e in man[part]:
            assert set(e) - {"workloads"} == want, e
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k]
    for c in man["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    for p in man["paths"]:
        assert (REPO / p).is_dir() and re.match(r"^[A-Za-z0-9_./-]{1,200}$", p)
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
