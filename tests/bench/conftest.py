"""A copy of the benchmark at tiny sizes, for the CPU.

``tiny_root`` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
directory and cuts every configuration's catalog and capacity by 100 and
its segment to 64 windows, so each cell runs end to end in a few seconds.
The roofline metric is left out there: the CPU has no entry in the table
of peaks, and an unknown device is an error by design.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


#: The open-loop serving cell waits for a rate fixed at four fifths of a
#: knee swept on the chip (PERF.md, Open questions); its driver, readers and
#: sweep are kept with the benchmark, so the tests add the cell here.
SERVE_MIX = {"mode": "serve", "alpha": 0.9, "ring_decisions": 512, "setup_decisions": 100,
             "rate_per_s": 200, "drain_s": 30}
SERVE = "cdn_ogb_1e6.serve"


def add_serve_cell(root, man):
    (root / "bench" / "traffic" / "serve.json").write_text(json.dumps(SERVE_MIX))
    man["workloads"].append({"name": SERVE, "config": "cdn_ogb_1e6", "traffic": "serve",
                             "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "decision_p95_ms", "unit": "ms", "better": "lower",
                              "bound": 0.25, "source": "host_clock", "workloads": [SERVE]})
    for name, layer, unit in [("device_idle_share.serve", "device", "%"),
                              ("api_host_ms_per_call.serve", "execution layer", "ms"),
                              ("decision_p50_ms", "serving loop", "ms")]:
        man["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                 "source": "host_clock", "layer": layer,
                                 "moves": "decision_p95_ms", "workloads": [SERVE]})


@pytest.fixture
def tiny_root(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in man["configs"]:
        path = root / entry["file"]
        cfg = json.loads(path.read_text())
        cfg.update(catalog_size=cfg["catalog_size"] // 100,
                   capacity=cfg["capacity"] // 100, segment=64 * cfg["window"])
        path.write_text(json.dumps(cfg))
    man["per_layer"] = [m for m in man["per_layer"] if m["name"] != "dense_step_roofline"]
    add_serve_cell(root, man)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root
