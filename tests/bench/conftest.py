"""A copy of the benchmark at tiny sizes, for the CPU.

``tiny_root`` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
directory and cuts every configuration's catalog and capacity by 100 and
its segment to 64 windows, so each cell runs end to end in a few seconds.
The roofline metric is left out there: the CPU has no entry in the table
of peaks, and an unknown device is an error by design.  Two cells that
the manifest does not hold are added there as files: open-loop serving,
and a fleet of four dense OGB tenants checked by a test-local reference.
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


#: A fleet of four dense OGB tenants (mode ``fleet``), each a tenth of
#: ``cdn_ogb_1e6`` cut by ten again, checked tenant by tenant against
#: ``bench/reference/ogb.py`` through ``fleet_reference.py``.
FLEET_MIX = {"mode": "fleet", "alpha": 0.9, "ring_segments": 3, "setup_segments": 2}
FLEET = "tiny_fleet.fleet"


def add_fleet_cell(root, man):
    cfg = json.loads((REPO / "bench" / "configs" / "cdn_ogb_1e6.json").read_text())
    cfg.update(name="tiny_fleet", reference="ogb_fleet", tenants=4, catalog_size=10_000,
               capacity=500, segment=8 * cfg["window"])
    (root / "bench" / "configs" / "tiny_fleet.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "fleet.json").write_text(json.dumps(FLEET_MIX))
    shutil.copy(Path(__file__).with_name("fleet_reference.py"),
                root / "bench" / "reference" / "ogb_fleet.py")
    man["configs"].append({"name": "tiny_fleet", "source": "test", "reduced": [],
                           "why": "test", "file": "bench/configs/tiny_fleet.json"})
    man["workloads"].append({"name": FLEET, "config": "tiny_fleet", "traffic": "fleet",
                             "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("requests_per_s", "device_idle_share.replay", "device_us_per_window",
                         "api_host_ms_per_call.replay"):
            m["workloads"].append(FLEET)


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
    add_fleet_cell(root, man)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root
