"""chip_smoke.py at tiny sizes on the CPU: every phase's path and checks.

The script itself refuses to run without a TPU; these tests call its phase
functions directly, and run the ``--chips 4`` fleet phase in a child
process on four virtual CPU devices.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_replay_phase(smoke):
    r = smoke.phase_replay(n=2_000, c=100, t=100_000, window=200,
                           lru_host_t=10_000, lru_dense_t=20_000,
                           oracle_windows=20)
    assert 0.0 < r["lru"] < r["ogb"] < r["opt"] < 1.0


def test_catalog_phase(smoke):
    r = smoke.phase_catalog(n=20_000, c=1_000, t=40_000, dense_t=20_000,
                            window=100)
    assert r["gap"] <= smoke.TREE_TOL


def test_stream_phase(smoke):
    r = smoke.phase_stream(n=2_000, c=100, t=30_050, window=100,
                           opt_window=5_000, profile_t=5_000)
    assert len(r["hits"]) == 300


def test_serve_phase(smoke):
    r = smoke.phase_serve(n=2_000, c=100, window=100, requests=60,
                          layers=2, experts=8)
    assert r["expert_cache"].requests == r["stream_window"].requests == 60


def test_check_failure_raises(smoke):
    with pytest.raises(smoke.CheckFailed, match="what failed"):
        smoke.check(False, "what failed")


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_script_alone_fails(tmp_path):
    """Copied away from the repository, the script fails and prints no
    result."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_fleet_phase_four_virtual_devices():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, json, sys
import jax
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
v = smoke.phase_fleet(jax.devices(), tenants=8, n=512, c=32, window=50,
                      windows=4)
print(json.dumps({k: v[k] for k in
                  ("ogb_carry_bytes_per_device", "lru_hit_ratio")}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code, SCRIPT], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0].startswith("fleet devices=4 tenants=8 ")
    v = json.loads(lines[-1])
    held = np.asarray(v["ogb_carry_bytes_per_device"])
    assert len(held) == 4 and held.min() == held.max() > 0
    assert 0.0 < v["lru_hit_ratio"] < 1.0
