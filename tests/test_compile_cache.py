"""The persistent compilation cache: a second cold process loads the scan."""

import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

CHILD = r"""
import json
import numpy as np
from repro import compile_cache, policy_def, run

cache_dir = compile_cache.enable()
trace = np.random.default_rng(0).integers(0, 500, 4000)
with compile_cache.CompileClock() as clock:
    r = run(policy_def("ogb"), trace, 500, 25, window=100, track_opt=False)
print(json.dumps({"dir": cache_dir, "hits": clock.cache_hits,
                  "seconds": clock.seconds, "hit_ratio": r.hit_ratio}))
"""


def _child(env):
    out = subprocess.run(
        [sys.executable, "-c", CHILD], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_second_process_loads_scan_from_cache(tmp_path):
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(ROOT, "src"),
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
    )
    cold = _child(env)
    warm = _child(env)
    assert cold["dir"] == warm["dir"] == str(tmp_path)
    assert cold["hits"] == 0
    assert warm["hits"] >= 1
    assert os.listdir(tmp_path)
    assert warm["hit_ratio"] == cold["hit_ratio"]


def test_default_dir_is_fixed_in_checkout():
    from repro import compile_cache

    assert compile_cache.DEFAULT_DIR == compile_cache.DEFAULT_DIR.resolve()
    assert str(compile_cache.DEFAULT_DIR) == os.path.join(ROOT, ".jax_cache")
