"""The plain references against the program, and the control.

At a size a test run holds (a tenth of each configuration's catalog, two
segments of 40 windows), on the CPU: the program's windows pass every
limit of its configuration against the float64 reference, and the
control, the same reference computed in bfloat16 in the program's place,
fails at least one.
"""

from __future__ import annotations

import json
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

from bench import check, harness, traffic
from repro import policy_def, run

REPO = Path(__file__).resolve().parents[2]


def _cell(name):
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(catalog_size=cfg["catalog_size"] // 10, capacity=cfg["capacity"] // 10)
    ref = harness.load_module(REPO / "bench" / "reference" / f"{cfg['reference']}.py",
                              f"bench.reference.{cfg['reference']}")
    return cfg, ref


@pytest.fixture(scope="module", params=["cdn_ogb_1e6", "cdn_ogb_tree_1e6"])
def readings(request):
    cfg, ref = _cell(request.param)
    rng, policy_seed = traffic.seeds(2**31 + 101)
    n, c, b = cfg["catalog_size"], cfg["capacity"], cfg["window"]
    ids = traffic.zipf_ids(traffic.zipf_cdf(n, 0.9), 80 * b, rng)
    pd = policy_def(cfg["policy"])
    res = run(pd, ids, n, c, window=b, eta=pd.default_eta(n, c, cfg["horizon"], b),
              seed=policy_seed, track_opt=False)
    got = {"reward": res.reward, "hits": res.hits, "occupancy": res.occupancy}
    want = ref.replay(ids.reshape(-1, b), cfg, policy_seed)
    low = ref.replay(ids.reshape(-1, b), cfg, policy_seed, dtype=ml_dtypes.bfloat16)
    return cfg, got, want, low


def test_program_passes_every_limit_against_float64(readings):
    cfg, got, want, _ = readings
    numbers = check.compare(got, want, cfg)
    numbers["window_compiles"] = 0.0
    verdict = check.judge(numbers, cfg["limits"])
    assert verdict["correct"], verdict["checks"]


def test_bfloat16_control_fails_a_limit(readings):
    cfg, _, want, low = readings
    numbers = check.compare(low, want, cfg)
    numbers["window_compiles"] = 0.0
    verdict = check.judge(numbers, cfg["limits"])
    assert not verdict["correct"], verdict["checks"]


def test_references_use_the_stated_learning_rate_and_table():
    from bench.reference import learning_rate, permanent_numbers

    cfg = {"catalog_size": 1_000_000, "capacity": 50_000, "horizon": 20_000_000}
    assert learning_rate(cfg) == pytest.approx(np.sqrt(5e4 * 0.95 / 2e7), rel=1e-12)
    assert policy_def("ogb").default_eta(10**6, 5 * 10**4, 2 * 10**7, 1000) == pytest.approx(
        learning_rate(cfg), rel=1e-12)
    p = permanent_numbers(7, 1000)
    assert p.dtype == np.float32 and 0 <= p.min() and p.max() < 1
    assert np.array_equal(p, permanent_numbers(7, 1000))
