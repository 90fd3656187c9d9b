"""Scan-compiled OGB replay == per-batch reference == float64 eager oracle.

Compiling the trace into one ``lax.scan`` (``api.run`` over
``policy_def("ogb")``) with a warm-started projection must change *nothing*
about the replayed dynamics — every metric must match the per-batch
``ogb_batch_update`` driver and (within float32 tolerance) the exact float64
numpy oracle, on both random and adversarial traces.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cachesim import api
from repro.cachesim.traces import adversarial, zipf
from repro.core.projection import capped_simplex_tau, project_capped_simplex
from repro.core.regret import best_static_hits
from repro.jaxcache.fractional import (
    DEFAULT_WARM_SWEEPS,
    FractionalState,
    capped_simplex_project,
    capped_simplex_project_warm,
    ogb_batch_update,
    ogb_batch_update_warm,
    permanent_random_numbers,
)

N, C, B = 301, 17, 16


def _per_batch_reference(trace, n, c, b, eta, seed=0):
    """The old driver: per-batch dispatch with identical Poisson sampling."""
    state = FractionalState.create(n, c)
    k_p, _ = jax.random.split(jax.random.key(seed))
    p = permanent_random_numbers(k_p, n)
    rewards, hits = [], []
    for i in range(len(trace) // b):
        ids = jnp.asarray(trace[i * b : (i + 1) * b], jnp.int32)
        fi = state.f[ids]
        rewards.append(float(jnp.sum(fi)))
        hits.append(int(jnp.sum(fi >= p[ids])))
        state, _ = ogb_batch_update(state, ids, jnp.float32(eta), c)
    return np.asarray(rewards), np.asarray(hits), np.asarray(state.f)


def _oracle_reference(trace, n, c, b, eta):
    """Float64 eager projection oracle (core/projection.py), batched."""
    f = np.full(n, c / n, dtype=np.float64)
    rewards = []
    for i in range(len(trace) // b):
        ids = trace[i * b : (i + 1) * b]
        rewards.append(f[ids].sum())
        y = f + eta * np.bincount(ids, minlength=n)
        f = project_capped_simplex(y, c)
    return np.asarray(rewards), f


@pytest.mark.parametrize(
    "make_trace",
    [
        lambda: zipf(N, 640, alpha=0.9, seed=3),
        lambda: adversarial(N, 640, seed=4),
    ],
    ids=["zipf", "adversarial"],
)
def test_scan_equals_per_batch_and_oracle(make_trace):
    trace = make_trace()
    eta = 0.03
    m = api.run(api.policy_def("ogb"), trace, N, C, window=B, eta=eta, seed=0)
    final_f = np.asarray(m.carry.f)

    ref_rewards, ref_hits, ref_f = _per_batch_reference(trace, N, C, B, eta)
    np.testing.assert_allclose(m.reward, ref_rewards, atol=1e-3)
    np.testing.assert_array_equal(m.hits, ref_hits)
    np.testing.assert_allclose(final_f, ref_f, atol=5e-6)

    orc_rewards, orc_f = _oracle_reference(trace, N, C, B, eta)
    np.testing.assert_allclose(m.reward, orc_rewards, atol=5e-3)
    np.testing.assert_allclose(final_f, orc_f, atol=5e-5)


def test_warm_tau_equals_cold_bisection():
    """Single-digit warm sweeps must match 50-sweep cold bisection to 1e-6."""
    trace = zipf(N, 800, alpha=0.8, seed=7)
    eta = 0.05
    sweeps = DEFAULT_WARM_SWEEPS
    warm = api.policy_def("ogb", projection="warm", sweeps=sweeps)
    cold = api.policy_def("ogb", projection="bisect")
    m_warm = api.run(warm, trace, N, C, window=B, eta=eta)
    m_cold = api.run(cold, trace, N, C, window=B, eta=eta)
    assert sweeps <= 10
    np.testing.assert_allclose(m_warm.aux, m_cold.aux, atol=1e-6)
    np.testing.assert_allclose(m_warm.reward, m_cold.reward, atol=1e-3)
    # and both match the exact float64 tau step by step
    f = np.full(N, C / N, dtype=np.float64)
    for i, tau_w in enumerate(m_warm.aux):
        y = f + eta * np.bincount(
            trace[i * B : (i + 1) * B], minlength=N
        )
        tau_ref = capped_simplex_tau(y, C)
        assert abs(tau_w - tau_ref) < 2e-5, (i, tau_w, tau_ref)
        f = project_capped_simplex(y, C)


def test_warm_projection_single_call():
    """capped_simplex_project_warm == cold bisection == float64 oracle."""
    rng = np.random.default_rng(11)
    y = rng.normal(0.3, 0.5, size=1024).astype(np.float32)
    cap = 100.0
    f_cold, tau_cold = capped_simplex_project(jnp.asarray(y), cap)
    # a deliberately poor seed still converges inside the provable bracket
    f_warm, tau_warm = capped_simplex_project_warm(
        jnp.asarray(y),
        cap,
        jnp.float32(float(y.min()) - 1.0),
        jnp.float32(float(y.max())),
        jnp.float32(0.0),
        sweeps=8,
    )
    assert abs(float(tau_warm) - float(tau_cold)) < 1e-6
    np.testing.assert_allclose(np.asarray(f_warm), np.asarray(f_cold), atol=2e-6)
    tau_ref = capped_simplex_tau(y.astype(np.float64), cap)
    assert abs(float(tau_warm) - tau_ref) < 2e-5


def test_ogb_batch_update_warm_chains():
    """Chained warm updates track the cold per-batch driver exactly."""
    rng = np.random.default_rng(5)
    s_cold = FractionalState.create(N, C)
    s_warm = FractionalState.create(N, C)
    tau = jnp.float32(0.0)
    eta = jnp.float32(0.04)
    for _ in range(30):
        ids = jnp.asarray(rng.integers(0, N, size=B), jnp.int32)
        s_cold, _ = ogb_batch_update(s_cold, ids, eta, C)
        s_warm, _, tau = ogb_batch_update_warm(s_warm, ids, eta, C, tau)
        np.testing.assert_allclose(
            np.asarray(s_warm.f), np.asarray(s_cold.f), atol=2e-6
        )


def test_opt_and_regret_match_host_reference():
    trace = zipf(N, 960, alpha=1.0, seed=9)
    m = api.run(api.policy_def("ogb"), trace, N, C, window=B, seed=1)
    assert m.opt_hits == best_static_hits(trace[: m.T], C)
    assert m.regret == pytest.approx(m.opt_hits - m.reward.sum())
    # no-regret sanity: the fractional reward is within the paper's bound of
    # OPT for this short horizon (loose check, not the theorem constant)
    assert m.reward.sum() > 0.25 * m.opt_hits


def test_madow_sampling_occupancy_exact():
    trace = zipf(N, 480, alpha=0.9, seed=13)
    pd = api.policy_def("ogb", sample="madow", madow_capacity=C)
    m = api.run(pd, trace, N, C, window=B, seed=2)
    # Madow draws exactly C items each chunk (fp cumsum tolerance +-1)
    assert np.all(np.abs(m.occupancy - C) <= 1)
    assert 0.0 <= m.hit_ratio <= 1.0


def test_windowed_metrics_partition_totals():
    trace = zipf(N, 640, alpha=0.9, seed=17)
    m = api.run(api.policy_def("ogb"), trace, N, C, window=B, seed=3)
    w = m.windowed_hit_ratio(160)
    assert w.shape == (4,)
    np.testing.assert_allclose(
        w.mean(), m.hits.sum() / m.T, atol=1e-12
    )


def test_sharded_warm_matches_unsharded():
    """8 fake XLA devices: warm sharded step == ogb_batch_update + same tau."""
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.jaxcache.fractional import FractionalState, ogb_batch_update
from repro.jaxcache.sharded import make_sharded_step

N, C, B, eta = 256, 32, 64, 0.04
mesh = jax.make_mesh((2, 4), ("data", "model"))
step, f_shard = make_sharded_step(mesh, N, C, B, eta, warm_start=True)
rng = np.random.default_rng(0)
f = jax.device_put(jnp.full((N,), C / N, jnp.float32), f_shard)
state = FractionalState.create(N, C)
tau = jnp.float32(0.0)
for i in range(4):
    ids = jnp.asarray(rng.integers(0, N, size=B), jnp.int32)
    f, reward_sh, tau = step(f, ids, tau)
    state, reward_un = ogb_batch_update(state, ids, jnp.float32(eta), C)
    np.testing.assert_allclose(np.asarray(f), np.asarray(state.f), atol=5e-5)
    np.testing.assert_allclose(float(reward_sh), float(reward_un), atol=1e-3)
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.join(os.path.dirname(__file__), "..", ".."),
    )
    assert "OK" in out.stdout, out.stderr[-3000:]
