"""Differential tests: scan automata == core/policies.py, bit for bit.

The engines' whole value is that compiling LRU/FIFO/LFU/FTPL into
``lax.scan`` slot automata changes *nothing* about the replayed dynamics —
the per-request hit sequence must equal the host policy's exactly (not in
distribution, not within tolerance) on every trace family, and OMD must
match a float64 numpy oracle within float32 headroom.  The dense
fractional chunks (``make_ogb_chunk`` / ``make_omd_chunk``) are pinned
against ``api.run`` bit for bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cachesim import api
from repro.cachesim.engines import (
    DEFAULT_OMD_SWEEPS,
    ENGINE_KINDS,
    MADOW_SAMPLES,
    init_engine_carry,
    make_ogb_chunk,
    make_omd_chunk,
)
from repro.cachesim.traces import adversarial, bursty, zipf
from repro.core.omd import OMDClassic, project_capped_simplex_kl
from repro.core.policies import make_policy
from repro.jaxcache.fractional import DEFAULT_BISECT_ITERS, DEFAULT_WARM_SWEEPS

N, C, T = 311, 23, 6000

TRACES = {
    "zipf": lambda: zipf(N, T, alpha=0.9, seed=3),
    "adversarial": lambda: adversarial(N, T, seed=4),
    "bursty": lambda: bursty(N, T, seed=5),
}


def engine_hit_sequence(kind, trace, n, c, **kw):
    """Per-request hit flags of the device engine (window=1)."""
    res = api.run(
        api.policy_def(kind), trace, n, c, window=1, track_opt=False,
        keep_carry=False, **kw,
    )
    return res.hits.astype(bool)


def _host_hits(kind, trace, n, c, **kw):
    pol = make_policy(kind, n, c, **kw)
    return np.fromiter(
        (pol.request(int(j)) for j in trace), dtype=bool, count=len(trace)
    )


@pytest.mark.parametrize("trace_name", sorted(TRACES))
@pytest.mark.parametrize("kind", ENGINE_KINDS)
def test_exact_hit_sequence_agreement(kind, trace_name):
    """Every automaton replays the exact host-policy hit sequence."""
    trace = TRACES[trace_name]()
    kw = {"horizon": T, "seed": 0} if kind == "ftpl" else {}
    dev = engine_hit_sequence(kind, trace, N, C, **kw)
    host = _host_hits(kind, trace, N, C, **kw)
    np.testing.assert_array_equal(dev, host)


def test_exact_agreement_at_issue_bounds():
    """The acceptance-criterion shape: N = 512, T = 20k, all automata."""
    n, c, t = 512, 31, 20_000
    trace = zipf(n, t, alpha=0.8, seed=11)
    for kind in ENGINE_KINDS:
        kw = {"horizon": t, "seed": 1} if kind == "ftpl" else {}
        dev = engine_hit_sequence(kind, trace, n, c, **kw)
        host = _host_hits(kind, trace, n, c, **kw)
        np.testing.assert_array_equal(dev, host, err_msg=kind)


def test_windowed_hits_partition_sequence():
    """Chunked replay (window > 1) sums the same per-request bits."""
    trace = TRACES["zipf"]()
    seq = engine_hit_sequence("lru", trace, N, C)
    res = api.run(api.policy_def("lru"), trace, N, C, window=500,
                  track_opt=False)
    np.testing.assert_array_equal(
        res.hits, seq[: res.T].reshape(-1, 500).sum(axis=1)
    )
    assert res.occupancy[-1] == C  # zipf fills the cache


def test_lfu_admission_filter_matches_host():
    """Adversarial-for-LFU trace: a hot prefix then a cold scan — the scan
    must be rejected by the admission rule on both sides."""
    hot = np.repeat(np.arange(C), 5)
    scan = np.arange(C, N)
    trace = np.concatenate([hot, scan, hot])
    dev = engine_hit_sequence("lfu", trace, N, C)
    host = _host_hits("lfu", trace, N, C)
    np.testing.assert_array_equal(dev, host)
    # the cold scan got no admissions: the second hot pass hits everything
    assert dev[-len(hot) :].all()


def test_ftpl_noise_grid_identical():
    """Engine and host draw the same float32 noise (the bit-exactness root)."""
    from repro.core.ftpl import FTPL, ftpl_noise

    pol = FTPL(N, C, zeta=2.0, seed=7)
    carry = init_engine_carry("ftpl", N, C, zeta=2.0, seed=7)
    np.testing.assert_array_equal(np.asarray(carry.noise), pol._noise)
    assert set(np.asarray(carry.slots).tolist()) == set(pol.cached)
    assert ftpl_noise(N, 2.0, seed=7).dtype == np.float32


# ---------------------------------------------------------------------------
# OMD vs float64 oracle
# ---------------------------------------------------------------------------
def test_omd_engine_matches_float64_oracle_pointwise():
    """Short horizon (inside float32 headroom): the engine's fractional state
    tracks the exact float64 oracle coordinate by coordinate."""
    B, eta = 16, 0.05
    trace = TRACES["zipf"]()[: 100 * B]
    m = api.run(
        api.policy_def("omd", sample="none"), trace, N, C, window=B, eta=eta
    )
    pol = OMDClassic(N, C, eta=eta, batch_size=B, integral=False)
    for j in trace[: m.T]:
        pol.request(int(j))
    np.testing.assert_allclose(np.asarray(m.carry.f), pol.f, atol=5e-5)
    rewards = np.asarray(m.reward)
    assert abs(rewards.sum() - pol.fractional_reward) < 1e-4 * max(
        pol.fractional_reward, 1.0
    )


def test_omd_per_step_threshold_matches_oracle():
    """Stepping the float64 oracle state: the float32 safeguarded-Newton
    threshold agrees with the exact water-filling lambda at every chunk
    (no compounding — this is the per-step contract)."""
    import jax.numpy as jnp

    from repro.cachesim.engines import _omd_project
    from repro.jaxcache.fractional import warm_bracket_hi

    B, eta = 16, 0.05
    trace = TRACES["zipf"]()
    pol = OMDClassic(N, C, eta=eta, batch_size=B, integral=False)
    for i in range(60):
        ids = trace[i * B : (i + 1) * B]
        pol.w = pol.w + eta * np.bincount(ids, minlength=N)
        f64, lam64 = project_capped_simplex_kl(pol.w, C, return_lam=True)
        lam32 = _omd_project(
            jnp.asarray(pol.w, jnp.float32),
            float(C),
            warm_bracket_hi(eta * B),
            10,
        )
        assert abs(float(lam32) - lam64) < 2e-6, (i, float(lam32), lam64)
        assert 0.0 <= lam64 <= eta * B  # the provable warm bracket
        pol.w -= lam64
        pol.f = f64


def test_omd_long_horizon_aggregates_and_feasibility():
    """Full horizon: float32 trajectories drift pointwise (mirror descent
    amplifies rounding multiplicatively) but the aggregate metrics, simplex
    feasibility and threshold bracket must all hold."""
    trace = TRACES["zipf"]()
    B, eta = 16, 0.05
    m = api.run(
        api.policy_def("omd", sample="none"), trace, N, C, window=B, eta=eta
    )
    pol = OMDClassic(N, C, eta=eta, batch_size=B, integral=False)
    for j in trace[: m.T]:
        pol.request(int(j))
    rewards = np.asarray(m.reward)
    assert abs(rewards.sum() - pol.fractional_reward) < 2e-3 * max(
        pol.fractional_reward, 1.0
    )
    # feasibility: the device state stays on the capped simplex
    final_f = np.asarray(m.carry.f)
    assert abs(float(np.sum(final_f)) - C) < 1e-3
    assert np.all(final_f >= 0) and np.all(final_f <= 1 + 1e-6)
    # the KL thresholds stay in the provable [0, eta*B] bracket
    assert np.all(m.aux >= 0) and np.all(m.aux <= eta * B * (1 + 1e-4) + 1e-6)


def test_kl_projection_oracle_properties():
    rng = np.random.default_rng(2)
    w = rng.normal(-1.0, 2.0, size=400)
    for cap in (1, 17, 399):
        f, lam = project_capped_simplex_kl(w, cap, return_lam=True)
        assert abs(f.sum() - cap) < 1e-9 * max(cap, 1)
        assert np.all(f >= 0) and np.all(f <= 1 + 1e-12)
        # unsaturated coordinates keep the exact exponential-weights ratio
        interior = f < 1.0 - 1e-12
        np.testing.assert_allclose(
            f[interior], np.exp(w[interior] - lam), rtol=1e-10
        )


def test_omd_learns_on_skewed_traffic():
    """Sanity: mirror descent concentrates mass on the hot set."""
    trace = zipf(N, 20_000, alpha=1.2, seed=9)
    m = api.run(api.policy_def("omd", sample="none"), trace, N, C, window=100)
    hot = np.argsort(np.bincount(trace, minlength=N))[-C // 2 :]
    assert np.asarray(m.carry.f)[hot].mean() > 3.0 * (C / N)
    w = m.windowed_frac_ratio(m.T // 4)
    assert w[-1] > w[0]  # the transient moves the right way


# ---------------------------------------------------------------------------
# vmapped sweeps == stacked single replays
# ---------------------------------------------------------------------------
def test_sweep_engine_rows_match_single_runs():
    trace = TRACES["zipf"]()
    caps = [7, 23]
    lru = api.policy_def("lru")
    sw = api.sweep(lru, trace, N, caps, seeds=(0,), window=500, track_opt=True)
    for cap in caps:
        single = api.run(lru, trace, N, cap, window=500, track_opt=False)
        r = sw.row(capacity=cap)
        np.testing.assert_array_equal(sw.hits[r], single.hits)
        np.testing.assert_array_equal(sw.occupancy[r], single.occupancy)
    assert sw.opt_hits[sw.row(capacity=23)] >= sw.opt_hits[sw.row(capacity=7)]


def test_sweep_engine_ftpl_seeds_differ():
    trace = TRACES["zipf"]()
    ftpl = api.policy_def("ftpl")
    sw = api.sweep(ftpl, trace, N, [C], seeds=(0, 1), window=500, horizon=T)
    assert not np.array_equal(
        sw.hits[sw.row(seed=0)], sw.hits[sw.row(seed=1)]
    )
    # and each seed row matches its single replay exactly
    single = api.run(
        ftpl, trace, N, C, window=500, seed=1, horizon=T, track_opt=False
    )
    np.testing.assert_array_equal(sw.hits[sw.row(seed=1)], single.hits)


def test_sweep_replay_grid_matches_single():
    trace = TRACES["zipf"]()
    ogb = api.policy_def("ogb")
    sw = api.sweep(
        ogb, trace, N, [11, 23], etas=[0.03, None], seeds=(0,), window=16
    )
    assert len(sw.combos) == 4
    single = api.run(ogb, trace, N, 23, window=16, eta=0.03, seed=0)
    r = sw.row(capacity=23, eta=0.03)
    np.testing.assert_allclose(sw.reward[r], single.reward, atol=1e-3)
    np.testing.assert_array_equal(sw.hits[r], single.hits)
    assert sw.opt_hits[r] == single.opt_hits
    assert sw.regrets[r] == pytest.approx(single.regret, abs=1e-2)
    # eta=None rows must resolve to run's default tuning, so a
    # default-tuned sweep reproduces default-tuned single replays exactly
    default = api.run(ogb, trace, N, 11, window=16, seed=0)
    r_def = sw.row(capacity=11, eta=default.extras["eta"])
    np.testing.assert_array_equal(sw.hits[r_def], default.hits)
    np.testing.assert_allclose(sw.reward[r_def], default.reward, atol=1e-3)


def test_engine_carry_capacity_padding_inert():
    """Padded (inactive) slots never cache anything: a padded sweep row
    equals the unpadded replay."""
    trace = TRACES["bursty"]()[:5000]
    padded = api.run(
        api.policy_def("lru", impl="dense"), trace, N, 7, window=100,
        n_slots=23, track_opt=False,
    )
    res = api.run(api.policy_def("lru"), trace, N, 7, window=100,
                  track_opt=False)
    np.testing.assert_array_equal(padded.hits, res.hits)
    assert int(np.max(padded.occupancy)) <= 7


# ---------------------------------------------------------------------------
# the dense fractional chunks == api.run, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sample", ["poisson", "madow", "madow_tree", "none"])
@pytest.mark.parametrize("kind", ["ogb", "omd"])
def test_dense_chunk_matches_api_run(kind, sample):
    """Scanning the engine's chunk from the PolicyDef's init carry gives
    api.run's per-window observables exactly: the chunk is the whole step."""
    trace = TRACES["zipf"]()[:640]
    B, eta, seed = 16, 0.05, 3
    madow_capacity = C if sample in MADOW_SAMPLES else None
    opts = {"sample": sample}
    if madow_capacity is not None:
        opts["madow_capacity"] = madow_capacity
    pd = api.policy_def(kind, **opts)
    if kind == "ogb":
        chunk = make_ogb_chunk(sample, "warm", DEFAULT_WARM_SWEEPS,
                               DEFAULT_BISECT_ITERS, madow_capacity)
    else:
        chunk = make_omd_chunk(sample, DEFAULT_OMD_SWEEPS, madow_capacity)
    carry = pd.init(N, C, seed=seed, eta=eta, horizon=len(trace))
    chunks = jnp.asarray(trace.reshape(-1, B), jnp.int32)
    carry, (reward, hits, aux, occ) = jax.jit(
        lambda c, x: jax.lax.scan(chunk, c, x)
    )(carry, chunks)
    res = api.run(pd, trace, N, C, window=B, eta=eta, seed=seed,
                  track_opt=False)
    np.testing.assert_array_equal(np.asarray(reward, np.float64), res.reward)
    np.testing.assert_array_equal(np.asarray(hits, np.int64), res.hits)
    np.testing.assert_array_equal(np.asarray(aux, np.float64), res.aux)
    np.testing.assert_array_equal(np.asarray(occ, np.float64), res.occupancy)
    assert int(carry.t) == len(chunks)
    np.testing.assert_array_equal(np.asarray(carry.f), np.asarray(res.carry.f))
