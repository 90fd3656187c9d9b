"""Property-based capped-simplex invariants (hypothesis).

Complements tests/core/test_projection.py with the OGB-shaped instances the
replay engines actually produce: y = f + eta * counts with f feasible.  On
those instances the warm-started bracketed-Newton projection (lo=0,
hi=warm_bracket_hi, tau0 seeded from the previous step) must agree with the
cold bisection and with the float64 oracle — the warm-vs-cold contract every
device path (scan replay, sharded, Pallas) relies on.
"""

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core.projection import (
    capped_simplex_tau,
    capped_simplex_tau_bisect,
    project_capped_simplex,
)
from repro.jaxcache.fractional import (
    capped_simplex_project,
    capped_simplex_project_warm,
    warm_bracket_hi,
)


def _ogb_instance(n, c_frac, eta, seed):
    """A feasible f plus one batched gradient step — the warm-path setting."""
    rng = np.random.default_rng(seed)
    C = max(1, int(round(n * c_frac)))
    f = project_capped_simplex(rng.normal(0.5, 1.0, size=n), C)
    counts = rng.integers(0, 5, size=n).astype(np.float64)
    if counts.sum() == 0:
        counts[rng.integers(0, n)] = 1.0
    return f, counts, C


@given(
    n=st.integers(2, 80),
    c_frac=st.floats(0.05, 0.95),
    eta=st.floats(0.01, 0.8),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=100, deadline=None)
def test_ogb_step_projection_feasible(n, c_frac, eta, seed):
    """Feasibility 0 <= x <= 1, sum x = C on post-gradient-step instances."""
    f, counts, C = _ogb_instance(n, c_frac, eta, seed)
    y = f + eta * counts
    x = project_capped_simplex(y, C)
    assert np.all(x >= -1e-9) and np.all(x <= 1 + 1e-9)
    assert abs(x.sum() - C) < 1e-6


@given(
    n=st.integers(2, 60),
    c_frac=st.floats(0.05, 0.95),
    eta=st.floats(0.01, 0.8),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=60, deadline=None)
def test_ogb_step_projection_idempotent(n, c_frac, eta, seed):
    f, counts, C = _ogb_instance(n, c_frac, eta, seed)
    x = project_capped_simplex(f + eta * counts, C)
    np.testing.assert_allclose(project_capped_simplex(x, C), x, atol=1e-8)


@given(
    n=st.integers(2, 60),
    c_frac=st.floats(0.1, 0.9),
    eta=st.floats(0.01, 0.5),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=60, deadline=None)
def test_warm_tau_in_provable_bracket(n, c_frac, eta, seed):
    """For y = f + eta*counts with f feasible: 0 <= tau <= eta*sum(counts)."""
    f, counts, C = _ogb_instance(n, c_frac, eta, seed)
    tau = capped_simplex_tau(f + eta * counts, C)
    assert tau >= -1e-9
    assert tau <= eta * counts.sum() + 1e-6


@given(
    n=st.integers(4, 60),
    c_frac=st.floats(0.1, 0.9),
    eta=st.floats(0.01, 0.5),
    batch=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_warm_vs_cold_tau_agreement(n, c_frac, eta, batch, seed):
    """Warm bracketed-Newton == cold bisection == float64 oracle.

    Replicates the replay setting the warm path is specified for: two
    consecutive OGB steps, where step 2 is warm-projected with the provable
    per-step bracket [0, eta*B] and tau0 threaded from step 1's threshold.
    """
    rng = np.random.default_rng(seed)
    C = max(1, int(round(n * c_frac)))
    f = project_capped_simplex(rng.normal(0.5, 1.0, size=n), C)
    # step 1 (cold) provides the tau seed
    counts1 = np.bincount(rng.integers(0, n, size=batch), minlength=n).astype(float)
    x1, tau1 = capped_simplex_project(jnp.asarray(f + eta * counts1, jnp.float32), float(C))
    # step 2: warm vs cold on the same instance
    counts = np.bincount(rng.integers(0, n, size=batch), minlength=n).astype(float)
    y64 = np.asarray(x1, np.float64) + eta * counts
    y = jnp.asarray(y64, jnp.float32)
    step_mass = eta * counts.sum()

    x_cold, tau_cold = capped_simplex_project(y, float(C))
    # sweeps=25 runs the warm solver to float32 convergence on arbitrary
    # instances (the 5-sweep default is a speed contract for steady-state
    # replay, covered by tests/cachesim/test_replay.py)
    x_warm, tau_warm = capped_simplex_project_warm(
        y,
        float(C),
        lo=jnp.float32(0.0),
        hi=warm_bracket_hi(step_mass),
        tau0=tau1,
        sweeps=25,
    )
    # the projected POINT is unique even when tau is not (g can be flat at C
    # when no coordinate is interior), so agreement is asserted on x and on
    # the capacity mass that each tau reproduces
    tau_oracle = capped_simplex_tau(y64, C)
    x_oracle = np.clip(y64 - tau_oracle, 0.0, 1.0)
    np.testing.assert_allclose(np.asarray(x_warm), np.asarray(x_cold), atol=1e-4)
    np.testing.assert_allclose(np.asarray(x_warm), x_oracle, atol=1e-4)
    for tau in (float(tau_warm), float(tau_cold)):
        assert abs(np.clip(y64 - tau, 0.0, 1.0).sum() - C) < 1e-3
    # where tau IS unique (interior coordinates exist at the oracle tau),
    # warm and cold must land on the same threshold
    interior = np.sum((x_oracle > 1e-4) & (x_oracle < 1 - 1e-4))
    if interior > 0:
        assert abs(float(tau_warm) - float(tau_cold)) < 1e-4
        assert abs(float(tau_warm) - tau_oracle) < 1e-4
    bis = capped_simplex_tau_bisect(y64, C, iters=80)
    assert abs(np.clip(y64 - bis, 0.0, 1.0).sum() - C) < 1e-9


def test_stub_or_real_hypothesis_importable():
    """The suite must run with either the real package or the vendored stub."""
    import hypothesis

    assert hasattr(hypothesis, "given") and hasattr(hypothesis, "settings")
