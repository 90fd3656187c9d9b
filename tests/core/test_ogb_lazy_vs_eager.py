"""The paper's central correctness claim: the lazy O(log N) projection
(Algorithm 2: f̃ + rho + ordered z) maintains *exactly* the same fractional
state as eagerly projecting after every request.

We drive both representations with identical request sequences (hypothesis-
generated, plus targeted corner-case sequences) and require allclose at every
step.  Both the lazy_init (implicit virgin group) and eager-materialization
modes are covered, and both ordered-store engines (treap / sortedcontainers).
"""

import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.ogb import OGB
from repro.core.projection import project_capped_simplex


def eager_reference(N, C, eta, requests):
    """Materialized per-request gradient + eager projection."""
    f = np.full(N, C / N, dtype=np.float64)
    states = []
    for j in requests:
        y = f.copy()
        y[j] += eta
        f = project_capped_simplex(y, C)
        states.append(f.copy())
    return states


def run_lazy(N, C, eta, requests, lazy_init, store_kind="sorted"):
    ogb = OGB(
        N, C, eta=eta, batch_size=1, lazy_init=lazy_init, store_kind=store_kind
    )
    states = []
    for j in requests:
        ogb.update_probabilities(j)
        states.append(ogb.fractional_vector())
    return ogb, states


@given(
    n=st.integers(3, 30),
    c_frac=st.floats(0.1, 0.9),
    eta_exp=st.floats(-2.5, 0.5),
    seed=st.integers(0, 2**31 - 1),
    lazy=st.booleans(),
)
@settings(max_examples=120, deadline=None)
def test_lazy_equals_eager_random(n, c_frac, eta_exp, seed, lazy):
    C = max(1, min(n - 1, int(round(n * c_frac))))
    eta = 10.0**eta_exp
    rng = np.random.default_rng(seed)
    # zipf-ish skew so some items are requested repeatedly (exercises the
    # one-clip corner case) and others never (exercises zero-pops)
    w = 1.0 / np.arange(1, n + 1) ** 1.2
    reqs = rng.choice(n, size=60, p=w / w.sum())
    ref = eager_reference(n, C, eta, reqs)
    ogb, lazy_states = run_lazy(n, C, eta, reqs, lazy_init=lazy)
    for t, (a, b) in enumerate(zip(lazy_states, ref)):
        np.testing.assert_allclose(
            a, b, atol=1e-8, err_msg=f"diverged at request {t} (item {reqs[t]})"
        )
    ogb.check_invariants()


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_lazy_equals_eager_treap_engine(seed):
    n, C, eta = 12, 4, 0.3
    rng = np.random.default_rng(seed)
    reqs = rng.integers(0, n, size=80)
    ref = eager_reference(n, C, eta, reqs)
    _, states = run_lazy(n, C, eta, reqs, lazy_init=True, store_kind="treap")
    for a, b in zip(states, ref):
        np.testing.assert_allclose(a, b, atol=1e-8)


def test_one_clip_corner_case():
    """Hammer one item until it saturates at 1, then keep requesting it."""
    n, C = 6, 2
    eta = 0.4
    reqs = [0] * 8 + [1, 0, 2, 0, 0]
    ref = eager_reference(n, C, eta, reqs)
    ogb, states = run_lazy(n, C, eta, reqs, lazy_init=True)
    for t, (a, b) in enumerate(zip(states, ref)):
        np.testing.assert_allclose(a, b, atol=1e-8, err_msg=f"t={t}")
    assert ogb.stats.one_clip_events >= 1
    # item 0 must be saturated and the projection must be the identity now
    assert abs(ogb.value(0) - 1.0) < 1e-9


def test_zero_pop_cascade():
    """Tiny capacity + large eta drives many coordinates to zero."""
    n, C, eta = 20, 1, 0.9
    rng = np.random.default_rng(0)
    reqs = rng.integers(0, n, size=50)
    ref = eager_reference(n, C, eta, reqs)
    ogb, states = run_lazy(n, C, eta, reqs, lazy_init=True)
    for t, (a, b) in enumerate(zip(states, ref)):
        np.testing.assert_allclose(a, b, atol=1e-8, err_msg=f"t={t}")
    assert ogb.stats.zero_pops > 0


def test_virgin_group_mass_pop():
    """lazy_init: the untouched group must retire exactly when C/N - rho <= 0."""
    n, C, eta = 1000, 10, 0.5
    reqs = list(np.random.default_rng(1).integers(0, 30, size=200))
    ref = eager_reference(n, C, eta, reqs)
    _, states = run_lazy(n, C, eta, reqs, lazy_init=True)
    np.testing.assert_allclose(states[-1], ref[-1], atol=1e-8)


def test_requested_when_saturated_is_noop():
    n, C, eta = 5, 2, 0.5
    ogb = OGB(n, C, eta=eta, batch_size=1, lazy_init=False)
    for _ in range(10):
        ogb.update_probabilities(3)
    f_before = ogb.fractional_vector()
    ogb.update_probabilities(3)  # saturated: must be a no-op
    np.testing.assert_allclose(ogb.fractional_vector(), f_before, atol=0)


def test_average_zero_pops_bounded():
    """Paper §4.2: on average <= 1 + (N-C)/t coordinates hit zero per request;
    empirically (Fig 9 right) < 0.5 per request on real traces."""
    n, C = 500, 50
    T = 4000
    rng = np.random.default_rng(7)
    w = 1.0 / np.arange(1, n + 1) ** 0.8
    reqs = rng.choice(n, size=T, p=w / w.sum())
    ogb = OGB(n, C, horizon=T, batch_size=1, lazy_init=True)
    for j in reqs:
        ogb.update_probabilities(int(j))
    # zero_pops counts include the one-time virgin-group retirement (N-C-ish)
    assert ogb.stats.zero_pops / T < 1.0 + (n - C) / T + 0.5
