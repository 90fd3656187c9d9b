"""The unified policy protocol: parity and streaming.

Two contracts lock the api redesign down:

* **Golden parity** — ``api.run`` must reproduce the committed per-scenario
  fixtures for every registered kind, using only the public protocol (no
  ``run_scenario``): exact for the discrete automata, within the usual
  float32 allowance for the fractional engines.
* **Streaming** — two chunked ``run`` calls with a handed-off carry replay
  the same dynamics as one full run, bit for bit, for every kind.
"""

import json
import os

import numpy as np
import pytest

from repro.cachesim import api
from repro.cachesim.scenarios import get_scenario
from repro.cachesim.traces import zipf

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
GOLDEN_FILES = sorted(
    f[: -len(".json")] for f in os.listdir(GOLDEN_DIR) if f.endswith(".json")
)

#: every kind the single run/sweep engine must serve (acceptance criterion)
API_KINDS = ("ogb", "omd", "lru", "fifo", "lfu", "ftpl")

FLOAT_KINDS = ("ogb", "omd")
FLOAT_ATOL = 5e-3


def test_all_kinds_registered():
    for kind in API_KINDS + ("ogb_grad",):
        pd = api.policy_def(kind)
        assert pd.kind == kind
        # memoized: the step identity is stable, which keys the compile cache
        assert api.policy_def(kind) is pd


@pytest.mark.parametrize("scenario", GOLDEN_FILES)
@pytest.mark.parametrize("kind", API_KINDS)
def test_api_run_reproduces_golden(scenario, kind):
    """Per-kind parity with the committed fixtures through bare api.run."""
    with open(os.path.join(GOLDEN_DIR, f"{scenario}.json")) as f:
        golden = json.load(f)
    sc = get_scenario(scenario)
    if kind not in sc.policies:
        pytest.skip(f"{kind} not in the {scenario} policy set")
    n, t, c = sc.dims("mini")
    assert (n, t, c) == (golden["N"], golden["T"], golden["C"])
    trace = sc.make_trace("mini")
    pd = api.policy_def(kind)
    window = (
        min(sc.batch, max(t // 20, 1)) if pd.fractional else max(t // 20, 1)
    )
    res = api.run(
        pd, trace, n, c, window=window, seed=0, horizon=t,
        track_opt=pd.fractional,
    )
    want = golden["rows"][pd.name]
    if kind in FLOAT_KINDS:
        assert res.hit_ratio == pytest.approx(
            want["hit_ratio"], abs=FLOAT_ATOL
        )
        assert res.regret == pytest.approx(
            want["regret"], abs=max(FLOAT_ATOL * t, abs(want["regret"]) * 5e-3)
        )
    else:
        # discrete automata: the port must be bit-exact (fixtures store the
        # ratio rounded to 10 digits, so compare on the same grid)
        assert round(res.hit_ratio, 10) == want["hit_ratio"]


N, C, T = 311, 23, 6400  # T/2 divisible by the window: clean resume point


@pytest.mark.parametrize("kind", API_KINDS)
def test_streaming_carry_resumes_bit_exact(kind):
    """Two chunked runs with a handed-off carry == one full run."""
    trace = zipf(N, T, alpha=0.9, seed=3)
    pd = api.policy_def(kind)
    kw = dict(window=16, eta=0.03, seed=0, horizon=T, track_opt=False)
    full = api.run(pd, trace, N, C, **kw)
    first = api.run(pd, trace[: T // 2], N, C, **kw)
    second = api.run(pd, trace[T // 2 :], capacity=C, carry=first.carry,
                     window=16, track_opt=False)
    np.testing.assert_array_equal(
        np.concatenate([first.hits, second.hits]), full.hits
    )
    np.testing.assert_array_equal(
        np.concatenate([first.reward, second.reward]), full.reward
    )
    # the final carries agree leaf by leaf (resume ends in the same state)
    import jax

    for a, b in zip(jax.tree.leaves(second.carry), jax.tree.leaves(full.carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sweep_matches_single_runs_across_kinds():
    """One vmapped grid == stacked single runs, for a fractional policy and
    an automaton (the two carry families)."""
    trace = zipf(N, T, alpha=0.9, seed=5)
    for kind, eta in (("omd", 0.05), ("fifo", None)):
        pd = api.policy_def(kind)
        sw = api.sweep(
            pd, trace, N, capacities=[7, 23], etas=(eta,), seeds=(0,),
            window=100, horizon=T,
        )
        for cap in (7, 23):
            single = api.run(
                pd, trace, N, cap, window=100, eta=eta, horizon=T,
                n_slots=23,
            )
            r = sw.row(capacity=cap)
            np.testing.assert_array_equal(sw.hits[r], single.hits)
            np.testing.assert_allclose(sw.reward[r], single.reward, atol=1e-3)
            assert sw.opt_hits[r] == single.opt_hits


def test_run_requires_shape_or_carry():
    pd = api.policy_def("lru")
    with pytest.raises(ValueError, match="catalog_size"):
        api.run(pd, zipf(N, 320, seed=1), window=16)
    with pytest.raises(ValueError, match="shorter than one window"):
        api.run(pd, zipf(N, 10, seed=1), N, C, window=16)


def test_resume_rejects_init_params():
    """A resumed run takes its parameters from the carry — passing eta
    alongside a carry would silently mislabel the result, so it raises."""
    trace = zipf(N, 320, alpha=0.9, seed=1)
    pd = api.policy_def("ogb")
    first = api.run(pd, trace, N, C, window=16, eta=0.03, track_opt=False)
    with pytest.raises(ValueError, match="carry's parameters"):
        api.run(pd, trace, capacity=C, window=16, carry=first.carry, eta=0.5)


def test_unknown_kind_lists_registry():
    with pytest.raises(KeyError, match="registered"):
        api.policy_def("nope")


def test_public_surface():
    """Top-level lazy re-exports resolve to the real objects."""
    import repro

    assert repro.run is api.run
    assert repro.sweep is api.sweep
    assert repro.PolicyDef is api.PolicyDef
    assert repro.policy_def is api.policy_def
    assert "RunResult" in repro.__all__ and "__version__" in repro.__all__
    assert isinstance(repro.__version__, str)
    with pytest.raises(AttributeError):
        repro.not_a_thing
