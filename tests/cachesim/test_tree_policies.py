"""Tree engines vs their dense oracles through the public policy API.

The lru/lfu/ftpl prefix-tree engines must be *bit-exact* against the dense
slot automata (same hit sequence, same occupancy); the lazy bucketized
``ogb_tree`` tracks dense ``ogb`` within its histogram quantization.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cachesim import api
from repro.cachesim import tree_engines as te
from repro.kernels.prefix_tree import ops as pt
from repro.kernels.prefix_tree.ref import stack_distance_hits_ref

AUTOMATA = ["lru", "lfu", "ftpl"]


def _zipf_trace(rng, n, t, a=1.2):
    ranks = rng.zipf(a, size=t * 3) - 1
    ranks = ranks[ranks < n][:t]
    return jnp.asarray(rng.permutation(n)[ranks], jnp.int32)


def _traces():
    rng = np.random.default_rng(42)
    n, t = 400, 6000
    zipf = _zipf_trace(rng, n, t)
    cyclic = jnp.asarray(np.tile(np.arange(50), t // 50), jnp.int32)
    bursty = jnp.asarray(
        np.concatenate(
            [np.repeat(rng.integers(0, n, 40), 30) for _ in range(5)]
        ),
        jnp.int32,
    )
    return {"zipf": (zipf, n), "cyclic": (cyclic, n), "bursty": (bursty, n)}


TRACES = _traces()


@pytest.mark.parametrize("kind", AUTOMATA)
@pytest.mark.parametrize("trace_name", sorted(TRACES))
@pytest.mark.parametrize("window", [1, 16, 250])
def test_tree_bit_exact_vs_dense(kind, trace_name, window):
    trace, n = TRACES[trace_name]
    c = 23
    rt = api.run(api.policy_def(kind), trace, n, c, window=window, seed=3)
    rd = api.run(
        api.policy_def(kind, impl="dense"), trace, n, c, window=window, seed=3
    )
    np.testing.assert_array_equal(rt.hits, rd.hits)
    np.testing.assert_array_equal(rt.occupancy, rd.occupancy)


def test_tree_lru_matches_stack_distance_oracle():
    """The reuse-distance formulation IS exact LRU — check against the
    O(T*W) python oracle, not just the dense automaton."""
    rng = np.random.default_rng(0)
    trace, n, c = _zipf_trace(rng, 120, 1500), 120, 11
    r = api.run(api.policy_def("lru"), trace, n, c, window=50)
    oracle = stack_distance_hits_ref(np.asarray(trace), c)
    assert int(r.hits.sum()) == int(oracle.sum())


@pytest.mark.parametrize("kind", AUTOMATA)
def test_tree_resume_bit_exact(kind):
    trace, n = TRACES["zipf"]
    c, w = 23, 16
    # ftpl's noise scale depends on horizon, which defaults to the replayed
    # length — pin it so the split replay runs the same dynamics
    h = len(trace)
    full = api.run(api.policy_def(kind), trace, n, c, window=w, seed=1,
                   horizon=h)
    pd = api.policy_def(kind)
    half = len(trace) // (2 * w) * w
    r1 = api.run(pd, trace[:half], n, c, window=w, seed=1, horizon=h)
    r2 = api.run(pd, trace[half:], capacity=c, window=w, carry=r1.carry)
    np.testing.assert_array_equal(
        np.concatenate([r1.hits, r2.hits]), full.hits
    )


@pytest.mark.parametrize("kind", AUTOMATA)
def test_tree_sweep_matches_single_runs(kind):
    trace, n = TRACES["zipf"]
    caps = [5, 23, 64]
    sw = api.sweep(api.policy_def(kind), trace, n, caps, window=100)
    for combo, hits in zip(sw.combos, sw.hits):
        single = api.run(
            api.policy_def(kind), trace, n, combo["capacity"],
            window=100, n_slots=max(caps),
        )
        np.testing.assert_array_equal(hits, single.hits)


def test_tree_lru_small_ring_compaction_exact():
    """Force ring compactions (ring barely above 4*n_slots) and check the
    rank-compaction path stays bit-exact vs dense."""
    rng = np.random.default_rng(5)
    n, c, t, w = 600, 40, 8000, 100
    trace = _zipf_trace(rng, n, t, a=1.1)
    rt = api.run(api.policy_def("lru"), trace, n, c, window=w, ring=256)
    rd = api.run(api.policy_def("lru", impl="dense"), trace, n, c, window=w)
    np.testing.assert_array_equal(rt.hits, rd.hits)


@pytest.mark.parametrize("sample", ["poisson", "none"])
def test_ogb_tree_tracks_dense_ogb(sample):
    rng = np.random.default_rng(9)
    n, c, t, w = 1500, 75, 40000, 200
    trace = _zipf_trace(rng, n, t)
    rd = api.run(api.policy_def("ogb", sample=sample), trace, n, c,
                 window=w, seed=3)
    rt = api.run(api.policy_def("ogb_tree", sample=sample), trace, n, c,
                 window=w, seed=3)
    # fractional reward is sampling-free: a tight relative check
    assert float(rt.reward.sum()) == pytest.approx(
        float(rd.reward.sum()), rel=1e-2
    )
    if sample == "poisson":
        assert abs(rt.hit_ratio - rd.hit_ratio) <= 5e-3
        # occupancy stays near capacity (bucket-quantized estimate)
        assert abs(np.mean(rt.occupancy) - c) < 0.2 * c


def test_ogb_tree_reanchor_path():
    """A tiny value grid (batch_hint=1) forces frequent re-anchor rebuilds;
    accuracy must not degrade."""
    rng = np.random.default_rng(10)
    n, c, t, w = 800, 50, 30000, 100
    trace = _zipf_trace(rng, n, t, a=1.3)
    rd = api.run(api.policy_def("ogb"), trace, n, c, window=w, eta=0.01)
    rt = api.run(api.policy_def("ogb_tree", batch_hint=1), trace, n, c,
                 window=w, eta=0.01)
    assert abs(rt.hit_ratio - rd.hit_ratio) <= 5e-3


def test_ogb_tree_rejects_madow():
    with pytest.raises(ValueError, match="madow"):
        api.policy_def("ogb_tree", sample="madow")


def _mass64(ccum, scum, cnt, sm, w, t):
    """The bucket mean-clip mass of ``_ogb_tree_mass`` in float64, from
    exclusive prefix sums ``ccum``/``scum`` of the leaf histograms."""
    v = cnt.shape[0]
    k0 = int(np.clip(np.floor((t + 1.0) / w), 0, v - 1))
    k1 = int(np.clip(np.floor((t + 2.0) / w), 0, v - 1))
    above = ccum[-1] - ccum[k1 + 1]
    mid = (scum[k1] - scum[k0 + 1]) - t * (ccum[k1] - ccum[k0 + 1])

    def bnd(k):
        mean = sm[k] / cnt[k] if cnt[k] > 0 else 0.0
        return cnt[k] * np.clip(mean - t, 0.0, 1.0)

    return above + mid + bnd(k0) + (bnd(k1) if k1 > k0 else 0.0)


@pytest.mark.parametrize("iters", [30, 12, 7])
@pytest.mark.parametrize("bracket", ["eta_b", "floor_4w", "near_zero",
                                     "near_top"])
def test_ogb_tree_solve_matches_float64_bisection(bracket, iters):
    """The K-ary threshold solve lands where a float64 bisection of the same
    mass does, within the final bracket width plus float32 rounding, and
    keeps ``mass(rho) >= cap``."""
    v, radix = te.OGB_TREE_BUCKETS, te.OGB_TREE_RADIX
    eta, b = 0.002, 1000
    span = 1.0 + 2.0 * te.OGB_TREE_GAIN * max(1.0, eta * 4096)
    w = float(np.float32((span + 1.0) / v))
    gridtop = w * v - 1.0
    width = 4.0 * w if bracket == "floor_4w" else eta * b
    solve = jax.jit(te._ogb_tree_solve, static_argnums=(7, 8, 9))
    for seed in range(4):
        rng = np.random.default_rng(seed)
        rho0 = {"eta_b": rng.uniform(1.0, 8.0),
                "floor_4w": rng.uniform(1.0, 8.0),
                "near_zero": 0.0,
                "near_top": gridtop - 1.0 - width - 2.0 * w}[bracket]
        rho0 = float(np.float32(rho0))
        hi0 = float(np.float32(rho0 + width))
        # items dense around [rho0, hi0 + 1], where the mass moves, plus a
        # sparse spread over the whole grid
        y = np.concatenate([
            rng.uniform(max(rho0 - 1.0, 0.0), hi0 + 1.5, 2000),
            rng.uniform(0.0, gridtop, 200),
        ]).astype(np.float32)
        leaf = np.clip(np.floor((y.astype(np.float64) + 1.0) / w), 0, v - 1)
        cnt = np.bincount(leaf.astype(np.int64), minlength=v).astype(np.float32)
        sm = np.zeros(v, np.float32)
        np.add.at(sm, leaf.astype(np.int64), y)
        c64, s64 = cnt.astype(np.float64), sm.astype(np.float64)
        ccum = np.concatenate([[0.0], np.cumsum(c64)])
        scum = np.concatenate([[0.0], np.cumsum(s64)])

        def mass(t):
            return _mass64(ccum, scum, c64, s64, w, t)

        # as the gradient step guarantees: mass(rho0) >= cap > mass(hi0)
        m0, m1 = mass(rho0), mass(hi0)
        cap = float(np.float32(m1 + rng.uniform(0.05, 0.95) * (m0 - m1)))
        lo, hi = rho0, hi0
        for _ in range(iters):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if mass(mid) >= cap else (lo, mid)

        ycnt = pt.tree_build(jnp.asarray(cnt), radix)
        ysum = pt.tree_build(jnp.asarray(sm), radix)
        total = pt.tree_total(ycnt, v, radix)
        f32 = jnp.float32
        rho = solve(ycnt, ysum, f32(w), total, f32(cap), f32(rho0), f32(hi0),
                    v, radix, iters)
        # the float32 mass carries rounding of about eps32 times its
        # largest term (measured <= 0.46x on such data), which moves the
        # root by that over the mass's slope: past ~20 halvings this, not
        # the bracket, limits agreement with float64
        err = 4.0 * float(np.finfo(np.float32).eps) * (
            ccum[-1] + scum[-1] + abs(lo) * ccum[-1])
        slope = (mass(lo - 1e-3) - mass(lo + 1e-3)) / 2e-3
        tol = ((hi0 - rho0) / 2**iters + 2.0 * float(np.spacing(rho))
               + err / slope)
        assert abs(float(rho) - lo) <= tol, (seed, float(rho), lo, tol)
        # rho is a left end of the grid that iters halvings of the bracket
        # cut, up to the float32 rounding of the probe points
        h = (hi0 - rho0) / 2**iters
        on_grid = rho0 + round((float(rho) - rho0) / h) * h
        assert abs(float(rho) - on_grid) <= 8.0 * float(np.spacing(
            np.float32(hi0)))
        assert mass(float(rho)) >= cap - err


@pytest.mark.parametrize("v,radix", [(65536, 64), (1000, 16), (70, 8),
                                     (10, 16)])
def test_ogb_tree_mass_reads_every_tree_geometry(v, radix):
    """The solve's row reads of the count and sum trees give the float64
    mass, also where a level's groups are partial (v not a power of the
    radix) and where the leaves are one group."""
    rng = np.random.default_rng(v)
    w = float(np.float32(20.0 / v))
    cnt = rng.integers(0, 5, v).astype(np.float32)
    sm = (cnt * rng.uniform(0.0, 18.0, v)).astype(np.float32)
    ycnt = pt.tree_build(jnp.asarray(cnt), radix)
    ysum = pt.tree_build(jnp.asarray(sm), radix)
    t = rng.uniform(-1.0, 19.0, 200).astype(np.float32)
    got = te._ogb_tree_mass(te._sibling_rows(ycnt, ysum, v, radix),
                            pt.tree_total(ycnt, v, radix), jnp.float32(w),
                            jnp.asarray(t), v, radix)
    c64, s64 = cnt.astype(np.float64), sm.astype(np.float64)
    ccum = np.concatenate([[0.0], np.cumsum(c64)])
    scum = np.concatenate([[0.0], np.cumsum(s64)])
    want = [_mass64(ccum, scum, c64, s64, w, float(x)) for x in t]
    # float32 rounding: eps32 times the mass's largest terms, as in the
    # solve test (|t| < 19)
    eps = float(np.finfo(np.float32).eps)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=4.0 * eps * (scum[-1] + 20.0 * ccum[-1]))


def _primitive_names(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitive_names(inner)


def test_ogb_tree_chunk_has_no_while_loop():
    """The threshold solve's rounds are unrolled: the chunk holds no loop,
    so the replay's scan is the only ``while`` around it.  (A ``fori_loop``
    with static bounds traces to ``scan``, which lowers to a ``while``.)"""
    n, v, radix, b = 64, 256, 16, 16
    carry = te.init_ogb_tree_carry(n, 8, eta=0.05, buckets=v, radix=radix,
                                   batch_hint=b)
    chunk = te.make_ogb_tree_chunk(n, v, radix, "poisson")
    jaxpr = jax.make_jaxpr(chunk)(carry, jnp.zeros(b, jnp.int32)).jaxpr
    names = set(_primitive_names(jaxpr))
    assert "cond" in names  # the walk reaches the re-anchor branch
    assert not names & {"while", "scan"}


def test_madow_tree_sampling_matches_dense_madow():
    """The O(C log N) tree-descent Madow draw through the dense OGB policy:
    same systematic sample up to f32 cumsum boundaries, so hit counts agree
    to a fraction of a percent."""
    rng = np.random.default_rng(11)
    n, c, t, w = 1000, 60, 20000, 200
    trace = _zipf_trace(rng, n, t)
    rm = api.run(api.policy_def("ogb", sample="madow", madow_capacity=c),
                 trace, n, c, window=w, seed=2)
    rt = api.run(api.policy_def("ogb", sample="madow_tree", madow_capacity=c),
                 trace, n, c, window=w, seed=2)
    assert abs(rt.hit_ratio - rm.hit_ratio) <= 2e-3
    np.testing.assert_array_equal(rt.occupancy, rm.occupancy)
