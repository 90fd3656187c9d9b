"""Sized-object pipeline: loaders -> catalog -> engines -> byte metrics.

The heterogeneous-size setting (paper §2.2/§8) threads per-object sizes
through every layer; these tests lock each joint:

* the CDN/text loaders actually surface the size column (the regression
  this PR fixes: it used to be parsed past and dropped), and
  ``write_trace(sizes=...)`` round-trips it;
* the device GDS tree engine is differential-exact against the host
  ``core.policies.GDS`` oracle under dyadic sizes/costs;
* ``ogb_sized`` (scan and tree) tracks the float64 weighted-projection
  oracle on byte hit ratio, and reduces **bit-exactly** to the unit OGB
  engines when every size is 1;
* byte accounting (``byte_hits``/``bytes_total``/``byte_hit_ratio``) is
  consistent across ``run``, ``sweep`` and ``run_stream``.
"""

import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.cachesim import api
from repro.cachesim.tracelab.catalog import CatalogRemap
from repro.cachesim.tracelab.loaders import load_trace, write_trace
from repro.core.ogb_sized import project_weighted
from repro.core.policies import GDS

SLABS = np.asarray([1.0, 4.0, 16.0, 64.0])
DATA = os.path.join(os.path.dirname(__file__), "data")


def _sized_instance(seed, n=120, t=4000, c=11):
    rng = np.random.default_rng(seed)
    trace = jnp.asarray(rng.integers(0, n, size=t), jnp.int32)
    sizes = SLABS[rng.integers(0, len(SLABS), size=n)]
    return trace, sizes, n, c


# -- satellite: the size column is no longer dropped ----------------------


@pytest.mark.parametrize(
    "fmt,fname,expect_first",
    [
        ("csv", "sample.csv", 229.0),
        ("tsv", "sample.tsv", 64.0),
        ("cdn", "sample_cdn.log", 889.0),
    ],
)
def test_loader_surfaces_size_column(fmt, fname, expect_first):
    """Every bundled text fixture carries real sizes; ``with_sizes=True``
    must return them (not a unit placeholder)."""
    path = os.path.join(DATA, fname)
    ids_plain = load_trace(path, fmt)
    ids, sizes = load_trace(path, fmt, with_sizes=True)
    np.testing.assert_array_equal(ids, ids_plain)
    assert sizes.shape == ids.shape and sizes.dtype == np.float64
    assert float(sizes[0]) == expect_first
    assert np.all(sizes > 0) and not np.all(sizes == 1.0)


@pytest.mark.parametrize("fmt,ext", [("csv", "csv"), ("tsv", "tsv"), ("cdn", "log")])
def test_write_trace_sizes_round_trip(fmt, ext, tmp_path):
    rng = np.random.default_rng(3)
    ids0 = rng.integers(0, 1000, size=64).astype(np.int64)
    sizes0 = np.concatenate(
        [SLABS[rng.integers(0, 4, size=32)], rng.uniform(0.5, 900.5, size=32)]
    )
    path = str(tmp_path / f"rt.{ext}")
    write_trace(path, ids0, fmt, sizes=sizes0)
    ids1, sizes1 = load_trace(path, fmt, with_sizes=True)
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_array_equal(sizes0, sizes1)  # bit-for-float
    # the same file still loads unsized (sizes simply ignored)
    np.testing.assert_array_equal(load_trace(path, fmt), ids0)


def test_binary_formats_reject_sizes(tmp_path):
    ids = np.arange(5, dtype=np.int64)
    with pytest.raises(ValueError, match="size"):
        write_trace(str(tmp_path / "t.bin"), ids, "bin64", sizes=np.ones(5))
    write_trace(str(tmp_path / "t.bin"), ids, "bin64")
    with pytest.raises(ValueError, match="size"):
        load_trace(str(tmp_path / "t.bin"), "bin64", with_sizes=True)


def test_write_trace_rejects_bad_sizes(tmp_path):
    ids = np.arange(4, dtype=np.int64)
    for bad in ([1.0, 0.0, 2.0, 3.0], [1.0, -1.0, 2.0, 3.0],
                [1.0, np.nan, 2.0, 3.0], [1.0, 2.0, 3.0]):
        with pytest.raises(ValueError):
            write_trace(str(tmp_path / "t.csv"), ids, "csv", sizes=bad)


def test_catalog_remap_item_sizes_first_seen_and_chunk_invariant():
    raw = np.asarray([70, 80, 70, 90, 80, 100], np.int64)
    szs = np.asarray([8.0, 2.0, 9.0, 4.0, 3.0, 1.0])
    cm1 = CatalogRemap()
    cm1.apply(raw, sizes=szs)
    # first-seen size wins: 70 -> 8 (not the later 9), 80 -> 2
    np.testing.assert_array_equal(cm1.item_sizes, [8.0, 2.0, 4.0, 1.0])
    # chunking cannot change the mapping or the recorded sizes
    cm2 = CatalogRemap()
    for sl in (slice(0, 1), slice(1, 4), slice(4, 6)):
        cm2.apply(raw[sl], sizes=szs[sl])
    np.testing.assert_array_equal(cm1.item_sizes, cm2.item_sizes)
    # ids never observed with a size read the unit default
    cm1.apply(np.asarray([110], np.int64))
    assert float(cm1.item_sizes[-1]) == 1.0


# -- GDS: device tree engine vs host oracle -------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("costs_mode", ["unit", "sizes", "dyadic"])
def test_gds_device_matches_host_oracle(seed, costs_mode):
    """Dyadic sizes/costs keep every H update exact in float32, so the
    device min-pair tree must replay the host GDS *bit-exactly* (same
    per-window hits, same byte accounting)."""
    trace, sizes, n, c = _sized_instance(seed, n=90, t=3000, c=9)
    rng = np.random.default_rng(seed + 100)
    if costs_mode == "unit":
        costs = None
    elif costs_mode == "sizes":
        costs = sizes.copy()
    else:
        costs = np.asarray([0.5, 1.0, 2.0, 4.0])[
            rng.integers(0, 4, size=n)
        ]
    w = 250
    r = api.run(
        api.policy_def("gds"), trace, n, c, window=w,
        sizes=sizes, costs=costs, track_opt=False,
    )
    host = GDS(n, c, sizes=sizes, costs=costs)
    ids = np.asarray(trace)
    hits_host, bytes_host = [], []
    for k in range(len(ids) // w):
        chunk = ids[k * w:(k + 1) * w]
        flags = [host.request(int(i)) for i in chunk]
        hits_host.append(sum(flags))
        bytes_host.append(float(np.sum(sizes[chunk][np.asarray(flags)])))
    np.testing.assert_array_equal(r.hits, hits_host)
    assert r.byte_hits is not None
    np.testing.assert_allclose(r.byte_hits, bytes_host, rtol=0, atol=0)
    assert r.bytes_total == pytest.approx(float(np.sum(sizes[ids])))
    assert 0.0 <= r.byte_hit_ratio <= 1.0


# -- ogb_sized: unit reduction + float64 oracle ---------------------------


def test_ogb_sized_scan_unit_sizes_bit_exact_vs_ogb():
    """With every size 1 the weighted machinery must vanish exactly:
    same normalization (sref=1), same bisection bracket, same floats."""
    trace, _, n, c = _sized_instance(7, n=150, t=4000, c=13)
    kw = dict(window=400, seed=5, eta=0.03, track_opt=False)
    rs = api.run(
        api.policy_def("ogb_sized", flavor="scan"), trace, n, c,
        sizes=np.ones(n), **kw,
    )
    ru = api.run(api.policy_def("ogb", projection="bisect"), trace, n, c, **kw)
    np.testing.assert_array_equal(np.asarray(rs.reward), np.asarray(ru.reward))
    np.testing.assert_array_equal(np.asarray(rs.hits), np.asarray(ru.hits))


def test_ogb_sized_tree_unit_sizes_bit_exact_vs_ogb_tree():
    trace, _, n, c = _sized_instance(8, n=150, t=4000, c=13)
    kw = dict(window=400, seed=5, eta=0.03, track_opt=False)
    rs = api.run(
        api.policy_def("ogb_sized", flavor="tree"), trace, n, c,
        sizes=np.ones(n), **kw,
    )
    ru = api.run(api.policy_def("ogb_tree"), trace, n, c, **kw)
    np.testing.assert_array_equal(np.asarray(rs.reward), np.asarray(ru.reward))
    np.testing.assert_array_equal(np.asarray(rs.hits), np.asarray(ru.hits))


def _f64_sized_oracle(trace, sizes, capacity, eta, window):
    """Float64 replay of the ogb_sized scan dynamics (the ground truth both
    device flavors are held to): mean-size normalization, byte-weighted
    ascent, exact weighted projection per chunk."""
    ids = np.asarray(trace)
    sref = float(np.mean(sizes))
    s = np.asarray(sizes, np.float64) / sref
    cap = float(capacity) / sref
    f = np.full(len(s), cap / float(np.sum(s)))
    reward = 0.0
    for k in range(len(ids) // window):
        chunk = ids[k * window:(k + 1) * window]
        reward += float(np.sum(s[chunk] * f[chunk]))  # w = s (byte reward)
        y = f.copy()
        np.add.at(y, chunk, eta * s[chunk])
        if float(np.sum(s * np.clip(y, 0.0, 1.0))) > cap:
            f = project_weighted(y, s, cap)
        else:
            f = np.clip(y, 0.0, 1.0)
    return reward * sref


@pytest.mark.parametrize("flavor", ["scan", "tree"])
def test_ogb_sized_tracks_float64_oracle(flavor):
    """Acceptance bound: fractional byte hit ratio within 5e-3 of the
    float64 weighted-projection oracle (slab sizes, so the tree's
    size-class quantization is lossless and only float32/bucketization
    error remains)."""
    trace, sizes, n, c = _sized_instance(11, n=200, t=6000, c=0)
    cap_bytes = 8.0 * float(np.mean(sizes))
    eta, w = 0.05, 500
    r = api.run(
        api.policy_def("ogb_sized", flavor=flavor), trace, n, cap_bytes,
        window=w, sizes=sizes, eta=eta, track_opt=False,
    )
    total_bytes = float(np.sum(sizes[np.asarray(trace)]))
    got = float(np.sum(np.asarray(r.reward, np.float64))) / total_bytes
    want = _f64_sized_oracle(trace, sizes, cap_bytes, eta, w) / total_bytes
    assert got == pytest.approx(want, abs=5e-3), (flavor, got, want)


@pytest.mark.parametrize("seed,n,t,cmul", [(11, 200, 6000, 8.0),
                                           (3, 2000, 60000, 40.0)])
def test_ogb_sized_tree_fine_grid_matches_float64_oracle(seed, n, t, cmul):
    """With a value grid fine enough that bucket quantization vanishes, the
    tree flavor's lazy dynamics are the oracle's: a touched item restarts
    from its clipped f (an item below the threshold holds f = 0, not a
    debt).  Without that re-anchoring the gap stays at ~4e-4..2e-3."""
    trace, sizes, n, _ = _sized_instance(seed, n=n, t=t, c=0)
    cap_bytes = cmul * float(np.mean(sizes))
    eta, w = 0.05, 500
    r = api.run(
        api.policy_def("ogb_sized", flavor="tree", buckets=1 << 20), trace,
        n, cap_bytes, window=w, sizes=sizes, eta=eta, track_opt=False,
    )
    total_bytes = float(np.sum(sizes[np.asarray(trace)]))
    got = float(np.sum(np.asarray(r.reward, np.float64))) / total_bytes
    want = _f64_sized_oracle(trace, sizes, cap_bytes, eta, w) / total_bytes
    assert got == pytest.approx(want, abs=1e-4), (got, want)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_ogb_sized_scan_oracle_sweep(seed):
    """Hypothesis sweep: random slab assignments/capacities, scan flavor
    vs the float64 oracle."""
    rng = np.random.default_rng(seed)
    n, t, w = 60, 1500, 250
    trace = jnp.asarray(rng.integers(0, n, size=t), jnp.int32)
    sizes = SLABS[rng.integers(0, len(SLABS), size=n)]
    cap_bytes = float(rng.uniform(4.0, 0.5 * float(np.sum(sizes))))
    eta = float(rng.uniform(0.01, 0.08))
    r = api.run(
        api.policy_def("ogb_sized", flavor="scan"), trace, n, cap_bytes,
        window=w, sizes=sizes, eta=eta, track_opt=False,
    )
    total_bytes = float(np.sum(sizes[np.asarray(trace)]))
    got = float(np.sum(np.asarray(r.reward, np.float64))) / total_bytes
    want = _f64_sized_oracle(trace, sizes, cap_bytes, eta, w) / total_bytes
    assert got == pytest.approx(want, abs=5e-3)


# -- byte accounting plumbing ---------------------------------------------


def test_sized_automaton_hits_unchanged_byte_ratio_differs():
    """Sizes never change a size-blind automaton's decisions — only the
    byte accounting. The unsized run must stay bit-identical."""
    trace, sizes, n, c = _sized_instance(21)
    for kind in ("lru", "lfu", "ftpl"):
        kw = dict(window=500, seed=2, horizon=len(trace), track_opt=False)
        r0 = api.run(api.policy_def(kind), trace, n, c, **kw)
        r1 = api.run(api.policy_def(kind), trace, n, c, sizes=sizes, **kw)
        np.testing.assert_array_equal(r0.hits, r1.hits)
        np.testing.assert_array_equal(r0.occupancy, r1.occupancy)
        assert r0.byte_hits is None and r0.bytes_total == 0.0
        assert r0.byte_hit_ratio == r0.hit_ratio  # unsized fallback
        assert r1.byte_hits is not None
        assert r1.bytes_total == pytest.approx(
            float(np.sum(sizes[np.asarray(trace)]))
        )
        assert 0.0 <= r1.byte_hit_ratio <= 1.0
        assert r1.byte_hit_ratio != pytest.approx(r1.hit_ratio, abs=1e-4)


def test_sized_lru_byte_hits_match_host_accounting():
    """Device byte accounting == host replay of the same (bit-exact) LRU
    decisions, window by window."""
    from repro.core.policies import LRU

    trace, sizes, n, c = _sized_instance(22)
    w = 400
    r = api.run(
        api.policy_def("lru"), trace, n, c, window=w, sizes=sizes,
        horizon=len(trace), track_opt=False,
    )
    host = LRU(n, c)
    ids = np.asarray(trace)
    want = []
    for k in range(len(ids) // w):
        chunk = ids[k * w:(k + 1) * w]
        flags = np.asarray([host.request(int(i)) for i in chunk])
        want.append(float(np.sum(sizes[chunk][flags])))
    np.testing.assert_allclose(r.byte_hits, want, rtol=0, atol=0)


def test_run_stream_sized_parity():
    """Chunked sized streaming == one-shot sized run, byte accounting
    included, bit for bit."""
    from repro.cachesim.tracelab.stream import run_stream

    trace, sizes, n, c = _sized_instance(23)
    w = 250
    one = api.run(
        api.policy_def("lru"), trace, n, c, window=w, sizes=sizes,
        horizon=len(trace), track_opt=False,
    )
    ids = np.asarray(trace)
    chunks = [ids[i:i + 707] for i in range(0, len(ids), 707)]
    sr = run_stream(
        api.policy_def("lru"), chunks, n, c, window=w, segment_len=1000,
        horizon=len(ids), sizes=sizes,
    )
    np.testing.assert_array_equal(one.hits, sr.hits)
    np.testing.assert_array_equal(one.byte_hits, sr.byte_hits)
    assert sr.bytes_total == pytest.approx(one.bytes_total)
    assert sr.byte_hit_ratio == pytest.approx(one.byte_hit_ratio)


def test_sweep_sized_byte_hit_ratios():
    trace, sizes, n, c = _sized_instance(24, n=80, t=2000, c=8)
    cap_bytes = int(round(c * float(np.mean(sizes))))
    res = api.sweep(
        api.policy_def("ogb_sized", flavor="scan"), trace, n,
        capacities=[cap_bytes], etas=[0.02, 0.05], window=250,
        sizes=sizes, track_opt=False,
    )
    assert len(res.byte_hit_ratios) == 2
    assert all(0.0 <= b <= 1.0 for b in res.byte_hit_ratios)


# -- unit policies reject what they cannot honor --------------------------


def test_unit_policies_reject_sizes_and_costs():
    trace, sizes, n, c = _sized_instance(25, n=40, t=1000, c=5)
    with pytest.raises(ValueError, match="unit-size"):
        api.run(
            api.policy_def("ogb"), trace, n, c, window=250, sizes=sizes,
            track_opt=False,
        )
    with pytest.raises(ValueError, match="costs"):
        api.run(
            api.policy_def("lru"), trace, n, c, window=250, sizes=sizes,
            costs=sizes, horizon=1000, track_opt=False,
        )
    with pytest.raises(ValueError, match="sizes"):
        api.run(
            api.policy_def("ogb_sized", flavor="scan"), trace, n, c,
            window=250, eta=0.05, track_opt=False,
        )


def test_run_rejects_bad_sizes():
    trace, _, n, c = _sized_instance(26, n=40, t=1000, c=5)
    for bad in (np.zeros(n), np.full(n, -1.0), np.full(n, np.nan),
                np.ones(n - 1)):
        with pytest.raises(ValueError):
            api.run(
                api.policy_def("lru"), trace, n, c, window=250,
                sizes=bad, horizon=1000, track_opt=False,
            )


# -- synthesizer size joint ------------------------------------------------


def test_synthesize_sizes_preserves_size_popularity_joint():
    """Fit on a trace whose sizes are anti-correlated with popularity;
    the synthesized catalog must reproduce the trend (popular small,
    tail large)."""
    from repro.cachesim.tracelab.synth import (
        fit_profile, synthesize_chunks, synthesize_sizes,
    )

    rng = np.random.default_rng(5)
    n = 400
    ranks = np.minimum(
        (rng.zipf(1.2, size=30_000) - 1), n - 1
    ).astype(np.int64)
    item_sizes = np.geomspace(1.0, 512.0, n)  # rank r -> bigger size
    prof = fit_profile(ranks, sizes=item_sizes[ranks])
    synth = np.concatenate(
        list(synthesize_chunks(prof, 30_000, seed=9))
    )
    szs = synthesize_sizes(prof, catalog=prof.catalog, seed=9)
    assert szs.shape == (prof.catalog,) and np.all(szs > 0)
    cnt = np.bincount(synth, minlength=prof.catalog)
    top = np.argsort(-cnt)[: max(prof.catalog // 10, 1)]
    tail = np.argsort(-cnt)[prof.catalog // 2:]
    assert float(np.median(szs[top])) < float(np.median(szs[tail]))
    # an unsized profile synthesizes unit sizes
    prof_u = fit_profile(ranks)
    np.testing.assert_array_equal(
        synthesize_sizes(prof_u, catalog=prof_u.catalog), 1.0
    )
