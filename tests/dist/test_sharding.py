"""Unit tests for the repro.dist.sharding mesh/rules registry."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    current_mesh,
    current_rules,
    default_rules,
    logical_to_spec,
    named_sharding,
    shard,
    use_sharding,
)
from repro.launch.mesh import make_debug_mesh

SIZES = {"data": 2, "model": 4}
POD_SIZES = {"pod": 2, "data": 2, "model": 4}


def test_default_rules_single_vs_multi_pod():
    single = default_rules()
    multi = default_rules(multi_pod=True)
    assert single.rules["batch"] == "data"
    assert multi.rules["batch"] == ("pod", "data")
    for r in (single, multi):
        assert r.rules["heads"] == "model"
        assert r.rules["fsdp"] == "data"
        assert r.rules["kv_heads"] is None


def test_rule_override_precedence():
    base = default_rules()
    over = base.with_overrides(embed="data", heads=None)
    # overrides win over the base table...
    assert over.mesh_axes("embed") == ("data",)
    assert over.mesh_axes("heads") == ()
    # ...without mutating the base, and untouched names pass through
    assert base.mesh_axes("embed") == ()
    assert over.mesh_axes("ff") == ("model",)
    # unknown logical names resolve to replicated, not an error
    assert over.mesh_axes("no_such_axis") == ()
    assert over.mesh_axes(None) == ()


def test_logical_to_spec_basics():
    rules = default_rules()
    spec = logical_to_spec(("batch", None, "heads"), rules, SIZES, (4, 3, 8))
    assert spec == P("data", None, "model")


def test_logical_to_spec_drops_non_divisible():
    rules = default_rules()
    # 7 % 4 != 0: the heads constraint must be dropped, batch kept
    spec = logical_to_spec(("batch", "heads"), rules, SIZES, (4, 7))
    assert spec == P("data")
    # without a shape there is no divisibility information: keep both
    spec = logical_to_spec(("batch", "heads"), rules, SIZES, None)
    assert spec == P("data", "model")


def test_logical_to_spec_drops_missing_mesh_axis():
    rules = default_rules(multi_pod=True)
    # "pod" is absent from a single-pod mesh: batch falls back to "data" only
    spec = logical_to_spec(("batch", None), rules, SIZES, (4, 3))
    assert spec == P("data")
    spec = logical_to_spec(("batch", None), rules, POD_SIZES, (4, 3))
    assert spec == P(("pod", "data"))


def test_logical_to_spec_no_mesh_axis_reuse():
    rules = default_rules()
    # "ff" and "heads" both map to "model": the later dim must be dropped
    spec = logical_to_spec(("ff", "heads"), rules, SIZES, (8, 8))
    assert spec == P("model")


def test_logical_to_spec_multi_axis_divisibility():
    rules = default_rules(multi_pod=True)
    # batch -> ("pod", "data"), total 4: 6 is not divisible -> dropped
    spec = logical_to_spec(("batch",), rules, POD_SIZES, (6,))
    assert spec == P()


def test_use_sharding_nesting_and_restoration():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    outer_rules = default_rules()
    inner_rules = outer_rules.with_overrides(batch=None)
    assert current_mesh() is None and current_rules() is None
    with use_sharding(mesh, outer_rules):
        assert current_mesh() is mesh
        assert current_rules() is outer_rules
        with use_sharding(mesh, inner_rules):
            assert current_rules() is inner_rules
        # inner exit restores the outer frame, not the empty stack
        assert current_rules() is outer_rules
    assert current_mesh() is None and current_rules() is None


def test_use_sharding_restores_on_exception():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(RuntimeError):
        with use_sharding(mesh, default_rules()):
            raise RuntimeError("boom")
    assert current_mesh() is None and current_rules() is None


def test_shard_is_noop_off_context():
    x = jnp.ones((4, 8))
    y = shard(x, "batch", "heads")
    assert y is x


def test_shard_applies_constraint_in_context():
    # shard() places with with_sharding_constraint, which takes Auto axes
    mesh = make_debug_mesh(1, 1)
    x = jnp.ones((4, 8))
    with use_sharding(mesh, default_rules()):
        y = shard(x, "batch", "heads")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        # and inside jit it must trace cleanly
        out = jax.jit(lambda a: shard(a * 2.0, "batch", "heads"))(x)
    np.testing.assert_array_equal(np.asarray(out), 2 * np.asarray(x))


def test_shard_rank_mismatch_raises():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with use_sharding(mesh, default_rules()):
        with pytest.raises(ValueError, match="rank"):
            shard(jnp.ones((4, 8)), "batch")
    # arity is validated off-context too, so CPU tests catch bad annotations
    with pytest.raises(ValueError, match="rank"):
        shard(jnp.ones((4, 8)), "batch")


def test_named_sharding_resolves_logical_names():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    s = named_sharding(mesh, ("batch", None), shape=(4, 3))
    assert s.mesh is mesh
    assert s.spec == P("data")
