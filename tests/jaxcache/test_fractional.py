"""JAX batched OGB_cl vs the float64 numpy oracle, and sharded == unsharded."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.projection import project_capped_simplex
from repro.jaxcache.fractional import (
    FractionalState,
    capped_simplex_project,
    madow_sample_jax,
    ogb_batch_update,
    permanent_random_numbers,
    poisson_sample,
    request_counts,
)


def test_counts():
    ids = jnp.array([1, 1, 3, 0], dtype=jnp.int32)
    c = request_counts(ids, 5)
    np.testing.assert_array_equal(np.asarray(c), [1, 2, 0, 1, 0])


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n,C", [(64, 8), (301, 17), (1024, 256)])
def test_projection_matches_oracle(seed, n, C):
    rng = np.random.default_rng(seed)
    y = rng.normal(0.3, 0.5, size=n).astype(np.float32)
    f_jax, tau = capped_simplex_project(jnp.asarray(y), float(C))
    f_ref = project_capped_simplex(y.astype(np.float64), C)
    np.testing.assert_allclose(np.asarray(f_jax), f_ref, atol=2e-5)
    assert abs(float(jnp.sum(f_jax)) - C) < 1e-2


def test_batch_update_matches_numpy_classic():
    """ogb_batch_update == numpy OGB_cl batch step."""
    N, C, B, eta = 128, 16, 32, 0.05
    rng = np.random.default_rng(0)
    f = np.full(N, C / N)
    state = FractionalState.create(N, C)
    for _ in range(5):
        ids = rng.integers(0, N, size=B).astype(np.int32)
        # numpy reference
        counts = np.bincount(ids, minlength=N)
        f = project_capped_simplex(f + eta * counts, C)
        # jax
        state, reward = ogb_batch_update(state, jnp.asarray(ids), jnp.float32(eta), C)
        np.testing.assert_allclose(np.asarray(state.f), f, atol=5e-5)


def test_poisson_sample_expectation():
    N, C = 4096, 512
    f = jnp.full(N, C / N, jnp.float32)
    p = permanent_random_numbers(jax.random.key(0), N)
    x = poisson_sample(f, p, C)
    occ = int(jnp.sum(x))
    assert abs(occ - C) < 4 * np.sqrt(C)  # ~4 sigma


def test_madow_sample_exact_size():
    N, C = 512, 64
    rng = np.random.default_rng(1)
    f = rng.random(N).astype(np.float32)
    f = np.clip(f * (C / f.sum()), 0, 1)
    f = f * (C / f.sum())
    mask = madow_sample_jax(jnp.asarray(f), jnp.float32(0.37), C)
    assert int(jnp.sum(mask)) in (C, C - 1, C + 1)  # fp cumsum edge tolerance


def test_sharded_matches_unsharded():
    """8 fake XLA host devices: sharded step == single-device step."""
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.jaxcache.fractional import FractionalState, ogb_batch_update
from repro.jaxcache.sharded import make_sharded_step

N, C, B, eta = 256, 32, 64, 0.04
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 4)
step, f_shard = make_sharded_step(mesh, N, C, B, eta)
rng = np.random.default_rng(0)
f = jax.device_put(jnp.full((N,), C / N, jnp.float32), f_shard)
state = FractionalState.create(N, C)
for i in range(4):
    ids = jnp.asarray(rng.integers(0, N, size=B), jnp.int32)
    f, reward_sh = step(f, ids)
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


def test_fleet_step_independent_caches():
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.jaxcache.fractional import FractionalState, ogb_batch_update
from repro.jaxcache.sharded import make_fleet_step

E, N, C, B, eta = 4, 128, 16, 32, 0.05
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 4)
step, f_shard, ids_shard = make_fleet_step(mesh, E, N, C, B, eta)
rng = np.random.default_rng(1)
f = jax.device_put(jnp.full((E, N), C / N, jnp.float32), f_shard)
states = [FractionalState.create(N, C) for _ in range(E)]
for i in range(3):
    ids = jnp.asarray(rng.integers(0, N, size=(E, B)), jnp.int32)
    f, rewards = step(jax.device_put(f, f_shard), jax.device_put(ids, ids_shard))
    for e in range(E):
        states[e], r = ogb_batch_update(states[e], ids[e], jnp.float32(eta), C)
        np.testing.assert_allclose(np.asarray(f[e]), np.asarray(states[e].f), atol=5e-5)
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


def test_fleet_step_warm_and_heterogeneous():
    """Warm-bracket Newton fleet step: (a) scalar eta/capacity and their
    (E,) broadcasts are bit-exact, (b) heterogeneous per-cache eta and
    capacity match the per-cache ``ogb_batch_update`` oracle, (c) the warm
    path tracks the cold bisection within bracket tolerance."""
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.jaxcache.fractional import FractionalState, ogb_batch_update
from repro.jaxcache.sharded import make_fleet_step

E, N, B = 4, 128, 32
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 4)
rng = np.random.default_rng(2)
ids_all = [jnp.asarray(rng.integers(0, N, size=(E, B)), jnp.int32)
           for _ in range(4)]

# (a) scalar params == (E,) broadcast, bit-exact
C, eta = 16, 0.05
step_s, f_sh, ids_sh = make_fleet_step(mesh, E, N, C, B, eta)
step_v, _, _ = make_fleet_step(
    mesh, E, N, jnp.full((E,), C, jnp.float32), B,
    jnp.full((E,), eta, jnp.float32))
f_s = jax.device_put(jnp.full((E, N), C / N, jnp.float32), f_sh)
f_v = f_s
for ids in ids_all:
    ids = jax.device_put(ids, ids_sh)
    f_s, r_s = step_s(f_s, ids)
    f_v, r_v = step_v(f_v, ids)
assert np.array_equal(np.asarray(f_s), np.asarray(f_v)), "broadcast drift"
assert np.array_equal(np.asarray(r_s), np.asarray(r_v))

# (b) heterogeneous (E,) eta/capacity vs the per-cache oracle
caps = jnp.asarray([8.0, 16.0, 24.0, 32.0], jnp.float32)
etas = jnp.asarray([0.02, 0.05, 0.08, 0.11], jnp.float32)
step_h, f_sh, ids_sh = make_fleet_step(mesh, E, N, caps, B, etas)
f = jnp.stack([jnp.full((N,), float(c) / N, jnp.float32) for c in caps])
f = jax.device_put(f, f_sh)
states = [FractionalState.create(N, int(c)) for c in caps]
for ids in ids_all:
    f, _ = step_h(f, jax.device_put(ids, ids_sh))
    for e in range(E):
        states[e], _ = ogb_batch_update(
            states[e], ids[e], etas[e], int(caps[e]))
        np.testing.assert_allclose(
            np.asarray(f[e]), np.asarray(states[e].f), atol=5e-5)

# (c) warm-start Newton vs cold bisection on the same stream
warm, f_sh, ids_sh, tau_sh = make_fleet_step(
    mesh, E, N, caps, B, etas, warm_start=True)
f_w = jax.device_put(
    jnp.stack([jnp.full((N,), float(c) / N, jnp.float32) for c in caps]),
    f_sh)
tau = jax.device_put(jnp.zeros((E,), jnp.float32), tau_sh)
f_c = f_w
for ids in ids_all:
    f_w, _, tau = warm(f_w, jax.device_put(ids, ids_sh), tau)
    f_c, _ = step_h(f_c, jax.device_put(ids, ids_sh))
drift = float(jnp.max(jnp.abs(f_w - f_c)))
assert drift < 1e-4, f"warm/cold drift {drift}"
assert bool(jnp.all(tau >= 0.0)), "negative dual variable"
# the warm path must actually hit the per-cache capacity constraints
mass = jnp.sum(f_w, axis=1)
np.testing.assert_allclose(np.asarray(mass), np.asarray(caps), rtol=1e-4)
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
