"""Catalog-sharded OGB across a TPU mesh (shard_map + one psum per iteration).

The fractional cache state ``f`` (catalog of N items) is sharded across every
mesh axis; request batches are replicated (single logical cache) or sharded
over ``pod`` with a cross-pod count reduction.  Each bisection iteration of
the capped-simplex projection needs exactly one scalar ``psum`` — everything
else is local to the shard, so the step is bandwidth-bound on the catalog
sweep and scales to catalogs of 10^9+ items across pods.

Also provides the *cache-fleet* form: E independent edge caches sharded over
the ``data`` axis, each with the catalog sharded over ``model`` — the
deployment shape for a CDN fleet.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .fractional import DEFAULT_BISECT_ITERS, DEFAULT_WARM_SWEEPS, warm_bracket_hi

def _local_histogram(
    ids: jax.Array, shard_size: int, offset: jax.Array
) -> jax.Array:
    """Histogram of the ids that fall inside [offset, offset+shard_size)."""
    local = ids - offset
    inb = (local >= 0) & (local < shard_size)
    idx = jnp.where(inb, local, 0)
    return jnp.zeros(shard_size, jnp.float32).at[idx].add(inb.astype(jnp.float32))


def make_sharded_step(
    mesh: Mesh,
    catalog_size: int,
    capacity: int,
    batch: int,
    eta: float,
    iters: int = DEFAULT_BISECT_ITERS,
    pod_axis: Optional[str] = None,
    warm_start: bool = False,
    sweeps: int = DEFAULT_WARM_SWEEPS,
):
    """Build the jitted sharded OGB step for `mesh`.

    Returns (step_fn, f_sharding) where step_fn(f, ids) -> (f', reward).
    ``f`` is (N,) sharded over every mesh axis; ``ids`` is (B,) replicated
    (or (B,) globally with pod-sharding when ``pod_axis`` is given).

    With ``warm_start=True`` the step becomes
    ``step_fn(f, ids, tau_prev) -> (f', reward, tau)``: the projection uses
    the provable warm bracket [0, eta*B] seeded at ``tau_prev`` and a
    bracketed-Newton iteration (one psum of the stacked (mass, interior-count)
    pair per sweep), so ``sweeps`` single-digit catalog sweeps replace
    ``iters`` ~50 bisection sweeps — one psum saved per sweep avoided.
    """
    axes = tuple(mesh.axis_names)
    n_dev = mesh.size
    if catalog_size % n_dev:
        raise ValueError(f"catalog {catalog_size} must divide devices {n_dev}")
    shard_size = catalog_size // n_dev
    f_spec = P(axes)  # (N,) sharded over the flattened device grid
    ids_spec = P(pod_axis) if pod_axis else P()
    eta_f = jnp.float32(eta)
    cap = float(capacity)

    def _local_prologue(f_local: jax.Array, ids: jax.Array):
        if pod_axis is not None:
            # each pod ingests its own request slice; the catalog range owned
            # by a device is globally unique, so every device must see every
            # id — one cheap DCN all-gather of the (B/pods,) int32 ids.
            ids = jax.lax.all_gather(ids, pod_axis, tiled=True)

        # flattened linear device index = position of this shard in f
        dev_linear = jnp.zeros((), jnp.int32)
        stride = 1
        for ax in reversed(axes):
            dev_linear = dev_linear + jax.lax.axis_index(ax) * stride
            stride *= mesh.shape[ax]
        offset = dev_linear * shard_size

        counts = _local_histogram(ids, shard_size, offset)

        # reward = sum_t f[r_t] at the pre-update state (only in-range ids)
        local = ids - offset
        inb = (local >= 0) & (local < shard_size)
        reward = jnp.sum(
            jnp.where(inb, f_local[jnp.where(inb, local, 0)], 0.0)
        )
        reward = jax.lax.psum(reward, axes)
        return f_local + eta_f * counts, reward

    def local_step(f_local: jax.Array, ids: jax.Array):
        y, reward = _local_prologue(f_local, ids)

        lo = jnp.float32(0.0)
        hi = jnp.float32(1.0) + eta_f * jnp.float32(batch)

        def body(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            mass = jax.lax.psum(jnp.sum(jnp.clip(y - mid, 0.0, 1.0)), axes)
            too_much = mass >= cap
            return jnp.where(too_much, mid, lo), jnp.where(too_much, hi, mid)

        lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
        tau = 0.5 * (lo + hi)
        return jnp.clip(y - tau, 0.0, 1.0), reward

    def local_step_warm(f_local: jax.Array, ids: jax.Array, tau_prev: jax.Array):
        y, reward = _local_prologue(f_local, ids)

        # provable per-step bracket for a feasible f: tau in [0, eta*B];
        # the carries stay replicated (mass/cnt are psum'd over every axis)
        lo = jnp.float32(0.0)
        hi = warm_bracket_hi(eta_f * jnp.float32(batch))
        t = jnp.clip(tau_prev, lo, hi)

        def body(_, carry):
            lo, hi, t = carry
            z = y - t
            part = jnp.stack(
                [
                    jnp.sum(jnp.clip(z, 0.0, 1.0)),
                    jnp.sum(
                        jnp.logical_and(z > 0.0, z < 1.0).astype(jnp.float32)
                    ),
                ]
            )
            mass, cnt = jax.lax.psum(part, axes)  # one psum per sweep
            too_much = mass >= cap
            lo = jnp.where(too_much, t, lo)
            hi = jnp.where(too_much, hi, t)
            t_newton = t + (mass - cap) / jnp.maximum(cnt, 1.0)
            t_mid = 0.5 * (lo + hi)
            ok = jnp.logical_and(
                cnt > 0.0, jnp.logical_and(t_newton >= lo, t_newton <= hi)
            )
            return lo, hi, jnp.where(ok, t_newton, t_mid)

        _lo, _hi, tau = jax.lax.fori_loop(0, sweeps, body, (lo, hi, t))
        return jnp.clip(y - tau, 0.0, 1.0), reward, tau

    if warm_start:
        shard_fn = jax.shard_map(
            local_step_warm,
            mesh=mesh,
            in_specs=(f_spec, ids_spec, P()),
            out_specs=(f_spec, P(), P()),
        )
    else:
        shard_fn = jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(f_spec, ids_spec),
            out_specs=(f_spec, P()),
        )
    step = jax.jit(shard_fn)
    f_sharding = NamedSharding(mesh, f_spec)
    return step, f_sharding


def _per_cache_param(value, n_caches: int, name: str) -> jax.Array:
    """Normalize a scalar or (E,) per-cache parameter to an (E,) f32 array."""
    arr = jnp.asarray(value, jnp.float32)
    if arr.ndim == 0:
        return jnp.full((n_caches,), arr)
    if arr.shape != (n_caches,):
        raise ValueError(
            f"{name} must be a scalar or an ({n_caches},) array, got shape "
            f"{arr.shape}"
        )
    return arr


def make_fleet_step(
    mesh: Mesh,
    n_caches: int,
    catalog_size: int,
    capacity,
    batch: int,
    eta,
    iters: int = DEFAULT_BISECT_ITERS,
    cache_axis: str = "data",
    catalog_axis: str = "model",
    warm_start: bool = False,
    sweeps: int = DEFAULT_WARM_SWEEPS,
):
    """E independent edge caches: f (E, N), ids (E, B). Per-cache projection.

    Caches shard over ``cache_axis``; the catalog dimension shards over
    ``catalog_axis``; the projection psum reduces over the catalog axis only,
    so caches never synchronize with each other (embarrassingly parallel
    across the fleet, as a real CDN deployment would be).

    ``eta`` and ``capacity`` may each be a scalar (one value for the whole
    fleet) or an ``(E,)`` array (heterogeneous edge nodes).  Scalars are
    broadcast to ``(E,)`` internally, which is bitwise identical to the old
    scalar-only path.

    With ``warm_start=True`` the step becomes
    ``step(f, ids, tau_prev) -> (f', reward, tau)`` with ``tau_prev``/``tau``
    of shape ``(E,)``: each cache's projection runs the bracketed-Newton
    iteration inside the provable warm bracket [0, eta_e*B], with a single
    psum of the stacked per-cache (mass, interior-count) pair per sweep —
    ``sweeps`` single-digit catalog sweeps instead of ``iters`` ~50 cold
    bisection sweeps, and half the psums per sweep.  The fourth return value
    is the (E,) tau sharding.
    """
    if n_caches % mesh.shape[cache_axis]:
        raise ValueError("n_caches must divide the cache axis")
    if catalog_size % mesh.shape[catalog_axis]:
        raise ValueError("catalog must divide the catalog axis")
    shard_n = catalog_size // mesh.shape[catalog_axis]
    eta_all = _per_cache_param(eta, n_caches, "eta")
    cap_all = _per_cache_param(capacity, n_caches, "capacity")

    def _prologue(f_local: jax.Array, ids_local: jax.Array, eta_c: jax.Array):
        # f_local: (E_loc, N_loc); ids_local: (E_loc, B); eta_c: (E_loc,)
        offset = jax.lax.axis_index(catalog_axis) * shard_n

        def counts_and_reward(f_c, ids_c):
            local = ids_c - offset
            inb = (local >= 0) & (local < shard_n)
            idx = jnp.where(inb, local, 0)
            counts = jnp.zeros(shard_n, jnp.float32).at[idx].add(
                inb.astype(jnp.float32)
            )
            reward_part = jnp.sum(jnp.where(inb, f_c[idx], 0.0))
            return counts, reward_part

        counts, reward_part = jax.vmap(counts_and_reward)(f_local, ids_local)
        reward = jax.lax.psum(reward_part, catalog_axis)  # (E_loc,)
        y = f_local + eta_c[:, None] * counts  # (E_loc, N_loc)
        return y, reward

    def local_step(
        f_local: jax.Array,
        ids_local: jax.Array,
        eta_c: jax.Array,
        cap_c: jax.Array,
    ):
        y, reward = _prologue(f_local, ids_local, eta_c)
        lo = jnp.zeros_like(eta_c)
        hi = 1.0 + eta_c * jnp.float32(ids_local.shape[1])

        def body(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            mass = jax.lax.psum(
                jnp.sum(jnp.clip(y - mid[:, None], 0.0, 1.0), axis=1),
                catalog_axis,
            )
            pred = mass >= cap_c
            return jnp.where(pred, mid, lo), jnp.where(pred, hi, mid)

        lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
        tau = 0.5 * (lo + hi)
        return jnp.clip(y - tau[:, None], 0.0, 1.0), reward

    def local_step_warm(
        f_local: jax.Array,
        ids_local: jax.Array,
        tau_prev: jax.Array,
        eta_c: jax.Array,
        cap_c: jax.Array,
    ):
        y, reward = _prologue(f_local, ids_local, eta_c)
        # provable per-cache bracket for a feasible f: tau_e in [0, eta_e*B]
        lo = jnp.zeros_like(eta_c)
        hi = warm_bracket_hi(eta_c * jnp.float32(ids_local.shape[1]))
        t = jnp.clip(tau_prev, lo, hi)

        def body(_, carry):
            lo, hi, t = carry
            z = y - t[:, None]
            part = jnp.stack(
                [
                    jnp.sum(jnp.clip(z, 0.0, 1.0), axis=1),
                    jnp.sum(
                        jnp.logical_and(z > 0.0, z < 1.0).astype(jnp.float32),
                        axis=1,
                    ),
                ]
            )  # (2, E_loc)
            mass, cnt = jax.lax.psum(part, catalog_axis)  # one psum per sweep
            too_much = mass >= cap_c
            lo = jnp.where(too_much, t, lo)
            hi = jnp.where(too_much, hi, t)
            t_newton = t + (mass - cap_c) / jnp.maximum(cnt, 1.0)
            t_mid = 0.5 * (lo + hi)
            ok = jnp.logical_and(
                cnt > 0.0, jnp.logical_and(t_newton >= lo, t_newton <= hi)
            )
            return lo, hi, jnp.where(ok, t_newton, t_mid)

        _lo, _hi, tau = jax.lax.fori_loop(0, sweeps, body, (lo, hi, t))
        return jnp.clip(y - tau[:, None], 0.0, 1.0), reward, tau

    f_spec = P(cache_axis, catalog_axis)
    ids_spec = P(cache_axis, None)
    par_spec = P(cache_axis)  # per-cache params slice with their cache

    if warm_start:
        shard_fn = jax.shard_map(
            local_step_warm,
            mesh=mesh,
            in_specs=(f_spec, ids_spec, par_spec, par_spec, par_spec),
            out_specs=(f_spec, par_spec, par_spec),
        )

        def step_warm(f, ids, tau_prev):
            return shard_fn(f, ids, tau_prev, eta_all, cap_all)

        return (
            jax.jit(step_warm),
            NamedSharding(mesh, f_spec),
            NamedSharding(mesh, ids_spec),
            NamedSharding(mesh, par_spec),
        )

    shard_fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(f_spec, ids_spec, par_spec, par_spec),
        out_specs=(f_spec, par_spec),
    )

    def step_cold(f, ids):
        return shard_fn(f, ids, eta_all, cap_all)

    step = jax.jit(step_cold)
    return step, NamedSharding(mesh, f_spec), NamedSharding(mesh, ids_spec)
