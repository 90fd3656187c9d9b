"""Pallas TPU kernels for the fused OGB capped-simplex projection.

TPU adaptation of the paper's projection (DESIGN.md §3): instead of K
bisection sweeps over HBM (the naive form — each sweep reads the whole
catalog), one *grid-mass* kernel evaluates the constraint function

    g(tau_k) = sum(clip(f + eta*counts - tau_k, 0, 1)),   k = 0..K-1

for K candidate thresholds in a single pass with the block resident in VMEM,
raising arithmetic intensity from ~1 to ~K FLOP/byte (the op is otherwise
purely memory-bound).  A few passes of K-way bracketing + an exact piecewise-
linear interpolation inside the final bracket replace ~50 bisection sweeps
with 2-3 sweeps.

Kernels:
  * ``mass_kernel``  — per-block partial masses + interior counts for K taus,
    accumulated across the grid into one (K, 128) lane-partial output block
    (TPU revisiting-output pattern); the candidates are scalars in SMEM.
  * ``apply_kernel`` — elementwise f' = clip(f + eta*counts - tau, 0, 1).

Blocks are (block_rows, 128) f32: 128-lane aligned for the VPU; the default
(256, 128) keeps f+counts+K-chunk intermediates well under VMEM (~1 MiB).

Warm-start invariant (used by ``ops.fused_ogb_update(tau0=...)`` and the
scan-replay engine): for a *feasible* pre-step state f (sum f = C,
0 <= f <= 1) and y = f + eta*counts with counts >= 0, the projection
threshold satisfies

    0 <= tau <= eta * sum(counts)

because g(0) = sum(clip(y, 0, 1)) >= sum(f) = C (each coordinate can only
grow) and g(eta*sum(counts)) <= sum(f) = C (no coordinate grew by more than
the total step).  K-way bracketing over that width-(eta*B) interval needs
``passes=2`` instead of 3+ over width (1 + eta*B).  Note the *per-step*
threshold is NOT monotone across chained projections of the re-projected f
(only the cumulative threshold rho_t = sum_{s<=t} tau_s of the lazy,
accumulated-y formulation is), so the previous step's tau is a valid
initial *guess* but never a valid lower bracket — the scan replay seeds its
bracketed-Newton solver with it inside the provable [0, eta*B] bracket
(``repro.jaxcache.fractional.capped_simplex_project_warm``).  Cold bisection
to the same accuracy costs ~50 catalog sweeps; the warm forms need single
digits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 256
DEFAULT_K = 64


def mass_kernel(f_ref, c_ref, taus_ref, mass_ref, cnt_ref, *, eta: float, k: int):
    """Accumulate sum(clip(y - tau_j, 0, 1)) and interior counts over blocks.

    ``taus_ref`` is the (K,) candidate vector in SMEM; ``mass_ref`` and
    ``cnt_ref`` are (K, 128) lane-partial accumulators (row j for tau_j),
    summed over lanes by the wrapper, so every vector stays 2-D."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        mass_ref[...] = jnp.zeros_like(mass_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    y = f_ref[...].astype(jnp.float32) + jnp.float32(eta) * c_ref[...].astype(
        jnp.float32
    )
    for j in range(k):
        z = y - taus_ref[j]
        mass_ref[j:j + 1, :] += jnp.sum(
            jnp.clip(z, 0.0, 1.0), axis=0, keepdims=True
        )
        interior = jnp.logical_and(z > 0.0, z < 1.0).astype(jnp.float32)
        cnt_ref[j:j + 1, :] += jnp.sum(interior, axis=0, keepdims=True)


def apply_kernel(f_ref, c_ref, tau_ref, out_ref, *, eta: float):
    y = f_ref[...].astype(jnp.float32) + jnp.float32(eta) * c_ref[...].astype(
        jnp.float32
    )
    out_ref[...] = jnp.clip(y - tau_ref[0], 0.0, 1.0).astype(out_ref.dtype)


def _grid_masses(
    f2: jax.Array,
    c2: jax.Array,
    taus: jax.Array,
    eta: float,
    block_rows: int,
    interpret: bool,
):
    rows = f2.shape[0]
    k = taus.shape[0]
    grid = (rows // block_rows,)
    mass, cnt = pl.pallas_call(
        functools.partial(mass_kernel, eta=eta, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((k, LANES), lambda i: (0, 0)),
            pl.BlockSpec((k, LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, LANES), jnp.float32),
            jax.ShapeDtypeStruct((k, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(f2, c2, taus)
    return jnp.sum(mass, axis=1), jnp.sum(cnt, axis=1)


def _grid_apply(
    f2: jax.Array,
    c2: jax.Array,
    tau: jax.Array,
    eta: float,
    block_rows: int,
    interpret: bool,
):
    rows = f2.shape[0]
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(apply_kernel, eta=eta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(f2.shape, f2.dtype),
        interpret=interpret,
    )(f2, c2, tau.reshape(1))
