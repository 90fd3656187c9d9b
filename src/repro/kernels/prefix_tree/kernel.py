"""Pallas TPU kernels for the prefix-tree family.

Two batch-level passes dominate the tree engines' device profile once the
per-request scatter/gather paths are in place, and both are plain block
reductions — exactly the shape Pallas is good at:

  * ``segsum_kernel`` — the tree *build* reduction: one level of the packed
    radix tree from its child level, each output node summing a contiguous
    ``radix`` group.  Used by ``ops.tree_build(..., use_kernel=True)`` for
    full rebuilds (compaction / re-anchoring); the jnp reshape-sum fallback
    is bit-identical.

  * ``bucket_mass_kernel`` — the lazy-OGB threshold solve: for K candidate
    thresholds, ``mass(t) = sum_b cnt_b * clip(mean_b - t, 0, 1)`` over the
    (V,) bucket-count / bucket-sum arrays, accumulated across grid blocks
    into one (K, 128) lane-partial output (TPU revisiting-output pattern,
    mirroring ``capped_simplex.kernel.mass_kernel``).  K-way bracketing over buckets
    replaces K full-catalog sweeps of the dense projection.

Blocks keep the 128-lane layout of the capped_simplex kernels; inputs are
padded host-side.  CPU hot paths use the jnp forms in :mod:`.ops` — these
kernels are the TPU artifacts, validated in interpret mode by the tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 256
#: rows per tree-build block: a 1-D f32 output is tiled by 1024 on TPU
SEGSUM_BLOCK_ROWS = 1024


def _auto_interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def segsum_kernel(x_ref, out_ref):
    """Sum each row of a (block_rows, radix) child block into one node."""
    out_ref[...] = jnp.sum(x_ref[...], axis=1)


def block_segment_sums(values: jax.Array, out_size: int, radix: int, *,
                       block_rows: int = SEGSUM_BLOCK_ROWS,
                       interpret: Optional[bool] = None) -> jax.Array:
    """One tree-build reduction level: (out_size,) per-group sums of a
    child level, groups of ``radix`` consecutive children."""
    interpret = _auto_interpret(interpret)
    pad_rows = -out_size % block_rows
    x2 = jnp.pad(values, (0, out_size * radix - values.shape[0]))
    x2 = jnp.pad(x2.reshape(out_size, radix), ((0, pad_rows), (0, 0)))
    rows = x2.shape[0]
    out = pl.pallas_call(
        segsum_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, radix), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((rows,), values.dtype),
        interpret=interpret,
    )(x2)
    return out[:out_size]


def bucket_mass_kernel(cnt_ref, sum_ref, taus_ref, mass_ref, *, k: int):
    """Accumulate sum_b cnt_b * clip(mean_b - tau_j, 0, 1) over blocks into
    (K, 128) lane partials (row j for tau_j; candidates are SMEM scalars)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        mass_ref[...] = jnp.zeros_like(mass_ref)

    cnt = cnt_ref[...].astype(jnp.float32)
    tot = sum_ref[...].astype(jnp.float32)
    mean = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), 0.0)
    for j in range(k):
        z = jnp.clip(mean - taus_ref[j], 0.0, 1.0)
        mass_ref[j:j + 1, :] += jnp.sum(cnt * z, axis=0, keepdims=True)


def bucket_masses(cnt: jax.Array, total: jax.Array, taus: jax.Array, *,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: Optional[bool] = None) -> jax.Array:
    """mass(tau_j) = sum_b cnt_b * clip(mean_b - tau_j, 0, 1) for K
    candidate thresholds over (V,) bucket count/sum arrays, one pass."""
    interpret = _auto_interpret(interpret)
    k = taus.shape[0]
    v = cnt.shape[0]
    cols = block_rows * LANES
    pad = -v % cols
    c2 = jnp.pad(cnt, (0, pad)).reshape(-1, LANES).astype(jnp.float32)
    s2 = jnp.pad(total, (0, pad)).reshape(-1, LANES).astype(jnp.float32)
    rows = c2.shape[0]
    mass = pl.pallas_call(
        functools.partial(bucket_mass_kernel, k=k),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((k, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, LANES), jnp.float32),
        interpret=interpret,
    )(c2, s2, taus.astype(jnp.float32))
    return jnp.sum(mass, axis=1)
