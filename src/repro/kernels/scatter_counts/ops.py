"""jit'd wrapper for the Pallas histogram."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK_ROWS, DEFAULT_ID_CHUNK, LANES, _grid_histogram


@functools.partial(
    jax.jit,
    static_argnames=("catalog_size", "block_rows", "id_chunk", "interpret"),
)
def scatter_counts(
    ids: jax.Array,
    catalog_size: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    id_chunk: int = DEFAULT_ID_CHUNK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Dense float32 histogram of ``ids`` over ``[0, catalog_size)``.

    Negative ids are padding and ignored (they never match a catalog slot).
    ``interpret=None`` selects by backend: compiled Pallas on TPU, the
    interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b = ids.shape[0]
    pad_b = (-b) % id_chunk
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, pad_b), constant_values=-1)
    block = block_rows * LANES
    n_blocks = -(-catalog_size // block)
    out2 = _grid_histogram(ids_p, n_blocks, block_rows, id_chunk, interpret)
    return out2.reshape(-1)[:catalog_size]
