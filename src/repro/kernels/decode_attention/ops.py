"""jit'd wrapper for GQA flash-decode attention."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_S_BLOCK, _grid_decode


@functools.partial(jax.jit, static_argnames=("s_block", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,  # (B, S, Hkv, D)
    lengths: jax.Array,  # (B,) int32
    s_block: int = DEFAULT_S_BLOCK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-token GQA attention over a (possibly padded) KV cache.

    ``interpret=None`` selects by backend: compiled Pallas on TPU, the
    interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, D = q.shape
    S = k.shape[1]
    if q.shape[0] != k.shape[0]:
        raise ValueError("batch mismatch")
    if H % k.shape[2]:
        raise ValueError("H must be a multiple of Hkv")
    s_blk = min(s_block, S)
    pad = (-S) % s_blk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return _grid_decode(q, k, v, lengths, s_blk, interpret)
