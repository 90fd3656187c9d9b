"""jit'd wrapper for the causal GQA prefill flash kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, _grid_prefill


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def flash_prefill(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,  # (B, S, Hkv, D)
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal GQA attention over a full prompt (no S x S buffer).

    ``interpret=None`` selects by backend: compiled Pallas on TPU, the
    interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, S, H, D = q.shape
    if H % k.shape[2]:
        raise ValueError("H must be a multiple of Hkv")
    bq = min(block_q, S)
    bk = min(block_k, S)
    pad = (-S) % max(bq, bk)
    if pad:
        # pad queries/keys; padded queries attend to nothing extra because
        # padded keys sit at positions > every real query under the causal
        # mask... except for padded q rows themselves, which are sliced off.
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = _grid_prefill(q, k, v, bq, bk, interpret)
    return out[:, :S]
