"""Pallas TPU causal GQA flash attention for prefill.

The prefill hot spot: S x S attention without materializing the score matrix.
Grid (B, H, n_q, n_k), kv innermost; VMEM scratch carries the online-softmax
state (m, l, acc) across kv blocks.  GQA needs no head replication at all:
the K/V BlockSpec index_map divides the q-head index by the group size, so
each q-head's grid step streams exactly its shared KV head.

Causality is exploited two ways:
  * fully-masked kv blocks (ki > qi) skip compute via pl.when,
  * the diagonal block applies the triangular mask; blocks below it skip
    masking entirely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def prefill_kernel(
    q_ref,  # (1, 1, bq, D)
    k_ref,  # (1, 1, bk, D)
    v_ref,  # (1, 1, bk, D)
    out_ref,  # (1, 1, bq, D)
    m_scr,  # (bq, 1)
    l_scr,  # (bq, 1)
    acc_scr,  # (bq, D)
    *,
    block_q: int,
    block_k: int,
    n_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    # position arithmetic (block_q and block_k may differ)
    q_start = qi * block_q
    q_last = q_start + block_q - 1
    k_start = ki * block_k
    k_last = k_start + block_k - 1

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(k_start <= q_last)  # skip fully-masked (future) kv blocks
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        def _update(s_blk, v_blk):
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
            p = jnp.exp(s_blk - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[...] = m_new

        @pl.when(k_last > q_start)  # block straddles the diagonal: mask
        def _mask_diag():
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            _update(jnp.where(k_pos <= q_pos, s, NEG_INF), v)

        @pl.when(k_last <= q_start)  # fully visible block
        def _no_mask():
            _update(s, v)

    @pl.when(ki == n_k - 1)
    def _finalize():
        out_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(out_ref.dtype)


def _grid_prefill(q, k, v, block_q, block_k, interpret):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    n_q = S // block_q
    n_k = S // block_k
    # head-major (B, heads, S, D): a block's last two dims are then
    # (block, D), as the TPU tiling requires
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))

    out = pl.pallas_call(
        functools.partial(
            prefill_kernel, block_q=block_q, block_k=block_k, n_k=n_k
        ),
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            # GQA: q-head h streams KV head h // g — no replication needed
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
