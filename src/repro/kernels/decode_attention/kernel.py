"""Pallas TPU GQA flash-decode attention kernel.

The serving hot spot that the OGB KV-page policy feeds (DESIGN.md §4): one
query token per sequence attends over a long KV cache.  The op is strictly
memory-bound (arithmetic intensity ~= 2 q-heads-per-kv FLOP per KV byte), so
the kernel's job is to stream K/V blocks HBM->VMEM exactly once with online
softmax in fp32 accumulators.

Grid: (batch, kv_head, s_blocks) with the s dimension innermost (TPU executes
the grid sequentially, so VMEM scratch carries the running max / denominator /
accumulator across s-blocks — the standard flash pattern).  The q-group
(H/Hkv queries sharing one kv head) rides along, giving the MXU a
(group x D) @ (D x s_blk) matmul per block.  K/V are read head-major,
(B, Hkv, S, D), so a block's last two dims are (s_blk, D) as the TPU tiling
requires; the per-sequence lengths are scalar-prefetched into SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_S_BLOCK = 512
NEG_INF = -1e30


def decode_kernel(
    len_ref,  # (B,) int32, SMEM
    q_ref,  # (1, 1, group, D)
    k_ref,  # (1, 1, s_blk, D)
    v_ref,  # (1, 1, s_blk, D)
    out_ref,  # (1, 1, group, D)
    m_scr,  # (group, 1) f32 running max
    l_scr,  # (group, 1) f32 running denominator
    acc_scr,  # (group, D) f32 running numerator
    *,
    s_block: int,
    n_s_blocks: int,
):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (group, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (s_blk, D)
    v = v_ref[0, 0].astype(jnp.float32)  # (s_blk, D)
    length = len_ref[pl.program_id(0)]

    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (group, s_blk)

    pos = s * s_block + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < length, scores, NEG_INF)

    m_prev = m_scr[...]  # (group, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)  # (group, 1)
    p = jnp.exp(scores - m_new)  # (group, s_blk)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_new = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(s == n_s_blocks - 1)
    def _finalize():
        out_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            out_ref.dtype
        )


def _grid_decode(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,
    lengths: jax.Array,  # (B,)
    s_block: int,
    interpret: bool,
):
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    n_s = S // s_block
    q4 = q.reshape(B, Hkv, group, D)
    kt = jnp.swapaxes(k, 1, 2)  # (B, Hkv, S, D)
    vt = jnp.swapaxes(v, 1, 2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, group, D), lambda b, h, s, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, s_block, D), lambda b, h, s, lens: (b, h, s, 0)),
            pl.BlockSpec((1, 1, s_block, D), lambda b, h, s, lens: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, D), lambda b, h, s, lens: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(decode_kernel, s_block=s_block, n_s_blocks=n_s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q4, kt, vt)
    return out.reshape(B, H, D)
