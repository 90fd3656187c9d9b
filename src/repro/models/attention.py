"""GQA/MQA attention: chunked-flash training path + KV-cache decode path.

The training/prefill path is a pure-jnp flash formulation (online softmax over
KV chunks inside a scan over Q chunks) so 32k-token prefill never materializes
an (S x S) score matrix; the decode path attends one token over a cached KV —
optionally via the Pallas flash-decode kernel (repro.kernels.decode_attention).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard

from .common import apply_rope, dense_init, rmsnorm, rmsnorm_init

NEG_INF = -1e30

# Backend switch for full-sequence causal attention: the pure-jnp chunked
# flash (default; shardable via GSPMD) or the Pallas flash_prefill kernel
# (compiled on TPU, interpreted elsewhere). Toggle via
# set_pallas_prefill(True) — parity is tested in tests/models.
_PALLAS_PREFILL = False


def set_pallas_prefill(enabled: bool) -> None:
    global _PALLAS_PREFILL
    _PALLAS_PREFILL = bool(enabled)


def init_attention(key, cfg, dtype) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kv * hd, dtype),
        "wv": dense_init(ks[2], d, kv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, h, hd)
    k = (x @ p["wk"]).reshape(B, S, kv, hd)
    v = (x @ p["wv"]).reshape(B, S, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, Hkv, D)
    v: jax.Array,  # (B, T, Hkv, D)
    causal: bool,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    q_offset: int = 0,
) -> jax.Array:
    """Chunked online-softmax attention in pure jnp (no S x T buffer).

    §Perf H1 (EXPERIMENTS.md): the head dim stays FLAT (B, S, H, D) end to
    end, sharded over "model" when H divides; GQA is realized by broadcasting
    each KV head to its q-group *inside* the kv-chunk loop.  Since k/v heads
    are replicated, the broadcast+slice is local to every shard: the kv-loop
    carries (m, l, acc) stay H-sharded and no per-iteration collectives are
    generated (the (Hkv, g) reshape of the baseline forced a layer-wide
    reshard of q/scores/acc every chunk).
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if _PALLAS_PREFILL and causal and q_offset == 0 and S == T:
        from repro.kernels.flash_prefill.ops import flash_prefill

        return flash_prefill(q, k, v)
    g = H // Hkv
    scale = 1.0 / math.sqrt(D)
    qc = min(q_chunk, S)
    kc = min(kv_chunk, T)
    s_pad = (-S) % qc
    t_pad = (-T) % kc
    qp = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    n_q, n_k = qp.shape[1] // qc, kp.shape[1] // kc

    q5 = qp.reshape(B, n_q, qc, H, D).astype(jnp.float32)
    k5 = kp.reshape(B, n_k, kc, Hkv, D).astype(jnp.float32)
    v5 = vp.reshape(B, n_k, kc, Hkv, D).astype(jnp.float32)
    q5 = shard(q5, "batch", None, None, "heads", None)

    def _expand_kv(blk):  # (B, kc, Hkv, D) -> (B, kc, H, D), local per shard
        out = jnp.broadcast_to(
            blk[:, :, :, None, :], (B, kc, Hkv, g, D)
        ).reshape(B, kc, H, D)
        return shard(out, "batch", None, "heads", None)

    def q_block(qi, q_blk):
        # q_blk: (B, qc, H, D)
        q_pos = q_offset + qi * qc + jnp.arange(qc)

        def kv_block(carry, inputs):
            m, l, acc = carry
            ki, k_blk, v_blk = inputs
            k_rep = _expand_kv(k_blk)
            v_rep = _expand_kv(v_blk)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_rep) * scale
            s = shard(s, "batch", "heads", None, None)
            k_pos = ki * kc + jnp.arange(kc)
            valid = (k_pos < T)[None, :]  # mask the T-padding keys
            if causal:
                mask = (k_pos[None, :] <= q_pos[:, None]) & valid
            else:
                mask = jnp.broadcast_to(valid, (qc, kc))
            s = jnp.where(mask[None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = alpha[..., None] * acc + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_rep
            )
            acc_new = shard(acc_new, "batch", "heads", None, None)
            return (m_new, l_new, acc_new), None

        m0 = shard(jnp.full((B, H, qc), NEG_INF, jnp.float32),
                   "batch", "heads", None)
        l0 = shard(jnp.zeros((B, H, qc), jnp.float32), "batch", "heads", None)
        a0 = shard(jnp.zeros((B, H, qc, D), jnp.float32),
                   "batch", "heads", None, None)
        ks = jnp.arange(n_k)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (ks, jnp.moveaxis(k5, 1, 0), jnp.moveaxis(v5, 1, 0))
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)  # (B, H, qc, D)
        return jnp.moveaxis(out, 2, 1)  # (B, qc, H, D)

    outs = jax.lax.map(
        lambda args: q_block(args[0], args[1]),
        (jnp.arange(n_q), jnp.moveaxis(q5, 1, 0)),
    )  # (n_q, B, qc, H, D)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_q * qc, H, D)
    return out[:, :S].astype(q.dtype)


def attention_forward(
    p: Dict,
    x: jax.Array,  # (B, S, d_model)
    cfg,
    positions: jax.Array,  # (B, S)
    causal: bool = True,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn KV override
) -> jax.Array:
    """Full-sequence attention (training / prefill / encoder / cross)."""
    B, S, _ = x.shape
    if kv is None:
        q, k, v = _project_qkv(p, x, cfg, positions)
    else:
        h, hd = cfg.n_heads, cfg.head_dim
        q = (x @ p["wq"]).reshape(B, S, h, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"])
        q = apply_rope(q, positions, cfg.rope_theta)
        k, v = kv
    out = flash_attention(q, k, v, causal=causal)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return shard(out @ p["wo"], "batch", None, None)


def project_cross_kv(p: Dict, enc: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Encoder-output K/V for cross-attention (computed once per utterance)."""
    B, T, _ = enc.shape
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    k = (enc @ p["wk"]).reshape(B, T, kvh, hd)
    v = (enc @ p["wv"]).reshape(B, T, kvh, hd)
    if cfg.qk_norm:
        k = rmsnorm(k, p["k_norm"])
    return k, v


def init_kv_cache(cfg, batch: int, max_len: int, dtype) -> Dict:
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    if getattr(cfg, "kv_cache_dtype", "compute") == "int8":
        return {
            "k": jnp.zeros((batch, max_len, kvh, hd), jnp.int8),
            "v": jnp.zeros((batch, max_len, kvh, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, max_len, kvh), jnp.float32),
            "v_scale": jnp.zeros((batch, max_len, kvh), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, max_len, kvh, hd), dtype),
        "v": jnp.zeros((batch, max_len, kvh, hd), dtype),
    }


def _quantize_kv(x: jax.Array):
    """Per-token-per-head symmetric int8: x ~ (B, S, Hkv, D)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def attention_decode(
    p: Dict,
    x: jax.Array,  # (B, 1, d_model)
    cache: Dict,  # {"k": (B, S, Hkv, D), "v": ...}
    position: jax.Array,  # () or (B,) current index
    cfg,
    use_pallas: bool = False,
) -> Tuple[jax.Array, Dict]:
    """One-token decode over the KV cache; returns (out, updated cache)."""
    B = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos_b = jnp.broadcast_to(jnp.asarray(position), (B,))
    q = (x @ p["wq"]).reshape(B, 1, h, hd)
    k = (x @ p["wk"]).reshape(B, 1, kvh, hd)
    v = (x @ p["wv"]).reshape(B, 1, kvh, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = apply_rope(q, pos_b[:, None], cfg.rope_theta)
    k = apply_rope(k, pos_b[:, None], cfg.rope_theta)

    quantized = "k_scale" in cache
    new_cache = {}
    if quantized:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        ck_q = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, position, axis=1)
        cv_q = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, position, axis=1)
        ks_c = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, position, axis=1
        )
        vs_c = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, position, axis=1
        )
        ck_q = shard(ck_q, "batch", "kv_seq", "kv_heads", None)
        cv_q = shard(cv_q, "batch", "kv_seq", "kv_heads", None)
        new_cache = {"k": ck_q, "v": cv_q, "k_scale": ks_c, "v_scale": vs_c}
        # dequantize for the attention math (reads 1B + scale vs 2B per elem)
        ck = ck_q.astype(jnp.float32) * ks_c[..., None]
        cv = cv_q.astype(jnp.float32) * vs_c[..., None]
        ck = ck.astype(x.dtype)
        cv = cv.astype(x.dtype)
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), position, axis=1
        )
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), position, axis=1
        )
        ck = shard(ck, "batch", "kv_seq", "kv_heads", None)
        cv = shard(cv, "batch", "kv_seq", "kv_heads", None)
        new_cache = {"k": ck, "v": cv}
    lengths = pos_b + 1

    if use_pallas:
        from repro.kernels.decode_attention.ops import decode_attention

        out = decode_attention(q[:, 0], ck, cv, lengths.astype(jnp.int32))
    else:
        S = ck.shape[1]
        g = h // kvh
        qg = q.reshape(B, kvh, g, hd).astype(jnp.float32)
        scale = 1.0 / math.sqrt(hd)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, ck.astype(jnp.float32)) * scale
        mask = jnp.arange(S)[None, :] < lengths[:, None]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", w, cv.astype(jnp.float32))
        out = out.reshape(B, h, hd)

    out = out.reshape(B, 1, h * hd).astype(x.dtype)
    return out @ p["wo"], new_cache
