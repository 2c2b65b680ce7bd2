"""Attention: GQA/MQA, full-causal, sliding-window, cross; flash-style blocking.

Three execution paths, all numerically equivalent (tests assert it):

  * ``naive_attention``  — materialized scores; smoke tests / tiny shapes.
  * ``flash_attention``  — blockwise online-softmax (lax.scan over KV blocks
    inside a scan over Q blocks).  This is what the big shapes lower: score
    matrices never exceed (block_q x block_k), which is what makes
    prefill_32k compile within per-chip HBM.  It is the jnp twin of the
    Pallas ``local_attention`` kernel (kernels/local_attention.py) — the
    kernel is the TPU-target implementation, this is the oracle/mesh path.
  * decode path — single-query attention against a KV cache (ring buffer for
    sliding-window mixers).

GQA is computed in grouped form (B, S, n_kv, group, d) without repeating KV —
KV bytes stay at n_kv heads, which the roofline memory term rewards.
"""
from __future__ import annotations

import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import layers

NEG_INF = -1e30


class AttnParams(NamedTuple):
    pass  # params are plain dicts; NamedTuple kept out intentionally


def init_attention(key, cfg, dtype=jnp.float32, cross: bool = False):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "q": layers.init_dense(kq, d, cfg.q_dim, bias=cfg.qkv_bias, dtype=dtype),
        "k": layers.init_dense(kk, d, cfg.kv_dim, bias=cfg.qkv_bias, dtype=dtype),
        "v": layers.init_dense(kv, d, cfg.kv_dim, bias=cfg.qkv_bias, dtype=dtype),
        "o": layers.init_dense(ko, cfg.q_dim, d, bias=False, dtype=dtype),
    }
    del cross
    return p


def init_attention_lora(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, len(cfg.lora.targets))
    dims = {"q": cfg.q_dim, "k": cfg.kv_dim, "v": cfg.kv_dim, "o": cfg.d_model}
    d_in = {"q": cfg.d_model, "k": cfg.d_model, "v": cfg.d_model, "o": cfg.q_dim}
    return {
        t: layers.init_lora(k, d_in[t], dims[t], cfg.lora.rank, dtype)
        for t, k in zip(cfg.lora.targets, ks)
    }


# ---------------------------------------------------------------------------
# Score-level helpers
# ---------------------------------------------------------------------------


def _split_heads(x: jnp.ndarray, n_kv: int, group: int, head_dim: int) -> jnp.ndarray:
    b, s, _ = x.shape
    return jnp.reshape(x, (b, s, n_kv, group, head_dim))


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, s, n_kv, group, hd = x.shape
    return jnp.reshape(x, (b, s, n_kv * group * hd))


def naive_attention(
    q: jnp.ndarray,  # (B, Sq, n_kv, G, D)
    k: jnp.ndarray,  # (B, Sk, n_kv, D)
    v: jnp.ndarray,
    *,
    causal: bool,
    window: int = 0,
    q_offset=0,
    kv_len: Optional[jnp.ndarray] = None,
    scale: float = 0.0,
) -> jnp.ndarray:
    """Materialized-score attention (small shapes only).  ``scale`` of the
    scores: 0 -> 1/sqrt(head_dim)."""
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhgd,bshd->bhgqs", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    if kv_len is not None:
        mask &= k_pos[None, :] < kv_len
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqs,bshd->bqhgd", p, v)


# Triangular causal-block scheduling: Q-block i only scans KV blocks that can
# contain unmasked keys ([lower, i] for causal, window-clipped lower bound).
# Halves causal-attention FLOPs vs masked-full-loop; for sliding-window
# prefill the scan touches ~window/block_k blocks per query block.  Disable
# (full masked loop, §Perf baseline) with REPRO_FULL_ATTN_BLOCKS=1.
CAUSAL_BLOCK_SCHEDULE = os.environ.get("REPRO_FULL_ATTN_BLOCKS", "0") != "1"
MAX_UNROLLED_Q_BLOCKS = 128


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, n_kv, G, D)
    k: jnp.ndarray,  # (B, Sk, n_kv, D)
    v: jnp.ndarray,
    *,
    causal: bool,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    scale: float = 0.0,
) -> jnp.ndarray:
    """Blockwise online-softmax attention (jnp flash; mesh execution path)."""
    b, sq, n_kv, g, d = q.shape
    sk = k.shape[1]
    scale = scale or 1.0 / math.sqrt(d)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    nq, nk = sq_p // block_q, sk_p // block_k

    # (nq, B, bq, n_kv, G, D)
    qb = jnp.moveaxis(jnp.reshape(q, (b, nq, block_q, n_kv, g, d)), 1, 0)
    kb = jnp.moveaxis(jnp.reshape(k, (b, nk, block_k, n_kv, d)), 1, 0)
    vb = jnp.moveaxis(jnp.reshape(v, (b, nk, block_k, n_kv, d)), 1, 0)

    k_valid = jnp.arange(sk_p) < sk  # mask out key padding
    k_validb = jnp.reshape(k_valid, (nk, block_k))

    def q_block(iq, q_i, kbs, vbs, validbs, j0):
        """Online softmax over the given KV blocks (global index j0 + local)."""
        q_pos = q_offset + iq * block_q + jnp.arange(block_q)
        n_local = kbs.shape[0]

        def kv_step(carry, inputs):
            m, l, acc = carry
            jk, k_j, v_j, kvalid_j = inputs
            k_pos = jk * block_k + jnp.arange(block_k)
            s_ij = jnp.einsum("bqhgd,bshd->bhgqs", q_i, k_j).astype(jnp.float32) * scale
            mask = kvalid_j[None, :]
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            if window:
                mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
            s_ij = jnp.where(mask[None, None, None], s_ij, NEG_INF)
            m_ij = jnp.max(s_ij, axis=-1)  # (b,h,g,q)
            m_new = jnp.maximum(m, m_ij)
            alpha = jnp.exp(m - m_new)
            p_ij = jnp.exp(s_ij - m_new[..., None])
            l_new = l * alpha + jnp.sum(p_ij, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqs,bshd->bhgqd", p_ij.astype(v_j.dtype), v_j
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, n_kv, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, block_q), jnp.float32)
        a0 = jnp.zeros((b, n_kv, g, block_q, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (j0 + jnp.arange(n_local), kbs, vbs, validbs)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (b, n_kv, g, bq, d) -> (b, bq, n_kv, g, d)
        return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(q.dtype)

    triangular = (
        CAUSAL_BLOCK_SCHEDULE
        and causal
        and q_offset == 0
        and sq_p == sk_p
        and nq <= MAX_UNROLLED_Q_BLOCKS
        and nq > 1
    )
    if triangular:
        rows = []
        for i in range(nq):
            # Static KV range for this Q block: [j_lo, i] inclusive.
            j_lo = max(0, (i * block_q + 1 - window) // block_k) if window else 0
            rows.append(
                q_block(i, qb[i], kb[j_lo : i + 1], vb[j_lo : i + 1],
                        k_validb[j_lo : i + 1], j_lo)
            )
        outs = jnp.stack(rows, axis=0)
    else:
        outs = jax.lax.map(
            lambda args: q_block(args[0], args[1], kb, vb, k_validb, 0),
            (jnp.arange(nq), qb),
        )
    out = jnp.reshape(jnp.moveaxis(outs, 0, 1), (b, sq_p, n_kv, g, d))
    return out[:, :sq]


def decode_attention(
    q: jnp.ndarray,  # (B, 1, n_kv, G, D)
    k_cache: jnp.ndarray,  # (B, S_cache, n_kv, D)
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,  # per-batch or scalar valid length (after insert)
    *,
    window: int = 0,
    ring: bool = False,
    scale: float = 0.0,
) -> jnp.ndarray:
    """Single-token attention against a cache.

    For sliding-window mixers the cache is a ring buffer of size ``window``
    (``ring=True``): every slot is valid once the buffer has wrapped, and
    relative recency is irrelevant to softmax, so no positional mask is
    needed beyond validity.
    """
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhgd,bshd->bhgqs", q, k_cache).astype(jnp.float32) * scale
    s = k_cache.shape[1]
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.reshape(cache_len, (-1, 1))
    if window and not ring:
        valid = valid & (pos[None, :] > jnp.reshape(cache_len, (-1, 1)) - window)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqs,bshd->bqhgd", p, v_cache)


# ---------------------------------------------------------------------------
# Module-level apply
# ---------------------------------------------------------------------------

FLASH_THRESHOLD = 2048  # use blockwise path at / beyond this many kv positions


def apply_attention(
    params,
    lora,
    x: jnp.ndarray,
    cfg,
    *,
    positions: jnp.ndarray,  # (B, S) int32, or (3, B, S) for M-RoPE
    window: int = 0,
    cache=None,  # {"k","v"} ring/linear buffers for decode; None for train/prefill
    cache_index=None,  # scalar int32 write offset (tokens already in cache)
    encoder_out: Optional[jnp.ndarray] = None,  # cross-attention memory
    use_rope: bool = True,
    causal: bool = True,
    return_cache: bool = False,  # prefill: emit the decode KV cache
    is_cross: bool = False,
):
    """Returns (output, new_cache)."""
    from repro.models.kvcache import KVCache
    lora = lora or {}
    scale = cfg.lora.scale
    n_kv, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim_

    b, sq = x.shape[0], x.shape[1]
    q = _split_heads(layers.dense(x, params["q"], lora.get("q"), scale), n_kv, g, hd)

    if is_cross and cache is not None:
        # Cached cross-attention: encoder K/V were projected once at prefill.
        out = naive_attention(q, cache.k.astype(q.dtype), cache.v.astype(q.dtype),
                              causal=False, scale=cfg.attention_multiplier)
        out = _merge_heads(out)
        return layers.dense(out, params["o"], lora.get("o"), scale), cache

    kv_src = encoder_out if is_cross else x
    k = layers.dense(kv_src, params["k"], lora.get("k"), scale)
    v = layers.dense(kv_src, params["v"], lora.get("v"), scale)
    k = jnp.reshape(k, (b, k.shape[1], n_kv, hd))
    v = jnp.reshape(v, (b, v.shape[1], n_kv, hd))

    if use_rope and not is_cross:
        if cfg.mrope:
            q = layers.apply_mrope(
                jnp.reshape(q, (b, sq, n_kv * g, hd)), positions, cfg.rope_theta, cfg.mrope_sections
            ).reshape(b, sq, n_kv, g, hd)
            k = layers.apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = layers.apply_rope(
                jnp.reshape(q, (b, sq, n_kv * g, hd)), positions, cfg.rope_theta, cfg.rope_pct
            ).reshape(b, sq, n_kv, g, hd)
            k = layers.apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)

    new_cache = cache
    if cache is not None and not is_cross:
        # Decode: insert the new K/V then attend to the cache.
        from repro.models.kvcache import QuantKVCache, dequantize_kv, quantize_kv

        if isinstance(cache, QuantKVCache):
            slot = cache_index % cache.k_q.shape[1] if window else cache_index
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            upd = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
                buf, val.astype(buf.dtype), slot, 1
            )
            new_cache = QuantKVCache(
                k_q=upd(cache.k_q, kq), v_q=upd(cache.v_q, vq),
                k_scale=upd(cache.k_scale, ks), v_scale=upd(cache.v_scale, vs),
            )
            # Dequant is an elementwise producer of the attention dots — XLA
            # fuses it, so HBM reads stay int8-sized (a Pallas decode kernel
            # would guarantee the fusion on TPU).
            k_cache = dequantize_kv(new_cache.k_q, new_cache.k_scale, q.dtype)
            v_cache = dequantize_kv(new_cache.v_q, new_cache.v_scale, q.dtype)
        else:
            slot = cache_index % cache.k.shape[1] if window else cache_index
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k.astype(cache.k.dtype), slot, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v.astype(cache.v.dtype), slot, 1)
            new_cache = cache._replace(k=k_cache, v=v_cache)
        total = cache_index + sq
        ring = bool(window)
        cache_len = jnp.minimum(total, k_cache.shape[1]) if ring else total
        out = decode_attention(
            q, k_cache, v_cache, jnp.full((b,), cache_len), window=window, ring=ring,
            scale=cfg.attention_multiplier,
        )
    else:
        if max(sq, k.shape[1]) >= FLASH_THRESHOLD:
            out = flash_attention(q, k, v, causal=causal, window=window,
                                  scale=cfg.attention_multiplier)
        else:
            out = naive_attention(q, k, v, causal=causal, window=window,
                                  scale=cfg.attention_multiplier)
        if return_cache:
            if window and k.shape[1] >= window:
                # Ring layout: decode writes token t at slot t % window, so
                # the trimmed prefill keys must land at those slots too.
                s_total = k.shape[1]
                kc = jnp.roll(k[:, -window:], shift=s_total % window, axis=1)
                vc = jnp.roll(v[:, -window:], shift=s_total % window, axis=1)
            else:
                kc, vc = k, v
            if getattr(cfg, "kv_quant", False):
                from repro.models.kvcache import QuantKVCache, quantize_kv

                kq, ks = quantize_kv(kc)
                vq, vs = quantize_kv(vc)
                new_cache = QuantKVCache(k_q=kq, v_q=vq, k_scale=ks, v_scale=vs)
            else:
                new_cache = KVCache(k=kc, v=vc)

    out = _merge_heads(out)
    out = layers.dense(out, params["o"], lora.get("o"), scale)
    return out, new_cache
