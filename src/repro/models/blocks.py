"""Transformer blocks: mixer (+ optional FFN/MoE), pre-norm residual wiring.

A block's *mixer kind* comes from ``ModelConfig.layer_pattern``:
  "attn"        full causal GQA attention
  "local_attn"  sliding-window attention (ring KV cache at decode)
  "ssd"         Mamba-2 SSD
  "rglru"       Griffin RG-LRU recurrent block
Decoder blocks of enc-dec models additionally carry cross-attention.

Prefill builds decode state in the same pass (the apply functions return
their cache/state directly — no projection recompute).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, ffn, layers, moe, rglru, ssd
from repro.models.kvcache import KVCache

ATTN_KINDS = ("attn", "local_attn")


def init_block(key, cfg, mixer_kind: str, dtype, *, cross: bool = False):
    k_mix, k_ffn, k_cross = jax.random.split(key, 3)
    p: dict = {"norm1": layers.init_norm(cfg.norm_kind, cfg.d_model, jnp.float32)}
    if mixer_kind in ATTN_KINDS:
        p["mixer"] = attention.init_attention(k_mix, cfg, dtype)
    elif mixer_kind == "ssd":
        p["mixer"] = ssd.init_ssd(k_mix, cfg, dtype)
    elif mixer_kind == "rglru":
        p["mixer"] = rglru.init_rglru(k_mix, cfg, dtype)
    else:
        raise ValueError(mixer_kind)
    if cross:
        p["norm_cross"] = layers.init_norm(cfg.norm_kind, cfg.d_model, jnp.float32)
        p["cross"] = attention.init_attention(k_cross, cfg, dtype)
    if cfg.d_ff > 0:
        p["norm2"] = layers.init_norm(cfg.norm_kind, cfg.d_model, jnp.float32)
        if cfg.experts_held:
            p["moe"] = moe.init_expert_share(k_ffn, cfg, dtype)
        elif cfg.n_experts > 0:
            p["moe"] = moe.init_moe(k_ffn, cfg.d_model, cfg.d_ff, cfg.n_experts, dtype)
        else:
            p["ffn"] = ffn.init_ffn(k_ffn, cfg.d_model, cfg.d_ff, cfg.ffn_kind, dtype)
    return p


def init_block_lora(key, cfg, mixer_kind: str, dtype, *, cross: bool = False):
    """LoRA adapters for a block — attention Q/V (paper setting) or, for
    attention-free mixers, the mixer's in/out projections; in an expert share
    (``cfg.experts_held``) also each held expert's input and output linears."""
    k_mix, k_cross = jax.random.split(key)
    lora: dict = {}
    if mixer_kind in ATTN_KINDS:
        lora["mixer"] = attention.init_attention_lora(k_mix, cfg, dtype)
    elif mixer_kind == "ssd":
        dims = ssd.ssd_dims(cfg)
        d_in_proj = dims["d_inner"] + dims["conv_dim"] + dims["n_heads"]
        ks = jax.random.split(k_mix, 2)
        lora["mixer"] = {
            "q": layers.init_lora(ks[0], cfg.d_model, d_in_proj, cfg.lora.rank, dtype),
            "v": layers.init_lora(ks[1], dims["d_inner"], cfg.d_model, cfg.lora.rank, dtype),
        }
    elif mixer_kind == "rglru":
        w = rglru.lru_width(cfg)
        ks = jax.random.split(k_mix, 2)
        lora["mixer"] = {
            "q": layers.init_lora(ks[0], cfg.d_model, w, cfg.lora.rank, dtype),
            "v": layers.init_lora(ks[1], w, cfg.d_model, cfg.lora.rank, dtype),
        }
    if cross:
        lora["cross"] = attention.init_attention_lora(k_cross, cfg, dtype)
    if cfg.experts_held:
        lora["moe"] = moe.init_expert_lora(jax.random.fold_in(key, 2), cfg, dtype)
    return lora


def zero_stats(cfg) -> dict:
    """What a block reports beside its output, summed over the stack:
    ``aux`` (the capacity-bounded MoE's load-balance loss) and, for an
    expert share, its counters (``moe.COUNTERS``)."""
    keys = ("aux", *moe.COUNTERS) if cfg.experts_held else ("aux",)
    return {k: jnp.zeros((), jnp.float32) for k in keys}


def add_stats(a: dict, b: dict) -> dict:
    return {k: moe.COUNTERS[k][0](a[k], b[k]) if k in moe.COUNTERS else a[k] + b[k]
            for k in a}


def _residual(x, out, cfg):
    if cfg.residual_multiplier != 1.0:
        out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
    return x + out


def apply_block(
    params,
    lora,
    x: jnp.ndarray,
    cfg,
    mixer_kind: str,
    *,
    positions,
    mode: str,  # "train" | "prefill" | "decode"
    cache=None,  # {"self": ..., "cross": KVCache?} or None
    cache_index=None,
    encoder_out: Optional[jnp.ndarray] = None,
    use_rope: bool = True,
    causal: bool = True,
) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Returns (x, new_cache, stats) with ``stats`` as ``zero_stats(cfg)``."""
    lora = lora or {}
    stats = zero_stats(cfg)
    h = layers.apply_norm(params["norm1"], x, cfg.norm_eps)
    window = cfg.window_size if mixer_kind == "local_attn" else 0
    prefill = mode == "prefill"

    if mixer_kind in ATTN_KINDS:
        self_cache = cache["self"] if cache is not None else None
        out, new_self = attention.apply_attention(
            params["mixer"],
            lora.get("mixer"),
            h,
            cfg,
            positions=positions,
            window=window,
            cache=self_cache,
            cache_index=cache_index,
            use_rope=use_rope,
            causal=causal,
            return_cache=prefill,
        )
    elif mixer_kind == "ssd":
        state = cache["self"] if cache is not None else None
        with jax.named_scope("local.ssd"):
            out, new_self = ssd.apply_ssd(
                params["mixer"], lora.get("mixer"), h, cfg,
                state=state, lora_scale=cfg.lora.scale, return_state=prefill,
            )
    elif mixer_kind == "rglru":
        state = cache["self"] if cache is not None else None
        out, new_self = rglru.apply_rglru(
            params["mixer"], lora.get("mixer"), h, cfg,
            state=state, lora_scale=cfg.lora.scale, return_state=prefill,
        )
    else:
        raise ValueError(mixer_kind)
    x = _residual(x, out, cfg)

    new_cross = None
    if "cross" in params:
        hc = layers.apply_norm(params["norm_cross"], x, cfg.norm_eps)
        cross_cache = cache.get("cross") if cache is not None else None
        out, _ = attention.apply_attention(
            params["cross"],
            lora.get("cross"),
            hc,
            cfg,
            positions=positions,
            cache=cross_cache,
            encoder_out=encoder_out,
            use_rope=False,
            causal=False,
            is_cross=True,
        )
        x = x + out
        if prefill and encoder_out is not None:
            new_cross = _encoder_kv(params["cross"], lora.get("cross"), encoder_out, cfg)

    if "ffn" in params:
        h2 = layers.apply_norm(params["norm2"], x, cfg.norm_eps)
        x = _residual(x, ffn.apply_ffn(params["ffn"], h2, cfg.ffn_kind), cfg)
    elif "moe" in params:
        h2 = layers.apply_norm(params["norm2"], x, cfg.norm_eps)
        if cfg.experts_held:
            out, counts = moe.apply_expert_share(params["moe"], lora.get("moe"), h2, cfg)
            stats.update(counts)
        else:
            out, stats["aux"] = moe.apply_moe(
                params["moe"], h2, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor
            )
        x = _residual(x, out, cfg)

    new_cache = cache
    if mode in ("prefill", "decode"):
        new_cache = {"self": new_self}
        if "cross" in params:
            new_cache["cross"] = new_cross if new_cross is not None else (
                cache.get("cross") if cache else None
            )
    return x, new_cache, stats


def _encoder_kv(cross_params, cross_lora, encoder_out, cfg) -> KVCache:
    """Cross-attention K/V computed once from encoder output at prefill."""
    lora = cross_lora or {}
    scale = cfg.lora.scale
    b, s, _ = encoder_out.shape
    k = layers.dense(encoder_out, cross_params["k"], lora.get("k"), scale)
    v = layers.dense(encoder_out, cross_params["v"], lora.get("v"), scale)
    return KVCache(
        k=jnp.reshape(k, (b, s, cfg.n_kv_heads, cfg.head_dim_)),
        v=jnp.reshape(v, (b, s, cfg.n_kv_heads, cfg.head_dim_)),
    )
