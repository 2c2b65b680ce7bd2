"""Model assembly: pattern-grouped scanned layer stacks, LoRA trees, loss.

Layer stacking (DESIGN.md §7): ``cfg.layer_pattern`` is the repeating mixer
unit (e.g. ("rglru","rglru","local_attn") for RecurrentGemma).  Parameters of
layer ``i`` live at pattern slot ``i % unit`` with a leading *group* axis of
size ``n_layers // unit``; layers that don't fill a whole unit sit unstacked
in ``tail``.  The forward pass is a ``lax.scan`` over groups (+ explicit tail)
so HLO size is O(unit), independent of depth — this is what makes the
95-layer deepseek-67b dry-run compile quickly.  Train mode with remat wraps
each block in ``jax.checkpoint``.

Modes:
  train    — full-sequence forward, logits for next-token loss.
  prefill  — full-sequence forward + decode caches (KV / ring / SSM / LRU).
  decode   — one token against caches (``serve_step``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks, layers
from repro.models.kvcache import KVCache, LRUState, QuantKVCache, SSMState, attn_cache
from repro.models import rglru as rglru_lib
from repro.models import ssd as ssd_lib

PyTree = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _stacked_init(fn, key, n: int):
    """vmap an init function over a leading group axis."""
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(key, cfg) -> PyTree:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    unit = len(cfg.layer_pattern)
    n_groups = cfg.n_pattern_groups
    cross = cfg.encoder_decoder

    params: dict = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model), dtype) * 0.02,
        "final_norm": layers.init_norm(cfg.norm_kind, cfg.d_model, jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[1], (cfg.d_model, cfg.vocab_size), dtype) * 0.02
        )

    groups = []
    for s, kind in enumerate(cfg.layer_pattern):
        fn = lambda k, kind=kind: blocks.init_block(k, cfg, kind, dtype, cross=cross)
        groups.append(_stacked_init(fn, jax.random.fold_in(keys[2], s), n_groups))
    params["groups"] = tuple(groups)

    tail = []
    for i in range(cfg.n_tail_layers):
        kind = cfg.layer_pattern[i % unit]
        tail.append(
            blocks.init_block(jax.random.fold_in(keys[3], i), cfg, kind, dtype, cross=cross)
        )
    params["tail"] = tuple(tail)

    if cfg.encoder_decoder:
        enc_groups = _stacked_init(
            lambda k: blocks.init_block(k, cfg, "attn", dtype, cross=False),
            keys[4],
            cfg.n_encoder_layers,
        )
        params["encoder"] = {
            "groups": (enc_groups,),
            "final_norm": layers.init_norm(cfg.norm_kind, cfg.d_model, jnp.float32),
            "pos_embed": _sinusoidal(cfg.encoder_seq, cfg.d_model).astype(dtype),
        }
        # Whisper decoder uses learned absolute positions, not RoPE.
        params["pos_embed"] = (
            jax.random.normal(keys[5], (cfg_max_positions(cfg), cfg.d_model), dtype) * 0.02
        )
    return params


def cfg_max_positions(cfg) -> int:
    """Decoder absolute-position table size (enc-dec archs only)."""
    return 32768


def _sinusoidal(length: int, dim: int) -> jnp.ndarray:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    half = dim // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = pos * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[:, :dim]


def init_lora_params(key, cfg) -> PyTree:
    dtype = jnp.dtype(cfg.lora.dtype)
    unit = len(cfg.layer_pattern)
    n_groups = cfg.n_pattern_groups
    cross = cfg.encoder_decoder
    groups = []
    for s, kind in enumerate(cfg.layer_pattern):
        fn = lambda k, kind=kind: blocks.init_block_lora(k, cfg, kind, dtype, cross=cross)
        groups.append(_stacked_init(fn, jax.random.fold_in(key, s), n_groups))
    tail = []
    for i in range(cfg.n_tail_layers):
        kind = cfg.layer_pattern[i % unit]
        tail.append(
            blocks.init_block_lora(
                jax.random.fold_in(key, 1000 + i), cfg, kind, dtype, cross=cross
            )
        )
    return {"groups": tuple(groups), "tail": tuple(tail)}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch, cfg, mode: str, cache_index):
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if cfg.frontend == "vision" and "vision_embeds" in batch and mode != "decode":
        ve = batch["vision_embeds"].astype(x.dtype)  # (B, n_vis, D)
        x = jax.lax.dynamic_update_slice(x, ve, (0, 0, 0))
    if "pos_embed" in params:
        if mode == "decode":
            pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], cache_index, 1, axis=0)
            x = x + pe[None, :, :]
        else:
            x = x + params["pos_embed"][None, :s, :]
    # Positions for RoPE / M-RoPE.
    if cfg.mrope:
        if "positions" in batch:
            positions = batch["positions"]  # (3, B, S)
        elif mode == "decode":
            positions = jnp.broadcast_to(cache_index, (3, b, s)).astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, b, s))
    else:
        if mode == "decode":
            positions = jnp.broadcast_to(cache_index, (b, s)).astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return x, positions


def _run_stack(
    x,
    params,
    lora,
    cfg,
    pattern,
    *,
    positions,
    mode,
    caches,
    cache_index,
    encoder_out,
    use_rope,
    causal,
    remat: bool,
):
    """Scan over pattern groups, then explicit tail layers."""
    unit = len(pattern)
    lora_groups = lora["groups"] if lora else tuple({} for _ in range(unit))
    lora_tail = lora["tail"] if lora else tuple({} for _ in params.get("tail", ()))

    def block(p, l, x, c, kind):
        run = functools.partial(
            blocks.apply_block,
            cfg=cfg,
            mixer_kind=kind,
            positions=positions,
            mode=mode,
            cache_index=cache_index,
            encoder_out=encoder_out,
            use_rope=use_rope,
            causal=causal,
        )
        if remat and mode == "train":
            # Per block: a group of several layers then keeps only its
            # blocks' inputs for the backward pass, not all its activations.
            run = jax.checkpoint(run)
        return run(p, l, x, cache=c)

    def group_body(carry, xs):
        x, stats = carry
        if caches is None:
            p_slots, l_slots = xs
            c_slots = (None,) * unit
        else:
            p_slots, l_slots, c_slots = xs
        new_cs = []
        for i, kind in enumerate(pattern):
            x, nc, a = block(p_slots[i], l_slots[i], x, c_slots[i], kind)
            new_cs.append(nc)
            stats = blocks.add_stats(stats, a)
        ys = tuple(new_cs) if mode in ("prefill", "decode") else None
        return (x, stats), ys

    xs = (params["groups"], lora_groups)
    if caches is not None:
        xs = xs + (caches["groups"],)
    (x, stats), group_caches = jax.lax.scan(group_body, (x, blocks.zero_stats(cfg)), xs)

    tail_caches = []
    for i, p in enumerate(params.get("tail", ())):
        kind = pattern[i % unit]
        c = caches["tail"][i] if caches is not None else None
        x, nc, a = block(p, lora_tail[i] if lora_tail else {}, x, c, kind)
        tail_caches.append(nc)
        stats = blocks.add_stats(stats, a)

    new_caches = None
    if mode in ("prefill", "decode"):
        new_caches = {"groups": group_caches, "tail": tuple(tail_caches)}
    return x, new_caches, stats


def encode(params, batch, cfg) -> jnp.ndarray:
    """Whisper-style encoder over stub frame embeddings (B, S_enc, D)."""
    frames = batch["encoder_frames"].astype(jnp.dtype(cfg.dtype))
    enc = params["encoder"]
    x = frames + enc["pos_embed"][None, : frames.shape[1], :]
    b, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x, _, _ = _run_stack(
        x,
        enc,
        None,
        cfg,
        ("attn",),
        positions=positions,
        mode="train",
        caches=None,
        cache_index=None,
        encoder_out=None,
        use_rope=False,
        causal=False,
        remat=False,
    )
    return layers.apply_norm(enc["final_norm"], x, cfg.norm_eps)


def forward(
    params: PyTree,
    lora: Optional[PyTree],
    batch: dict,
    cfg,
    *,
    mode: str = "train",
    caches: Optional[PyTree] = None,
    cache_index=None,
    remat: bool = True,
) -> Tuple[jnp.ndarray, Optional[PyTree], jnp.ndarray]:
    """Returns (logits, new_caches, stats): ``stats`` sums the blocks'
    ``aux`` load-balance losses and an expert share's counters
    (``blocks.zero_stats``)."""
    x, positions = _embed_inputs(params, batch, cfg, mode, cache_index)

    encoder_out = None
    if cfg.encoder_decoder and mode != "decode":
        encoder_out = encode(params, batch, cfg)

    # whisper: learned absolute positions; granite-4.0-h: none at all.
    use_rope = cfg.use_rope and not cfg.encoder_decoder
    x, new_caches, stats = _run_stack(
        x,
        params,
        lora,
        cfg,
        cfg.layer_pattern,
        positions=positions,
        mode=mode,
        caches=caches,
        cache_index=cache_index,
        encoder_out=encoder_out,
        use_rope=use_rope,
        causal=True,
        remat=remat,
    )
    x = layers.apply_norm(params["final_norm"], x, cfg.norm_eps)
    if mode == "prefill":
        # Serving only needs next-token logits; a full (B, 32k, V) logits
        # tensor would dominate prefill memory for nothing.
        x = x[:, -1:]
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    logits = layers.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits, new_caches, stats


def loss_fn(params, lora, batch, cfg, *, remat: bool = True) -> Tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy; labels < 0 are masked.

    Sharding-aware formulation: the label gather is a masked reduction over
    the (model-axis-sharded) vocab dim instead of ``take_along_axis`` — a
    cross-shard gather there makes GSPMD replicate the full fp32 logits
    tensor per chip (measured +20 GiB on llama4 train; EXPERIMENTS.md §Perf).
    The select+reduce fuses and only the (B, S) partials cross shards.
    """
    logits, _, stats = forward(params, lora, batch, cfg, mode="train", remat=remat)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels_safe = jnp.maximum(labels, 0)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    label_hit = vocab_iota == labels_safe[..., None]
    # logsumexp over vocab (sharded-reduction friendly) minus the true logit.
    lse = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.sum(jnp.where(label_hit, logits, 0.0), axis=-1)
    nll = lse - true_logit
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + cfg.router_aux_weight * stats["aux"]
    return total, {"ce": loss, **stats}


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------


def _block_cache(cfg, kind: str, batch: int, cache_len: int, dtype, cross: bool):
    quant = getattr(cfg, "kv_quant", False)
    if kind == "attn":
        self_c = attn_cache(batch, cache_len, cfg.n_kv_heads, cfg.head_dim_, dtype,
                            quantized=quant)
    elif kind == "local_attn":
        self_c = attn_cache(
            batch, min(cfg.window_size, cache_len), cfg.n_kv_heads, cfg.head_dim_, dtype,
            quantized=quant,
        )
    elif kind == "ssd":
        self_c = ssd_lib.init_ssm_state(batch, cfg, dtype)
    elif kind == "rglru":
        self_c = rglru_lib.init_lru_state(batch, cfg, dtype)
    else:
        raise ValueError(kind)
    cache = {"self": self_c}
    if cross:
        cache["cross"] = attn_cache(batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim_, dtype)
    return cache


def init_decode_caches(cfg, batch: int, cache_len: int, dtype=None) -> PyTree:
    """Zeroed caches sized for ``cache_len`` already-generated tokens."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    cross = cfg.encoder_decoder
    n_groups = cfg.n_pattern_groups

    def stack(c):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_groups, *x.shape)), c
        )

    groups = tuple(
        stack(_block_cache(cfg, kind, batch, cache_len, dtype, cross))
        for kind in cfg.layer_pattern
    )
    unit = len(cfg.layer_pattern)
    tail = tuple(
        _block_cache(cfg, cfg.layer_pattern[i % unit], batch, cache_len, dtype, cross)
        for i in range(cfg.n_tail_layers)
    )
    return {"groups": groups, "tail": tail}


def extend_caches(caches: PyTree, extra: int, cfg) -> PyTree:
    """Pad *full-attention self* KV buffers with ``extra`` decode slots.

    Prefill emits caches sized exactly to the prompt; full-attention decode
    needs headroom.  Ring (sliding-window) buffers, recurrent states, and
    cross-attention caches must NOT be padded: decode attends every valid
    ring/cross slot, so zero-padding would be silently attended — and a
    ring's modulo indexing depends on its exact size.  Mixer kinds come from
    ``cfg.layer_pattern``.
    """
    pattern = cfg.layer_pattern

    def pad_kv(node):
        if isinstance(node, QuantKVCache):
            def pad(x):
                pw = [(0, 0)] * x.ndim
                pw[-3] = (0, extra)
                return jnp.pad(x, pw)

            return QuantKVCache(*(pad(x) for x in node))
        pad_width = [(0, 0)] * node.k.ndim
        pad_width[-3] = (0, extra)  # seq axis of (…, S, n_kv, hd)
        return KVCache(k=jnp.pad(node.k, pad_width), v=jnp.pad(node.v, pad_width))

    def fix_block(cache, kind: str):
        out = dict(cache)
        if kind == "attn" and isinstance(cache["self"], (KVCache, QuantKVCache)):
            out["self"] = pad_kv(cache["self"])
        return out

    return {
        "groups": tuple(
            fix_block(c, pattern[i]) for i, c in enumerate(caches["groups"])
        ),
        "tail": tuple(
            fix_block(c, pattern[i % len(pattern)]) for i, c in enumerate(caches["tail"])
        ),
    }


def decode_step(params, lora, tokens, caches, cache_index, cfg):
    """serve_step: one token (B, 1) against caches; returns (logits, caches)."""
    logits, new_caches, _ = forward(
        params,
        lora,
        {"tokens": tokens},
        cfg,
        mode="decode",
        caches=caches,
        cache_index=cache_index,
        remat=False,
    )
    return logits, new_caches
