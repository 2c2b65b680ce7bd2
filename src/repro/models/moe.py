"""Mixture-of-Experts FFN: capacity-bounded scatter dispatch, and the
dropless expert layer of one expert-parallel share.

Expert-parallel design (DESIGN.md §7): expert weights are sharded over the
``model`` mesh axis (leading expert dim), tokens over the client/data axes.
Dispatch uses a scatter-add into an (E, C, D) buffer and a gather back —
under GSPMD the cross-shard movement lowers to all-to-all-style collectives,
which the roofline collective term accounts for.

The router is jointly trained in full fine-tuning, but in the federated LoRA
setting (the paper's) routers and experts are *frozen* base weights; the
adapters train (attention or mixer projections, and in an expert share
each held expert's two linears).  ``apply_moe``'s aux
load-balance loss is still computed so full-model training is supported by
the framework.

``apply_expert_share`` is the layer of one chip of an expert-parallel group
(granite-4.0-h, ``cfg.experts_held`` > 0): the router spans all
``cfg.n_experts``; the chip holds the contiguous experts
``[cfg.expert_offset, + cfg.experts_held)`` and returns
their part of the result, each expert with the combine weight it has in the
whole model, plus the shared expert once.  It is dropless: every held expert
runs over every token of the batch (the worst-case capacity) and the tokens
not routed to it get combine weight 0, so no assignment is ever cut: the
layout is dropless by construction, and no counter can see otherwise.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import ffn, layers


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype=jnp.float32, *,
             n_held: int = 0):
    """Router over ``n_experts``; SwiGLU weights of the ``n_held`` experts
    held here (0 -> all)."""
    kr, kg, ku, kd = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)
    held = n_held or n_experts
    return {
        "router": layers.init_dense(kr, d_model, n_experts, dtype=jnp.float32),
        # Expert-stacked SwiGLU weights: leading axis = expert (model-sharded).
        "gate": jax.random.uniform(kg, (held, d_model, d_ff), dtype, -scale_in, scale_in),
        "up": jax.random.uniform(ku, (held, d_model, d_ff), dtype, -scale_in, scale_in),
        "down": jax.random.uniform(kd, (held, d_ff, d_model), dtype, -scale_out, scale_out),
    }


def init_expert_share(key, cfg, dtype=jnp.float32):
    """An expert share's weights: the router over every expert, the
    ``cfg.experts_held`` held experts, and the shared expert if
    ``cfg.shared_d_ff``."""
    p = init_moe(key, cfg.d_model, cfg.d_ff, cfg.n_experts, dtype, n_held=cfg.experts_held)
    if cfg.shared_d_ff:
        p["shared"] = ffn.init_ffn(jax.random.fold_in(key, 1), cfg.d_model, cfg.shared_d_ff,
                                   "swiglu", dtype)
    return p


def init_expert_lora(key, cfg, dtype=jnp.float32):
    """Per held expert, LoRA on its input linear ``[gate | up]`` (d_model ->
    2 d_ff) and its output linear (d_ff -> d_model), stacked over experts."""
    e, d, f, r = cfg.experts_held, cfg.d_model, cfg.d_ff, cfg.lora.rank
    k_in, k_out = jax.random.split(key)
    stack = lambda k, d_in, d_out: jax.vmap(
        lambda kk: layers.init_lora(kk, d_in, d_out, r, dtype))(jax.random.split(k, e))
    return {"in": stack(k_in, d, 2 * f), "out": stack(k_out, f, d)}


# The expert share's counters: how two readings combine (over layers and
# microbatches) and how a stack of them reduces (over local steps and
# clients).  The load ratio keeps the worst.
COUNTERS = {"moe_load_max": (jnp.maximum, jnp.max)}


def apply_expert_share(params, lora, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, dict]:
    """The held experts' part of a top-k MoE layer plus the shared expert.

    Returns (output, stats): ``moe_load_max`` is the busiest held expert's
    assignments over the held experts' mean.
    """
    b, s, d = x.shape
    t, held, k = b * s, cfg.experts_held, cfg.top_k
    xt = jnp.reshape(x, (t, d))
    with jax.named_scope("local.moe.route"):
        logits = layers.dense(xt.astype(jnp.float32), params["router"])  # (T, E)
        top_v, top_e = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(top_v, axis=-1)  # over the k chosen logits
        # One-hot over the held experts: zero rows for experts held elsewhere.
        onehot = jax.nn.one_hot(top_e - cfg.expert_offset, held, dtype=jnp.float32)
        combine = jnp.einsum("tk,tke->te", gates, onehot)  # (T, held)
        load = jnp.sum(onehot, axis=(0, 1))  # assignments per held expert
        stats = {"moe_load_max": jnp.max(load) / jnp.maximum(jnp.mean(load), 1.0)}
    with jax.named_scope("local.moe.experts"):
        w = lambda n: params[n].astype(x.dtype)
        hg = jnp.einsum("td,edf->tef", xt, w("gate"))
        hu = jnp.einsum("td,edf->tef", xt, w("up"))
        lo = lora or {}
        if "in" in lo:
            a, bb = lo["in"]["A"].astype(x.dtype), lo["in"]["B"].astype(x.dtype)
            delta = cfg.lora.scale * jnp.einsum(
                "ter,erf->tef", jnp.einsum("td,edr->ter", xt, a), bb)
            hg = hg + delta[..., : cfg.d_ff]
            hu = hu + delta[..., cfg.d_ff:]
        h = jax.nn.silu(hg) * hu * combine.astype(x.dtype)[..., None]
        out = jnp.einsum("tef,efd->td", h, w("down"))
        if "out" in lo:
            a, bb = lo["out"]["A"].astype(x.dtype), lo["out"]["B"].astype(x.dtype)
            out = out + cfg.lora.scale * jnp.einsum(
                "ter,erd->td", jnp.einsum("tef,efr->ter", h, a), bb)
    if "shared" in params:
        with jax.named_scope("local.moe.shared"):
            out = out + ffn.apply_ffn(params["shared"], xt, "swiglu")
    return jnp.reshape(out, (b, s, d)), stats


def _capacity(n_tokens: int, top_k: int, n_experts: int, capacity_factor: float) -> int:
    cap = int(math.ceil(n_tokens * top_k * capacity_factor / n_experts))
    return max(8, -(-cap // 8) * 8)  # round up to 8 for TPU-friendly tiling


def apply_moe(
    params,
    x: jnp.ndarray,  # (B, S, D)
    *,
    top_k: int,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output, aux_load_balance_loss)."""
    b, s, d = x.shape
    n_experts = params["gate"].shape[0]
    t = b * s
    xt = jnp.reshape(x, (t, d))

    logits = layers.dense(xt.astype(jnp.float32), params["router"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)  # (T, K)
    # Renormalize combine weights over the selected experts (std practice).
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    # Aux load-balance loss (Switch-style): E * sum_e f_e * p_e
    dispatch_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32), axis=1), axis=0
    )
    mean_prob = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(dispatch_frac * mean_prob)

    capacity = _capacity(t, top_k, n_experts, capacity_factor)

    # Position of each (token, k) entry within its expert's capacity buffer.
    flat_e = jnp.reshape(top_e, (t * top_k,))
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)  # (T*K, E)
    pos_all = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.sum(pos_all * onehot, axis=-1)  # (T*K,)
    keep = pos < capacity
    slot = jnp.where(keep, flat_e * capacity + pos, n_experts * capacity)  # drop slot

    # Scatter tokens into the (E*C + 1, D) dispatch buffer (last row = dropped).
    token_idx = jnp.repeat(jnp.arange(t), top_k)
    buf = jnp.zeros((n_experts * capacity + 1, d), x.dtype)
    buf = buf.at[slot].add(xt[token_idx] if top_k > 1 else xt)
    expert_in = jnp.reshape(buf[: n_experts * capacity], (n_experts, capacity, d))

    # Expert SwiGLU, batched over the expert axis (einsum keeps E sharded).
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, params["gate"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, params["up"].astype(x.dtype))
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["down"].astype(x.dtype))

    # Gather back and combine with router weights.
    flat_out = jnp.reshape(expert_out, (n_experts * capacity, d))
    flat_out = jnp.concatenate([flat_out, jnp.zeros((1, d), x.dtype)], axis=0)
    per_k = flat_out[slot]  # (T*K, D); dropped entries pull zeros
    weights = jnp.reshape(top_p, (t * top_k,)).astype(x.dtype)
    combined = jnp.reshape(per_k * weights[:, None], (t, top_k, d)).sum(axis=1)
    return jnp.reshape(combined, (b, s, d)), aux
