"""Architecture registry + ShapeDtypeStruct input specs for the dry-run.

``get_config(arch_id)`` returns the exact assigned config; ``input_specs``
builds allocation-free stand-ins (jax.ShapeDtypeStruct) for every model input
of a given (config, shape, step-kind) — the multi-pod dry-run lowers against
these.

long_500k policy (DESIGN.md §4): sub-quadratic archs (ssm / hybrid) run
natively; quadratic archs run their sliding-window variant (window 4096)
selected by ``config_for_shape``; whisper-medium skips the shape entirely.
"""
from __future__ import annotations

import importlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.configs.shapes import SHAPES, TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K

_ARCH_MODULES = {
    "recurrentgemma-2b": "recurrentgemma_2b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "qwen1.5-32b": "qwen1_5_32b",
    "stablelm-1.6b": "stablelm_1_6b",
    "deepseek-67b": "deepseek_67b",
    "whisper-medium": "whisper_medium",
    "mamba2-130m": "mamba2_130m",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "gemma-7b": "gemma_7b",
    "paper-vit-b32": "paper_vit_b32",
    "granite-4.0-h-small": "granite_4_0_h_small",
}

# The assigned architectures; the paper's ViT and the benchmark's
# granite-4.0-h-small are registered beside them.
ARCH_IDS = tuple(k for k in _ARCH_MODULES if k not in ("paper-vit-b32", "granite-4.0-h-small"))


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch_id]}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def shape_supported(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    """Whether (arch, shape) is part of the dry-run matrix."""
    if shape.name == "long_500k":
        # Whisper's decoder has a hard bounded context; skip (DESIGN.md §4).
        return not cfg.encoder_decoder
    return True


def config_for_shape(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """Arch variant actually lowered for a shape.

    long_500k on quadratic archs switches full attention to the framework's
    sliding-window variant (window 4096) so the decode state is bounded.
    """
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        pattern = tuple("local_attn" if k == "attn" else k for k in cfg.layer_pattern)
        return cfg.replace(layer_pattern=pattern, window_size=4096)
    return cfg


def input_specs(
    cfg: ModelConfig,
    shape: ShapeConfig,
    *,
    n_clients: Optional[int] = None,
) -> dict:
    """ShapeDtypeStruct stand-ins for every input of the step being lowered.

    train: federated layout — tokens/labels (n_clients, per_client_batch, S).
    prefill: request batch (B, S) (+ frontend stubs).
    decode: one token (B, 1) + cache handled by the launcher (cache specs come
      from ``model.init_decode_caches`` under eval_shape).
    """
    i32 = jnp.int32
    s, b = shape.seq_len, shape.global_batch
    specs: dict = {}
    if shape.kind == "train":
        m = n_clients or 1
        per = max(b // m, 1)
        specs["tokens"] = jax.ShapeDtypeStruct((m, per, s), i32)
        specs["labels"] = jax.ShapeDtypeStruct((m, per, s), i32)
        lead = (m, per)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        lead = (b,)
    else:  # decode
        specs["tokens"] = jax.ShapeDtypeStruct((b, 1), i32)
        lead = (b,)

    dtype = jnp.dtype(cfg.dtype)
    if cfg.frontend == "vision" and shape.kind != "decode":
        # Stub ViT frontend: precomputed patch embeddings.  M-RoPE positions
        # default to the text fallback inside the model (all three streams =
        # arange), which is exact for text tokens and shape-identical for the
        # vision prefix — the dry-run/roofline cost is unchanged.
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (*lead, cfg.n_vision_tokens, cfg.d_model), dtype
        )
    if cfg.frontend == "audio" and shape.kind != "decode":
        specs["encoder_frames"] = jax.ShapeDtypeStruct(
            (*lead, cfg.encoder_seq, cfg.d_model), dtype
        )
    return specs


__all__ = [
    "ARCH_IDS",
    "SHAPES",
    "TRAIN_4K",
    "PREFILL_32K",
    "DECODE_32K",
    "LONG_500K",
    "all_configs",
    "config_for_shape",
    "get_config",
    "input_specs",
    "shape_supported",
]
