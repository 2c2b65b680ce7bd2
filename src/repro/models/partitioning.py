"""Sharding rules: parameter / batch / cache PartitionSpecs for the mesh.

Tensor-parallel layout over the ``model`` axis (Megatron-style):

  embed (V, D)                  -> vocab-sharded            P(model, None)
  attn q/k/v w (D, H*hd)        -> head(out)-sharded        P(None, model)
  attn o w (H*hd, D)            -> head(in)-sharded         P(model, None)
  ffn gate/up (D, F)            -> hidden-sharded           P(None, model)
  ffn down (F, D)               -> hidden-sharded           P(model, None)
  moe gate/up/down (E, .., ..)  -> expert-sharded           P(model, None, None)
  lora A/B                      -> replicated (rank is tiny; replication makes
                                   the delta all-gather client-axis-only)
  norms / biases / conv / A_log -> replicated

Scan-stacked leaves carry a leading group axis, so rules index from the
*trailing* dims.  Dims not divisible by the axis size fall back to
replication (e.g. whisper's 51865 vocab).

The client/data batch axes: federated stacked-client tensors shard their
leading client axis over ("pod","data"); plain batches shard batch over the
same axes.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any

# Keys whose *last* dim is model-sharded (column parallel).
_COL_KEYS = {"q", "k", "v", "gate", "up", "in_proj", "proj_x", "proj_gate", "gate_a", "gate_x"}
# Keys whose second-to-last dim is model-sharded (row parallel).
_ROW_KEYS = {"o", "down", "out_proj"}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
        elif hasattr(p, "name"):
            names.append(str(p.name))
    return tuple(names)


def _divisible(dim: int, mesh_axis_size: int) -> bool:
    return dim % mesh_axis_size == 0


def param_pspec(
    path,
    leaf,
    *,
    model_axis: str = "model",
    model_size: int = 16,
    policy: str = "tp",
    fsdp_axes: Tuple[str, ...] = ("data",),
    fsdp_size: int = 16,
) -> P:
    """Sharding policies (see EXPERIMENTS.md §Perf for the measured trade-offs):

      tp            Megatron tensor-parallel over ``model`` only (baseline —
                    weights replicated across the data axis; does not fit
                    >~20B-param archs on v5e).
      tp_fsdp       tp + the weight's other big dim sharded over the data
                    axes (ZeRO-3-style; GSPMD inserts just-in-time gathers).
      dp            fully replicated weights; all parallelism from the batch
                    (LoRA-only training syncs nothing but tiny adapter grads).
      ep_replicated tp, but MoE expert weights shard d_ff over ``model``
                    instead of the expert axis — kills the dispatch
                    all-to-all for small-expert MoEs (granite).
    """
    names = _path_names(path)
    ndim = leaf.ndim
    spec = [None] * ndim
    if policy == "dp":
        return P(*spec)

    def ok(axis_from_end: int) -> bool:
        return ndim >= axis_from_end and _divisible(leaf.shape[-axis_from_end], model_size)

    def fsdp_ok(axis_from_end: int) -> bool:
        return (
            policy == "tp_fsdp"
            and ndim >= axis_from_end
            and _divisible(leaf.shape[-axis_from_end], fsdp_size)
        )

    if "embed" in names and "pos" not in "".join(names):
        if ndim >= 2 and _divisible(leaf.shape[-2], model_size):
            spec[-2] = model_axis  # (V, D) vocab-sharded
            if fsdp_ok(1):
                spec[-1] = fsdp_axes
        return P(*spec)
    if "lm_head" in names:
        if ok(1):
            spec[-1] = model_axis
            if fsdp_ok(2):
                spec[-2] = fsdp_axes
        return P(*spec)
    if "pos_embed" in names or ndim <= 1:
        return P(*spec)
    if "A" in names or "B" in names:  # LoRA factors: replicated
        return P(*spec)
    if "moe" in names:
        if names[-1] in ("gate", "up", "down") and ndim >= 3:
            if policy == "ep_replicated":
                # shard the ffn dim over model instead of the expert axis
                dim = -1 if names[-1] in ("gate", "up") else -2
                if _divisible(leaf.shape[dim], model_size):
                    spec[dim] = model_axis
                return P(*spec)
            if _divisible(leaf.shape[-3], model_size):
                spec[-3] = model_axis  # expert axis
                ffn_dim = -1 if names[-1] in ("gate", "up") else -2
                if policy == "moe2d" and _divisible(leaf.shape[ffn_dim], fsdp_size):
                    # 2D expert sharding: E over model, d_ff over data — the
                    # 775B expert bank stays RESIDENT at 1/(16*16) per chip,
                    # no FSDP regather (EXPERIMENTS.md §Perf llama4).
                    spec[ffn_dim] = fsdp_axes
                elif fsdp_ok(1):
                    spec[-1] = fsdp_axes
            return P(*spec)
        return P(*spec)  # router etc.
    if "conv_w" in names or "norm" in "".join(names):
        return P(*spec)

    owner = None
    for n in reversed(names):
        if n in _COL_KEYS or n in _ROW_KEYS:
            owner = n
            break
    if owner in _COL_KEYS and ok(1):
        spec[-1] = model_axis
        if fsdp_ok(2):
            spec[-2] = fsdp_axes
    elif owner in _ROW_KEYS and ok(2):
        spec[-2] = model_axis
        if fsdp_ok(1):
            spec[-1] = fsdp_axes
    return P(*spec)


def param_pspecs(
    params: PyTree,
    *,
    model_axis: str = "model",
    model_size: int = 16,
    policy: str = "tp",
    fsdp_axes: Tuple[str, ...] = ("data",),
    fsdp_size: int = 16,
) -> PyTree:
    return jax.tree_util.tree_map_with_path(
        lambda p, l: param_pspec(
            p, l, model_axis=model_axis, model_size=model_size,
            policy=policy, fsdp_axes=fsdp_axes, fsdp_size=fsdp_size,
        ),
        params,
    )


def batch_pspecs(
    batch: PyTree, client_axes: Tuple[str, ...], client_size: int = 0
) -> PyTree:
    """Shard the leading (batch or client) axis of every batch leaf.

    Leaves whose leading dim doesn't divide the client-axis size (e.g. the
    long_500k single-request decode) are replicated — latency-bound decode
    parallelism then comes from the model axis alone.
    """

    def spec(leaf):
        if client_size and leaf.shape[0] % client_size != 0:
            return P(*([None] * leaf.ndim))
        return P(client_axes, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map(spec, batch)


def cache_pspecs(
    caches: PyTree,
    cfg,
    client_axes: Tuple[str, ...],
    *,
    model_axis: str = "model",
    model_size: int = 16,
    client_size: int = 0,
    stacked_groups: bool = True,
) -> PyTree:
    """KV caches: batch over data axes; kv-head dim over model when divisible.

    Leaves: KVCache k/v (G, B, L, n_kv, hd) or states (G, B, ...); tail
    entries lack the G axis.  A batch dim that doesn't divide the client-axis
    size (long_500k B=1) is replicated.
    """

    def spec_for(path, leaf):
        names = _path_names(path)
        in_groups = "groups" in names
        batch_dim = 1 if in_groups else 0
        spec = [None] * leaf.ndim
        if leaf.ndim > batch_dim and not (
            client_size and leaf.shape[batch_dim] % client_size != 0
        ):
            spec[batch_dim] = client_axes
        # KV head dim of attention caches sits at -2 for k/v buffers; when the
        # head count doesn't divide (MHA w/ 40 heads on a 16-way axis), shard
        # head_dim instead — otherwise the cache replicates across the model
        # axis (measured 324 GiB/chip on qwen1.5-32b decode; §Perf).
        is_kv = names[-1] in ("k", "v", "k_q", "v_q")
        if is_kv and leaf.ndim >= 2:
            if _divisible(leaf.shape[-2], model_size):
                spec[-2] = model_axis
            elif _divisible(leaf.shape[-1], model_size):
                spec[-1] = model_axis
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, caches)


def lora_pspecs(lora: PyTree) -> PyTree:
    """LoRA adapters are replicated over the whole mesh (tiny)."""
    return jax.tree_util.tree_map(lambda l: P(*([None] * l.ndim)), lora)


def stacked_lora_pspecs(lora: PyTree, client_axes: Tuple[str, ...]) -> PyTree:
    """Per-client LoRA stacks: leading client axis sharded over client axes."""
    return jax.tree_util.tree_map(
        lambda l: P(client_axes, *([None] * (l.ndim - 1))), lora
    )


def padded_cohort(d2: int, shards: int) -> int:
    """Smallest multiple of ``shards`` >= ``d2``: the client-axis length a
    ragged cohort (``d2 % shards != 0``) takes when its columns are placed
    shard-major with zero-mask padding."""
    if shards <= 0:
        raise ValueError(f"shards must be positive, got {shards}")
    return shards * (-(-d2 // shards))
