"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``--xla_force_host_platform_device_count=512`` before first jax init.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.config import MeshConfig


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding code places arrays with
    ``with_sharding_constraint`` and ``shard_map``, which Explicit axes (the
    ``make_mesh`` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = 256 chips (data, model).
    Multi-pod: (2, 16, 16) = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape: Tuple[int, ...] = (1, 1), axes=("data", "model")) -> Mesh:
    """1-device mesh for CPU smoke runs of the mesh code path."""
    return _auto_mesh(shape, axes)


def client_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def client_shard_count(mesh: Mesh | None) -> int:
    """Number of shards of the packed client axis under ``mesh``.

    The single consistency point for every consumer of ``client_axes``:
    ``None`` and any mesh whose client axes multiply to 1 (the ``(1, 1)``
    debug mesh included) report exactly one shard, and callers MUST take
    the unsharded single-device code path in that case — the sharded agg
    delegates so the 1-shard result stays bitwise identical.
    """
    if mesh is None:
        return 1
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n


def make_host_mesh(n: int) -> Mesh:
    """(n, 1) host-platform mesh over ("data", "model") for sharded agg runs.

    Requires the process to have been started with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` (or a real
    backend with >= n devices) — jax locks the device count at first init,
    so this asserts eagerly with the fix instead of letting ``make_mesh``
    fail with an opaque reshape error deep in the first jitted call.
    """
    if n < 1:
        raise ValueError(f"mesh shard count must be >= 1, got {n}")
    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"make_host_mesh({n}) needs {n} devices but jax sees {have}. "
            "On CPU, set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} in the environment BEFORE the first jax init (jax locks "
            "the device count at first use; see launch/dryrun.py)."
        )
    return _auto_mesh((n, 1), ("data", "model"))


def named(mesh: Mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def mesh_config_of(mesh: Mesh) -> MeshConfig:
    return MeshConfig(multi_pod="pod" in mesh.axis_names)
