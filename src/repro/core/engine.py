"""Batched aggregation engine: shape-bucketed leaf packing + one-dispatch ops.

The per-leaf reference path (``repro.core.aggregators`` with
``engine="reference"``) walks the client-delta pytree in Python — every leaf
launches its own vmapped ADMM loop with its own tiny eigh and its own stack
of unfused elementwise ops, so at production module counts dispatch overhead
and HBM round-trips dominate the server step.  This module replaces that
walk with three layers (DESIGN.md §1-2):

  1. *Packing*: ``pack`` walks any stacked delta pytree once at trace time,
     converts each leaf to its (modules, vec_dim, n_clients) matrices
     (``stacking.leaf_matrices``), zero-pads vec_dim up to a canonical
     bucket size, and concatenates everything that shares a
     ``(padded_vec, n_clients, dtype)`` key into a single bucket tensor.
     The returned ``PackSpec`` is invertible: ``unpack`` slices, splits and
     reshapes each module's rows back into the original tree structure.

  2. *Dispatch*: every aggregator runs as ONE batched call per bucket —
     a mean, a batched TIES election, or a single ``robust_pca_bucket``
     fori/while loop — instead of one call per leaf.  Zero padding is
     lossless for every method (see the per-method notes below).

  3. *Diagnostics*: per-module arrays (beta, sparse-energy E^(t), residual)
     come back as flat (modules,) arrays keyed by the PackSpec bucket, with
     helpers to regroup them per tree path — no ad-hoc ``leaf{i}/...`` keys.

Padding-correctness notes: zero rows contribute nothing to means, Gram
matrices, TIES elections (|0| never beats a top-k threshold, and zeroed
entries are excluded from the disjoint mean), FedExP norms, or RPCA (zero
rows stay exactly zero through SVT and shrinkage; mu/lam use the true dims
carried per module) — so every bucketed result row equals its per-leaf
counterpart, which the parity suite in tests/test_engine.py asserts.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core import rpca as rpca_lib
from repro.core import stacking
from repro.core.aggregators import (
    CARRY_MODES,
    AggregatorConfig,
    _client_weights,
    _dare_keep,
    _is_ab_node,
    sparse_energy_ratio,
)

PyTree = Any

# Bucket key: (padded_vec_dim, n_clients, dtype_name).
BucketKey = tuple


@dataclasses.dataclass(frozen=True)
class PackEntry:
    """One packed tree node: a plain leaf or a joint (A, B) adapter pair."""

    kind: str  # "leaf" | "ab_pair"
    path: tuple  # tree path of dict keys / sequence indices
    bucket: BucketKey
    offset: int  # first module row of this entry within its bucket
    n_modules: int
    vec_dim: int  # true (unpadded) vec dim; ab_pair: va + vb
    shapes: tuple  # per-part one-client delta shapes (1 part, or A and B)
    dtypes: tuple  # matching per-part dtypes
    split: tuple  # vec-dim split points between parts (ab_pair: (va,))


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static, invertible description of one packing (the unpack program)."""

    entries: tuple
    skeleton: Any  # original structure with entry indices at leaf positions
    n_clients: int  # original (pre-padding) cohort size
    bucket_dims: Mapping[BucketKey, tuple]  # key -> (total_modules, padded_vec)
    cohort_size: int = 0  # canonical (padded) client-axis length; 0 -> n_clients
    # Per-client declared LoRA/svt ranks for heterogeneous-rank cohorts
    # (None = uniform).  Static descriptor only: the rank *masks* are
    # applied to the deltas before packing (fed.partition.client_rank_masks
    # — the PR 9 ragged zero idiom, bitwise unobservable in the bucket),
    # so the packed layout itself is rank-agnostic.
    client_ranks: tuple | None = None


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One shape bucket: the packed tensor + per-module true vec dims.

    ``client_mask`` / ``weights`` are the per-client validity mask and
    normalized aggregation weights for shape-static partial participation
    (None on the dense unweighted path).  When a mask is present the packed
    ``data`` already has its inactive columns zeroed, so the zero-*column*
    padding argument mirrors the zero-row one in the module docstring.
    """

    data: jnp.ndarray  # (total_modules, padded_vec, cohort_size)
    true_dims: jnp.ndarray  # (total_modules,) int32
    dims: tuple = ()  # the same true dims as static Python ints
    client_mask: jnp.ndarray | None = None  # (cohort_size,) float32 validity
    weights: jnp.ndarray | None = None  # (cohort_size,) float32, normalized


@jax.named_scope("agg.pack")
def pack(
    stacked: PyTree,
    *,
    granularity: str = "module",
    joint_ab: bool = False,
    client_mask=None,
    weights=None,
    cohort_size: int | None = None,
    mesh=None,
) -> tuple[dict, PackSpec]:
    """Pack a stacked client-delta pytree into shape buckets.

    ``granularity="module"`` splits scan-stacked leaves along their layer
    axes (one matrix per module, the fedrpca layout); ``"leaf"`` keeps each
    leaf as a single flattened matrix (the TIES layout, where trim/elect
    operate over the whole leaf).  ``joint_ab`` concatenates each
    ``{"A": ..., "B": ...}`` node's vec dims into one joint matrix (the
    paper's App. B.2 joint mode).

    ``client_mask`` marks valid client slots (1) vs cohort padding (0);
    masked columns of every bucket are zeroed so garbage in padded slots is
    inert.  ``weights`` are normalized per-client aggregation weights (the
    engine passes them pre-masked and normalized); both ride on the
    returned ``Bucket``s.  ``cohort_size`` zero-pads the client axis up to
    a canonical size (``stacking.canonical_cohort_size``) and extends the
    mask with zeros — the shape-static partial-participation layout.

    ``mesh`` (with more than one client shard) constrains every bucket's
    client axis onto the mesh's client axes (shard-major column placement:
    contiguous column blocks per shard, so tier gathers, ``migrate_carry``
    and ``plan_retier`` stay shard-local) and the mask/weight vectors along
    the same axis.  This is the layout of every method but fedrpca, whose
    RPCA loop moves each bucket to vec rows once on entry
    (``rpca.bucket_pspecs``, DESIGN.md §10).  One-shard meshes are a no-op
    — callers normalize them to None via ``plan_aggregation``.
    """
    if granularity not in ("module", "leaf"):
        raise ValueError(f"unknown granularity: {granularity!r}")
    orig_clients = None
    if cohort_size is not None:
        leaves = jax.tree_util.tree_leaves(stacked)
        if not leaves:
            raise ValueError("pack: empty pytree")
        orig_clients = int(jnp.asarray(leaves[0]).shape[0])
        pad_c = cohort_size - orig_clients
        if pad_c < 0:
            raise ValueError(f"cohort_size {cohort_size} < client count {orig_clients}")
        if pad_c:
            stacked = stacking.pad_cohort(stacked, cohort_size)
            base = (
                jnp.ones((orig_clients,), jnp.float32)
                if client_mask is None
                else jnp.asarray(client_mask, jnp.float32)
            )
            client_mask = jnp.concatenate([base, jnp.zeros((pad_c,), jnp.float32)])
            if weights is not None:
                weights = jnp.concatenate(
                    [jnp.asarray(weights, jnp.float32), jnp.zeros((pad_c,), jnp.float32)]
                )
    entries: list[PackEntry] = []
    mats_by_bucket: dict[BucketKey, list] = {}
    dims_by_bucket: dict[BucketKey, list] = {}
    offsets: dict[BucketKey, int] = {}
    n_clients_seen: list[int] = []

    def add_matrices(mats: jnp.ndarray, vec_dim: int, dtype) -> tuple[BucketKey, int]:
        nc = mats.shape[-1]
        n_clients_seen.append(nc)
        padded = stacking.canonical_vec_dim(vec_dim)
        key = (padded, nc, jnp.dtype(dtype).name)
        off = offsets.get(key, 0)
        mats_by_bucket.setdefault(key, []).append(
            stacking.pad_matrices(mats.astype(dtype), padded)
        )
        dims_by_bucket.setdefault(key, []).extend([vec_dim] * mats.shape[0])
        offsets[key] = off + mats.shape[0]
        return key, off

    def walk(node, path):
        if joint_ab and _is_ab_node(node):
            a, b = jnp.asarray(node["A"]), jnp.asarray(node["B"])
            mats_a = stacking.leaf_matrices(a)
            mats_b = stacking.leaf_matrices(b)
            if mats_a.shape[0] != mats_b.shape[0]:
                raise ValueError(
                    f"(A, B) module counts differ at {path}: "
                    f"{mats_a.shape[0]} vs {mats_b.shape[0]}"
                )
            joint = jnp.concatenate([mats_a, mats_b], axis=1)
            dtype = jnp.result_type(a.dtype, b.dtype)
            key, off = add_matrices(joint, joint.shape[1], dtype)
            entries.append(
                PackEntry(
                    kind="ab_pair",
                    path=path,
                    bucket=key,
                    offset=off,
                    n_modules=joint.shape[0],
                    vec_dim=joint.shape[1],
                    shapes=(a.shape[1:], b.shape[1:]),
                    dtypes=(a.dtype, b.dtype),
                    split=(mats_a.shape[1],),
                )
            )
            return len(entries) - 1
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            walked = [walk(v, path + (i,)) for i, v in enumerate(node)]
            if hasattr(node, "_fields"):  # namedtuple
                return type(node)(*walked)
            return type(node)(walked)
        leaf = jnp.asarray(node)
        layer_axes = None if granularity == "module" else 0
        mats = stacking.leaf_matrices(leaf, layer_axes)
        key, off = add_matrices(mats, mats.shape[1], leaf.dtype)
        entries.append(
            PackEntry(
                kind="leaf",
                path=path,
                bucket=key,
                offset=off,
                n_modules=mats.shape[0],
                vec_dim=mats.shape[1],
                shapes=(leaf.shape[1:],),
                dtypes=(leaf.dtype,),
                split=(),
            )
        )
        return len(entries) - 1

    skeleton = walk(stacked, ())
    if not entries:
        raise ValueError("pack: empty pytree")
    if len(set(n_clients_seen)) != 1:
        raise ValueError(f"inconsistent client counts across leaves: {set(n_clients_seen)}")

    mask32 = None if client_mask is None else jnp.asarray(client_mask, jnp.float32)
    w32 = None if weights is None else jnp.asarray(weights, jnp.float32)

    sharded = mesh is not None and rpca_lib.mesh_client_shards(mesh) > 1
    if sharded:
        from jax.sharding import NamedSharding, PartitionSpec as P

        n_shards = rpca_lib.mesh_client_shards(mesh)
        ax = rpca_lib.mesh_client_axes(mesh)
        ax = ax if len(ax) > 1 else ax[0]

        def constrain(x, spec, client_dim):
            # Placement hint only.  Eager with_sharding_constraint routes
            # through jit out_shardings, which rejects unevenly divisible
            # dims — ragged cohorts skip the hint and let GSPMD place
            # the columns.
            if x.shape[client_dim] % n_shards:
                return x
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

        if mask32 is not None:
            mask32 = constrain(mask32, P(ax), 0)
        if w32 is not None:
            w32 = constrain(w32, P(ax), 0)

    def build(mats, key):
        data = jnp.concatenate(mats, axis=0)
        if mask32 is not None:
            data = data * mask32.astype(data.dtype)
        if sharded:
            data = constrain(data, P(None, None, ax), 2)
        return Bucket(
            data=data,
            true_dims=jnp.asarray(dims_by_bucket[key], jnp.int32),
            dims=tuple(dims_by_bucket[key]),
            client_mask=mask32,
            weights=w32,
        )

    buckets = {key: build(mats, key) for key, mats in mats_by_bucket.items()}
    spec = PackSpec(
        entries=tuple(entries),
        skeleton=skeleton,
        n_clients=orig_clients if orig_clients is not None else n_clients_seen[0],
        bucket_dims={k: (b.data.shape[0], b.data.shape[1]) for k, b in buckets.items()},
        cohort_size=n_clients_seen[0],
    )
    return buckets, spec


@jax.named_scope("agg.unpack")
def unpack(spec: PackSpec, updates: Mapping[BucketKey, jnp.ndarray]) -> PyTree:
    """Invert ``pack``: per-bucket (total_modules, padded_vec) update arrays
    back to a pytree shaped like one client's delta."""

    def rebuild(skel):
        if isinstance(skel, int):
            e = spec.entries[skel]
            rows = updates[e.bucket][e.offset : e.offset + e.n_modules, : e.vec_dim]
            parts = jnp.split(rows, list(e.split), axis=1) if e.split else [rows]
            outs = [
                jnp.reshape(p, shp).astype(dt)
                for p, shp, dt in zip(parts, e.shapes, e.dtypes)
            ]
            if e.kind == "ab_pair":
                return {"A": outs[0], "B": outs[1]}
            return outs[0]
        if isinstance(skel, dict):
            return {k: rebuild(v) for k, v in skel.items()}
        if hasattr(skel, "_fields"):
            return type(skel)(*(rebuild(v) for v in skel))
        return type(skel)(rebuild(v) for v in skel)

    return rebuild(spec.skeleton)


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineDiagnostics:
    """Per-module diagnostic arrays keyed by PackSpec bucket.

    Each field maps bucket key -> (total_modules,) array; ``spec`` maps rows
    back to tree paths.  Replaces the reference path's ad-hoc
    ``leaf{i}/beta_mean`` scalar dict.  ``scalars`` holds whole-round
    scalar health signals (cross-round sessions add ``fallback_count`` and
    ``carry_hit_rate`` here; stateless calls leave it empty).
    """

    spec: PackSpec
    arrays: Mapping[str, Mapping[BucketKey, jnp.ndarray]]
    scalars: Mapping[str, jnp.ndarray] = dataclasses.field(default_factory=dict)

    def flat(self, name: str) -> jnp.ndarray:
        """All modules' values for one diagnostic, bucket order."""
        return jnp.concatenate([v for v in self.arrays[name].values()])

    def mean(self, name: str) -> jnp.ndarray:
        return jnp.mean(self.flat(name))

    def max(self, name: str) -> jnp.ndarray:
        return jnp.max(self.flat(name))

    def per_entry(self, name: str) -> dict:
        """Regroup a diagnostic by tree path: {"/".join(path): (modules,)}."""
        out = {}
        for e in self.spec.entries:
            arr = self.arrays[name][e.bucket][e.offset : e.offset + e.n_modules]
            out["/".join(str(p) for p in e.path)] = arr
        return out


# Registered as a pytree (arrays are children, the static PackSpec is aux
# data) so jitted callers can return diagnostics directly.
jax.tree_util.register_pytree_node(
    EngineDiagnostics,
    lambda d: ((d.arrays, d.scalars), d.spec),
    lambda spec, children: EngineDiagnostics(
        spec=spec, arrays=children[0], scalars=children[1]
    ),
)


# ---------------------------------------------------------------------------
# Batched per-bucket aggregators
# ---------------------------------------------------------------------------


def _bucket_mean(bucket: Bucket) -> jnp.ndarray:
    """Mean over the client axis: legacy unweighted, or the normalized
    weighted sum (masked slots carry weight zero) accumulated in float32."""
    if bucket.weights is None:
        return jnp.mean(bucket.data, axis=-1)
    return jnp.einsum(
        "mvc,c->mv", bucket.data.astype(jnp.float32), bucket.weights
    ).astype(bucket.data.dtype)


def _ties_bucket(
    data: jnp.ndarray, dims: tuple, keep: float, scale: float, w=None
) -> jnp.ndarray:
    """Batched TIES (trim -> elect sign -> disjoint mean) over one bucket.

    ``data`` is (B, d, nc); per-module k comes from the static true vec dims
    (``dims``, Python ints) with the reference path's exact host-side
    ``max(int(keep * d), 1)`` arithmetic, so a bucket may mix leaves of
    different sizes without float32 truncation skew.  Padded zeros never
    survive the trim (kth threshold > 0 excludes them; a zero threshold
    keeps them as zero values, which the ``trimmed != 0`` mask drops).
    ``w`` (normalized per-client weights) switches the election to weighted
    mass and the disjoint mean to a weighted average, mirroring
    ``aggregators._ties_leaf``.
    """
    b, d, nc = data.shape
    flat = jnp.swapaxes(data, 1, 2).astype(jnp.float32)  # (B, nc, d)
    k_list = [max(int(keep * di), 1) for di in dims]
    k = jnp.asarray(k_list, jnp.int32)
    absx = jnp.abs(flat)
    # top_k once at the bucket's max k; each module reads its own k-th value.
    topv = jax.lax.top_k(absx, max(k_list))[0]  # (B, nc, max_k) descending
    kth_idx = jnp.broadcast_to((k - 1)[:, None, None], (b, nc, 1))
    kth = jnp.take_along_axis(topv, kth_idx, axis=-1)  # per-client k-th largest
    trimmed = jnp.where(absx >= kth, flat, 0.0)
    if w is None:
        elected = jnp.sign(jnp.sum(trimmed, axis=1))  # (B, d)
        elected = jnp.where(elected == 0.0, 1.0, elected)
        agree = (jnp.sign(trimmed) == elected[:, None, :]) & (trimmed != 0.0)
        num = jnp.sum(jnp.where(agree, trimmed, 0.0), axis=1)
        den = jnp.maximum(jnp.sum(agree.astype(jnp.float32), axis=1), 1.0)
    else:
        wc = w[None, :, None]
        elected = jnp.sign(jnp.sum(wc * trimmed, axis=1))
        elected = jnp.where(elected == 0.0, 1.0, elected)
        agree = (jnp.sign(trimmed) == elected[:, None, :]) & (trimmed != 0.0)
        num = jnp.sum(jnp.where(agree, wc * trimmed, 0.0), axis=1)
        den = jnp.maximum(jnp.sum(wc * agree.astype(jnp.float32), axis=1), 1e-12)
    return scale * num / den


def _fedrpca_bucket(
    bucket: Bucket,
    cfg,
    shrink_fn: Callable,
    carry=None,
    svt_rank: int | None = None,
    mesh=None,
    uplink=None,
    true_cols: int | None = None,
) -> tuple[jnp.ndarray, dict, Any]:
    """One-dispatch FedRPCA over a bucket: ((B, vec) update, diag, carry').

    The bucket's client mask rides into ``robust_pca_bucket`` (n_eff ADMM
    constants, masked tail) and the column means become weighted sums over
    the active clients.  ``weighting="data_size_rpca"`` column-scales the
    bucket by n_eff-normalized weights *before* the split (importance-
    weighted RPCA — weights shape the subspace) and reverts to uniform
    means over active clients afterwards, mirroring the reference path's
    ``col_scale`` branch exactly.

    ``carry`` is this bucket's cross-round ``BucketCarry`` (or None for the
    stateless call, in which case the returned carry is None too);
    ``svt_rank`` overrides the config's basis-width cap — the two-tier
    re-pack runs converged tiers at a tighter cap.  ``mesh`` (multi-shard)
    runs the ADMM loop on the bucket's vec rows split over the mesh
    (``robust_pca_bucket(mesh=...)``); the column-mean tail stays a plain
    einsum, which GSPMD partitions along the loop's row layout, where the
    client means are shard-local.

    ``uplink`` (an active ``fed.sketch.UplinkConfig``, carry required)
    replaces the dense client columns with their sketch round-trip —
    basis coefficients + top-k residual against the carry-derived uplink
    basis — gated per bucket on residual energy: a cold/invalid carry or
    a basis-drift round selects the raw dense columns via ``jnp.where``,
    which is bitwise the uncompressed path (DESIGN.md §12).  The diag dict
    then grows ``uplink_bytes_up`` / ``uplink_bytes_down`` / ``uplink_hit``
    scalars.  ``true_cols`` caps the carried subspace width by the true
    cohort count when the bucket's client axis is padded
    (``rpca.subspace_rank``).
    """
    m = bucket.data.astype(jnp.float32)
    col_scaled = cfg.weighting == "data_size_rpca" and bucket.weights is not None
    if bucket.client_mask is None:
        n_eff = float(m.shape[-1])
        w_uniform = None
    else:
        n_eff = jnp.maximum(jnp.sum(bucket.client_mask), 1.0)
        w_uniform = bucket.client_mask / n_eff
    uplink_diag = {}
    if uplink is not None and getattr(uplink, "active", False) and carry is not None:
        # Compressed uplink (DESIGN.md §12): sketch the client columns
        # against the carry-derived basis, decode straight back into the
        # bucket layout, and gate on the energy the sketch would drop.
        # The where-select keeps the program shape-static, and a tripped
        # gate is bitwise the dense path (where(False, a, m) IS m).
        from repro.fed import sketch as sketch_lib

        basis = sketch_lib.uplink_basis(carry.l, carry.v)
        sk = sketch_lib.encode_delta(m, basis, uplink.k)
        m_hat = sketch_lib.decode_into_bucket(sk, basis)
        use_sketch = jnp.logical_and(
            carry.valid, jnp.max(sk.energy_frac) <= uplink.energy_tol
        )
        m = jnp.where(use_sketch, m_hat, m)
        b_mod, d1, r = basis.shape
        kk = min(int(uplink.k), d1)
        dense_b = sketch_lib.dense_bytes_per_client(bucket.dims)
        sketch_b = sketch_lib.sketch_bytes_per_client(b_mod, r, kk)
        hit = use_sketch.astype(jnp.float32)
        uplink_diag = {
            "uplink_bytes_up": jnp.where(hit > 0, sketch_b, dense_b) * n_eff,
            "uplink_bytes_down": jnp.asarray(
                sketch_lib.basis_bytes(b_mod, d1, r), jnp.float32
            ),
            "uplink_hit": hit,
        }
    if col_scaled:
        m = m * (bucket.weights * n_eff)[None, None, :]
    sharded = mesh is not None and rpca_lib.mesh_client_shards(mesh) > 1
    res = rpca_lib.robust_pca_bucket(
        m,
        bucket.true_dims,
        mesh=mesh,
        n_iter=cfg.rpca_iters,
        tol=None if cfg.rpca_fixed_iters else cfg.rpca_tol,
        shrink_fn=shrink_fn,
        fused_tail=cfg.rpca_fused_tail,
        client_mask=bucket.client_mask,
        svt_mode=cfg.svt_mode,
        svt_rank=cfg.svt_rank if svt_rank is None else svt_rank,
        svt_sweeps=cfg.svt_sweeps,
        svt_fallback_tol=cfg.svt_fallback_tol,
        carry=carry,
        return_carry=carry is not None,
        carry_gate=cfg.carry_gate,
        true_cols=true_cols,
    )
    new_carry = None
    if carry is not None:
        res, new_carry = res
    with jax.named_scope("agg.tail"):
        w_post = w_uniform if col_scaled else bucket.weights
        diag_extra = {}
        if cfg.guard_energy_k > 0:
            # Sparse-energy quarantine (DESIGN.md §11): per-module per-client
            # column scores replace the shared weight vector with a guarded
            # (flagged clients exactly zero) per-module one.  Off (k=0) keeps
            # the legacy shared-vector einsums bit-for-bit.
            client_energy = rpca_lib.client_sparse_energy(m, res.sparse)
            gw, flags = rpca_lib.energy_guard_weights(
                client_energy, cfg.guard_energy_k, base_w=w_post,
                valid=bucket.client_mask,
            )
            low_mean = jnp.einsum("mvc,mc->mv", res.low_rank, gw)
            sparse_mean = jnp.einsum("mvc,mc->mv", res.sparse, gw)
            diag_extra = {
                "client_energy": jnp.max(client_energy, axis=0),
                "client_flagged": jnp.max(flags, axis=0),
            }
        elif w_post is None:
            low_mean = jnp.mean(res.low_rank, axis=-1)
            sparse_mean = jnp.mean(res.sparse, axis=-1)
        else:
            low_mean = jnp.einsum("mvc,c->mv", res.low_rank, w_post)
            sparse_mean = jnp.einsum("mvc,c->mv", res.sparse, w_post)
        # E^(t) = ||S . 1|| / ||M . 1|| per module (App. B.3); padded rows and
        # masked columns are 0 so they drop out of both sums.
        energy = jax.vmap(sparse_energy_ratio)(m, res.sparse)
        if cfg.adaptive_beta:
            beta = jnp.clip(1.0 / jnp.maximum(energy, 1e-12), cfg.beta_min, cfg.beta_max)
        else:
            beta = jnp.full(energy.shape, cfg.beta, jnp.float32)
        update = low_mean + beta[:, None] * sparse_mean
        if sharded:
            # The loop leaves L and S row-sharded: replicate the
            # (B, vec) update here, once, and not leaf by leaf in unpack.
            from jax.sharding import NamedSharding, PartitionSpec as P

            update = jax.lax.with_sharding_constraint(update, NamedSharding(mesh, P()))
        diag = {
            "beta": beta, "energy": energy, "residual": res.residual,
            "n_iter": res.n_iter, **diag_extra, **uplink_diag,
        }
    return update, diag, new_carry


def _dare_rescale(stacked: PyTree, drop_rate: float, key, mask=None) -> PyTree:
    """Per-leaf DARE drop + rescale, RNG-identical to the reference path
    (``aggregators._dare_keep``: fold_in by flattened leaf index, and by
    client slot when a cohort mask is present)."""
    if key is None:
        raise ValueError("dare requires an explicit PRNG key (got key=None)")
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    out = []
    for i, leaf in enumerate(leaves):
        keep = _dare_keep(key, i, leaf.shape, drop_rate, mask)
        out.append(jnp.where(keep, leaf, 0) / (1.0 - drop_rate))
    return jax.tree_util.tree_unflatten(treedef, out)


def aggregate_packed(
    stacked: PyTree,
    cfg=None,
    *,
    shrink_fn: Callable = rpca_lib.soft_threshold,
    key=None,
    mask=None,
    weights=None,
    with_diagnostics: bool = False,
    mesh=None,
):
    """Aggregate stacked client deltas with one batched call per shape bucket.

    Drop-in replacement for the per-leaf reference dispatch: same methods,
    same results (see tests/test_engine.py parity suite), but the traced
    program contains exactly one RPCA loop / mean / TIES election per bucket
    regardless of how many leaves the delta tree has.

    ``mask``/``weights`` are the per-client validity mask and raw weights of
    shape-static partial participation (see ``aggregators.aggregate``); the
    engine zeroes masked bucket columns at pack time and threads normalized
    weights through every bucket op.  Both None -> the legacy unweighted
    dispatch, bit-for-bit.

    ``mesh`` shards every bucket's client axis (DESIGN.md §10): fedrpca
    runs the shard-mapped ADMM loop, every other method relies on GSPMD
    partitioning the batched means/elections along the ``pack`` constraint.
    A one-shard mesh is normalized away — the single-device trace, bitwise.
    """
    cfg = cfg or AggregatorConfig()
    method = cfg.method
    if mesh is not None and rpca_lib.mesh_client_shards(mesh) == 1:
        mesh = None
    mask32 = None if mask is None else jnp.asarray(mask, jnp.float32)
    w = _client_weights(mask32, weights)
    if method == "dare":
        stacked = _dare_rescale(stacked, cfg.dare_drop, key, mask=mask32)

    granularity = "leaf" if method == "ties" else "module"
    joint = method == "fedrpca" and cfg.joint_ab
    buckets, spec = pack(
        stacked, granularity=granularity, joint_ab=joint,
        client_mask=mask32, weights=w, mesh=mesh,
    )

    updates: dict[BucketKey, jnp.ndarray] = {}
    diag_arrays: dict[str, dict] = {}

    if method in ("fedavg", "dare"):
        for bkey, bucket in buckets.items():
            updates[bkey] = _bucket_mean(bucket)
    elif method == "task_arithmetic":
        for bkey, bucket in buckets.items():
            updates[bkey] = (cfg.beta * _bucket_mean(bucket)).astype(bucket.data.dtype)
    elif method == "ties":
        for bkey, bucket in buckets.items():
            updates[bkey] = _ties_bucket(
                bucket.data, bucket.dims, cfg.ties_keep, cfg.ties_scale, bucket.weights
            )
    elif method == "fedexp":
        # Global extrapolation factor over ALL buckets (padding adds zeros,
        # and masked columns were zeroed at pack time, so the squared-norm
        # sums run over active clients only).
        eps = 1e-3
        sum_sq = 0.0
        mean_sq = 0.0
        means = {}
        n_eff = (
            spec.n_clients
            if mask32 is None
            else jnp.maximum(jnp.sum(mask32), 1.0)
        )
        for bkey, bucket in buckets.items():
            sum_sq += jnp.sum(jnp.square(bucket.data.astype(jnp.float32)))
            mean = _bucket_mean(bucket)
            means[bkey] = mean
            mean_sq += jnp.sum(jnp.square(mean.astype(jnp.float32)))
        eta = jnp.maximum(1.0, sum_sq / (2.0 * n_eff * (mean_sq + eps)))
        for bkey, mean in means.items():
            updates[bkey] = (eta * mean).astype(mean.dtype)
    elif method == "fedrpca":
        names = ("beta", "energy", "residual") + (
            ("client_energy", "client_flagged") if cfg.guard_energy_k > 0 else ()
        )
        diag_arrays = {k: {} for k in names}
        for bkey, bucket in buckets.items():
            updates[bkey], d, _ = _fedrpca_bucket(
                bucket, cfg, shrink_fn, mesh=mesh, true_cols=spec.n_clients
            )
            for k in names:
                diag_arrays[k][bkey] = d[k]
    else:
        raise ValueError(f"unknown aggregation method: {method!r}")

    out = unpack(spec, updates)
    if with_diagnostics:
        # Non-fedrpca methods have no per-module diagnostics: return a plain
        # empty dict, matching the reference engine's contract.
        if not diag_arrays:
            return out, {}
        return out, EngineDiagnostics(spec=spec, arrays=diag_arrays)
    return out


# ---------------------------------------------------------------------------
# Stateful cross-round aggregation sessions (DESIGN.md §7)
# ---------------------------------------------------------------------------
#
# The stateless ``aggregate_packed`` re-derives everything per call and
# throws all RPCA state away, so every federated round cold-starts the ADMM
# loop and pays the exact-eigh burn-in that svt_mode="subspace" was built to
# avoid — even though client deltas correlate strongly across rounds (the
# paper's core observation).  The session API splits aggregation into a
# trace-time *plan* (``AggPlan``: PackSpec + two-tier bucket layout, built
# once per tree structure) and a runtime *step* (``aggregate_planned``) that
# takes and returns an ``AggCarry`` pytree of per-bucket-tier
# ``rpca.BucketCarry`` states, so warm rounds enter the ADMM loop at the
# previous round's fixed point.  The carry is an ordinary pytree of fixed
# shapes: threading it through a jitted round adds zero extra compiles.

#: AggCarry: {(bucket_key, tier_name): rpca.BucketCarry}.  An empty dict is
#: the carry of a plan with no session state (carry_mode="none" or a
#: non-fedrpca method) — structurally stable either way.
AggCarry = dict


@dataclasses.dataclass(frozen=True)
class TierSpec:
    """Static two-tier split of one bucket's module rows.

    ``full_idx`` modules run at the config's ``svt_rank`` cap (the burn-in
    tier); ``low_idx`` modules have converged to a small live rank and run
    at the tighter ``low_cap`` (smaller carried basis, cheaper sweeps and
    r x r Ritz solves).  Either side may be empty; membership is static
    Python data, so tier changes re-trace — ``plan_retier`` therefore runs
    on a K-round cadence, never per round.
    """

    low_idx: tuple = ()
    full_idx: tuple = ()
    low_cap: int = 0

    def tiers(self):
        """Non-empty (name, module_idx, rank_cap_or_None) tiers."""
        out = []
        if self.full_idx:
            out.append(("full", self.full_idx, None))
        if self.low_idx:
            out.append(("low", self.low_idx, self.low_cap))
        return out


@dataclasses.dataclass(frozen=True)
class AggPlan:
    """Trace-time half of an aggregation session: everything static.

    Built once per delta-tree structure by ``plan_aggregation`` and reused
    every round: the invertible ``PackSpec``, the packing granularity, the
    per-bucket two-tier layout, and whether a carry threads at all.  The
    plan is the compilation key — rounds that share a plan share one trace.
    """

    cfg: AggregatorConfig
    spec: PackSpec
    granularity: str
    joint_ab: bool
    carry: bool  # whether step() threads an AggCarry
    tiers: Mapping[BucketKey, TierSpec]
    # Device mesh the packed client axis shards across (DESIGN.md §10).
    # Always None when the mesh has a single client shard —
    # ``plan_aggregation`` normalizes, so ``mesh is None`` IS the
    # single-device path and sharded steps never retrace against it.
    mesh: Any = None
    # Uplink codec (``fed.sketch.UplinkConfig``; DESIGN.md §12).  None or
    # dense mode never enters the codec — the traced step is bit-for-bit
    # the uncompressed path.  Sketch mode requires a carrying plan (the
    # codec projects onto the carried basis); stateless plans stay dense.
    uplink: Any = None


def _plan_carry(cfg) -> bool:
    if cfg.carry_mode not in CARRY_MODES:
        raise ValueError(
            f"unknown carry_mode: {cfg.carry_mode!r} (expected one of {CARRY_MODES})"
        )
    if cfg.carry_mode == "none" or cfg.method != "fedrpca":
        return False
    if cfg.carry_mode == "subspace" and cfg.svt_mode != "subspace":
        raise ValueError(
            'carry_mode="subspace" persists the subspace-SVT eigenbasis and '
            'requires svt_mode="subspace"; use carry_mode="full" to carry '
            "bare ADMM iterates under gram mode"
        )
    return True


def plan_aggregation(
    stacked: PyTree,
    cfg=None,
    *,
    cohort_size: int | None = None,
    mesh=None,
    uplink=None,
    client_ranks=None,
) -> AggPlan:
    """Build the trace-time plan for aggregating trees shaped like ``stacked``.

    ``stacked`` may be concrete arrays or tracers — only its structure,
    shapes and dtypes matter.  The initial plan puts every bucket's modules
    in the burn-in tier; ``plan_retier`` moves converged modules to the
    low-rank tier between rounds.

    ``mesh`` requests client-axis sharding.  Plans normalize one-shard
    meshes (the ``(1, 1)`` debug mesh included) to ``mesh=None`` so the
    single-device trace stays bitwise identical.  The sharded loop splits
    vec rows, so ragged cohorts (``cohort_size % shards != 0``) shard as
    they are, and ``rpca_fused_tail`` runs the Pallas tail kernels
    shard-locally on each shard's rows (DESIGN.md §10).

    ``uplink`` is the compressed-uplink codec config (a
    ``fed.sketch.UplinkConfig``, or a spec string for
    ``fed.sketch.parse_uplink``; DESIGN.md §12).  Dense/None plans never
    enter the codec — the traced step is bit-for-bit the uncompressed
    path.  Sketch mode requires a carrying plan (the codec projects onto
    the carried basis); a non-carrying plan ignores it with a warning.
    ``client_ranks`` records the per-client declared ranks of a
    heterogeneous cohort on the ``PackSpec`` (descriptor only — the rank
    masks are applied to the deltas upstream).
    """
    cfg = cfg or AggregatorConfig()
    if mesh is not None and rpca_lib.mesh_client_shards(mesh) == 1:
        mesh = None
    granularity = "leaf" if cfg.method == "ties" else "module"
    joint = cfg.method == "fedrpca" and cfg.joint_ab
    _, spec = pack(
        stacked, granularity=granularity, joint_ab=joint, cohort_size=cohort_size
    )
    if client_ranks is not None:
        spec = dataclasses.replace(
            spec, client_ranks=tuple(int(r) for r in client_ranks)
        )
    tiers = {
        key: TierSpec(low_idx=(), full_idx=tuple(range(dims[0])), low_cap=0)
        for key, dims in spec.bucket_dims.items()
    }
    carry = _plan_carry(cfg)
    if uplink is not None:
        from repro.fed import sketch as sketch_lib

        uplink = sketch_lib.parse_uplink(uplink)
        if uplink.active and not carry:
            import warnings

            warnings.warn(
                "uplink sketch mode needs a carrying fedrpca plan (the codec "
                "projects onto the carried basis); running dense",
                stacklevel=2,
            )
            uplink = None
        elif not uplink.active:
            uplink = None  # dense IS the no-codec path; keep plans stable
    return AggPlan(
        cfg=cfg,
        spec=spec,
        granularity=granularity,
        joint_ab=joint,
        carry=carry,
        tiers=tiers,
        mesh=mesh,
        uplink=uplink,
    )


def init_agg_carry(plan: AggPlan) -> AggCarry:
    """Empty (invalid) carry matching the plan's bucket/tier layout."""
    if not plan.carry:
        return {}
    out = {}
    for bkey, tier in plan.tiers.items():
        padded_vec, d2 = bkey[0], bkey[1]
        for name, idx, cap in tier.tiers():
            rank = plan.cfg.svt_rank if cap is None else cap
            out[(bkey, name)] = rpca_lib.init_bucket_carry(
                len(idx), padded_vec, d2, rank, true_cols=plan.spec.n_clients
            )
    return out


@jax.named_scope("agg.pack")
def _sub_bucket(bucket: Bucket, idx: tuple) -> Bucket:
    """Static module-row subset of a bucket (a tier's view)."""
    ia = jnp.asarray(idx, jnp.int32)
    return Bucket(
        data=bucket.data[ia],
        true_dims=bucket.true_dims[ia],
        dims=tuple(bucket.dims[i] for i in idx),
        client_mask=bucket.client_mask,
        weights=bucket.weights,
    )


def aggregate_planned(
    plan: AggPlan,
    stacked: PyTree,
    carry: AggCarry | None = None,
    *,
    shrink_fn: Callable = rpca_lib.soft_threshold,
    key=None,
    mask=None,
    weights=None,
    with_diagnostics: bool = False,
):
    """Runtime step of an aggregation session: one round under a fixed plan.

    Packs ``stacked`` into the plan's buckets (the packing walk happens at
    trace time; compiled rounds re-run only the device ops), dispatches each
    bucket *tier* as one batched call with its own rank cap and its own
    slot of the carry, and returns ``(update, new_carry)`` — plus an
    ``EngineDiagnostics`` when ``with_diagnostics`` (fedrpca adds
    per-module ``live_rank`` and the ``fallback_count`` / ``svt_steps`` /
    ``carry_hit_rate`` scalars when a carry threads: ``svt_steps`` counts
    the SVT steps run, summed over the carried tiers, so ``fallback_count
    / svt_steps`` is the share that fell back to the exact eigh;
    sketch-uplink plans add the ``bytes_up`` / ``bytes_down_basis`` /
    ``uplink_hit_rate`` / ``uplink_dense_falls`` wire-accounting scalars,
    DESIGN.md §12).

    ``carry=None`` (or ``{}``) with a carrying plan cold-starts every
    bucket; ``carry_mode="none"`` plans pass the empty carry through
    unchanged and produce bit-for-bit the stateless result.
    """
    cfg = plan.cfg
    method = cfg.method
    if method != "fedrpca":
        # Only fedrpca has session state; every other method (dare's drop/
        # rescale included) delegates wholesale to the stateless dispatch
        # and passes the (empty) carry through.
        out = aggregate_packed(
            stacked, cfg, shrink_fn=shrink_fn, key=key, mask=mask,
            weights=weights, with_diagnostics=with_diagnostics,
            mesh=plan.mesh,
        )
        new_carry = {} if carry is None else carry
        if with_diagnostics:
            return out[0], new_carry, out[1]
        return out, new_carry

    mask32 = None if mask is None else jnp.asarray(mask, jnp.float32)
    w = _client_weights(mask32, weights)
    buckets, spec = pack(
        stacked, granularity=plan.granularity, joint_ab=plan.joint_ab,
        client_mask=mask32, weights=w, mesh=plan.mesh,
    )
    if dict(spec.bucket_dims) != dict(plan.spec.bucket_dims):
        raise ValueError(
            "stacked tree does not match the session plan "
            f"({dict(spec.bucket_dims)} vs {dict(plan.spec.bucket_dims)}); "
            "re-plan with plan_aggregation for a new tree structure"
        )
    if plan.carry and not carry:
        carry = init_agg_carry(plan)

    updates: dict[BucketKey, jnp.ndarray] = {}
    # Guard diagnostics are (cohort,)-shaped, not per-module: tiers combine
    # them element-wise (max = "any module flagged") instead of scattering.
    client_keys = (
        ("client_energy", "client_flagged") if cfg.guard_energy_k > 0 else ()
    )
    arrays: dict[str, dict] = {
        k: {}
        for k in ("beta", "energy", "residual")
        + (("live_rank",) if plan.carry else ())
        + client_keys
    }
    new_carry: AggCarry = {}
    falls, hits, svt_steps = [], [], []
    # Uplink byte accounting (sketch plans only): per-tier wire bytes and
    # gate hits, summed into round scalars (DESIGN.md §12).
    up_bytes, down_bytes, up_hits = [], [], []

    def run_tier(sub_bucket, ck, cap):
        upd_t, d_t, c2 = _fedrpca_bucket(
            sub_bucket, cfg, shrink_fn,
            carry=carry.get(ck) if plan.carry else None, svt_rank=cap,
            mesh=plan.mesh, uplink=plan.uplink,
            true_cols=plan.spec.n_clients,
        )
        if "uplink_bytes_up" in d_t:
            up_bytes.append(d_t["uplink_bytes_up"])
            down_bytes.append(d_t["uplink_bytes_down"])
            up_hits.append(d_t["uplink_hit"])
        return upd_t, d_t, c2

    for bkey, bucket in buckets.items():
        tier = plan.tiers[bkey]
        b_total, padded_vec = plan.spec.bucket_dims[bkey]
        tiers = tier.tiers()
        if len(tiers) == 1 and tiers[0][1] == tuple(range(b_total)):
            # Single whole-bucket tier: skip the gather/scatter round-trip.
            name, _, cap = tiers[0]
            ck = (bkey, name)
            upd, d, c2 = run_tier(bucket, ck, cap)
            updates[bkey] = upd
            per_mod = dict(d)
            if plan.carry:
                new_carry[ck] = c2
                per_mod["live_rank"] = c2.n_live.astype(jnp.float32)
                falls.append(c2.fall_count)
                hits.append(c2.hit)
                svt_steps.append(jnp.max(d["n_iter"]))
        else:
            upd = jnp.zeros((b_total, padded_vec), jnp.float32)
            per_mod = {
                k: jnp.zeros((b_total,), jnp.float32)
                for k in arrays
                if k not in client_keys
            }
            for name, idx, cap in tiers:
                ck = (bkey, name)
                sub = _sub_bucket(bucket, idx)
                u_t, d_t, c2 = run_tier(sub, ck, cap)
                with jax.named_scope("agg.unpack"):
                    ia = jnp.asarray(idx, jnp.int32)
                    upd = upd.at[ia].set(u_t.astype(jnp.float32))
                    for k in ("beta", "energy", "residual"):
                        per_mod[k] = per_mod[k].at[ia].set(d_t[k])
                    for k in client_keys:
                        per_mod[k] = (
                            d_t[k] if k not in per_mod
                            else jnp.maximum(per_mod[k], d_t[k])
                        )
                    if plan.carry:
                        new_carry[ck] = c2
                        per_mod["live_rank"] = per_mod["live_rank"].at[ia].set(
                            c2.n_live.astype(jnp.float32)
                        )
                if plan.carry:
                    falls.append(c2.fall_count)
                    hits.append(c2.hit)
                    svt_steps.append(jnp.max(d_t["n_iter"]))
            updates[bkey] = upd
        for k in arrays:
            arrays[k][bkey] = per_mod[k]

    out = unpack(spec, updates)
    if not with_diagnostics:
        return out, new_carry
    scalars = {}
    if plan.carry:
        scalars = {
            "fallback_count": sum(falls, jnp.zeros((), jnp.int32)),
            "svt_steps": sum(svt_steps, jnp.zeros((), jnp.int32)),
            "carry_hit_rate": jnp.mean(jnp.stack(hits)),
        }
    if up_bytes:
        scalars["bytes_up"] = sum(up_bytes, jnp.zeros((), jnp.float32))
        scalars["bytes_down_basis"] = sum(down_bytes, jnp.zeros((), jnp.float32))
        scalars["uplink_hit_rate"] = jnp.mean(jnp.stack(up_hits))
        scalars["uplink_dense_falls"] = jnp.sum(1.0 - jnp.stack(up_hits))
    diag = EngineDiagnostics(spec=spec, arrays=arrays, scalars=scalars)
    return out, new_carry, diag


def plan_retier(plan: AggPlan, carry: AggCarry, *, margin: int | None = None) -> AggPlan:
    """Two-tier re-pack: move converged modules to a tighter-rank tier.

    Host-side (reads the carry's live ranks): a module whose carried live
    rank sits at least ``margin + 1`` below the full cap joins the low
    tier, whose cap is the max live rank among its members plus ``margin``
    headroom.  Buckets with an invalid carry (or nothing worth splitting)
    keep a single burn-in tier.  Returns a NEW plan — membership is static,
    so stepping the new plan re-traces once; call on a K-round cadence
    (``AggregatorConfig.retier_every``), not per round.
    """
    cfg = plan.cfg
    if not plan.carry:
        return plan
    margin = cfg.retier_margin if margin is None else margin
    new_tiers = {}
    for bkey, tier in plan.tiers.items():
        b_total = plan.spec.bucket_dims[bkey][0]
        d2 = bkey[1]
        r_full = rpca_lib.subspace_rank(d2, cfg.svt_rank, plan.spec.n_clients)
        single = TierSpec(low_idx=(), full_idx=tuple(range(b_total)), low_cap=0)
        n_live = [0] * b_total
        ok = True
        for name, idx, _cap in tier.tiers():
            c = carry.get((bkey, name))
            if c is None or not bool(c.valid):
                ok = False
                break
            for i, mod in enumerate(idx):
                n_live[mod] = int(c.n_live[i])
        if not ok:
            new_tiers[bkey] = single
            continue
        lows = tuple(i for i in range(b_total) if 0 < n_live[i] + margin < r_full)
        low_cap = max((n_live[i] for i in lows), default=0) + margin
        if not lows or low_cap >= r_full:
            new_tiers[bkey] = single
            continue
        fulls = tuple(i for i in range(b_total) if i not in set(lows))
        new_tiers[bkey] = TierSpec(low_idx=lows, full_idx=fulls, low_cap=low_cap)
    return dataclasses.replace(plan, tiers=new_tiers)


def migrate_carry(old_plan: AggPlan, old_carry: AggCarry, new_plan: AggPlan) -> AggCarry:
    """Re-key a carry onto a re-tiered plan (same PackSpec, new membership).

    Module rows (warm L/S/Y iterates, live ranks) move with their modules;
    each module's basis is column-sliced to the destination tier's width
    (eigh orders ascending, so the trailing columns are the top directions)
    or front-padded with identity columns when the width grows.  The
    validity scalars transfer, so migrated buckets warm-start immediately;
    any basis mismatch the slice introduces is caught by the subspace
    fallback gate, never silently wrong.
    """
    if not new_plan.carry:
        return {}
    if not old_carry:
        return init_agg_carry(new_plan)
    out = init_agg_carry(new_plan)
    for bkey, new_tier in new_plan.tiers.items():
        # Gather old per-module state for this bucket.
        by_mod = {}
        meta = None
        for name, idx, _cap in old_plan.tiers[bkey].tiers():
            c = old_carry.get((bkey, name))
            if c is None:
                continue
            meta = c
            for i, mod in enumerate(idx):
                by_mod[mod] = (c.l[i], c.s[i], c.y[i], c.v[i], c.n_live[i])
        if meta is None:
            continue
        for name, idx, cap in new_tier.tiers():
            ck = (bkey, name)
            tgt = out[ck]
            if any(mod not in by_mod for mod in idx):
                continue  # keep the invalid zero-carry for this tier
            r_new = tgt.v.shape[-1]

            def fit_basis(v):
                r_old = v.shape[-1]
                if r_old >= r_new:
                    return v[:, r_old - r_new:]
                d2 = v.shape[0]
                pad = jnp.eye(d2, r_new - r_old, dtype=v.dtype)
                return jnp.concatenate([pad, v], axis=-1)

            stack = lambda j: jnp.stack([by_mod[mod][j] for mod in idx])
            out[ck] = rpca_lib.BucketCarry(
                l=stack(0),
                s=stack(1),
                y=stack(2),
                v=jnp.stack([fit_basis(by_mod[mod][3]) for mod in idx]),
                n_live=jnp.minimum(stack(4), r_new).astype(jnp.int32),
                n_eff=meta.n_eff,
                valid=meta.valid,
                fall_count=jnp.zeros((), jnp.int32),
                hit=jnp.zeros((), jnp.float32),
            )
    return out


class AggSession:
    """Stateful cross-round aggregation: plan once, step every round.

    The session owns the plan, the carry, and one jitted step per plan.
    ``step`` lazily plans on the first call (from that call's tree
    structure), re-tiers every ``cfg.retier_every`` rounds (0 = never), and
    threads the carry automatically:

        session = AggSession(AggregatorConfig(
            method="fedrpca", svt_mode="subspace", carry_mode="subspace"))
        for round_tree in rounds:
            update, diag = session.step(round_tree)

    ``fed.server.make_round_fn`` inlines the same plan/step pair inside its
    jitted round (the carry rides on ``RoundState.agg_carry``); this class
    is the standalone driver for benchmarks, notebooks, and the async
    pipeline work the ROADMAP points at.
    """

    def __init__(
        self,
        cfg=None,
        *,
        shrink_fn: Callable = rpca_lib.soft_threshold,
        mesh=None,
        uplink=None,
    ):
        self.cfg = cfg or AggregatorConfig()
        self.shrink_fn = shrink_fn
        self.mesh = mesh
        self.uplink = uplink
        self.plan: AggPlan | None = None
        self.carry: AggCarry = {}
        self.round_idx = 0
        self._step = None

    def _compile(self):
        plan, shrink_fn = self.plan, self.shrink_fn

        @jax.jit
        def step(stacked, carry, key, mask, weights):
            return aggregate_planned(
                plan, stacked, carry, shrink_fn=shrink_fn, key=key,
                mask=mask, weights=weights, with_diagnostics=True,
            )

        self._step = step

    def reset(self):
        """Drop all cross-round state (the next step cold-starts)."""
        if self.plan is not None:
            self.carry = init_agg_carry(self.plan)
        self.round_idx = 0

    def retier(self):
        """Re-evaluate the two-tier split now and migrate the carry."""
        new_plan = plan_retier(self.plan, jax.device_get(self.carry))
        if new_plan.tiers != self.plan.tiers:
            self.carry = migrate_carry(self.plan, self.carry, new_plan)
            self.plan = new_plan
            self._compile()

    def step(self, stacked, *, key=None, mask=None, weights=None):
        """Aggregate one round's stacked deltas; returns (update, diag)."""
        if self.plan is None:
            self.plan = plan_aggregation(
                stacked, self.cfg, mesh=self.mesh, uplink=self.uplink
            )
            self.carry = init_agg_carry(self.plan)
            self._compile()
        elif (
            self.cfg.retier_every
            and self.round_idx
            and self.round_idx % self.cfg.retier_every == 0
        ):
            self.retier()
        out, self.carry, diag = self._step(stacked, self.carry, key, mask, weights)
        self.round_idx += 1
        return out, diag
