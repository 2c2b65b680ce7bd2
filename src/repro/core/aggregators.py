"""Server-side aggregation strategies for federated LoRA.

Implements, over stacked client delta pytrees (leading axis = clients):

  * ``fedavg``          — Eq. 4: plain mean.
  * ``task_arithmetic`` — Eq. 5: scaled mean, beta > 1 (also the FedExP /
                           server-learning-rate view).
  * ``ties``            — TIES-Merging (trim -> elect sign -> disjoint mean).
  * ``fedrpca``         — Algorithm 1: per-module Robust-PCA split M = L + S,
                           update = mean(L) + beta * mean(S), with the
                           adaptive beta^(t) = 1 / E^(t) heuristic of App. B.3.

All aggregators are pure jittable functions: stacked deltas in, single update
pytree out (same structure as one client's delta).  They are used both by the
CPU simulation loop and inside the mesh ``fed_train_step`` (where the stacked
leaves arrive via an all-gather over the client mesh axes).

Two execution engines back ``aggregate``: the per-leaf functions in this
module (``engine="reference"``, one vmapped call per leaf — kept as the
parity oracle) and the batched engine in ``repro.core.engine``
(``engine="packed"``, the default: leaves are packed into shape buckets and
every method runs as one batched call per bucket).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import rpca as rpca_lib
from repro.core import stacking

PyTree = Any


#: Client weighting schemes understood by the round drivers: "uniform"
#: averages active clients equally; "data_size" weights each client's delta
#: by its local dataset size (the paper's FedAvg, Eq. 4 with n_k / n);
#: "data_size_rpca" additionally column-scales the RPCA input M by the
#: normalized data-size weights *before* the low-rank/sparse split, so
#: weights shape the recovered subspace rather than only the final means
#: (non-fedrpca methods treat it exactly like "data_size").
WEIGHTINGS = ("uniform", "data_size", "data_size_rpca")

#: Cross-round aggregation carry modes (DESIGN.md §7): "none" keeps the
#: per-round stateless behavior bit-for-bit; "subspace" persists each
#: bucket's subspace-SVT session (eigenbasis + the ADMM iterates it tracks)
#: across rounds and requires ``svt_mode="subspace"``; "full" carries the
#: ADMM iterates under either svt mode (in gram mode there is no eigh to
#: skip, but tolerance-mode rounds re-converge in far fewer iterations).
#: The carry threads through the packed engine's session API
#: (``repro.core.engine.AggSession`` / ``aggregate_planned``); the
#: reference engine ignores it.
CARRY_MODES = ("none", "subspace", "full")


@dataclasses.dataclass(frozen=True)
class AggregatorConfig:
    """Configuration shared by all aggregation strategies."""

    method: str = "fedrpca"  # fedavg | task_arithmetic | ties | fedrpca
    weighting: str = "uniform"  # uniform | data_size | data_size_rpca
    beta: float = 2.0  # scaling factor (task_arithmetic, fixed-beta fedrpca)
    adaptive_beta: bool = True  # fedrpca: beta = 1 / E^(t)
    beta_min: float = 1.0  # clip range for the adaptive beta
    beta_max: float = 100.0
    rpca_iters: int = 50  # ADMM iteration count / cap (shape-static cost)
    rpca_tol: float = 1e-7  # stopping tolerance when rpca_fixed_iters=False
    rpca_fixed_iters: bool = True  # False: tolerance-based early stopping
    rpca_fused_tail: bool = False  # packed engine: Pallas fused ADMM tail
    svt_mode: str = "gram"  # gram (per-iteration eigh) | subspace (warm-started)
    svt_rank: int = 8  # subspace mode: carried basis width cap
    svt_sweeps: int = 2  # subspace mode: power sweeps per ADMM iteration
    svt_fallback_tol: float = 1e-3  # subspace-residual bound before eigh fallback
    carry_mode: str = "none"  # cross-round session carry (see CARRY_MODES)
    carry_gate: float = 1.0  # warm-start gate: max initial residual vs cold (=1.0)
    retier_every: int = 0  # AggSession: re-split tiers every K rounds (0 = off)
    retier_margin: int = 1  # live-rank headroom kept by the low tier's rank cap
    ties_keep: float = 0.1  # TIES trim: fraction of entries kept per client
    ties_scale: float = 1.0  # TIES final scaling (lambda in the paper)
    dare_drop: float = 0.9  # DARE drop rate
    joint_ab: bool = False  # RPCA jointly over concatenated vec(A),vec(B)
    # (App. B.2: "we also apply this jointly across the (A,B) pairs")
    # Sparse-energy quarantine (DESIGN.md §11): clients whose per-module
    # RPCA sparse-energy score exceeds guard_energy_k x the module's median
    # are zero-weighted in the post-split means (both engines).  0.0 = off,
    # the legacy bit-for-bit path.
    guard_energy_k: float = 0.0

    def replace(self, **kw) -> "AggregatorConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Client validity masks and weights (shape-static partial participation)
# ---------------------------------------------------------------------------
#
# Every aggregator takes an optional per-client validity ``mask`` (1 = the
# slot holds a sampled client's delta, 0 = cohort padding) and raw
# nonnegative ``weights`` (e.g. local dataset sizes).  With both None the
# legacy unweighted code paths run unchanged — bit-for-bit — which is the
# full-participation uniform default.


def _client_weights(mask=None, weights=None):
    """Normalized (n_clients,) float32 weights, or None for the legacy
    unweighted path.  Masked slots get weight exactly zero, so garbage in
    padded cohort columns never reaches a weighted reduction."""
    if mask is None and weights is None:
        return None
    if weights is None:
        w = jnp.asarray(mask, jnp.float32)
    else:
        w = jnp.asarray(weights, jnp.float32)
        if mask is not None:
            w = w * jnp.asarray(mask, jnp.float32)
    return w / jnp.maximum(jnp.sum(w), 1e-12)


def _wmean_leaf(leaf: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Weighted mean over the leading client axis, accumulated in float32."""
    return jnp.tensordot(w, leaf.astype(jnp.float32), axes=(0, 0)).astype(leaf.dtype)


def _mask_n_eff(mask, n_clients: int):
    return n_clients if mask is None else jnp.maximum(jnp.sum(jnp.asarray(mask, jnp.float32)), 1.0)


# ---------------------------------------------------------------------------
# Simple strategies
# ---------------------------------------------------------------------------


def fedavg(stacked: PyTree, mask=None, weights=None) -> PyTree:
    """Eq. 4.  Unweighted mean by default; with ``weights`` (data sizes)
    and/or a cohort ``mask`` it is the paper's true FedAvg sum_k (n_k/n) d_k
    over the active clients."""
    w = _client_weights(mask, weights)
    if w is None:
        return jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), stacked)
    return jax.tree_util.tree_map(lambda x: _wmean_leaf(x, w), stacked)


def task_arithmetic(stacked: PyTree, beta: float = 2.0, mask=None, weights=None) -> PyTree:
    w = _client_weights(mask, weights)
    if w is None:
        return jax.tree_util.tree_map(lambda x: beta * jnp.mean(x, axis=0), stacked)
    return jax.tree_util.tree_map(lambda x: (beta * _wmean_leaf(x, w)).astype(x.dtype), stacked)


def fedexp(stacked: PyTree, eps: float = 1e-3, mask=None, weights=None) -> PyTree:
    """FedExP (Jhunjhunwala et al., ICLR 2023 — ref [36] in the paper):
    server extrapolation with a data-derived global step size

        eta_g = max(1, sum_i ||d_i||^2 / (2 M (||mean(d)||^2 + eps)))

    A diversity-adaptive Task-Arithmetic: orthogonal client updates get a
    large eta, aligned ones fall back to plain averaging.  Masked cohorts
    sum ||d_i||^2 over active clients only and use M = n_eff."""
    mean = fedavg(stacked, mask=mask, weights=weights)
    bmask = (
        None
        if mask is None
        else jnp.asarray(mask, jnp.float32)
    )

    def sq_stacked(x):
        x = x.astype(jnp.float32)
        if bmask is not None:
            x = x * bmask.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.sum(jnp.square(x))

    sq = lambda t, f: sum(f(x) for x in jax.tree_util.tree_leaves(t))
    n_eff = _mask_n_eff(mask, jax.tree_util.tree_leaves(stacked)[0].shape[0])
    mean_sq = sq(mean, lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))))
    eta = jnp.maximum(1.0, sq(stacked, sq_stacked) / (2.0 * n_eff * (mean_sq + eps)))
    return jax.tree_util.tree_map(lambda x: (eta * x).astype(x.dtype), mean)


def _dare_keep(key, leaf_index: int, leaf_shape, drop_rate: float, mask=None):
    """Bernoulli keep mask for one stacked leaf.

    With ``mask=None`` (dense cohorts) a single draw covers the whole leaf —
    the legacy stream, unchanged.  With a mask, each client *slot* gets its
    own fold_in key so slot j draws the same pattern whether the cohort is
    padded to 8 or materialized densely at size j+1 — the property the
    masked-vs-dense parity suite relies on."""
    k = jax.random.fold_in(key, leaf_index)
    if mask is None:
        return jax.random.bernoulli(k, 1.0 - drop_rate, leaf_shape)
    keys = jax.vmap(lambda j: jax.random.fold_in(k, j))(jnp.arange(leaf_shape[0]))
    return jax.vmap(
        lambda kk: jax.random.bernoulli(kk, 1.0 - drop_rate, leaf_shape[1:])
    )(keys)


def dare(stacked: PyTree, drop_rate: float = 0.9, key=None, mask=None, weights=None) -> PyTree:
    """DARE (Yu et al. 2024 — ref [92]): randomly drop ``drop_rate`` of each
    client delta's entries and rescale the rest by 1/(1-p) before averaging
    (an unbiased sparsifier that reduces merging interference).

    ``key`` is required: a silent constant key would repeat the same drop
    pattern every round, defeating the unbiasedness argument."""
    if key is None:
        raise ValueError("dare requires an explicit PRNG key (got key=None)")
    w = _client_weights(mask, weights)
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    out = []
    for i, leaf in enumerate(leaves):
        keep = _dare_keep(key, i, leaf.shape, drop_rate, mask)
        rescaled = jnp.where(keep, leaf, 0) / (1.0 - drop_rate)
        if w is None:
            out.append(jnp.mean(rescaled, axis=0).astype(leaf.dtype))
        else:
            out.append(_wmean_leaf(rescaled, w).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# TIES-Merging
# ---------------------------------------------------------------------------


def _ties_leaf(leaf: jnp.ndarray, keep: float, scale: float, w=None) -> jnp.ndarray:
    """TIES on one stacked leaf: (clients, ...) -> (...).

    ``w`` (normalized per-client weights, masked slots zero) switches the
    sign election to weighted mass and the disjoint mean to a weighted
    average; None keeps the legacy unweighted path bit-for-bit."""
    n_clients = leaf.shape[0]
    flat = jnp.reshape(leaf, (n_clients, -1)).astype(jnp.float32)
    d = flat.shape[1]
    k = max(int(keep * d), 1)
    # 1) Trim: keep top-k |value| entries per client, zero the rest.
    #    lax.top_k is O(d log k) on the server hot path vs the O(d log d)
    #    full sort it replaced; the k-th-largest threshold value is identical.
    absx = jnp.abs(flat)
    kth = jax.lax.top_k(absx, k)[0][:, -1:]  # per-client k-th largest
    trimmed = jnp.where(absx >= kth, flat, 0.0)
    if w is None:
        # 2) Elect sign by total mass.
        elected = jnp.sign(jnp.sum(trimmed, axis=0))
        elected = jnp.where(elected == 0.0, 1.0, elected)
        # 3) Disjoint mean: average only entries agreeing with the elected sign.
        agree = (jnp.sign(trimmed) == elected[None, :]) & (trimmed != 0.0)
        num = jnp.sum(jnp.where(agree, trimmed, 0.0), axis=0)
        den = jnp.maximum(jnp.sum(agree.astype(jnp.float32), axis=0), 1.0)
    else:
        wc = w[:, None]
        elected = jnp.sign(jnp.sum(wc * trimmed, axis=0))
        elected = jnp.where(elected == 0.0, 1.0, elected)
        agree = (jnp.sign(trimmed) == elected[None, :]) & (trimmed != 0.0)
        num = jnp.sum(jnp.where(agree, wc * trimmed, 0.0), axis=0)
        # weighted "count": zero only where no weighted client agrees, in
        # which case num is zero too — 0/eps = 0, matching the legacy clamp.
        den = jnp.maximum(jnp.sum(wc * agree.astype(jnp.float32), axis=0), 1e-12)
    merged = scale * num / den
    return jnp.reshape(merged, leaf.shape[1:]).astype(leaf.dtype)


def ties_merging(
    stacked: PyTree, keep: float = 0.1, scale: float = 1.0, mask=None, weights=None
) -> PyTree:
    w = _client_weights(mask, weights)
    fn = functools.partial(_ties_leaf, keep=keep, scale=scale, w=w)
    return jax.tree_util.tree_map(fn, stacked)


# ---------------------------------------------------------------------------
# FedRPCA (the paper)
# ---------------------------------------------------------------------------


def sparse_energy_ratio(m_mat: jnp.ndarray, s_mat: jnp.ndarray) -> jnp.ndarray:
    """E^(t) = ||S . 1|| / ||M . 1||  (App. B.3), for one (vec, clients) matrix."""
    s_sum = jnp.linalg.norm(jnp.sum(s_mat, axis=-1))
    m_sum = jnp.linalg.norm(jnp.sum(m_mat, axis=-1))
    return s_sum / jnp.maximum(m_sum, 1e-12)


def _fedrpca_matrix(
    m_mat: jnp.ndarray,
    cfg: AggregatorConfig,
    shrink_fn: Callable,
    mask=None,
    w=None,
    col_scale=None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """FedRPCA on one (vec_dim, n_clients) matrix.

    ``mask`` zeroes inactive client columns and switches the ADMM constants
    to the effective client count n_eff (numel = d1 * n_eff, lam =
    1/sqrt(max(d1, n_eff))) so the decomposition of the active sub-matrix
    matches a dense sub-cohort call; ``w`` (normalized weights, masked slots
    zero) replaces the plain column means.  ``col_scale`` (per-client
    scale, importance-weighted RPCA — ``weighting="data_size_rpca"``)
    multiplies M's columns *before* the split so weights shape the
    recovered subspace; the caller then passes the uniform-over-active
    ``w`` because the scaling already encodes the weighting.  The n_eff
    derivation is intentionally re-stated here rather than shared with
    ``rpca.robust_pca_bucket`` — this path is the parity oracle for the
    packed engine, so the two must agree without sharing code; change them
    together.

    ``cfg.guard_energy_k > 0`` (the sparse-energy quarantine) swaps the
    post-split mean weights for ``rpca.energy_guard_weights``'s guarded
    vector so anomalous clients contribute exactly zero.

    Returns (update_vector, beta, energy_ratio, residual, client_energy,
    client_flagged)."""
    mu = lam = None
    if col_scale is not None:
        m_mat = m_mat * jnp.asarray(col_scale, m_mat.dtype)[None, :]
    if mask is not None:
        cmask = jnp.asarray(mask, m_mat.dtype)
        m_mat = m_mat * cmask
        d1 = m_mat.shape[0]
        n_eff = jnp.maximum(jnp.sum(cmask.astype(jnp.float32)), 1.0)
        abs_sum = jnp.sum(jnp.abs(m_mat))
        mu = jnp.where(
            abs_sum > 1e-12, (d1 * n_eff) / (4.0 * jnp.maximum(abs_sum, 1e-12)), 1.0
        )
        lam = 1.0 / jnp.sqrt(jnp.maximum(jnp.asarray(d1, jnp.float32), n_eff))
    svt_kw = dict(
        svt_mode=cfg.svt_mode, svt_rank=cfg.svt_rank, svt_sweeps=cfg.svt_sweeps,
        svt_fallback_tol=cfg.svt_fallback_tol,
    )
    if cfg.rpca_fixed_iters:
        res = rpca_lib.robust_pca_fixed_iters(
            m_mat, n_iter=cfg.rpca_iters, mu=mu, lam=lam, shrink_fn=shrink_fn,
            **svt_kw,
        )
    else:
        res = rpca_lib.robust_pca(
            m_mat, tol=cfg.rpca_tol, max_iter=cfg.rpca_iters, mu=mu, lam=lam,
            shrink_fn=shrink_fn, **svt_kw,
        )
    n_clients = m_mat.shape[-1]
    client_energy = rpca_lib.client_sparse_energy(m_mat, res.sparse)
    client_flagged = jnp.zeros((n_clients,), jnp.float32)
    if cfg.guard_energy_k > 0:
        # Sparse-energy quarantine: replace the post-split means' weights
        # with the guard-renormalized vector (flagged clients exactly zero).
        # Mirrors the packed engine's per-module guard bit-for-bit — the
        # matrix here IS one module.
        w, client_flagged = rpca_lib.energy_guard_weights(
            client_energy, cfg.guard_energy_k, base_w=w, valid=mask,
        )
    if w is None:
        low_rank_mean = jnp.mean(res.low_rank, axis=-1)
        sparse_mean = jnp.mean(res.sparse, axis=-1)
    else:
        low_rank_mean = res.low_rank @ w
        sparse_mean = res.sparse @ w
    energy = sparse_energy_ratio(m_mat, res.sparse)
    if cfg.adaptive_beta:
        beta = jnp.clip(1.0 / jnp.maximum(energy, 1e-12), cfg.beta_min, cfg.beta_max)
    else:
        beta = jnp.asarray(cfg.beta, jnp.float32)
    update = low_rank_mean + beta * sparse_mean
    return update, beta, energy, res.residual, client_energy, client_flagged


def _fedrpca_leaf(
    leaf: jnp.ndarray, cfg: AggregatorConfig, shrink_fn: Callable, mask=None, w=None,
    col_scale=None,
):
    """FedRPCA on one stacked leaf; vmaps RPCA across the module (layer) axis.

    Parallel-across-layers per the paper's App. B.2 efficiency note.
    """
    mats = stacking.leaf_matrices(leaf)  # (modules, vec, clients)
    fn = functools.partial(
        _fedrpca_matrix, cfg=cfg, shrink_fn=shrink_fn, mask=mask, w=w,
        col_scale=col_scale,
    )
    updates, betas, energies, residuals, ce, cf = jax.vmap(fn)(
        mats.astype(jnp.float32)
    )
    update_leaf = stacking.matrices_to_leaf_update(updates, leaf)
    return update_leaf, betas, energies, residuals, ce, cf


def _fedrpca_joint_ab(
    node: dict, cfg: AggregatorConfig, shrink_fn: Callable, mask=None, w=None,
    col_scale=None,
):
    """App. B.2 joint mode: RPCA over concatenated [vec(dA); vec(dB)] columns
    of one adapter pair, then split the update back."""
    mats_a = stacking.leaf_matrices(node["A"]).astype(jnp.float32)  # (mod, va, M)
    mats_b = stacking.leaf_matrices(node["B"]).astype(jnp.float32)  # (mod, vb, M)
    va = mats_a.shape[1]
    joint = jnp.concatenate([mats_a, mats_b], axis=1)
    fn = functools.partial(
        _fedrpca_matrix, cfg=cfg, shrink_fn=shrink_fn, mask=mask, w=w,
        col_scale=col_scale,
    )
    updates, betas, energies, residuals, ce, cf = jax.vmap(fn)(joint)
    upd_a = stacking.matrices_to_leaf_update(updates[:, :va], node["A"])
    upd_b = stacking.matrices_to_leaf_update(updates[:, va:], node["B"])
    return {"A": upd_a, "B": upd_b}, betas, energies, residuals, ce, cf


def _is_ab_node(node) -> bool:
    return isinstance(node, dict) and set(node.keys()) == {"A", "B"}


def fedrpca(
    stacked: PyTree,
    cfg: Optional[AggregatorConfig] = None,
    shrink_fn: Callable = rpca_lib.soft_threshold,
    with_diagnostics: bool = False,
    mask=None,
    weights=None,
):
    """Algorithm 1 server update over a stacked client-delta pytree.

    ``cfg.joint_ab`` applies Robust-PCA jointly over each module's
    concatenated (dA, dB) columns — the paper's App. B.2 variant.

    Diagnostics carry both the legacy per-leaf scalar keys
    (``leaf{i}/beta_mean``) and flat per-module arrays under ``"beta"``,
    ``"energy"`` and ``"residual"`` — the same quantities the packed
    engine's ``EngineDiagnostics`` exposes, so ``rpca_diag_summary`` works
    on either engine's output."""
    cfg = cfg or AggregatorConfig()
    w = _client_weights(mask, weights)
    col_scale = None
    if cfg.weighting == "data_size_rpca" and w is not None:
        # Importance-weighted RPCA: fold the normalized weights into M's
        # columns (scaled by n_eff so uniform weights are a no-op) and fall
        # back to uniform-over-active means after the split — the scaling
        # already encodes the weighting, so the subspace sees it too.
        n_clients = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        col_scale = w * _mask_n_eff(mask, n_clients)
        w = None if mask is None else _client_weights(mask, None)
    diag = {}
    flats = {"beta": [], "energy": [], "residual": []}
    # Per-client guard stats: max energy / any-flag over every module seen.
    client = {"energy": None, "flagged": None}

    def record(betas, energies, residuals, ce, cf):
        flats["beta"].append(jnp.ravel(betas))
        flats["energy"].append(jnp.ravel(energies))
        flats["residual"].append(jnp.ravel(residuals))
        ce = jnp.max(ce, axis=0)
        cf = jnp.max(cf, axis=0)
        client["energy"] = ce if client["energy"] is None else jnp.maximum(client["energy"], ce)
        client["flagged"] = cf if client["flagged"] is None else jnp.maximum(client["flagged"], cf)

    def finish(out):
        diag.update({k: jnp.concatenate(v) for k, v in flats.items()})
        if cfg.guard_energy_k > 0:
            diag["client_energy"] = client["energy"]
            diag["client_flagged"] = client["flagged"]
        return out, diag

    if cfg.joint_ab:
        idx = [0]

        def walk(node):
            if _is_ab_node(node):
                upd, betas, energies, residuals, ce, cf = _fedrpca_joint_ab(
                    node, cfg, shrink_fn, mask=mask, w=w, col_scale=col_scale
                )
                diag[f"pair{idx[0]}/beta_mean"] = jnp.mean(betas)
                diag[f"pair{idx[0]}/energy_mean"] = jnp.mean(energies)
                record(betas, energies, residuals, ce, cf)
                idx[0] += 1
                return upd
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, (tuple, list)):
                return type(node)(walk(v) for v in node)
            # bare leaf outside an (A, B) pair: fall back to per-leaf RPCA
            upd, betas, energies, residuals, ce, cf = _fedrpca_leaf(
                node, cfg, shrink_fn, mask=mask, w=w, col_scale=col_scale
            )
            record(betas, energies, residuals, ce, cf)
            return upd

        out = walk(stacked)
        if with_diagnostics:
            return finish(out)
        return out

    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    updates = []
    for i, leaf in enumerate(leaves):
        upd, betas, energies, residuals, ce, cf = _fedrpca_leaf(
            leaf, cfg, shrink_fn, mask=mask, w=w, col_scale=col_scale
        )
        updates.append(upd)
        diag[f"leaf{i}/beta_mean"] = jnp.mean(betas)
        diag[f"leaf{i}/energy_mean"] = jnp.mean(energies)
        record(betas, energies, residuals, ce, cf)
    out = jax.tree_util.tree_unflatten(treedef, updates)
    if with_diagnostics:
        return finish(out)
    return out


def rpca_diag_summary(diag) -> dict:
    """Engine-agnostic scalar summary of fedrpca diagnostics.

    Accepts either the packed engine's ``EngineDiagnostics`` or the
    reference path's dict (which carries flat "beta"/"energy"/"residual"
    arrays); both engines therefore report the same keys from
    ``fed/server.py`` round diagnostics."""
    if hasattr(diag, "arrays"):  # EngineDiagnostics (duck-typed, no import)
        out = {
            "beta_mean": diag.mean("beta"),
            "energy_mean": diag.mean("energy"),
            "rpca_residual_max": diag.max("residual"),
        }
        # Cross-round session health (present only when a carry threads
        # through aggregate_planned): exact-eigh fallbacks this round,
        # mean live rank of the carried subspaces, and the fraction of
        # bucket tiers that warm-started.  Carry regressions show up here
        # in training logs long before they show up in wall time.
        if "live_rank" in diag.arrays:
            out["live_rank_mean"] = diag.mean("live_rank")
        if "client_flagged" in diag.arrays:
            # Sparse-energy quarantine: per-client any-flag across buckets
            # (buckets share the client axis, so element-wise max is "any").
            flags = functools.reduce(
                jnp.maximum, diag.arrays["client_flagged"].values()
            )
            out["guard_flagged"] = jnp.sum(flags)
            out["client_energy_max"] = diag.max("client_energy")
        # Uplink wire accounting rides the same scalar channel (present
        # only under sketch-uplink plans, DESIGN.md §12) so per-round
        # bytes land in the training logs next to the carry health.
        for k in (
            "fallback_count", "svt_steps", "carry_hit_rate", "bytes_up",
            "bytes_down_basis", "uplink_hit_rate", "uplink_dense_falls",
        ):
            if k in diag.scalars:
                out[k] = diag.scalars[k]
        return out
    out = {
        "beta_mean": jnp.mean(diag["beta"]),
        "energy_mean": jnp.mean(diag["energy"]),
        "rpca_residual_max": jnp.max(diag["residual"]),
    }
    if "client_flagged" in diag:
        out["guard_flagged"] = jnp.sum(diag["client_flagged"])
        out["client_energy_max"] = jnp.max(diag["client_energy"])
    return out


def client_flag_vector(diag):
    """Per-client sparse-energy quarantine flags from either engine's
    fedrpca diagnostics: (cohort,) float32 with 1 = flagged in at least one
    module, or None when the guard (``guard_energy_k``) was off."""
    if hasattr(diag, "arrays"):
        if "client_flagged" not in getattr(diag, "arrays", {}):
            return None
        return functools.reduce(
            jnp.maximum, diag.arrays["client_flagged"].values()
        )
    if isinstance(diag, dict) and "client_flagged" in diag:
        return diag["client_flagged"]
    return None


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

_SIMPLE = {
    "fedavg": lambda stacked, cfg, key, mask, weights: fedavg(
        stacked, mask=mask, weights=weights
    ),
    "task_arithmetic": lambda stacked, cfg, key, mask, weights: task_arithmetic(
        stacked, cfg.beta, mask=mask, weights=weights
    ),
    "ties": lambda stacked, cfg, key, mask, weights: ties_merging(
        stacked, cfg.ties_keep, cfg.ties_scale, mask=mask, weights=weights
    ),
    "fedexp": lambda stacked, cfg, key, mask, weights: fedexp(
        stacked, mask=mask, weights=weights
    ),
    "dare": lambda stacked, cfg, key, mask, weights: dare(
        stacked, cfg.dare_drop, key, mask=mask, weights=weights
    ),
}


ENGINES = ("packed", "reference")


def aggregate(
    stacked: PyTree,
    cfg: Optional[AggregatorConfig] = None,
    shrink_fn: Callable = rpca_lib.soft_threshold,
    *,
    engine: str = "packed",
    key=None,
    mask=None,
    weights=None,
    with_diagnostics: bool = False,
    mesh=None,
) -> PyTree:
    """Aggregate stacked client deltas per ``cfg.method``.

    ``engine="packed"`` (default) routes through the batched engine
    (``repro.core.engine``): one dispatch per shape bucket.
    ``engine="reference"`` keeps the per-leaf path for parity testing.
    ``key`` seeds the stochastic methods (dare — required for them); both
    engines fold it identically so results match across engines.

    ``mask`` is a per-client validity vector for shape-static partial
    participation: padded cohort slots carry mask 0 and are excluded from
    every method (the masked-padded result equals the dense sub-cohort
    result).  ``weights`` are raw nonnegative per-client weights (e.g. local
    dataset sizes — the round drivers pass them when
    ``cfg.weighting == "data_size"``); they are mask-zeroed and normalized
    internally.  With both None the legacy unweighted code paths run
    bit-for-bit unchanged.

    ``mesh`` shards the packed client axis across a device mesh (packed
    engine only; DESIGN.md §10).  The reference engine is the single-device
    parity oracle, so passing a multi-shard mesh with it is an error; a
    one-shard mesh is accepted and ignored on both engines.
    """
    cfg = cfg or AggregatorConfig()
    if cfg.weighting not in WEIGHTINGS:
        raise ValueError(f"unknown weighting: {cfg.weighting!r} (expected one of {WEIGHTINGS})")
    if cfg.carry_mode not in CARRY_MODES:
        raise ValueError(
            f"unknown carry_mode: {cfg.carry_mode!r} (expected one of {CARRY_MODES})"
        )
    if cfg.svt_mode not in rpca_lib.SVT_MODES:
        raise ValueError(
            f"unknown svt_mode: {cfg.svt_mode!r} (expected one of {rpca_lib.SVT_MODES})"
        )
    if cfg.method == "dare" and key is None:
        raise ValueError("dare requires an explicit PRNG key (got key=None)")
    if engine == "packed":
        from repro.core import engine as engine_lib

        return engine_lib.aggregate_packed(
            stacked, cfg, shrink_fn=shrink_fn, key=key, mask=mask, weights=weights,
            with_diagnostics=with_diagnostics, mesh=mesh,
        )
    if engine != "reference":
        raise ValueError(f"unknown engine: {engine!r} (expected one of {ENGINES})")
    if mesh is not None and rpca_lib.mesh_client_shards(mesh) > 1:
        raise ValueError(
            "the reference engine is the single-device parity oracle and "
            "cannot shard the client axis; use engine='packed' with a mesh"
        )
    if cfg.method in _SIMPLE:
        out = _SIMPLE[cfg.method](stacked, cfg, key, mask, weights)
        return (out, {}) if with_diagnostics else out
    if cfg.method == "fedrpca":
        return fedrpca(
            stacked, cfg, shrink_fn, with_diagnostics=with_diagnostics,
            mask=mask, weights=weights,
        )
    raise ValueError(f"unknown aggregation method: {cfg.method!r}")


METHODS = tuple(sorted([*_SIMPLE.keys(), "fedrpca"]))
