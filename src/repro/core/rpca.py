"""Robust Principal Component Analysis via ADMM / Principal Component Pursuit.

Faithful JAX port of the paper's Algorithm 2 (Appendix B.1), which is itself
the inexact-ALM PCP of Candès et al. (2011):

    minimize  ||L||_* + lam * ||S||_1   s.t.  M = L + S

with the paper's default hyper-parameters

    mu  = numel(M) / (4 * ||M||_1)         (step size)
    lam = 1 / sqrt(max(d1, d2))            (sparsity weight)
    rho = 1 / mu

and iterates

    L <- SVT_rho(M - S + rho * Y)
    S <- shrink_{rho*lam}(M - L + rho * Y)
    Y <- Y + mu * (M - L - S)
    stop when ||M - L - S||_F <= tol * ||M||_F.

TPU adaptation (see DESIGN.md §3): the singular-value thresholding (SVT) step
is computed with the *Gram trick* instead of a tall-skinny SVD.  The RPCA
inputs in federated LoRA are ``(r*d) x n_clients`` with ``n_clients`` tiny
(<= 100), so ``G = X^T X`` is a small symmetric matrix; ``eigh(G)`` yields the
right singular vectors and squared singular values, and

    SVT_t(X) = X @ P,   P = V diag(shrink(s, t) / s) V^T

never materializes the tall U factor.  This is numerically identical to the
SVD route for full-column-rank X (guarded by an eps on s) and is MXU-friendly:
two matmuls over the long side (the Gram and X @ P), one tiny C x C product
and one tiny eigh instead of a LAPACK-style SVD.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

_EPS = 1e-12


def _f32_matmuls(fn: Callable) -> Callable:
    """Trace ``fn`` with its float32 matmuls at full (``"highest"``) precision.

    Applied to every RPCA entry point below, so each caller (the stateless
    engines, the planned session step, the sharded loop) gets it.  On TPU
    the default precision of an f32 matmul is one bf16 MXU pass.  The Gram
    SVT squares the spectrum, so at that precision the small singular
    values are noise: on a TPU v5e, a 1-ulp perturbation of real
    stablelm-1.6b client deltas moved the 30-iteration FedRPCA update by
    1.5x its RMS, against 2.5e-4 at full precision.  CPU f32 matmuls are
    exact already; there this changes nothing.
    """

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def soft_threshold(x: jnp.ndarray, t) -> jnp.ndarray:
    """Elementwise shrinkage ``sign(x) * max(|x| - t, 0)``.

    This is the pure-jnp reference; ``repro.kernels.soft_threshold`` provides
    the Pallas TPU kernel with identical semantics (see kernels/ref.py).
    """
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def _shrink_projector(gram, t, shrink_fn):
    """Exact SVT projector of ``X`` from its Gram ``G = X^T X``:
    ``P = V diag(shrink(s, t) / s) V^T`` over the full eigenbasis, so that
    ``SVT_t(X) = X @ P``.  ``gram`` is (..., n, n) and ``t`` broadcasts
    against the (..., n) singular values.  Returns ``(P, V, shrink(s, t))``
    with ``V`` in eigh's ascending order."""
    w, v = jnp.linalg.eigh(gram)
    s = jnp.sqrt(jnp.maximum(w, 0.0))
    s_shrunk = shrink_fn(s, t)
    coef = jnp.where(s > _EPS, s_shrunk / jnp.maximum(s, _EPS), 0.0)
    p = (v * coef[..., None, :]) @ jnp.swapaxes(v, -1, -2)
    return p, v, s_shrunk


def svt_gram(x: jnp.ndarray, t, shrink_fn: Callable = soft_threshold) -> jnp.ndarray:
    """Singular-value thresholding via the Gram matrix (thin side).

    Works on any 2-D ``x``; the eigendecomposition is taken on the smaller
    Gram matrix and the shrink is applied as one projector, ``X @ P``, so
    the long side is streamed twice (the Gram and the projection) and cost
    is O(min(d1,d2)^3 + d1*d2*min(d1,d2)).
    """
    d1, d2 = x.shape
    transpose = d1 < d2
    if transpose:
        x = x.T  # now tall: rows >= cols
    gram = jnp.einsum("dc,de->ce", x, x)  # X^T X (cols x cols), symmetric PSD
    p, _, _ = _shrink_projector(gram, t, shrink_fn)
    low_rank = x @ p
    return low_rank.T if transpose else low_rank


def svt_svd(x: jnp.ndarray, t, shrink_fn: Callable = soft_threshold) -> jnp.ndarray:
    """Reference SVT via full thin SVD (used in tests to validate svt_gram)."""
    u, s, vh = jnp.linalg.svd(x, full_matrices=False)
    return (u * shrink_fn(s, t)[None, :]) @ vh


# ---------------------------------------------------------------------------
# Sparse-energy client anomaly scores (DESIGN.md §11)
# ---------------------------------------------------------------------------
#
# RPCA's sparse component is a free byzantine detector: a corrupted client's
# delta cannot be explained by the shared low-rank subspace, so its energy
# concentrates in its own S-column.  These helpers score each client's
# column and fold anomalies out of the aggregation weight vector; they are
# shared by both engines (per packed bucket here, per matrix on the
# reference path) so masked cross-engine parity holds.


def client_sparse_energy(m: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Per-client column energy ratio ``||S[:, c]|| / ||M[:, c]||``.

    ``m``/``s`` have clients on the last axis and the vec dimension second
    to last (``(..., vec, clients)``); leading axes (e.g. the packed module
    axis) broadcast.  Padded rows and masked columns are zero in both, so
    inactive clients score 0.
    """
    num = jnp.linalg.norm(s, axis=-2)
    den = jnp.linalg.norm(m, axis=-2)
    return num / jnp.maximum(den, _EPS)


def energy_guard_weights(
    energy: jnp.ndarray,
    k: float,
    base_w=None,
    valid=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Zero out anomalous clients' weights and renormalize, per module.

    A client is flagged when its sparse-energy score exceeds ``k`` times
    the median score over valid clients of the same module (the median is
    robust to the anomalies being scored).  ``energy`` is ``(...,
    n_clients)``; ``base_w`` (broadcastable to it) supplies the unguarded
    weights (None = uniform) and ``valid`` is the (n_clients,) float mask.
    Returns ``(weights, flagged)``: normalized per-module weights with
    flagged clients at exactly zero, and the float32 flag matrix.  A module
    whose every valid client is flagged keeps all-zero weights — a zero
    update beats aggregating known-suspect columns.
    """
    vals = energy if valid is None else jnp.where(valid > 0, energy, jnp.nan)
    med = jnp.nanmedian(vals, axis=-1, keepdims=True)
    flagged = energy > k * jnp.maximum(med, _EPS)
    if valid is not None:
        flagged = flagged & (valid > 0)
    if base_w is None:
        w = jnp.ones_like(energy)
    else:
        w = jnp.broadcast_to(jnp.asarray(base_w, jnp.float32), energy.shape)
    if valid is not None:
        w = w * valid
    w = jnp.where(flagged, 0.0, w)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), _EPS)
    return w, flagged.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Warm-started subspace-iteration SVT (DESIGN.md §6)
# ---------------------------------------------------------------------------
#
# Near the ADMM fixed point the low-rank iterate L lives in a slowly-rotating
# right-singular subspace, so the eigenbasis of G = X^T X barely changes
# between iterations.  Instead of a fresh full eigh per iteration, the loop
# carries an orthonormal basis V in R^{d2 x r} and refines it with a few
# matmul-only power sweeps + a Rayleigh-Ritz step on the tiny r x r
# projection; the full eigh runs only on the cold start, when the live-
# direction subspace residual exceeds a tolerance, or when the post-shrink
# rank saturates the carried width r (the subspace might then be truncating
# super-threshold singular values, so exactness requires the full basis).

#: Valid ``svt_mode`` values for the RPCA drivers / AggregatorConfig.
SVT_MODES = ("gram", "subspace")


class SubspaceState(NamedTuple):
    """Warm-start carry threaded through the ADMM loop.

    ``v``: (B, d2, r) orthonormal basis of the tracked right-singular
    subspace.  ``g``: (B, d2, d2) Gram matrix ``X^T X`` of the *current*
    ADMM iterate X (refreshed by the loop body after the S/Y update, or by
    the fused Pallas kernel's accumulator).  ``n_live``: (B,) int32 count
    of post-shrink live directions from the last SVT — the rank-adaptive
    signal.  ``rel``: (B,) last subspace residual estimate over the live
    directions (drives both the eigh fallback and the sweep-count cut).
    """

    v: jnp.ndarray
    g: jnp.ndarray
    n_live: jnp.ndarray
    rel: jnp.ndarray


class SVTSubspaceResult(NamedTuple):
    low_rank: jnp.ndarray
    v: jnp.ndarray  # warm-start basis for the next call
    n_live: jnp.ndarray
    rel: jnp.ndarray
    fell_back: jnp.ndarray  # True when the exact eigh path ran


def subspace_rank(d2: int, rank: int, true_cols: int | None = None) -> int:
    """Static carried subspace width: the user cap, but never more than half
    the Gram dimension — tracking the majority of the spectrum costs as much
    as the full eigh (r x r Ritz eigh ~ d2 x d2 eigh), at which point gram
    mode is strictly cheaper.  Small cohorts therefore auto-narrow: d2=8
    carries r<=4 regardless of the cap.

    ``true_cols`` is the true (unpadded) cohort column count when the bucket
    carries masked padding columns — e.g. 7 live clients packed into 8 slots,
    or 9 into a 16-slot canonical cohort.  The cap then respects the live
    count, and rounds UP on odd cohorts (ceil(c/2)): with the floor cap an
    odd cohort like nc=7 would carry r=3 while the shrunk spectrum keeps 4+
    live directions, so every warm round would trip the rank-saturation
    guard into the exact-eigh fallback.  Even counts are unchanged
    ((c+1)//2 == c//2), keeping existing cohorts bitwise identical."""
    c = d2 if true_cols is None else max(1, min(int(true_cols), d2))
    return max(1, min(rank, (c + 1) // 2)) if c > 1 else 1


def subspace_init(m: jnp.ndarray, rank: int, true_cols: int | None = None) -> SubspaceState:
    """Cold-start carry for a (B, d1, d2) bucket: identity-column basis (the
    first SVT always takes the exact path) and the Gram of X_0 = M."""
    b, _, d2 = m.shape
    r = subspace_rank(d2, rank, true_cols)
    v = jnp.broadcast_to(jnp.eye(d2, r, dtype=jnp.float32), (b, d2, r))
    g = jnp.einsum("bdc,bde->bce", m, m)
    return SubspaceState(
        v=v,
        g=g,
        n_live=jnp.full((b,), r, jnp.int32),
        rel=jnp.full((b,), jnp.inf, jnp.float32),
    )


def _exact_projector(g, t, r, shrink_fn):
    """Full-eigh fallback: exact SVT projector P with all d2 directions,
    plus the top-r eigenbasis to (re)seed the warm-start carry."""
    p, v_full, s_shrunk = _shrink_projector(g, t[:, None], shrink_fn)
    # Top-r eigenbasis in eigh's ascending order (top directions LAST) —
    # the same column convention the Ritz path stores, so consumers that
    # truncate a carried basis (engine.migrate_carry) can slice trailing
    # columns regardless of which path produced it.  The warm path itself
    # only uses the span, so ordering is otherwise free.
    v_top = v_full[:, :, -r:]
    n_live = jnp.sum((s_shrunk > 0.0).astype(jnp.int32), axis=-1)
    rel = jnp.zeros(t.shape, jnp.float32)  # basis is exact at this iterate
    return p, v_top, n_live, rel


def _orthonormalize(z):
    """Batched CholeskyQR: Q with span(Q) = span(Z), via Z^T Z = R^T R and
    Q = Z R^{-1}.  Pure batched matmuls + one tiny (r, r) Cholesky /
    triangular solve — MXU-friendly where a batched LAPACK thin QR is not.
    A trace-scaled jitter keeps rank-deficient Z (converged ADMM iterates
    whose trailing directions died) factorizable; the junk directions it
    admits carry near-zero Ritz values and are shrunk to zero downstream.
    """
    szz = jnp.einsum("bnr,bns->brs", z, z)
    r = szz.shape[-1]
    # Relative jitter well above f32 round-off: exactly-low-rank iterates
    # make Z rank-deficient, and an un-jittered Cholesky would go NaN.
    jitter = (1e-6 / r) * (jnp.trace(szz, axis1=-2, axis2=-1) + _EPS)[:, None, None]
    chol = jnp.linalg.cholesky(szz + jitter * jnp.eye(r, dtype=szz.dtype))
    return jax.lax.linalg.triangular_solve(
        chol, z, left_side=False, lower=True, transpose_a=True
    )


def _ritz_projector(g, t, v, n_sweeps, shrink_fn):
    """Matmul-only refinement: ``n_sweeps`` power sweeps (G @ V +
    CholeskyQR) advancing the span, then Rayleigh-Ritz on the r x r
    projection with the shrink applied to the Ritz values.

    The final G-apply serves triple duty: it forms the Ritz projection
    ``T = V^T (G V)``, reuses ``(G V) W`` for the subspace residual, and
    on CPU keeps the warm path's op count below the batched eigh it
    replaces (tiny batched ops are dispatch-bound, not flop-bound).

    Returns (P, Ritz basis, live count, live-direction subspace residual).
    The residual is restricted to directions the shrink keeps: converged
    modules whose trailing junk directions still rotate do strictly less
    work because those directions can neither trip the fallback nor demand
    extra sweeps.
    """
    for _ in range(n_sweeps):  # static unroll: n_sweeps is a Python int
        v = _orthonormalize(jnp.einsum("bnm,bmr->bnr", g, v))
    gv = jnp.einsum("bnm,bmr->bnr", g, v)
    t_small = jnp.einsum("bnr,bns->brs", v, gv)  # V^T G V, (B, r, r)
    theta, w_rot = jnp.linalg.eigh(t_small)  # ascending Ritz values
    # One fused rotation for [V; GV] @ W — tiny batched ops are dispatch-
    # bound on CPU, so fewer dispatches beat fewer flops.
    both = jnp.einsum("bnr,brs->bns", jnp.concatenate([v, gv], axis=1), w_rot)
    d2 = v.shape[1]
    vr, gvr = both[:, :d2], both[:, d2:]  # Ritz basis and G @ Vr
    s = jnp.sqrt(jnp.maximum(theta, 0.0))
    s_shrunk = shrink_fn(s, t[:, None])
    coef = jnp.where(s > _EPS, s_shrunk / jnp.maximum(s, _EPS), 0.0)
    p = jnp.einsum("bnr,br,bmr->bnm", vr, coef, vr)
    live = (s_shrunk > 0.0).astype(jnp.float32)
    res = (gvr - vr * theta[:, None, :]) * live[:, None, :]
    # Normalize by the captured spectral mass (trace of the projection) —
    # free from theta, same scale as ||G||_F for the low-rank spectra this
    # tracks, and one fewer full pass over G.
    g_mass = jnp.sum(jnp.maximum(theta, 0.0), axis=-1)
    rel = jnp.sqrt(jnp.sum(res * res, axis=(1, 2))) / jnp.maximum(g_mass, _EPS)
    n_live = jnp.sum(live.astype(jnp.int32), axis=-1)
    return p, vr, n_live, rel


@jax.named_scope("agg.svt")
def svt_subspace_step(
    t: jnp.ndarray,
    state: SubspaceState,
    *,
    cold,
    sweeps: int = 2,
    fallback_tol: float = 1e-3,
    shrink_fn: Callable = soft_threshold,
    across: Callable | None = None,
) -> tuple[jnp.ndarray, SubspaceState, jnp.ndarray]:
    """One warm-started SVT on the Gram carry: (P, new state, fell_back).

    The batched full eigh runs (under ``lax.cond``) in three cases: the
    cold start; *pre-routed* saturation — the previous step's post-shrink
    rank filled the carried width, a condition that persists through the
    ADMM burn-in, so those iterations skip the wasted Ritz attempt and pay
    exactly the gram-mode cost; and *post-guard* breach — the Ritz attempt
    ran but its live-direction subspace residual exceeded ``fallback_tol``
    or its live count saturated, so the one transition iteration pays both.
    When the previous step's residuals were all far inside tolerance the
    sweep count drops to 1 (a ``lax.cond`` between statically-unrolled
    sweep chains) — with the live-masked residual and the saturation
    routing, the rank-adaptive "converged buckets do strictly less work"
    path.  The caller applies P as ``L = X @ P`` and refreshes ``state.g``
    from the post-tail iterate.

    ``across`` reduces a bucket-wide maximum (or any) over the shards that
    hold the bucket's other modules, when ``state`` holds one shard's
    modules: every shard then takes the branch the whole bucket would.
    """
    r = state.v.shape[-1]
    g = state.g
    bucket = (lambda x: x) if across is None else across

    def exact():
        p, v2, live, rel = _exact_projector(g, t, r, shrink_fn)
        return p, v2, live, rel, jnp.asarray(True)

    def attempt():
        # Steady state (last residuals far inside tolerance): one sweep
        # tracks the slow rotation.  Otherwise advance the span the full
        # `sweeps` power applications to re-capture it.
        if sweeps > 1:
            p, v2, live, rel = jax.lax.cond(
                bucket(jnp.max(state.rel)) <= 0.1 * fallback_tol,
                lambda: _ritz_projector(g, t, state.v, 1, shrink_fn),
                lambda: _ritz_projector(g, t, state.v, sweeps, shrink_fn),
            )
        else:
            p, v2, live, rel = _ritz_projector(g, t, state.v, max(sweeps, 1), shrink_fn)
        bad = bucket(jnp.logical_or(jnp.any(rel > fallback_tol), jnp.any(live >= r)))
        return jax.lax.cond(bad, exact, lambda: (p, v2, live, rel, jnp.asarray(False)))

    pre_full = jnp.logical_or(jnp.asarray(cold), bucket(jnp.any(state.n_live >= r)))
    p, v2, live2, rel2, fell = jax.lax.cond(pre_full, exact, attempt)
    # An exact step leaves no residual signal (its basis is exact *for this
    # iterate*), but the subspace is still rotating — report rel at half the
    # fallback tolerance so the next attempt runs real tracking sweeps
    # instead of the 0-sweep span-hold (which right after a fallback cannot
    # follow the rotation and would ping-pong back to the eigh forever).
    rel2 = jnp.where(fell, 0.5 * fallback_tol, rel2)
    return p, SubspaceState(v=v2, g=g, n_live=live2, rel=rel2), fell


def svt_subspace(
    x: jnp.ndarray,
    t,
    v: jnp.ndarray | None = None,
    *,
    rank: int = 8,
    sweeps: int = 2,
    fallback_tol: float = 1e-3,
    shrink_fn: Callable = soft_threshold,
) -> SVTSubspaceResult:
    """Single-matrix warm-started subspace SVT (the svt_gram counterpart).

    ``v=None`` is a cold start: the exact eigh path runs and the returned
    ``v`` (top-``rank`` right-singular basis) warm-starts the next call.
    With a basis the call is matmul-only (plus an r x r eigh) unless the
    subspace residual or rank saturation trips the exact fallback.  The
    Gram matrix lives on the d2 side unconditionally — unlike ``svt_gram``
    there is no transpose trick, so prefer gram mode for wide matrices.
    """
    if x.ndim != 2:
        raise ValueError(f"svt_subspace expects a 2-D matrix, got {x.shape}")
    d2 = x.shape[1]
    r = subspace_rank(d2, rank)
    xb = x[None].astype(jnp.float32)
    g = jnp.einsum("bdc,bde->bce", xb, xb)
    cold = v is None
    vb = (
        jnp.broadcast_to(jnp.eye(d2, r, dtype=jnp.float32), (1, d2, r))
        if cold
        else v[None].astype(jnp.float32)
    )
    # Warm calls start below saturation with a mid-tolerance residual: the
    # Ritz attempt runs with full tracking sweeps and the post-guard (not
    # the pre-route) decides whether the exact path is needed.
    state = SubspaceState(
        v=vb,
        g=g,
        n_live=jnp.zeros((1,), jnp.int32),
        rel=jnp.full((1,), 0.5 * fallback_tol, jnp.float32),
    )
    tb = jnp.asarray(t, jnp.float32).reshape(1)
    p, state, fell = svt_subspace_step(
        tb, state, cold=cold, sweeps=sweeps, fallback_tol=fallback_tol,
        shrink_fn=shrink_fn,
    )
    low = jnp.einsum("bdc,bce->bde", xb, p)[0].astype(x.dtype)
    return SVTSubspaceResult(
        low_rank=low, v=state.v[0], n_live=state.n_live[0], rel=state.rel[0],
        fell_back=fell,
    )


class RPCAResult(NamedTuple):
    low_rank: jnp.ndarray
    sparse: jnp.ndarray
    n_iter: jnp.ndarray
    residual: jnp.ndarray  # ||M - L - S||_F / ||M||_F at exit


class BucketCarry(NamedTuple):
    """Cross-round warm-start state of one bucket's RPCA (DESIGN.md §7).

    Client LoRA deltas correlate strongly across federated rounds (the
    paper's shared-common-knowledge observation), so the ADMM fixed point of
    round t is an excellent initial iterate for round t+1.  The carry holds
    the full session state: the converged iterates ``l``/``s``/dual ``y``
    (f32, bucket layout ``(B, padded_vec, d2)``), the subspace-SVT
    eigenbasis ``v`` ``(B, d2, r)`` with its live-rank tracker ``n_live``,
    and the validity/health scalars.  A warm start is accepted only when
    ``valid`` is set, the cohort fingerprint ``n_eff`` matches (carry is
    keyed to canonical buckets, not cohort identity — a same-size resampled
    cohort may warm-start, a resized one may not), and the initial relative
    residual ``||M - l - s||_F / ||M||_F`` does not exceed ``carry_gate``
    (cold start scores exactly 1.0, so the default gate accepts any init
    that is no worse than cold).  ``fall_count`` / ``hit`` are diagnostics
    of the call that *produced* the carry: whole-bucket exact-eigh SVT
    steps taken, and whether that call itself warm-started.
    """

    l: jnp.ndarray
    s: jnp.ndarray
    y: jnp.ndarray
    v: jnp.ndarray
    n_live: jnp.ndarray
    n_eff: jnp.ndarray  # () f32 cohort fingerprint at save time
    valid: jnp.ndarray  # () bool — the carry holds real state
    fall_count: jnp.ndarray  # () i32 exact-eigh steps in the producing call
    hit: jnp.ndarray  # () f32 — 1.0 iff the producing call warm-started


def init_bucket_carry(
    n_modules: int, padded_vec: int, d2: int, svt_rank: int,
    true_cols: int | None = None,
) -> BucketCarry:
    """Empty (invalid) carry with the static shapes of one bucket.

    ``true_cols`` is the true cohort column count when ``d2`` includes
    masked padding slots (see ``subspace_rank``); it must match the value
    the consuming ``robust_pca_bucket`` call uses, or the carried basis
    width disagrees with the session's."""
    r = subspace_rank(d2, svt_rank, true_cols)
    z = lambda *s: jnp.zeros(s, jnp.float32)
    return BucketCarry(
        l=z(n_modules, padded_vec, d2),
        s=z(n_modules, padded_vec, d2),
        y=z(n_modules, padded_vec, d2),
        v=z(n_modules, d2, r),
        n_live=jnp.zeros((n_modules,), jnp.int32),
        n_eff=jnp.zeros((), jnp.float32),
        valid=jnp.zeros((), bool),
        fall_count=jnp.zeros((), jnp.int32),
        hit=jnp.zeros((), jnp.float32),
    )


@_f32_matmuls
@jax.named_scope("agg.admm")
def robust_pca(
    m: jnp.ndarray,
    *,
    mu: float | None = None,
    lam: float | None = None,
    tol: float = 1e-7,
    max_iter: int = 200,
    svt_fn: Callable = svt_gram,
    shrink_fn: Callable = soft_threshold,
    svt_mode: str = "gram",
    svt_rank: int = 8,
    svt_sweeps: int = 2,
    svt_fallback_tol: float = 1e-3,
    carry: BucketCarry | None = None,
    return_carry: bool = False,
    carry_gate: float = 1.0,
) -> RPCAResult:
    """Decompose ``m`` into low-rank + sparse, per the paper's Algorithm 2.

    Args:
      m: 2-D matrix (any float dtype; computation is in float32).
      mu, lam: ADMM hyper-parameters; paper defaults when None.
      tol: relative Frobenius residual stopping tolerance.
      max_iter: compile-time iteration cap (lax.while_loop bound).
      svt_fn / shrink_fn: pluggable SVT and shrinkage (e.g. Pallas kernel).
      svt_mode: "gram" (per-iteration eigh, the legacy exact path) or
        "subspace" (warm-started subspace-iteration SVT, DESIGN.md §6 —
        routes through the B=1 bucket loop so the eigenbasis carry threads
        the ADMM iterations).
      svt_rank / svt_sweeps / svt_fallback_tol: subspace-mode knobs.
      carry / return_carry / carry_gate: cross-round session state
        (DESIGN.md §7) — a B=1 ``BucketCarry`` (``init_bucket_carry(1,
        ...)``); any carry routes through the bucket loop, gram mode
        included.

    Returns:
      RPCAResult(low_rank=L, sparse=S, n_iter, residual)
      [, BucketCarry when return_carry].
    """
    if m.ndim != 2:
        raise ValueError(f"robust_pca expects a 2-D matrix, got shape {m.shape}")
    if svt_mode != "gram" or carry is not None or return_carry:
        if svt_fn is not svt_gram:
            raise ValueError(
                "custom svt_fn is only honored on the carry-less "
                "svt_mode='gram' path; the bucket loop owns its SVT"
            )
        res = robust_pca_bucket(
            m[None], n_iter=max_iter, tol=tol, mu=mu, lam=lam,
            shrink_fn=shrink_fn, svt_mode=svt_mode, svt_rank=svt_rank,
            svt_sweeps=svt_sweeps, svt_fallback_tol=svt_fallback_tol,
            carry=carry, return_carry=return_carry, carry_gate=carry_gate,
        )
        if return_carry:
            res, new_carry = res
            return (
                RPCAResult(res.low_rank[0], res.sparse[0], res.n_iter[0], res.residual[0]),
                new_carry,
            )
        return RPCAResult(res.low_rank[0], res.sparse[0], res.n_iter[0], res.residual[0])
    orig_dtype = m.dtype
    m = m.astype(jnp.float32)
    d1, d2 = m.shape

    abs_sum = jnp.sum(jnp.abs(m))
    mu_v = jnp.where(abs_sum > _EPS, (d1 * d2) / (4.0 * jnp.maximum(abs_sum, _EPS)), 1.0)
    if mu is not None:
        mu_v = jnp.asarray(mu, jnp.float32)
    lam_v = jnp.asarray(lam if lam is not None else 1.0 / jnp.sqrt(max(d1, d2)), jnp.float32)
    rho = 1.0 / mu_v

    m_norm = jnp.maximum(jnp.linalg.norm(m), _EPS)

    def cond(state):
        _, _, _, i, err = state
        return jnp.logical_and(i < max_iter, err > tol)

    def body(state):
        _, s, y, i, _ = state
        l = svt_fn(m - s + rho * y, rho, shrink_fn)
        s = shrink_fn(m - l + rho * y, rho * lam_v)
        resid = m - l - s
        y = y + mu_v * resid
        err = jnp.linalg.norm(resid) / m_norm
        return (l, s, y, i + 1, err)

    zeros = jnp.zeros_like(m)
    init = (zeros, zeros, zeros, jnp.asarray(0, jnp.int32), jnp.asarray(jnp.inf, jnp.float32))
    l, s, _, n_iter, err = jax.lax.while_loop(cond, body, init)
    return RPCAResult(l.astype(orig_dtype), s.astype(orig_dtype), n_iter, err)


@_f32_matmuls
@jax.named_scope("agg.admm")
def robust_pca_fixed_iters(
    m: jnp.ndarray,
    *,
    n_iter: int = 50,
    mu: float | None = None,
    lam: float | None = None,
    svt_fn: Callable = svt_gram,
    shrink_fn: Callable = soft_threshold,
    svt_mode: str = "gram",
    svt_rank: int = 8,
    svt_sweeps: int = 2,
    svt_fallback_tol: float = 1e-3,
    carry: BucketCarry | None = None,
    return_carry: bool = False,
    carry_gate: float = 1.0,
) -> RPCAResult:
    """Fixed-iteration RPCA (fori_loop) — deterministic cost for the mesh path.

    The production ``fed_train_step`` lowers this variant so that the compiled
    program's FLOP count is shape-static (no data-dependent trip count), which
    both keeps SPMD pipelining simple and makes the roofline analysis exact.
    ``svt_mode="subspace"`` threads the warm-started eigenbasis through the
    loop via the B=1 bucket path (note: the whole-bucket eigh fallback
    ``lax.cond`` lowers to a select under ``jax.vmap``, so vmapped callers
    pay both branches — batch via ``robust_pca_bucket`` instead).  A
    ``carry`` (B=1 ``BucketCarry``, DESIGN.md §7) likewise routes through
    the bucket loop under either svt mode.
    """
    if m.ndim != 2:
        raise ValueError(f"robust_pca expects a 2-D matrix, got shape {m.shape}")
    if svt_mode != "gram" or carry is not None or return_carry:
        if svt_fn is not svt_gram:
            raise ValueError(
                "custom svt_fn is only honored on the carry-less "
                "svt_mode='gram' path; the bucket loop owns its SVT"
            )
        res = robust_pca_bucket(
            m[None], n_iter=n_iter, tol=None, mu=mu, lam=lam,
            shrink_fn=shrink_fn, svt_mode=svt_mode, svt_rank=svt_rank,
            svt_sweeps=svt_sweeps, svt_fallback_tol=svt_fallback_tol,
            carry=carry, return_carry=return_carry, carry_gate=carry_gate,
        )
        if return_carry:
            res, new_carry = res
            return (
                RPCAResult(res.low_rank[0], res.sparse[0], res.n_iter[0], res.residual[0]),
                new_carry,
            )
        return RPCAResult(res.low_rank[0], res.sparse[0], res.n_iter[0], res.residual[0])
    orig_dtype = m.dtype
    m = m.astype(jnp.float32)
    d1, d2 = m.shape

    abs_sum = jnp.sum(jnp.abs(m))
    mu_v = jnp.where(abs_sum > _EPS, (d1 * d2) / (4.0 * jnp.maximum(abs_sum, _EPS)), 1.0)
    if mu is not None:
        mu_v = jnp.asarray(mu, jnp.float32)
    lam_v = jnp.asarray(lam if lam is not None else 1.0 / jnp.sqrt(max(d1, d2)), jnp.float32)
    rho = 1.0 / mu_v
    m_norm = jnp.maximum(jnp.linalg.norm(m), _EPS)

    def body(_, state):
        _, s, y = state
        l = svt_fn(m - s + rho * y, rho, shrink_fn)
        s = shrink_fn(m - l + rho * y, rho * lam_v)
        y = y + mu_v * (m - l - s)
        return (l, s, y)

    zeros = jnp.zeros_like(m)
    l, s, _ = jax.lax.fori_loop(0, n_iter, body, (zeros, zeros, zeros))
    err = jnp.linalg.norm(m - l - s) / m_norm
    return RPCAResult(
        l.astype(orig_dtype), s.astype(orig_dtype), jnp.asarray(n_iter, jnp.int32), err
    )


def batched_robust_pca(ms: jnp.ndarray, **kwargs) -> RPCAResult:
    """vmap RPCA over a leading batch axis (parallel across layers/modules).

    Implements the paper's App. B.2 suggestion of parallelizing Robust-PCA
    across layers: ``ms`` has shape (batch, d1, d2).
    """
    fn = functools.partial(robust_pca_fixed_iters, **kwargs)
    return jax.vmap(fn)(ms)


# ---------------------------------------------------------------------------
# Bucket layout on a mesh (DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# On a mesh the bucket's vec ROWS (d1) are split over the client axes
# ("pod", "data"); every shard holds every client.  Everything elementwise
# (shrink, dual ascent, masking) runs on the shard untouched, and a Gram is
# a sum over rows, G = sum_k X_k^T X_k, so an ADMM iteration exchanges only
# (B, C, C) blocks: the Gram partials are reduce-scattered over B, each
# shard forms the SVT projectors of its B / shards modules, and the
# projectors are all-gathered back for the shard-local L_k = X_k @ P.  One
# shard is the same loop with every collective the identity.

#: Mesh axis names the packed client axis may shard over.
CLIENT_AXIS_NAMES = ("pod", "data")


def mesh_client_axes(mesh) -> tuple:
    """Client axes of ``mesh`` (same filter as ``launch.mesh.client_axes``)."""
    return tuple(a for a in mesh.axis_names if a in CLIENT_AXIS_NAMES)


def mesh_client_shards(mesh) -> int:
    """Product of client-axis sizes; 1 means 'take the single-device path'."""
    if mesh is None:
        return 1
    n = 1
    for a in mesh_client_axes(mesh):
        n *= mesh.shape[a]
    return n


def bucket_pspecs(client_axes: tuple):
    """The row layout of one bucket over ``client_axes``: the spec of a
    ``(B, vec, clients)`` array (vec rows split, shard-major) and a
    ``BucketCarry`` of specs for its carry (``l``/``s``/``y`` as the
    bucket, the basis and scalars replicated).  These are the sharded
    loop's ``shard_map`` specs."""
    ax = client_axes if len(client_axes) > 1 else client_axes[0]
    row, rep = P(None, ax, None), P()
    return row, BucketCarry(
        l=row, s=row, y=row, v=rep, n_live=rep, n_eff=rep, valid=rep,
        fall_count=rep, hit=rep,
    )


def _shard_index(mesh):
    """This shard's position along the mesh's client axes, inside
    ``shard_map``: the order of a tiled collective over them."""
    idx = jnp.zeros((), jnp.int32)
    for a in mesh_client_axes(mesh):
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# One-dispatch bucket RPCA (the batched aggregation engine's hot loop)
# ---------------------------------------------------------------------------


@_f32_matmuls
@jax.named_scope("agg.admm")
def robust_pca_bucket(
    m: jnp.ndarray,
    true_dims: jnp.ndarray | None = None,
    *,
    mesh=None,
    n_iter: int = 50,
    tol: float | None = None,
    mu: float | None = None,
    lam: float | None = None,
    shrink_fn: Callable = soft_threshold,
    fused_tail: bool = False,
    interpret: bool | None = None,
    client_mask: jnp.ndarray | None = None,
    svt_mode: str = "gram",
    svt_rank: int = 8,
    svt_sweeps: int = 2,
    svt_fallback_tol: float = 1e-3,
    carry: BucketCarry | None = None,
    return_carry: bool = False,
    carry_gate: float = 1.0,
    true_cols: int | None = None,
) -> RPCAResult:
    """RPCA over a whole shape bucket in ONE dispatch (no per-leaf Python).

    ``m`` is a (B, vec_dim, n_clients) bucket whose modules may have been
    zero-padded along vec_dim up to the bucket's canonical size;
    ``true_dims`` carries each module's unpadded vec dim so the ADMM
    constants (mu = numel / (4 ||M||_1), lam = 1 / sqrt(max(d1, d2))) match
    the per-matrix reference exactly.  Padded rows stay identically zero
    through both the Gram-trick SVT and the elementwise tail, so the result
    rows equal the unpadded per-matrix decomposition.

    ``client_mask`` is the column-axis twin of the zero-row story: a
    (n_clients,) validity mask for shape-static partial participation.
    Masked columns are zeroed on entry, the ADMM constants use the
    *effective* client count ``n_eff = sum(mask)`` (numel = true_dim *
    n_eff, lam = 1/sqrt(max(true_dim, n_eff))), and the tail re-masks S/Y
    each iteration so eigh round-off in the SVT cannot leak into padded
    slots — the active sub-matrix decomposition matches the dense
    sub-cohort call (DESIGN.md §5).

    ``tol=None`` runs the fixed-iteration fori_loop (shape-static cost).
    With a tolerance, a while_loop iterates until every module's relative
    residual passes, freezing already-converged modules — the same
    semantics as ``jax.vmap(robust_pca)``.

    The SVT step forms each module's shrink projector ``P`` from the Gram
    ``G = X^T X`` and applies it as ``L = X @ P``.  ``svt_mode="gram"``
    takes a full eigh of every Gram each iteration.  ``svt_mode="subspace"``
    replaces it with the warm-started subspace-iteration SVT (DESIGN.md §6):
    the loop carries a ``SubspaceState`` (eigenbasis V, the Gram of the
    current iterate, live-rank/residual trackers) and each iteration runs
    matmul-only power sweeps + an r x r Rayleigh-Ritz shrink, falling back
    to the full eigh only on the cold start, on subspace-residual breach,
    or on rank saturation.

    ``fused_tail=True`` routes the S/Y/residual tail through a Pallas
    kernel (one VMEM pass): ``repro.kernels.rpca_admm.admm_tail`` in gram
    mode, ``repro.kernels.svt_subspace.subspace_apply`` in subspace mode,
    which also applies ``L = X @ P`` and accumulates the next iteration's
    Gram.

    ``mesh`` with more than one client shard splits the bucket's vec rows
    over the mesh's client axes (DESIGN.md §10) and runs the same loop
    under one ``shard_map``: the norms' partial sums and the Gram partials
    cross shards, nothing else does.  The bucket and its carry change
    layout once on entry; the results and the carry come back in global
    shapes, equal to the one-device call up to the order of float32
    partial sums.  ``mesh=None`` or one shard calls the loop directly.

    ``carry`` threads cross-round session state (DESIGN.md §7): a valid
    carry whose cohort fingerprint matches and whose initial relative
    residual passes ``carry_gate`` warm-starts ``L``/``S``/``Y`` and (in
    subspace mode) the eigenbasis, so a warm round enters the ADMM loop at
    the previous round's fixed point and skips the exact-eigh burn-in
    entirely.  Any gate failure selects the ordinary cold start — the
    result is then identical to a carry-less call.  ``return_carry=True``
    additionally returns the exit-state ``BucketCarry`` (f32 iterates,
    basis, live ranks, fallback/hit diagnostics) for the next round.

    ``true_cols`` caps the static subspace width by the true (unpadded)
    cohort column count instead of ``d2`` when the bucket carries masked
    padding columns (see ``subspace_rank``) — e.g. 9 live clients packed
    into a 16-slot canonical cohort carry r <= 5, not r <= 8.
    """
    if m.ndim != 3:
        raise ValueError(f"robust_pca_bucket expects (B, d1, d2), got {m.shape}")
    if svt_mode not in SVT_MODES:
        raise ValueError(f"unknown svt_mode: {svt_mode!r} (expected one of {SVT_MODES})")
    if fused_tail and shrink_fn is not soft_threshold:
        raise ValueError(
            "fused_tail hardcodes soft-threshold shrinkage in the Pallas "
            "kernel; custom shrink_fn requires fused_tail=False"
        )
    b, d1p, d2 = m.shape
    r = subspace_rank(d2, svt_rank, true_cols)
    use_subspace = svt_mode == "subspace"
    if carry is not None:
        if carry.l.shape != m.shape:
            raise ValueError(
                f"carry shape {carry.l.shape} does not match bucket {m.shape}"
            )
        if use_subspace and carry.v.shape != (b, d2, r):
            raise ValueError(
                f"carry basis shape {carry.v.shape} != {(b, d2, r)}; "
                "was the carry built with a different svt_rank?"
            )
    orig_dtype = m.dtype
    m = m.astype(jnp.float32)
    if true_dims is None:
        true_dims = jnp.full((b,), d1p, jnp.int32)
    dims_f = true_dims.astype(jnp.float32)
    cmask = None if client_mask is None else jnp.asarray(client_mask, jnp.float32)
    shards = mesh_client_shards(mesh)
    loop = functools.partial(
        _bucket_loop, mesh=mesh if shards > 1 else None, n_iter=n_iter, tol=tol,
        mu=mu, lam=lam, shrink_fn=shrink_fn, fused_tail=fused_tail,
        interpret=interpret, svt_mode=svt_mode, r=r, svt_sweeps=svt_sweeps,
        svt_fallback_tol=svt_fallback_tol, return_carry=return_carry,
        carry_gate=carry_gate,
    )
    if shards == 1:
        out = loop(m, dims_f, cmask, carry)
    else:
        row, carry_spec = bucket_pspecs(mesh_client_axes(mesh))
        # Zero rows to a shard multiple of d1 are inert: they add nothing
        # to a Gram or a norm and stay zero through the tail.
        pad_rows = shards * -(-d1p // shards) - d1p

        def to_rows(t):
            # The one layout change of the call: buckets arrive with their
            # client columns sharded (``pack``) and enter the loop row-sharded.
            t = jnp.pad(t, ((0, 0), (0, pad_rows), (0, 0)))
            with jax.named_scope("agg.psum"):
                return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, row))

        if carry is not None:
            carry = carry._replace(l=to_rows(carry.l), s=to_rows(carry.s),
                                   y=to_rows(carry.y))
        if cmask is None:
            # A dense cohort masks with ones here: the program this path
            # has always compiled to.
            cmask = jnp.ones((d2,), jnp.float32)
        out = jax.shard_map(
            loop, mesh=mesh,
            in_specs=(row, P(), P(), P() if carry is None else carry_spec),
            out_specs=(row, row, P(), P()) + ((carry_spec,) if return_carry else ()),
            check_vma=False,
        )(to_rows(m), dims_f, cmask, carry)
        cut = lambda t: t[:, :d1p]
        out = (cut(out[0]), cut(out[1]), *out[2:4]) + tuple(
            c._replace(l=cut(c.l), s=cut(c.s), y=cut(c.y)) for c in out[4:]
        )
    l, s, n_done, err = out[:4]
    result = RPCAResult(l.astype(orig_dtype), s.astype(orig_dtype), n_done, err)
    return (result, out[4]) if return_carry else result


def _bucket_loop(
    m, dims_f, cmask, carry, *, mesh, n_iter, tol, mu, lam, shrink_fn,
    fused_tail, interpret, svt_mode, r, svt_sweeps, svt_fallback_tol,
    return_carry, carry_gate,
):
    """The ADMM loop of ``robust_pca_bucket`` on a float32 bucket: on one
    device (``mesh=None``), or inside ``shard_map`` on this shard's vec rows
    of every client.  Returns ``(L, S, n_iter, err)`` and, with
    ``return_carry``, the exit ``BucketCarry``."""
    b, _, d2 = m.shape
    use_subspace = svt_mode == "subspace"
    if mesh is None:
        red = scatter = gather = loc = lambda a, *_: a
        across = None
    else:
        axes = mesh_client_axes(mesh)
        ax = axes if len(axes) > 1 else axes[0]
        shards = mesh_client_shards(mesh)
        # Each shard owns B / shards modules' projectors; zero modules pad
        # B to a shard multiple (a zero Gram shrinks to a zero projector,
        # and its trackers, padded with zeros, trip no bucket-wide test).
        b_loc = -(-b // shards)
        pad_b = b_loc * shards - b

        def red(x):
            with jax.named_scope("agg.psum"):
                return jax.lax.psum(x, ax)

        def scatter(g):
            if pad_b:
                g = jnp.pad(g, ((0, pad_b), (0, 0), (0, 0)))
            with jax.named_scope("agg.psum"):
                return jax.lax.psum_scatter(g, ax, scatter_dimension=0, tiled=True)

        def gather(p):
            with jax.named_scope("agg.psum"):
                return jax.lax.all_gather(p, ax, axis=0, tiled=True)[:b]

        def loc(a, fill=0):
            # This shard's modules of a replicated (B, ...) array.
            if pad_b:
                pads = ((0, pad_b),) + ((0, 0),) * (a.ndim - 1)
                a = jnp.pad(a, pads, constant_values=fill)
            return jax.lax.dynamic_slice_in_dim(a, _shard_index(mesh) * b_loc, b_loc)

        def across(x):
            with jax.named_scope("agg.psum"):
                return jax.lax.pmax(x, ax)

    if cmask is not None:
        m = m * cmask  # zero inactive columns (idempotent if pre-masked)
        n_eff = jnp.maximum(jnp.sum(cmask), 1.0)
        mask = lambda a: a * cmask
    else:
        n_eff = float(d2)
        mask = lambda a: a

    abs_sum = red(jnp.sum(jnp.abs(m), axis=(1, 2)))
    numel = dims_f * n_eff
    mu_v = jnp.where(abs_sum > _EPS, numel / (4.0 * jnp.maximum(abs_sum, _EPS)), 1.0)
    if mu is not None:
        mu_v = jnp.full((b,), mu, jnp.float32)
    lam_v = (
        jnp.full((b,), lam, jnp.float32)
        if lam is not None
        else 1.0 / jnp.sqrt(jnp.maximum(dims_f, n_eff))
    )
    rho = 1.0 / mu_v
    thresh = rho * lam_v
    m_norm = jnp.maximum(jnp.sqrt(red(jnp.sum(m * m, axis=(1, 2)))), _EPS)
    n_eff_s = jnp.asarray(n_eff, jnp.float32)
    # The (B, 1, 1) views of rho and mu: the loop carries them on a mesh and
    # forms them in the body on one device, as each path always compiled.
    if mesh is None:
        rho_b, mu_b = (lambda: rho[:, None, None]), (lambda: mu_v[:, None, None])
    else:
        rho_c, mu_c = rho[:, None, None], mu_v[:, None, None]
        rho_b, mu_b = (lambda: rho_c), (lambda: mu_c)
    rho_loc = loc(rho, 1.0)  # the SVT thresholds of this shard's modules

    # Cross-round warm start (DESIGN.md §7): accept the carried iterates only
    # when the carry is valid, the cohort fingerprint matches, and starting
    # from them is no worse than the cold start (whose initial relative
    # residual is exactly 1.0).  The gate is a whole-bucket scalar so the
    # subspace loop's cold/warm routing stays a single cheap cond.
    zeros = jnp.zeros_like(m)
    if carry is not None:
        # A carry saved under a different active set may hold nonzeros in
        # currently-masked columns; re-mask on load so padded slots stay
        # inert (the gate below then scores the masked iterates).
        cl, cs, cy = mask(carry.l), mask(carry.s), mask(carry.y)
        init_res = m - cl - cs
        init_err = jnp.sqrt(red(jnp.sum(init_res * init_res, axis=(1, 2)))) / m_norm
        warm = jnp.logical_and(
            jnp.asarray(carry.valid),
            jnp.logical_and(
                carry.n_eff == n_eff_s, jnp.all(init_err <= carry_gate)
            ),
        )
        wsel = lambda a: jnp.where(warm, a, 0.0)
        l0, s0, y0 = wsel(cl), wsel(cs), wsel(cy)
    else:
        warm = jnp.asarray(False)
        l0 = s0 = y0 = zeros

    if fused_tail:
        from repro.kernels import rpca_admm, svt_subspace
        from repro.kernels.backend import resolve_interpret

        interp = resolve_interpret(interpret)

    def tail(l, y):
        # S/Y update and the residual norm; the fused kernel reads and
        # writes each state tile once.  Only the norm's partial crosses
        # shards.
        if fused_tail:
            s, y_new, rsq = rpca_admm.admm_tail(
                m, l, y, rho, mu_v, thresh, mask=cmask, interpret=interp
            )
            return s, y_new, jnp.sqrt(red(rsq))
        s = mask(shrink_fn(m - l + rho_b() * y, thresh[:, None, None]))
        resid = mask(m - l - s)
        y_new = mask(y + mu_b() * resid)
        return s, y_new, jnp.sqrt(red(jnp.sum(resid * resid, axis=(1, 2))))

    gram = lambda x: jnp.einsum("bdc,bde->bce", x, x)

    @jax.named_scope("agg.svt")
    def projectors(g, sub, it):
        # The SVT stage: reduce-scatter the (B, C, C) Gram partials over B,
        # form this shard's modules' shrink projectors, all-gather them.
        g = scatter(g)
        fell = None
        if use_subspace:
            # A warm-started session is never cold at iteration 0: the
            # carried basis tracks the carried iterates, so the Ritz attempt
            # runs immediately (the post-guard still protects exactness).
            p, sub, fell = svt_subspace_step(
                rho_loc, sub._replace(g=g),
                cold=jnp.logical_and(it == 0, jnp.logical_not(warm)),
                sweeps=svt_sweeps, fallback_tol=svt_fallback_tol,
                shrink_fn=shrink_fn, across=across,
            )
        else:
            p = jax.vmap(lambda gi, ti: _shrink_projector(gi, ti, shrink_fn)[0])(
                g, rho_loc)
        return gather(p), sub, fell

    def step(l, s, y, aux, it):
        if not use_subspace:
            x = m - s + rho_b() * y
            p, _, _ = projectors(gram(x), None, it)
            # The barrier keeps XLA from fusing the tail's M - L into X @ P
            # as a second output, which would hold X, L and M - L live at
            # once: one state-sized buffer more than the step needs.
            with jax.named_scope("agg.svt"):
                l = jax.lax.optimization_barrier(jnp.einsum("bdc,bce->bde", x, p))
            s, y, rnorm = tail(l, y)
            return l, s, y, rnorm / m_norm, aux
        # Subspace mode carries the Gram of the iterate from the last tail.
        sub, falls = aux
        p, sub, fell = projectors(sub.g, sub, it)
        if fused_tail:
            l, s, y, rsq, g = svt_subspace.subspace_apply(
                m, s, y, p, rho, mu_v, thresh, mask=cmask, interpret=interp
            )
            rnorm = jnp.sqrt(red(rsq))
        else:
            x = m - s + rho_b() * y
            l = jnp.einsum("bdc,bce->bde", x, p)
            s, y, rnorm = tail(l, y)
            g = gram(m - s + rho_b() * y)
        return l, s, y, rnorm / m_norm, (sub._replace(g=g), falls + fell.astype(jnp.int32))

    err0 = jnp.full((b,), jnp.inf, jnp.float32)
    falls0 = jnp.zeros((), jnp.int32)
    aux0 = ()
    if use_subspace:
        # Gram of the *initial* iterate X0 = M - S0 + rho Y0 (cold start:
        # S0 = Y0 = 0 reduces this to subspace_init's Gram of M).  A warm
        # start seeds the basis/live-rank/rel trackers from the carry so the
        # first SVT runs the matmul-only Ritz attempt with full sweeps.  The
        # trackers of this shard's modules ride the loop; the Gram is the
        # shard's partial over its rows.
        g0 = gram(m - s0 + rho_b() * y0)
        eye = jnp.broadcast_to(jnp.eye(d2, r, dtype=jnp.float32), (b, d2, r))
        if carry is not None:
            v0 = jnp.where(warm, carry.v, eye)
            nl0 = jnp.where(warm, carry.n_live, jnp.full((b,), r, jnp.int32))
            rel0 = jnp.where(
                warm,
                jnp.full((b,), 0.5 * svt_fallback_tol, jnp.float32),
                jnp.full((b,), jnp.inf, jnp.float32),
            )
        else:
            v0 = eye
            nl0 = jnp.full((b,), r, jnp.int32)
            rel0 = jnp.full((b,), jnp.inf, jnp.float32)
        aux0 = (SubspaceState(v=loc(v0), g=g0, n_live=loc(nl0), rel=loc(rel0)), falls0)

    if tol is None:
        l, s, y, err, aux = jax.lax.fori_loop(
            0, n_iter, lambda it, st: step(*st[:3], st[4], it),
            (l0, s0, y0, err0, aux0),
        )
        n_done = jnp.full((b,), n_iter, jnp.int32)
    else:

        def cond(state):
            _, _, _, err, i, _, _ = state
            return jnp.logical_and(i < n_iter, jnp.any(err > tol))

        def body(state):
            l, s, y, err, i, niter, aux = state
            l2, s2, y2, err2, aux2 = step(l, s, y, aux, i)
            active = err > tol  # matches vmap(while_loop) select semantics
            sel = lambda new, old: jnp.where(active[:, None, None], new, old)
            if use_subspace:
                # Frozen modules keep their basis/Gram carry so a later thaw
                # (impossible here, but cheap to keep exact) resumes cleanly.
                (sub2, falls), (sub, _) = aux2, aux
                act = loc(active)
                aux2 = (SubspaceState(
                    v=jnp.where(act[:, None, None], sub2.v, sub.v),
                    g=sel(sub2.g, sub.g),
                    n_live=jnp.where(act, sub2.n_live, sub.n_live),
                    rel=jnp.where(act, sub2.rel, sub.rel),
                ), falls)
            return (
                sel(l2, l),
                sel(s2, s),
                sel(y2, y),
                jnp.where(active, err2, err),
                i + 1,
                jnp.where(active, i + 1, niter),
                aux2,
            )

        init = (l0, s0, y0, err0, jnp.asarray(0, jnp.int32),
                jnp.zeros((b,), jnp.int32), aux0)
        l, s, y, err, _, n_done, aux = jax.lax.while_loop(cond, body, init)

    # S/Y are masked inside the tail; the final L gets one mask pass so
    # eigh round-off cannot leave residue in inactive columns.
    l = mask(l)
    if not return_carry:
        return l, s, n_done, err
    if use_subspace:
        sub_f, falls = aux
        v_out, nl_out = gather(sub_f.v), gather(sub_f.n_live)
    else:
        falls = falls0
        if carry is not None:
            # Gram mode has no basis to track; keep the slots shape-stable.
            v_out, nl_out = carry.v, carry.n_live
        else:
            v_out = jnp.zeros((b, d2, r), jnp.float32)
            nl_out = jnp.zeros((b,), jnp.int32)
    new_carry = BucketCarry(
        l=l,
        s=s,
        y=y,
        v=v_out,
        n_live=nl_out,
        n_eff=n_eff_s,
        valid=jnp.ones((), bool),
        fall_count=falls,
        hit=warm.astype(jnp.float32),
    )
    return l, s, n_done, err, new_carry
