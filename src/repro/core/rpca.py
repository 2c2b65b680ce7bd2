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
    """
    r = state.v.shape[-1]
    g = state.g

    def exact():
        p, v2, live, rel = _exact_projector(g, t, r, shrink_fn)
        return p, v2, live, rel, jnp.asarray(True)

    def attempt():
        # Steady state (last residuals far inside tolerance): one sweep
        # tracks the slow rotation.  Otherwise advance the span the full
        # `sweeps` power applications to re-capture it.
        if sweeps > 1:
            p, v2, live, rel = jax.lax.cond(
                jnp.max(state.rel) <= 0.1 * fallback_tol,
                lambda: _ritz_projector(g, t, state.v, 1, shrink_fn),
                lambda: _ritz_projector(g, t, state.v, sweeps, shrink_fn),
            )
        else:
            p, v2, live, rel = _ritz_projector(g, t, state.v, max(sweeps, 1), shrink_fn)
        bad = jnp.logical_or(jnp.any(rel > fallback_tol), jnp.any(live >= r))
        return jax.lax.cond(bad, exact, lambda: (p, v2, live, rel, jnp.asarray(False)))

    pre_full = jnp.logical_or(jnp.asarray(cold), jnp.any(state.n_live >= r))
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
# One-dispatch bucket RPCA (the batched aggregation engine's hot loop)
# ---------------------------------------------------------------------------


@jax.named_scope("agg.svt")
def svt_gram_batched(
    x: jnp.ndarray, t: jnp.ndarray, shrink_fn: Callable = soft_threshold
) -> jnp.ndarray:
    """Batched Gram-trick SVT: ``x`` is (B, d1, d2), ``t`` per-module (B,).

    A vmap of ``svt_gram`` — one batched eigh, two batched matmuls over the
    vec dimension (the Gram and ``X @ P``) and one C x C product forming
    ``P``; the static transpose decision is shared by the whole bucket.
    Padded zero rows contribute nothing to the Gram matrix and stay exactly
    zero in the thresholded output (DESIGN.md §3), so bucket padding is
    lossless.
    ``shrink_fn`` must broadcast over an array threshold (the jnp reference
    does; the scalar-threshold Pallas shrink kernel does not — the fused-tail
    kernel covers the S update instead).
    """
    return jax.vmap(lambda xi, ti: svt_gram(xi, ti, shrink_fn))(x, t)


@_f32_matmuls
@jax.named_scope("agg.admm")
def robust_pca_bucket(
    m: jnp.ndarray,
    true_dims: jnp.ndarray | None = None,
    *,
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

    ``tol=None`` runs the fixed-iteration fori_loop (shape-static cost, the
    mesh path).  With a tolerance, a while_loop iterates until every module's
    relative residual passes, freezing already-converged modules — the same
    semantics as ``jax.vmap(robust_pca)``.

    ``fused_tail=True`` routes the S/Y/residual tail through the Pallas
    kernel ``repro.kernels.rpca_admm.admm_tail`` (one VMEM pass).

    ``svt_mode="subspace"`` replaces the per-iteration batched eigh with
    the warm-started subspace-iteration SVT (DESIGN.md §6): the loop carry
    grows a ``SubspaceState`` (eigenbasis V, Gram of the current iterate,
    live-rank/residual trackers) and each iteration runs matmul-only power
    sweeps + an r x r Rayleigh-Ritz shrink, falling back to the full eigh
    only on the cold start, on subspace-residual breach, or on rank
    saturation.  With ``fused_tail=True`` the sweep tail (reconstruction
    ``L = X @ P``, shrink, dual ascent, residual partial sums, and the
    next iteration's Gram accumulation) runs as one Pallas VMEM pass
    (``repro.kernels.svt_subspace.subspace_apply``).

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
    orig_dtype = m.dtype
    m = m.astype(jnp.float32)
    b, d1p, d2 = m.shape
    if true_dims is None:
        true_dims = jnp.full((b,), d1p, jnp.int32)
    dims_f = true_dims.astype(jnp.float32)

    if client_mask is not None:
        cmask = jnp.asarray(client_mask, jnp.float32)
        m = m * cmask  # zero inactive columns (idempotent if pre-masked)
        n_eff = jnp.maximum(jnp.sum(cmask), 1.0)
    else:
        cmask = None
        n_eff = float(d2)

    abs_sum = jnp.sum(jnp.abs(m), axis=(1, 2))
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
    m_norm = jnp.maximum(jnp.sqrt(jnp.sum(m * m, axis=(1, 2))), _EPS)
    n_eff_s = jnp.asarray(n_eff, jnp.float32)

    use_subspace = svt_mode == "subspace"
    use_sub_kernel = use_subspace and fused_tail

    # Cross-round warm start (DESIGN.md §7): accept the carried iterates only
    # when the carry is valid, the cohort fingerprint matches, and starting
    # from them is no worse than the cold start (whose initial relative
    # residual is exactly 1.0).  The gate is a whole-bucket scalar so the
    # subspace loop's cold/warm routing stays a single cheap cond.
    zeros = jnp.zeros_like(m)
    if carry is not None:
        if carry.l.shape != m.shape:
            raise ValueError(
                f"carry shape {carry.l.shape} does not match bucket {m.shape}"
            )
        cl, cs, cy = carry.l, carry.s, carry.y
        if cmask is not None:
            # A carry saved under a different active set may hold nonzeros in
            # currently-masked columns; re-mask on load so padded slots stay
            # inert (the gate below then scores the masked iterates).
            cl, cs, cy = cl * cmask, cs * cmask, cy * cmask
        init_res = m - cl - cs
        init_err = jnp.sqrt(jnp.sum(init_res * init_res, axis=(1, 2))) / m_norm
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
        from repro.kernels.backend import resolve_interpret

        if shrink_fn is not soft_threshold:
            raise ValueError(
                "fused_tail hardcodes soft-threshold shrinkage in the Pallas "
                "kernel; custom shrink_fn requires fused_tail=False"
            )
        interp = resolve_interpret(interpret)

    if fused_tail and not use_subspace:
        from repro.kernels import rpca_admm as _tail_kernel

        def tail(l, y):
            s, y_new, rsq = _tail_kernel.admm_tail(
                m, l, y, rho, mu_v, thresh, mask=cmask, interpret=interp
            )
            return s, y_new, jnp.sqrt(rsq)

    elif cmask is not None:

        def tail(l, y):
            s = shrink_fn(m - l + rho[:, None, None] * y, thresh[:, None, None]) * cmask
            resid = (m - l - s) * cmask
            y_new = (y + mu_v[:, None, None] * resid) * cmask
            return s, y_new, jnp.sqrt(jnp.sum(resid * resid, axis=(1, 2)))

    else:

        def tail(l, y):
            s = shrink_fn(m - l + rho[:, None, None] * y, thresh[:, None, None])
            resid = m - l - s
            y_new = y + mu_v[:, None, None] * resid
            return s, y_new, jnp.sqrt(jnp.sum(resid * resid, axis=(1, 2)))

    if use_subspace:
        if use_sub_kernel:
            from repro.kernels import svt_subspace as _sub_kernel

        def step_sub(l, s, y, sub, it):
            # A warm-started session is never cold at iteration 0: the
            # carried basis tracks the carried iterates, so the Ritz attempt
            # runs immediately (the post-guard still protects exactness).
            p, sub, fell = svt_subspace_step(
                rho, sub, cold=jnp.logical_and(it == 0, jnp.logical_not(warm)),
                sweeps=svt_sweeps,
                fallback_tol=svt_fallback_tol, shrink_fn=shrink_fn,
            )
            if use_sub_kernel:
                l, s2, y2, rsq, g2 = _sub_kernel.subspace_apply(
                    m, s, y, p, rho, mu_v, thresh, mask=cmask, interpret=interp
                )
                rnorm = jnp.sqrt(rsq)
            else:
                x = m - s + rho[:, None, None] * y
                l = jnp.einsum("bdc,bce->bde", x, p)
                s2, y2, rnorm = tail(l, y)
                x2 = m - s2 + rho[:, None, None] * y2
                g2 = jnp.einsum("bdc,bde->bce", x2, x2)
            return l, s2, y2, rnorm / m_norm, sub._replace(g=g2), fell

    else:

        def step(l, s, y):
            # The barrier keeps XLA from fusing the tail's M - L into X @ P
            # as a second output, which would hold X, L and M - L live at
            # once: one state-sized buffer more than the step needs.
            l = jax.lax.optimization_barrier(
                svt_gram_batched(m - s + rho[:, None, None] * y, rho, shrink_fn))
            s, y, rnorm = tail(l, y)
            return l, s, y, rnorm / m_norm

    err0 = jnp.full((b,), jnp.inf, jnp.float32)
    falls0 = jnp.zeros((), jnp.int32)
    r = subspace_rank(d2, svt_rank, true_cols)

    if use_subspace:
        # Gram of the *initial* iterate X0 = M - S0 + rho Y0 (cold start:
        # S0 = Y0 = 0 reduces this to subspace_init's Gram of M).  A warm
        # start seeds the basis/live-rank/rel trackers from the carry so the
        # first SVT runs the matmul-only Ritz attempt with full sweeps.
        x0 = m - s0 + rho[:, None, None] * y0
        g0 = jnp.einsum("bdc,bde->bce", x0, x0)
        eye = jnp.broadcast_to(jnp.eye(d2, r, dtype=jnp.float32), (b, d2, r))
        if carry is not None:
            if carry.v.shape != (b, d2, r):
                raise ValueError(
                    f"carry basis shape {carry.v.shape} != {(b, d2, r)}; "
                    "was the carry built with a different svt_rank?"
                )
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
        sub0 = SubspaceState(v=v0, g=g0, n_live=nl0, rel=rel0)
    else:
        sub0 = None

    sub_f = sub0
    falls = falls0
    if tol is None:
        if use_subspace:

            def body_sub(it, state):
                l, s, y, _err, sub, fc = state
                l2, s2, y2, err2, sub2, fell = step_sub(l, s, y, sub, it)
                return (l2, s2, y2, err2, sub2, fc + fell.astype(jnp.int32))

            l, s, y, err, sub_f, falls = jax.lax.fori_loop(
                0, n_iter, body_sub, (l0, s0, y0, err0, sub0, falls0)
            )
        else:

            def body(_, state):
                l, s, y, _err = state
                return step(l, s, y)

            l, s, y, err = jax.lax.fori_loop(0, n_iter, body, (l0, s0, y0, err0))
        n_done = jnp.full((b,), n_iter, jnp.int32)
    elif use_subspace:

        def cond_sub(state):
            _, _, _, err, i, _, _, _ = state
            return jnp.logical_and(i < n_iter, jnp.any(err > tol))

        def body_sub(state):
            l, s, y, err, i, niter, sub, fc = state
            l2, s2, y2, err2, sub2, fell = step_sub(l, s, y, sub, i)
            active = err > tol  # matches vmap(while_loop) select semantics
            sel = lambda new, old: jnp.where(active[:, None, None], new, old)
            selv = lambda new, old: jnp.where(active, new, old)
            # Frozen modules keep their basis/Gram carry so a later thaw
            # (impossible here, but cheap to keep exact) resumes cleanly.
            sub_sel = SubspaceState(
                v=sel(sub2.v, sub.v),
                g=sel(sub2.g, sub.g),
                n_live=selv(sub2.n_live, sub.n_live),
                rel=selv(sub2.rel, sub.rel),
            )
            return (
                sel(l2, l),
                sel(s2, s),
                sel(y2, y),
                selv(err2, err),
                i + 1,
                jnp.where(active, i + 1, niter),
                sub_sel,
                fc + fell.astype(jnp.int32),
            )

        init = (
            l0, s0, y0, err0,
            jnp.asarray(0, jnp.int32), jnp.zeros((b,), jnp.int32), sub0, falls0,
        )
        l, s, y, err, _, n_done, sub_f, falls = jax.lax.while_loop(
            cond_sub, body_sub, init
        )
    else:

        def cond(state):
            _, _, _, err, i, _ = state
            return jnp.logical_and(i < n_iter, jnp.any(err > tol))

        def body(state):
            l, s, y, err, i, niter = state
            l2, s2, y2, err2 = step(l, s, y)
            active = err > tol  # matches vmap(while_loop) select semantics
            sel = lambda new, old: jnp.where(active[:, None, None], new, old)
            return (
                sel(l2, l),
                sel(s2, s),
                sel(y2, y),
                jnp.where(active, err2, err),
                i + 1,
                jnp.where(active, i + 1, niter),
            )

        init = (l0, s0, y0, err0, jnp.asarray(0, jnp.int32), jnp.zeros((b,), jnp.int32))
        l, s, y, err, _, n_done = jax.lax.while_loop(cond, body, init)

    if cmask is not None:
        # S/Y are masked inside the tail; the final L gets one mask pass so
        # eigh round-off cannot leave residue in inactive columns.
        l = l * cmask
    result = RPCAResult(l.astype(orig_dtype), s.astype(orig_dtype), n_done, err)
    if not return_carry:
        return result
    if use_subspace:
        v_out, nl_out = sub_f.v, sub_f.n_live
    elif carry is not None:
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
    return result, new_carry


# ---------------------------------------------------------------------------
# Mesh-sharded bucket RPCA (DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# The sharded loop splits a bucket over the mesh's client axes ("pod",
# "data"); everything elementwise (shrink, dual ascent, masking) runs on the
# shard untouched.  The gram SVT splits the vec ROWS (d1): G = sum_k X_k^T
# X_k, so an iteration reduce-scatters the (B, C, C) Gram partials over B,
# each shard eighs its B / shards modules, and the shrink projectors are
# all-gathered back for the shard-local L_k = X_k @ P.
#
# The subspace SVT splits the client COLUMNS (d2) and decomposes around
# the projected factor W = X @ V:
#
#   W      = psum_k( X_k @ V_k )         one (B, d1, r) all-reduce per sweep
#   (GV)_k = X_k^T @ W                   shard-local rows of G @ V
#   CholeskyQR / Rayleigh-Ritz           r x r psums, solves replicated
#   L_k    = (W @ W_rot) coef V_k^T      shard-local columns of L
#
# so the d2 x d2 Gram is never materialized and per-ADMM-iteration traffic
# is (sweeps + 1) * B * d1 * r floats plus a few r x r reductions — constant
# in the cohort size.  Only the exact-eigh fallback (cold start / residual
# breach / rank saturation) all-gathers X to form the full Gram; warm-carry
# rounds take zero fallbacks, so steady-state sharded sessions never gather.

#: Mesh axis names the packed client axis may shard over.
CLIENT_AXIS_NAMES = ("pod", "data")

#: Bucket-axis chunk count for ``mesh_overlap=True``: each chunk's psum is
#: an independent collective, so up to this many all-reduces can be in
#: flight against other chunks' tail/matmul compute.  Buckets smaller than
#: this fall back to one chunk per module.
_MESH_OVERLAP_CHUNKS = 4


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


def _shard_index(mesh):
    """This shard's position along the mesh's client axes, inside
    ``shard_map``: the order of a tiled collective over them."""
    idx = jnp.zeros((), jnp.int32)
    for a in mesh_client_axes(mesh):
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _gram_rows_sharded(
    m, dims_f, *, mesh, n_iter, tol, mu, lam, shrink_fn, fused_tail, interpret,
    client_mask, r, carry, return_carry, carry_gate, mesh_overlap,
):
    """The gram path of ``robust_pca_bucket_sharded`` on a float32 bucket:
    its vec axis ``d1`` split over the mesh's client axes, every client on
    every shard (DESIGN.md §10).  Returns ``(L, S, n_iter, err)`` and, with
    ``return_carry``, the exit ``BucketCarry``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = mesh_client_axes(mesh)
    ax = axes if len(axes) > 1 else axes[0]
    shards = mesh_client_shards(mesh)
    b, d1p, d2 = m.shape
    cmask = (
        jnp.ones((d2,), jnp.float32)
        if client_mask is None
        else jnp.asarray(client_mask, jnp.float32)
    )
    # Zero rows (to a shard multiple of d1) and zero modules (to a shard
    # multiple of B, Grams only) are inert: they add nothing to a Gram or a
    # norm, and a zero Gram shrinks to a zero projector (DESIGN.md §1, §3).
    d1s = shards * (-(-d1p // shards))
    b_loc = -(-b // shards)
    row = P(None, ax, None)
    rep = P()

    def to_rows(t):
        # The one layout change of the call: buckets arrive with their
        # client columns sharded (``pack``) and enter the loop row-sharded.
        t = jnp.pad(t, ((0, 0), (0, d1s - d1p), (0, 0)))
        with jax.named_scope("agg.psum"):
            return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, row))

    m = to_rows(m)
    has_carry = carry is not None
    if has_carry:
        carry = carry._replace(l=to_rows(carry.l), s=to_rows(carry.s),
                               y=to_rows(carry.y))
    carry_spec = BucketCarry(
        l=row, s=row, y=row, v=rep, n_live=rep, n_eff=rep, valid=rep,
        fall_count=rep, hit=rep,
    )
    if fused_tail:
        from repro.kernels import rpca_admm as _tail_kernel
        from repro.kernels.backend import resolve_interpret

        interp = resolve_interpret(interpret)

    def inner(m_k, dims_f, cmask, *rest):
        def gs(x):
            with jax.named_scope("agg.psum"):
                return jax.lax.psum(x, ax)

        m_k = m_k * cmask
        # The mask is replicated: n_eff is already the whole cohort's.
        n_eff = jnp.maximum(jnp.sum(cmask), 1.0)
        abs_sum = gs(jnp.sum(jnp.abs(m_k), axis=(1, 2)))
        numel = dims_f * n_eff
        mu_v = jnp.where(
            abs_sum > _EPS, numel / (4.0 * jnp.maximum(abs_sum, _EPS)), 1.0
        )
        if mu is not None:
            mu_v = jnp.full((b,), mu, jnp.float32)
        lam_v = (
            jnp.full((b,), lam, jnp.float32)
            if lam is not None
            else 1.0 / jnp.sqrt(jnp.maximum(dims_f, n_eff))
        )
        rho = 1.0 / mu_v
        thresh = rho * lam_v
        m_norm = jnp.maximum(jnp.sqrt(gs(jnp.sum(m_k * m_k, axis=(1, 2)))), _EPS)
        rho_b = rho[:, None, None]
        mu_b = mu_v[:, None, None]
        # The SVT thresholds of this shard's modules of the reduce-scattered
        # Grams (a padding module's Gram is zero, whatever its threshold).
        rho_loc = jax.lax.dynamic_slice_in_dim(
            jnp.pad(rho, (0, b_loc * shards - b), constant_values=1.0),
            _shard_index(mesh) * b_loc, b_loc,
        )

        if has_carry:
            cin = rest[0]
            cl, cs, cy = cin.l * cmask, cin.s * cmask, cin.y * cmask
            init_res = m_k - cl - cs
            init_err = (
                jnp.sqrt(gs(jnp.sum(init_res * init_res, axis=(1, 2)))) / m_norm
            )
            warm = jnp.logical_and(
                jnp.asarray(cin.valid),
                jnp.logical_and(cin.n_eff == n_eff, jnp.all(init_err <= carry_gate)),
            )
            wsel = lambda a: jnp.where(warm, a, 0.0)
            l0, s0, y0 = wsel(cl), wsel(cs), wsel(cy)
        else:
            warm = jnp.asarray(False)
            l0 = s0 = y0 = jnp.zeros_like(m_k)

        # The fused tail's residual psum issues per B-chunk under the
        # overlap knob, as in the client-sharded path.
        bsl = [(0, b)]
        if mesh_overlap and b > 1:
            nch = min(b, _MESH_OVERLAP_CHUNKS)
            step_b = -(-b // nch)
            bsl = [(lo, min(lo + step_b, b)) for lo in range(0, b, step_b)]

        @jax.named_scope("agg.svt")
        def svt(x_k):
            # G = sum over shards of X_k^T X_k: reduce-scatter the (B, C, C)
            # partials over B, eigh this shard's modules, all-gather their
            # projectors; L_k = X_k @ P stays on the shard.
            g = jnp.einsum("bdc,bde->bce", x_k, x_k)
            g = jnp.pad(g, ((0, b_loc * shards - b), (0, 0), (0, 0)))
            with jax.named_scope("agg.psum"):
                g = jax.lax.psum_scatter(g, ax, scatter_dimension=0, tiled=True)
            p, _, _ = _shrink_projector(g, rho_loc[:, None], shrink_fn)
            with jax.named_scope("agg.psum"):
                p = jax.lax.all_gather(p, ax, axis=0, tiled=True)[:b]
            return jnp.einsum("bdc,bce->bde", x_k, p)

        def tail(l, y):
            if fused_tail:
                outs = [
                    _tail_kernel.admm_tail(
                        m_k[lo:hi], l[lo:hi], y[lo:hi], rho[lo:hi],
                        mu_v[lo:hi], thresh[lo:hi], mask=cmask,
                        interpret=interp,
                    )
                    for lo, hi in bsl
                ]
                s = jnp.concatenate([o[0] for o in outs], axis=0)
                y_new = jnp.concatenate([o[1] for o in outs], axis=0)
                rsq = jnp.concatenate([gs(o[2]) for o in outs], axis=0)
                return s, y_new, jnp.sqrt(rsq)
            s = shrink_fn(m_k - l + rho_b * y, thresh[:, None, None]) * cmask
            resid = (m_k - l - s) * cmask
            y_new = (y + mu_b * resid) * cmask
            return s, y_new, jnp.sqrt(gs(jnp.sum(resid * resid, axis=(1, 2))))

        def step(l, s, y):
            # robust_pca_bucket's barrier: X @ P is not fused with the
            # tail's M - L.
            l = jax.lax.optimization_barrier(svt(m_k - s + rho_b * y))
            s, y, rnorm = tail(l, y)
            return l, s, y, rnorm / m_norm

        err0 = jnp.full((b,), jnp.inf, jnp.float32)
        if tol is None:
            l, s, y, err = jax.lax.fori_loop(
                0, n_iter, lambda _, st: step(*st[:3]), (l0, s0, y0, err0)
            )
            n_done = jnp.full((b,), n_iter, jnp.int32)
        else:

            def cond(state):
                return jnp.logical_and(state[4] < n_iter, jnp.any(state[3] > tol))

            def body(state):
                l, s, y, err, i, niter = state
                l2, s2, y2, err2 = step(l, s, y)
                active = err > tol
                sel = lambda new, old: jnp.where(active[:, None, None], new, old)
                return (
                    sel(l2, l), sel(s2, s), sel(y2, y),
                    jnp.where(active, err2, err),
                    i + 1, jnp.where(active, i + 1, niter),
                )

            init = (l0, s0, y0, err0, jnp.asarray(0, jnp.int32),
                    jnp.zeros((b,), jnp.int32))
            l, s, y, err, _, n_done = jax.lax.while_loop(cond, body, init)

        l = l * cmask
        outs = (l, s, n_done, err)
        if not return_carry:
            return outs
        if has_carry:
            v_out, nl_out = cin.v, cin.n_live
        else:
            # Gram mode has no basis to track; keep the slots shape-stable.
            v_out = jnp.zeros((b, d2, r), jnp.float32)
            nl_out = jnp.zeros((b,), jnp.int32)
        new_carry = BucketCarry(
            l=l, s=s, y=y, v=v_out, n_live=nl_out, n_eff=n_eff,
            valid=jnp.ones((), bool), fall_count=jnp.zeros((), jnp.int32),
            hit=warm.astype(jnp.float32),
        )
        return outs + (new_carry,)

    in_specs = [row, rep, rep]
    args = [m, dims_f, cmask]
    if has_carry:
        in_specs.append(carry_spec)
        args.append(carry)
    out_specs = (row, row, rep, rep)
    if return_carry:
        out_specs = out_specs + (carry_spec,)
    out = jax.shard_map(
        inner, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False,
    )(*args)
    cut = lambda t: t[:, :d1p]
    l, s, n_done, err = out[:4]
    if not return_carry:
        return cut(l), cut(s), n_done, err
    c = out[4]
    return cut(l), cut(s), n_done, err, c._replace(l=cut(c.l), s=cut(c.s), y=cut(c.y))


@_f32_matmuls
@jax.named_scope("agg.admm")
def robust_pca_bucket_sharded(
    m: jnp.ndarray,
    true_dims: jnp.ndarray | None = None,
    *,
    mesh,
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
    mesh_overlap: bool = False,
    true_cols: int | None = None,
) -> RPCAResult:
    """``robust_pca_bucket`` sharded across ``mesh``'s client axes.

    Same contract as the single-device loop (fp32-allclose results, same
    carry pytree).  The two SVT modes shard different axes (DESIGN.md §10):

    - ``svt_mode="gram"`` splits the bucket's vec axis ``d1``; every shard
      holds every client.  A Gram is a sum over rows, so an ADMM iteration
      exchanges only ``(B, C, C)``: the Gram partials are reduce-scattered
      over the module axis B (each shard eighs ``B / shards`` modules, B
      zero-padded to a shard multiple) and the shrink projectors
      all-gathered back; ``L_k = X_k @ P``, the tail and the scalar norms'
      partials stay on the shard.  The bucket's one layout change, from
      ``pack``'s client columns to rows, happens once on entry.
    - ``svt_mode="subspace"`` splits the client axis ``d2``, with the
      eigenbasis rows client-sharded internally and reassembled on exit.  One client shard (``mesh_client_shards(mesh) ==
    1``, the ``(1, 1)`` debug mesh included) delegates to
    ``robust_pca_bucket`` — the single-device path stays bitwise identical.

    ``fused_tail=True`` runs the Pallas tail kernels *shard-locally*: each
    shard calls ``kernels.rpca_admm.admm_tail`` (exact-SVT iterations) or
    ``kernels.svt_subspace.subspace_apply_factored`` (Ritz iterations — the
    rank-r reconstruction ``L_k = F Vr_k^T`` fused with the elementwise
    tail, no d2^2 projector ever materialized) on its own slice, and only
    the scalar residual partials are psum-reduced afterward.  The kernels
    stay single-device; sharding only crosses in the reductions.

    Ragged buckets are accepted.  Gram mode zero-pads ``d1`` to the next
    shard multiple (zero rows add nothing to a Gram or a norm and stay zero
    through the tail).  Subspace mode zero-pads ``d2`` with zero-mask
    columns threaded through pack/psum/tail, so padded columns contribute
    exactly zero to every reduction and ``n_eff`` stays the true active
    count.  Outputs are sliced back on exit; the padding comes out zero.

    ``mesh_overlap=True`` chunks the bucket axis B so each chunk's
    collective — the ``(B, d1, r)`` sweep psum and the fused tail's
    residual psum — is dispatched independently of the other chunks'
    compute, letting the scheduler overlap chunk k's all-reduce with chunk
    k+1's tail/matmuls.  Chunking a psum along B does not change any value
    (modules reduce independently), and ``mesh_overlap=False`` runs the
    exact unchunked schedule, so the knob is bit-for-bit off by default.

    The subspace path's exact-eigh fallback still all-gathers X's client
    columns to form the full Gram; warm-carry rounds never take it.
    """
    shards = mesh_client_shards(mesh)
    if shards == 1:
        return robust_pca_bucket(
            m, true_dims, n_iter=n_iter, tol=tol, mu=mu, lam=lam,
            shrink_fn=shrink_fn, fused_tail=fused_tail, interpret=interpret,
            client_mask=client_mask, svt_mode=svt_mode, svt_rank=svt_rank,
            svt_sweeps=svt_sweeps, svt_fallback_tol=svt_fallback_tol,
            carry=carry, return_carry=return_carry, carry_gate=carry_gate,
            true_cols=true_cols,
        )
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
    has_carry = carry is not None
    if has_carry:
        if carry.l.shape != m.shape:
            raise ValueError(
                f"carry shape {carry.l.shape} does not match bucket {m.shape}"
            )
        if carry.v.shape != (b, d2, r):
            raise ValueError(
                f"carry basis shape {carry.v.shape} != {(b, d2, r)}; "
                "was the carry built with a different svt_rank?"
            )
    orig_dtype = m.dtype
    m = m.astype(jnp.float32)
    if true_dims is None:
        true_dims = jnp.full((b,), d1p, jnp.int32)
    dims_f = true_dims.astype(jnp.float32)
    if not use_subspace:
        l, s, n_done, err, *new_carry = _gram_rows_sharded(
            m, dims_f, mesh=mesh, n_iter=n_iter, tol=tol, mu=mu, lam=lam,
            shrink_fn=shrink_fn, fused_tail=fused_tail, interpret=interpret,
            client_mask=client_mask, r=r, carry=carry,
            return_carry=return_carry, carry_gate=carry_gate,
            mesh_overlap=mesh_overlap,
        )
        result = RPCAResult(l.astype(orig_dtype), s.astype(orig_dtype), n_done, err)
        return (result, *new_carry) if return_carry else result
    from jax.sharding import PartitionSpec as P

    axes = mesh_client_axes(mesh)
    ax = axes if len(axes) > 1 else axes[0]
    cmask_full = (
        jnp.ones((d2,), jnp.float32)
        if client_mask is None
        else jnp.asarray(client_mask, jnp.float32)
    )
    # Ragged cohorts: pad the client axis to the next shard multiple with
    # zero-mask columns.  The rank cap keeps the *true* d2 (carry shapes and
    # the 1-shard delegate must agree), the padded mask keeps n_eff exact,
    # and every padded column stays identically zero through the loop.
    d2p = shards * (-(-d2 // shards))
    pad_c = d2p - d2
    if pad_c:
        m = jnp.pad(m, ((0, 0), (0, 0), (0, pad_c)))
        cmask_full = jnp.pad(cmask_full, (0, pad_c))
        if has_carry:
            padc = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad_c)))
            carry = carry._replace(
                l=padc(carry.l), s=padc(carry.s), y=padc(carry.y),
                v=jnp.pad(carry.v, ((0, 0), (0, pad_c), (0, 0))),
            )
    d2_loc = d2p // shards
    if fused_tail:
        from repro.kernels import rpca_admm as _tail_kernel
        from repro.kernels import svt_subspace as _sub_kernel
        from repro.kernels.backend import resolve_interpret

        interp = resolve_interpret(interpret)

    col = P(None, None, ax)
    rep = P()
    carry_spec = BucketCarry(
        l=col, s=col, y=col, v=P(None, ax, None),
        n_live=rep, n_eff=rep, valid=rep, fall_count=rep, hit=rep,
    )

    def inner(m_k, dims_f, cmask_k, *rest):
        def gs(x):
            with jax.named_scope("agg.psum"):
                return jax.lax.psum(x, ax)

        m_k = m_k * cmask_k
        n_eff = jnp.maximum(gs(jnp.sum(cmask_k)), 1.0)
        abs_sum = gs(jnp.sum(jnp.abs(m_k), axis=(1, 2)))
        numel = dims_f * n_eff
        mu_v = jnp.where(
            abs_sum > _EPS, numel / (4.0 * jnp.maximum(abs_sum, _EPS)), 1.0
        )
        if mu is not None:
            mu_v = jnp.full((b,), mu, jnp.float32)
        lam_v = (
            jnp.full((b,), lam, jnp.float32)
            if lam is not None
            else 1.0 / jnp.sqrt(jnp.maximum(dims_f, n_eff))
        )
        rho = 1.0 / mu_v
        thresh = rho * lam_v
        m_norm = jnp.maximum(jnp.sqrt(gs(jnp.sum(m_k * m_k, axis=(1, 2)))), _EPS)
        n_eff_s = jnp.asarray(n_eff, jnp.float32)
        rho_b = rho[:, None, None]
        mu_b = mu_v[:, None, None]

        zeros = jnp.zeros_like(m_k)
        if has_carry:
            cin = rest[0]
            cl, cs, cy = cin.l * cmask_k, cin.s * cmask_k, cin.y * cmask_k
            init_res = m_k - cl - cs
            init_err = (
                jnp.sqrt(gs(jnp.sum(init_res * init_res, axis=(1, 2)))) / m_norm
            )
            warm = jnp.logical_and(
                jnp.asarray(cin.valid),
                jnp.logical_and(
                    cin.n_eff == n_eff_s, jnp.all(init_err <= carry_gate)
                ),
            )
            wsel = lambda a: jnp.where(warm, a, 0.0)
            l0, s0, y0 = wsel(cl), wsel(cs), wsel(cy)
        else:
            cin = None
            warm = jnp.asarray(False)
            l0 = s0 = y0 = zeros

        # B-chunk schedule for the overlap knob: slicing a (B, ...) psum (or
        # a kernel call) along the module axis changes no value — modules
        # reduce independently — but makes each chunk's collective a
        # separate op with no dependence on the other chunks' compute, so
        # the scheduler can fly chunk k's all-reduce while chunk k+1's
        # tail/matmuls execute.  mesh_overlap=False keeps the single
        # unchunked call (the PR 7 schedule, bit-for-bit).
        bsl = [(0, b)]
        if mesh_overlap and b > 1:
            nch = min(b, _MESH_OVERLAP_CHUNKS)
            step_b = -(-b // nch)
            bsl = [(lo, min(lo + step_b, b)) for lo in range(0, b, step_b)]

        def tail(l, y):
            s = shrink_fn(m_k - l + rho_b * y, thresh[:, None, None]) * cmask_k
            resid = (m_k - l - s) * cmask_k
            y_new = (y + mu_b * resid) * cmask_k
            return s, y_new, jnp.sqrt(gs(jnp.sum(resid * resid, axis=(1, 2))))

        if fused_tail:

            def fused_plain_tail(l, y):
                # Shard-local Pallas ADMM tail on this shard's column slice;
                # only the scalar residual partials cross shards.  Chunked
                # along B when overlapping so each chunk's psum dispatches
                # while the next chunk's kernel runs.
                outs = [
                    _tail_kernel.admm_tail(
                        m_k[lo:hi], l[lo:hi], y[lo:hi], rho[lo:hi],
                        mu_v[lo:hi], thresh[lo:hi], mask=cmask_k,
                        interpret=interp,
                    )
                    for lo, hi in bsl
                ]
                s = jnp.concatenate([o[0] for o in outs], axis=0)
                y_new = jnp.concatenate([o[1] for o in outs], axis=0)
                rsq = jnp.concatenate([gs(o[2]) for o in outs], axis=0)
                return s, y_new, jnp.sqrt(rsq)

            def fused_factored_tail(f, vr_k, y):
                # Ritz-path fused tail: L_k = F Vr_k^T rebuilt inside the
                # kernel from the replicated (B, d1, r) shrink factor and
                # this shard's basis rows, fused with shrink/dual/residual.
                outs = [
                    _sub_kernel.subspace_apply_factored(
                        m_k[lo:hi], y[lo:hi], f[lo:hi], vr_k[lo:hi],
                        rho[lo:hi], mu_v[lo:hi], thresh[lo:hi], mask=cmask_k,
                        interpret=interp,
                    )
                    for lo, hi in bsl
                ]
                l = jnp.concatenate([o[0] for o in outs], axis=0)
                s = jnp.concatenate([o[1] for o in outs], axis=0)
                y_new = jnp.concatenate([o[2] for o in outs], axis=0)
                rsq = jnp.concatenate([gs(o[3]) for o in outs], axis=0)
                return l, s, y_new, jnp.sqrt(rsq)

        @jax.named_scope("agg.svt")
        def exact_svt(x_k, t):
            # Exact fallback: the full d2 x d2 Gram needs every column, so
            # gather X once, eigh replicated, and slice the projector
            # application back to this shard's client columns/basis rows.
            with jax.named_scope("agg.psum"):
                xg = jax.lax.all_gather(x_k, ax, axis=2, tiled=True)
            g = jnp.einsum("bdc,bde->bce", xg, xg)
            w_eig, v_full = jnp.linalg.eigh(g)  # ascending
            s_ = jnp.sqrt(jnp.maximum(w_eig, 0.0))
            s_shrunk = shrink_fn(s_, t[:, None])
            coef = jnp.where(s_ > _EPS, s_shrunk / jnp.maximum(s_, _EPS), 0.0)
            xv = jnp.einsum("bdc,bck->bdk", xg, v_full)
            v_loc = jax.lax.dynamic_slice_in_dim(
                v_full, _shard_index(mesh) * d2_loc, d2_loc, axis=1
            )  # this shard's client rows of the full eigenbasis
            l_k = jnp.einsum("bdk,bk,bck->bdc", xv, coef, v_loc)
            v_top = v_loc[:, :, -r:]
            n_live = jnp.sum((s_shrunk > 0.0).astype(jnp.int32), axis=-1)
            return l_k, v_top, n_live, jnp.zeros(t.shape, jnp.float32)

        eye_r = jnp.eye(r, dtype=jnp.float32)

        def sweep_wz(x_k, v_k):
            # W = psum(X V) and Z_k = X_k^T W — the sweep's only non-tiny
            # collective plus its local consumer.  Chunked along B when
            # overlapping so chunk k+1's psum dispatches while chunk k's Z
            # matmul executes (pipelined-multicast SUMMA schedule).
            if len(bsl) == 1:
                w = gs(jnp.einsum("bdc,bcr->bdr", x_k, v_k))
                return w, jnp.einsum("bdc,bdr->bcr", x_k, w)
            ws, zs = [], []
            for lo, hi in bsl:
                wc = gs(jnp.einsum("bdc,bcr->bdr", x_k[lo:hi], v_k[lo:hi]))
                ws.append(wc)
                zs.append(jnp.einsum("bdc,bdr->bcr", x_k[lo:hi], wc))
            return jnp.concatenate(ws, axis=0), jnp.concatenate(zs, axis=0)

        @jax.named_scope("agg.svt")
        def ritz_factors(x_k, t, v_k, n_sweeps):
            # Power sweeps on local rows: W = X V is the only non-tiny
            # collective; (G V)_k = X_k^T W never leaves the shard.
            for _ in range(n_sweeps):
                w, z_k = sweep_wz(x_k, v_k)
                szz = gs(jnp.einsum("bcr,bcs->brs", z_k, z_k))
                jitter = (1e-6 / r) * (
                    jnp.trace(szz, axis1=-2, axis2=-1) + _EPS
                )[:, None, None]
                chol = jnp.linalg.cholesky(szz + jitter * eye_r)
                v_k = jax.lax.linalg.triangular_solve(
                    chol, z_k, left_side=False, lower=True, transpose_a=True
                )
            w, gv_k = sweep_wz(x_k, v_k)
            t_small = gs(jnp.einsum("bcr,bcs->brs", v_k, gv_k))
            theta, w_rot = jnp.linalg.eigh(t_small)  # ascending Ritz values
            vr_k = jnp.einsum("bcr,brs->bcs", v_k, w_rot)
            gvr_k = jnp.einsum("bcr,brs->bcs", gv_k, w_rot)
            s_ = jnp.sqrt(jnp.maximum(theta, 0.0))
            s_shrunk = shrink_fn(s_, t[:, None])
            coef = jnp.where(s_ > _EPS, s_shrunk / jnp.maximum(s_, _EPS), 0.0)
            # X Vr = W @ W_rot is already in hand and replicated: the
            # shard's L columns come from (B, d1, r) factors alone.
            xvr = jnp.einsum("bdr,brs->bds", w, w_rot)
            live = (s_shrunk > 0.0).astype(jnp.float32)
            res = (gvr_k - vr_k * theta[:, None, :]) * live[:, None, :]
            g_mass = jnp.sum(jnp.maximum(theta, 0.0), axis=-1)
            rel = jnp.sqrt(gs(jnp.sum(res * res, axis=(1, 2)))) / jnp.maximum(
                g_mass, _EPS
            )
            n_live = jnp.sum(live.astype(jnp.int32), axis=-1)
            return xvr, coef, vr_k, n_live, rel

        def ritz_svt(x_k, t, v_k, n_sweeps):
            xvr, coef, vr_k, n_live, rel = ritz_factors(x_k, t, v_k, n_sweeps)
            # L_k = (X Vr) coef Vr_k^T — same contraction as before the
            # factored split, so the unfused path is numerically unchanged.
            l_k = jnp.einsum("bds,bs,bcs->bdc", xvr, coef, vr_k)
            return l_k, vr_k, n_live, rel

        @jax.named_scope("agg.svt")
        def svt_step(x_k, v_k, n_live, rel_prev, cold):
            t = rho

            def exact():
                l_k, v2, live, rel = exact_svt(x_k, t)
                return l_k, v2, live, rel, jnp.asarray(True)

            def attempt():
                if svt_sweeps > 1:
                    l_k, v2, live, rel = jax.lax.cond(
                        jnp.max(rel_prev) <= 0.1 * svt_fallback_tol,
                        lambda: ritz_svt(x_k, t, v_k, 1),
                        lambda: ritz_svt(x_k, t, v_k, svt_sweeps),
                    )
                else:
                    l_k, v2, live, rel = ritz_svt(x_k, t, v_k, max(svt_sweeps, 1))
                bad = jnp.logical_or(
                    jnp.any(rel > svt_fallback_tol), jnp.any(live >= r)
                )
                return jax.lax.cond(
                    bad, exact, lambda: (l_k, v2, live, rel, jnp.asarray(False))
                )

            # All gate predicates derive from psum-reduced or replicated
            # values, so every shard takes the same branch and the
            # collectives inside the branches line up.
            pre_full = jnp.logical_or(cold, jnp.any(n_live >= r))
            l_k, v2, live2, rel2, fell = jax.lax.cond(pre_full, exact, attempt)
            rel2 = jnp.where(fell, 0.5 * svt_fallback_tol, rel2)
            return l_k, v2, live2, rel2, fell

        def svt_step_fused(x_k, y, v_k, n_live, rel_prev, cold):
            # The fused twin of svt_step: the elementwise tail moves inside
            # each gate branch so the Ritz path can hand its rank-r factors
            # straight to the factored Pallas kernel (no d2^2 projector) and
            # the exact path reuses the plain ADMM-tail kernel on the
            # gathered reconstruction.  Gates stay psum-derived.
            t = rho

            def exact():
                l_k, v2, live, rel = exact_svt(x_k, t)
                s2, y2, rnorm = fused_plain_tail(l_k, y)
                return l_k, s2, y2, rnorm, v2, live, rel, jnp.asarray(True)

            def attempt():
                if svt_sweeps > 1:
                    xvr, coef, vr_k, live, rel = jax.lax.cond(
                        jnp.max(rel_prev) <= 0.1 * svt_fallback_tol,
                        lambda: ritz_factors(x_k, t, v_k, 1),
                        lambda: ritz_factors(x_k, t, v_k, svt_sweeps),
                    )
                else:
                    xvr, coef, vr_k, live, rel = ritz_factors(
                        x_k, t, v_k, max(svt_sweeps, 1)
                    )
                bad = jnp.logical_or(
                    jnp.any(rel > svt_fallback_tol), jnp.any(live >= r)
                )

                def ok():
                    f = xvr * coef[:, None, :]
                    l_k, s2, y2, rnorm = fused_factored_tail(f, vr_k, y)
                    return l_k, s2, y2, rnorm, vr_k, live, rel, jnp.asarray(False)

                return jax.lax.cond(bad, exact, ok)

            pre_full = jnp.logical_or(cold, jnp.any(n_live >= r))
            l_k, s2, y2, rnorm, v2, live2, rel2, fell = jax.lax.cond(
                pre_full, exact, attempt
            )
            rel2 = jnp.where(fell, 0.5 * svt_fallback_tol, rel2)
            return l_k, s2, y2, rnorm, v2, live2, rel2, fell

        err0 = jnp.full((b,), jnp.inf, jnp.float32)
        falls0 = jnp.zeros((), jnp.int32)
        eye_loc = jax.lax.dynamic_slice_in_dim(
            jnp.broadcast_to(jnp.eye(d2p, r, dtype=jnp.float32), (b, d2p, r)),
            _shard_index(mesh) * d2_loc, d2_loc, axis=1,
        )
        if has_carry:
            v0 = jnp.where(warm, cin.v, eye_loc)
            nl0 = jnp.where(warm, cin.n_live, jnp.full((b,), r, jnp.int32))
            rel0 = jnp.where(
                warm,
                jnp.full((b,), 0.5 * svt_fallback_tol, jnp.float32),
                jnp.full((b,), jnp.inf, jnp.float32),
            )
        else:
            v0 = eye_loc
            nl0 = jnp.full((b,), r, jnp.int32)
            rel0 = jnp.full((b,), jnp.inf, jnp.float32)

        def step_sub(l, s, y, v_k, n_live, rel, it):
            x_k = m_k - s + rho_b * y
            cold = jnp.logical_and(it == 0, jnp.logical_not(warm))
            if fused_tail:
                l2, s2, y2, rnorm, v2, live2, rel2, fell = svt_step_fused(
                    x_k, y, v_k, n_live, rel, cold
                )
            else:
                l2, v2, live2, rel2, fell = svt_step(x_k, v_k, n_live, rel, cold)
                s2, y2, rnorm = tail(l2, y)
            return l2, s2, y2, rnorm / m_norm, v2, live2, rel2, fell

        if tol is None:

            def body_sub(it, state):
                l, s, y, _err, v_k, nl, rl, fc = state
                l2, s2, y2, err2, v2, nl2, rl2, fell = step_sub(
                    l, s, y, v_k, nl, rl, it
                )
                return (l2, s2, y2, err2, v2, nl2, rl2, fc + fell.astype(jnp.int32))

            l, s, y, err, v_f, nl_f, _, falls = jax.lax.fori_loop(
                0, n_iter, body_sub, (l0, s0, y0, err0, v0, nl0, rel0, falls0)
            )
            n_done = jnp.full((b,), n_iter, jnp.int32)
        else:

            def cond_sub(state):
                return jnp.logical_and(state[4] < n_iter, jnp.any(state[3] > tol))

            def body_sub(state):
                l, s, y, err, i, niter, v_k, nl, rl, fc = state
                l2, s2, y2, err2, v2, nl2, rl2, fell = step_sub(
                    l, s, y, v_k, nl, rl, i
                )
                active = err > tol
                sel = lambda new, old: jnp.where(active[:, None, None], new, old)
                selv = lambda new, old: jnp.where(active, new, old)
                return (
                    sel(l2, l), sel(s2, s), sel(y2, y), selv(err2, err),
                    i + 1, jnp.where(active, i + 1, niter),
                    sel(v2, v_k), selv(nl2, nl), selv(rl2, rl),
                    fc + fell.astype(jnp.int32),
                )

            init = (
                l0, s0, y0, err0, jnp.asarray(0, jnp.int32),
                jnp.zeros((b,), jnp.int32), v0, nl0, rel0, falls0,
            )
            l, s, y, err, _, n_done, v_f, nl_f, _, falls = jax.lax.while_loop(
                cond_sub, body_sub, init
            )

        l = l * cmask_k
        outs = (l, s, n_done, err)
        if not return_carry:
            return outs
        new_carry = BucketCarry(
            l=l, s=s, y=y, v=v_f, n_live=nl_f, n_eff=n_eff_s,
            valid=jnp.ones((), bool), fall_count=falls,
            hit=warm.astype(jnp.float32),
        )
        return outs + (new_carry,)

    in_specs = [col, rep, P(ax)]
    args = [m, dims_f, cmask_full]
    if has_carry:
        in_specs.append(carry_spec)
        args.append(carry)
    out_specs = (col, col, rep, rep)
    if return_carry:
        out_specs = out_specs + (carry_spec,)
    mapped = jax.shard_map(
        inner, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False,
    )
    out = mapped(*args)
    l, s, n_done, err = out[:4]
    if pad_c:
        # Drop the ragged padding columns (exactly zero on output: every
        # padded column carries a zero mask through tail and final mask).
        l, s = l[:, :, :d2], s[:, :, :d2]
    result = RPCAResult(l.astype(orig_dtype), s.astype(orig_dtype), n_done, err)
    if not return_carry:
        return result
    new_carry = out[4]
    if pad_c:
        new_carry = new_carry._replace(
            l=new_carry.l[:, :, :d2], s=new_carry.s[:, :, :d2],
            y=new_carry.y[:, :, :d2], v=new_carry.v[:, :d2, :],
        )
    return result, new_carry
