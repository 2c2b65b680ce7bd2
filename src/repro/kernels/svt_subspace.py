"""Pallas TPU kernel: fused subspace-SVT sweep tail (one VMEM pass).

In subspace SVT mode (DESIGN.md §6) one ADMM iteration factors into

  (a) the small-matrix algebra: power sweeps, thin QR, the r x r
      Rayleigh-Ritz eigh and the shrink of the Ritz values, which yield a
      (d2 x d2) *shrink projector* P = Vr diag(shrink(s)/s) Vr^T — all
      O(d2^2 r) work that stays in jnp (the MXU-trivial part), and
  (b) the sweep tail over the tall (B, d1, d2) bucket tensors:

          X      = M - S + rho * Y          (reconstruction input)
          L      = X @ P                    (SVT reconstruction)
          S'     = shrink(M - L + rho * Y, rho * lam)
          resid  = M - L - S'
          Y'     = Y + mu * resid
          err    = sum(resid^2)             (per-module partial sums)
          G'     = X'^T X',  X' = M - S' + rho * Y'   (next iteration's Gram)

This kernel fuses all of (b): each (1, block_vec, d2) tile of M/S/Y is read
once, L/S'/Y' tiles are written once, and *two* accumulators ride across the
inner grid dimension — the per-module residual partial sums ``(B,)`` and
the next iteration's Gram matrix ``(B, d2, d2)`` (TPU grids execute the
inner dimension sequentially, so revisiting the same output block is the
standard accumulation pattern).  Folding the Gram accumulation in removes
the separate full pass over X' that the unfused path pays, so the only
per-iteration work outside this kernel is the O(d2^2 r) basis algebra.

Per-module scalars (rho, mu, thresh) and the residual sums live in SMEM as
whole (B,) arrays indexed by ``program_id(0)``; the optional client
validity mask rides as one VMEM-resident (1, 1, d2) block exactly as
in ``kernels/rpca_admm`` — S'/Y'/resid are masked in-register so padded
cohort slots stay exactly zero, and M's masked columns are zero on entry so
the Gram accumulator never sees them.  L is deliberately *not* masked here
(parity with the jnp path; ``robust_pca_bucket`` applies the single final
mask pass).  The jnp oracle is ``kernels/ref.py::svt_subspace_apply_ref``.

On a mesh (DESIGN.md §10) the kernel runs shard-locally on the shard's vec
rows of every client, with the full projector: its residual sums and Gram
are the shard's partials, which the loop reduces across shards.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

DEFAULT_BLOCK_VEC = 512


def _kernel(
    rho_ref, mu_ref, th_ref, mask_ref, p_ref, m_ref, s_ref, y_ref,
    l_ref, so_ref, yo_ref, r_ref, g_ref,
):
    i, j = pl.program_id(0), pl.program_id(1)
    rho = rho_ref[i]
    mu = mu_ref[i]
    th = th_ref[i]
    msk = mask_ref[0]  # (1, d2) client validity; all-ones when dense
    p = p_ref[0]  # (d2, d2) shrink projector
    m = m_ref[0]  # (block_vec, d2)
    s = s_ref[0]
    y = y_ref[0]
    x = m - s + rho * y
    l = jnp.dot(x, p, preferred_element_type=jnp.float32).astype(m.dtype)
    z = m - l + rho * y
    s_new = (jnp.sign(z) * jnp.maximum(jnp.abs(z) - th, 0.0)) * msk
    resid = (m - l - s_new) * msk
    y_new = (y + mu * resid) * msk
    l_ref[0] = l
    so_ref[0] = s_new
    yo_ref[0] = y_new
    x_next = (m - s_new + rho * y_new).astype(jnp.float32)
    g_part = jnp.dot(x_next.T, x_next, preferred_element_type=jnp.float32)
    r_part = jnp.sum(jnp.square(resid.astype(jnp.float32)))

    @pl.when(j == 0)
    def _init():
        r_ref[i] = r_part
        g_ref[0] = g_part

    @pl.when(j > 0)
    def _acc():
        r_ref[i] += r_part
        g_ref[0] += g_part


@functools.partial(jax.jit, static_argnames=("block_vec", "interpret"))
def subspace_apply(
    m: jnp.ndarray,
    s: jnp.ndarray,
    y: jnp.ndarray,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    mu: jnp.ndarray,
    thresh: jnp.ndarray,
    *,
    mask: Optional[jnp.ndarray] = None,
    block_vec: int = DEFAULT_BLOCK_VEC,
    interpret: Optional[bool] = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused subspace-SVT ADMM iteration tail over a shape bucket.

    Args:
      m, s, y: (B, vec_dim, d2) current iterate (zero-padded rows stay
        exactly zero through the whole tail).
      p: (B, d2, d2) per-module shrink projector from
        ``rpca.svt_subspace_step`` (exact-eigh or Rayleigh-Ritz path).
      rho, mu, thresh: per-module (B,) ADMM scalars; ``thresh = rho * lam``.
      mask: optional (d2,) client validity mask — masked columns of S'/Y'
        are forced to exactly zero and excluded from the residual sums;
        ``None`` multiplies by 1.0 (bit-identical dense path).
      block_vec: tile size along the vec dimension.
      interpret: Pallas interpret mode; None autodetects per platform.

    Returns:
      (L, S', Y', resid_sumsq, G') with resid_sumsq a (B,) float32 array
      and G' the (B, d2, d2) float32 Gram of the *next* iterate
      ``M - S' + rho Y'`` (what ``SubspaceState.g`` carries forward).
    """
    interpret = backend.resolve_interpret(interpret)
    if m.ndim != 3:
        raise ValueError(f"expected (B, vec, clients) input, got {m.shape}")
    if m.shape != s.shape or m.shape != y.shape:
        raise ValueError(f"shape mismatch: {m.shape} {s.shape} {y.shape}")
    b, d1, d2 = m.shape
    if p.shape != (b, d2, d2):
        raise ValueError(f"projector shape {p.shape} != {(b, d2, d2)}")
    bv = min(block_vec, max(d1, 1))
    pad_v = (-d1) % bv
    if pad_v:
        padder = lambda t: jnp.pad(t, ((0, 0), (0, pad_v), (0, 0)))
        m, s, y = padder(m), padder(s), padder(y)
    grid = (b, m.shape[1] // bv)
    scal = lambda v: jnp.asarray(v, jnp.float32).reshape(b)
    mvec = jnp.ones((d2,), jnp.float32) if mask is None else jnp.asarray(mask, jnp.float32)
    mvec = mvec.reshape(1, 1, d2)
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole (B,) array
    mspec = pl.BlockSpec((1, 1, d2), lambda i, j: (0, 0, 0))
    pspec = pl.BlockSpec((1, d2, d2), lambda i, j: (i, 0, 0))
    tspec = pl.BlockSpec((1, bv, d2), lambda i, j: (i, j, 0))
    l, s_new, y_new, rsq, g_next = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[sspec, sspec, sspec, mspec, pspec, tspec, tspec, tspec],
        out_specs=[tspec, tspec, tspec, sspec, pspec],
        out_shape=[
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct((b,), jnp.float32),
            jax.ShapeDtypeStruct((b, d2, d2), jnp.float32),
        ],
        interpret=interpret,
    )(scal(rho), scal(mu), scal(thresh), mvec, p.astype(jnp.float32), m, s, y)
    if pad_v:
        l, s_new, y_new = l[:, :d1, :], s_new[:, :d1, :], y_new[:, :d1, :]
    return l, s_new, y_new, rsq, g_next
