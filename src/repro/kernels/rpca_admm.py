"""Pallas TPU kernel: fused RPCA ADMM elementwise tail (one VMEM pass).

One ADMM/PCP iteration is ``L <- SVT`` (matmul/eigh — stays in jnp via
``svt_gram``, it wants the MXU) followed by an elementwise tail of ~10 ops
that the per-op path round-trips through HBM five times:

    S     <- shrink(M - L + rho * Y, rho * lam)
    resid  = M - L - S
    Y     <- Y + mu * resid
    err    = sum(resid^2)            (per-module partial sums)

This kernel fuses the whole tail: each (1, block_vec, n_clients) tile of
M/L/Y is read once, S and the new Y are written once, and the blockwise
residual partial sums accumulate into a per-module (B,) SMEM output across
the inner grid dimension (TPU grids execute sequentially, so revisiting the
same output is the standard accumulation pattern).  Per-module scalars
(rho, mu, threshold = rho * lam) ride along as whole (B,) SMEM arrays read
at ``program_id(0)`` — the bucket mixes modules with different true vec
dims, so every module carries its own ADMM constants.  (A (1, 1) VMEM block
over a (B, 1) array is refused by the TPU lowering: the last two block dims
must be (8, 128)-divisible or span the array.)  See DESIGN.md §4 for the
memory plan.

The kernel is single-device by construction, which is exactly what the
mesh-sharded loop (DESIGN.md §10) needs: each shard calls ``admm_tail`` on
its own (B, vec, d2_loc) column slice with ``mask`` set to the shard's
slice of the cohort validity mask (ragged cohorts pad with zero-mask
columns, which contribute nothing to any sum), and the returned per-shard
``resid_sumsq`` partials are psum-reduced by the caller before the
convergence check — the elementwise tail never crosses shards.
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


def _kernel(rho_ref, mu_ref, th_ref, mask_ref, m_ref, l_ref, y_ref, s_ref, yo_ref, r_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    rho = rho_ref[i]
    mu = mu_ref[i]
    th = th_ref[i]
    msk = mask_ref[...]  # (1, 1, nc) client validity mask; all-ones when dense
    m = m_ref[...]
    l = l_ref[...]
    y = y_ref[...]
    z = m - l + rho * y
    s = (jnp.sign(z) * jnp.maximum(jnp.abs(z) - th, 0.0)) * msk
    resid = (m - l - s) * msk
    s_ref[...] = s
    yo_ref[...] = (y + mu * resid) * msk
    part = jnp.sum(jnp.square(resid.astype(jnp.float32)))

    @pl.when(j == 0)
    def _init():
        r_ref[i] = part

    @pl.when(j > 0)
    def _acc():
        r_ref[i] += part


@functools.partial(jax.jit, static_argnames=("block_vec", "interpret"))
def admm_tail(
    m: jnp.ndarray,
    l: jnp.ndarray,
    y: jnp.ndarray,
    rho: jnp.ndarray,
    mu: jnp.ndarray,
    thresh: jnp.ndarray,
    *,
    mask: Optional[jnp.ndarray] = None,
    block_vec: int = DEFAULT_BLOCK_VEC,
    interpret: Optional[bool] = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused ADMM tail over a shape bucket.

    Args:
      m, l, y: (B, vec_dim, n_clients) float arrays (zero rows in the padded
        vec region stay exactly zero through the tail).
      rho, mu, thresh: per-module (B,) scalars; ``thresh = rho * lam``.
      mask: optional (n_clients,) client validity mask for shape-static
        partial participation.  Masked (zero) columns of S and the new Y are
        forced to exactly zero and excluded from the blockwise residual
        partial sums, so padded cohort slots never contribute — even when
        the SVT step leaked tiny nonzeros into them (DESIGN.md §5).  ``None``
        is equivalent to all-ones (multiplying by 1.0 is exact, so the dense
        path is bit-identical).
      block_vec: tile size along the vec dimension.
      interpret: Pallas interpret mode; None autodetects (interpret off-TPU,
        compiled on TPU — same policy as the ops.py wrappers).

    Returns:
      (S, Y_new, resid_sumsq) with resid_sumsq a (B,) float32 array of
      ``sum((M - L - S)^2)`` per module (active columns only when masked).
    """
    interpret = backend.resolve_interpret(interpret)
    if m.ndim != 3:
        raise ValueError(f"expected (B, vec, clients) input, got {m.shape}")
    if m.shape != l.shape or m.shape != y.shape:
        raise ValueError(f"shape mismatch: {m.shape} {l.shape} {y.shape}")
    b, d1, nc = m.shape
    bv = min(block_vec, max(d1, 1))
    pad_v = (-d1) % bv
    if pad_v:
        padder = lambda t: jnp.pad(t, ((0, 0), (0, pad_v), (0, 0)))
        m, l, y = padder(m), padder(l), padder(y)
    grid = (b, m.shape[1] // bv)
    scal = lambda v: jnp.asarray(v, jnp.float32).reshape(b)
    mvec = jnp.ones((nc,), jnp.float32) if mask is None else jnp.asarray(mask, jnp.float32)
    mvec = mvec.reshape(1, 1, nc)
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole (B,) array
    mspec = pl.BlockSpec((1, 1, nc), lambda i, j: (0, 0, 0))
    tspec = pl.BlockSpec((1, bv, nc), lambda i, j: (i, j, 0))
    s, y_new, rsq = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[sspec, sspec, sspec, mspec, tspec, tspec, tspec],
        out_specs=[tspec, tspec, sspec],
        out_shape=[
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct((b,), jnp.float32),
        ],
        interpret=interpret,
    )(scal(rho), scal(mu), scal(thresh), mvec, m, l, y)
    if pad_v:
        s, y_new = s[:, :d1, :], y_new[:, :d1, :]
    return s, y_new, rsq
