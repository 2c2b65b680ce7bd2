"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def soft_threshold_ref(x: jnp.ndarray, t) -> jnp.ndarray:
    """RPCA shrinkage: sign(x) * max(|x| - t, 0)."""
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def rpca_admm_tail_ref(
    m: jnp.ndarray,  # (B, vec, clients)
    l: jnp.ndarray,
    y: jnp.ndarray,
    rho: jnp.ndarray,  # (B,) per-module scalars
    mu: jnp.ndarray,
    thresh: jnp.ndarray,
    mask=None,  # optional (clients,) validity mask
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused ADMM tail: S update, dual ascent, per-module residual sumsq.

    ``mask`` zeroes inactive client columns of S / new-Y and excludes them
    from the residual sums (shape-static partial participation); ``None``
    behaves as all-ones.
    """
    rho_ = rho[:, None, None].astype(m.dtype)
    mu_ = mu[:, None, None].astype(m.dtype)
    th_ = thresh[:, None, None].astype(m.dtype)
    msk = 1.0 if mask is None else jnp.asarray(mask, m.dtype)[None, None, :]
    s = soft_threshold_ref(m - l + rho_ * y, th_) * msk
    resid = (m - l - s) * msk
    y_new = (y + mu_ * resid) * msk
    rsq = jnp.sum(jnp.square(resid.astype(jnp.float32)), axis=(1, 2))
    return s, y_new, rsq


def svt_subspace_apply_ref(
    m: jnp.ndarray,  # (B, vec, clients)
    s: jnp.ndarray,
    y: jnp.ndarray,
    p: jnp.ndarray,  # (B, clients, clients) shrink projector
    rho: jnp.ndarray,  # (B,) per-module scalars
    mu: jnp.ndarray,
    thresh: jnp.ndarray,
    mask=None,  # optional (clients,) validity mask
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused subspace-SVT sweep tail: reconstruction L = (M - S + rho Y) @ P,
    shrink, dual ascent, per-module residual sumsq, and the next iterate's
    Gram matrix (what the warm-start carry threads forward).

    ``mask`` zeroes inactive client columns of S'/Y' and excludes them from
    the residual sums; L is left unmasked (the bucket driver applies the
    single final mask pass), and M's masked columns are zero on entry so
    the Gram of the next iterate never sees masked slots.
    """
    rho_ = rho[:, None, None].astype(m.dtype)
    mu_ = mu[:, None, None].astype(m.dtype)
    th_ = thresh[:, None, None].astype(m.dtype)
    msk = 1.0 if mask is None else jnp.asarray(mask, m.dtype)[None, None, :]
    x = m - s + rho_ * y
    low = jnp.einsum("bdc,bce->bde", x.astype(jnp.float32), p.astype(jnp.float32))
    low = low.astype(m.dtype)
    s_new = soft_threshold_ref(m - low + rho_ * y, th_) * msk
    resid = (m - low - s_new) * msk
    y_new = (y + mu_ * resid) * msk
    rsq = jnp.sum(jnp.square(resid.astype(jnp.float32)), axis=(1, 2))
    x_next = (m - s_new + rho_ * y_new).astype(jnp.float32)
    g_next = jnp.einsum("bdc,bde->bce", x_next, x_next)
    return low, s_new, y_new, rsq, g_next


def lora_matmul_ref(
    x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, scale: float
) -> jnp.ndarray:
    """y = x @ w + scale * (x @ a) @ b   (fused base + LoRA projection)."""
    return x @ w + scale * (x @ a) @ b


def gathered_lora_matmul_ref(
    x: jnp.ndarray,  # (M, K)
    w: jnp.ndarray,  # (K, N)
    a_pool: jnp.ndarray,  # (n_slots, K, R)
    b_pool: jnp.ndarray,  # (n_slots, R, N)
    row_slot: jnp.ndarray,  # (M,) int32; -1 = no adapter (base only)
    scale: float = 1.0,
) -> jnp.ndarray:
    """Grouped-by-adapter oracle: every slot's full-batch LoRA product,
    masked to the rows that own it.  O(n_slots) dense matmuls — slow, but
    independent of the segment layout, and bitwise-comparable in fp32
    because XLA's matmul rows are tiling-stable."""
    m = x.shape[0]
    n = w.shape[1]
    lora = jnp.zeros((m, n), jnp.float32)
    for s in range(a_pool.shape[0]):
        sel = (row_slot == s)[:, None]
        term = (x @ a_pool[s]) @ b_pool[s]
        lora = lora + jnp.where(sel, term.astype(jnp.float32), 0.0)
    base = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return (base + scale * lora).astype(x.dtype)


def local_attention_ref(
    q: jnp.ndarray,  # (BH, S, D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    window: int,
    causal: bool = True,
) -> jnp.ndarray:
    """Sliding-window causal attention, materialized scores."""
    s = q.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_scan_ref(
    x: jnp.ndarray,  # (BH, S, P)   dt-premultiplied input per head
    da: jnp.ndarray,  # (BH, S)      log-decay increments (dt * A, negative)
    b: jnp.ndarray,  # (BH, S, N)
    c: jnp.ndarray,  # (BH, S, N)
    chunk: int,
) -> jnp.ndarray:
    """Chunked SSD core: y_t = sum_{j<=t} C_t . B_j exp(sum_{j<k<=t} da_k) x_j.

    Sequential-scan reference (exact); the Pallas kernel and the model's
    associative-scan implementation must both match this.
    """
    bh, s, p = x.shape
    n = b.shape[-1]

    def step(h, inp):
        x_t, da_t, b_t, c_t = inp
        h = jnp.exp(da_t)[:, None, None] * h + jnp.einsum("bn,bp->bnp", b_t, x_t)
        y_t = jnp.einsum("bn,bnp->bp", c_t, h)
        return h, y_t

    h0 = jnp.zeros((bh, n, p), jnp.float32)
    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(da, 1, 0).astype(jnp.float32),
        jnp.moveaxis(b, 1, 0).astype(jnp.float32),
        jnp.moveaxis(c, 1, 0).astype(jnp.float32),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    del chunk  # reference is chunk-free (exact)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)
