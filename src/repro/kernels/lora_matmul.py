"""Pallas TPU kernels: fused base + LoRA projection  y = xW + s*(xA)B.

Two variants share the accumulation scheme:

``lora_matmul``
    Single-adapter serving/local-training hot path.  Unfused, the (x A)
    intermediate round-trips HBM; fused, both accumulators live in VMEM
    across the K loop and the rank-R correction is applied on the final K
    step — one HBM pass over x and W.

``gathered_lora_matmul``
    Multi-tenant serving path (Punica/S-LoRA-style SGMV).  Adapters live in
    a padded pool ``(n_slots, K, R)`` / ``(n_slots, R, N)`` and every row of
    the batch names its adapter slot.  Rows are sorted by slot and padded so
    each M-tile is single-adapter; a scalar-prefetch tile→slot map then
    drives the A/B block gather *inside* the kernel (``PrefetchScalarGridSpec``
    index maps), so a mixed-tenant batch runs in one ``pallas_call`` with no
    per-request adapter materialization.  ``gathered_lora_matmul_xla`` is the
    same segment layout lowered to plain XLA (tile-level ``jnp.take`` + two
    batched GEMMs) — the fast path on CPU hosts and the shape used by the
    grouped oracle tests.

Grid (M/bm, N/bn, K/bk), K innermost (sequential accumulation semantics).
Block sizes default to MXU-aligned (128, 128, 512); the LoRA rank dimension
is zero-padded to the 128 lane width by the wrapper (real rank <= 64, and the
pad multiplies away as A/B pads are zero).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend


def _kernel(x_ref, w_ref, a_ref, b_ref, s_ref, o_ref, acc_ref, accr_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        accr_ref[...] = jnp.zeros_like(accr_ref)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    accr_ref[...] += jnp.dot(x, a_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        scale = s_ref[0, 0]
        lora = jnp.dot(
            accr_ref[...].astype(b_ref.dtype), b_ref[...],
            preferred_element_type=jnp.float32,
        )
        o_ref[...] = (acc_ref[...] + scale * lora).astype(o_ref.dtype)


def _rank_pad(r: int) -> int:
    return max(128 - r, 0) if r < 128 else (-r) % 128


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def lora_matmul(
    x: jnp.ndarray,  # (M, K)
    w: jnp.ndarray,  # (K, N)
    a: jnp.ndarray,  # (K, R)
    b: jnp.ndarray,  # (R, N)
    scale: float = 1.0,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    interpret = backend.resolve_interpret(interpret)
    m, kdim = x.shape
    _, n = w.shape
    r = a.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-kdim) % bk
    r_pad = _rank_pad(r)

    xp = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    wp = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    ap = jnp.pad(a, ((0, pad_k), (0, r_pad)))
    bp = jnp.pad(b, ((0, r_pad), (0, pad_n)))
    rp = r + r_pad
    mp, np_, kp = m + pad_m, n + pad_n, kdim + pad_k
    nk = kp // bk
    s_arr = jnp.full((1, 1), scale, jnp.float32)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, rp), lambda i, j, k: (k, 0)),
            pl.BlockSpec((rp, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, rp), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp, ap, bp, s_arr)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Gathered multi-adapter variant (paged pool + per-row slot indices)
# ---------------------------------------------------------------------------


def segment_layout(
    row_slot: jnp.ndarray,  # (M,) int32 slot per row, already >= 0
    n_slots: int,
    *,
    block_m: int,
    max_segments: Optional[int] = None,
):
    """Sorted/padded segment layout so every ``block_m`` row-tile is
    single-adapter.

    Rows are stably sorted by slot; each slot's run is padded up to a
    ``block_m`` multiple so tiles never straddle two adapters.  The padded
    length is *static*: worst case every non-empty segment wastes
    ``block_m - 1`` rows, and there are at most ``min(n_slots,
    max_segments or M)`` non-empty segments.  Serving passes
    ``max_segments = n_requests`` (each request contributes one slot), which
    keeps the bound tight when the pool is much larger than the batch.

    Returns ``(order, pos, tile_slot, m_pad)``:
      order:     (M,) argsort of ``row_slot`` (gather ``x[order]`` to sort),
      pos:       (M,) destination row of each *sorted* row in the padded
                 layout (scatter to ``(m_pad, K)``; inverse-gather to unsort),
      tile_slot: (m_pad // block_m,) adapter slot of each tile (the scalar-
                 prefetch operand of the Pallas kernel),
      m_pad:     static padded row count (``n_tiles * block_m``).
    """
    (m,) = row_slot.shape
    n_seg = min(n_slots, m if max_segments is None else max_segments)
    n_tiles = (m + n_seg * (block_m - 1) + block_m - 1) // block_m
    m_pad = n_tiles * block_m
    order = jnp.argsort(row_slot)
    sorted_slot = jnp.take(row_slot, order)
    counts = jnp.bincount(row_slot, length=n_slots)
    padded = ((counts + block_m - 1) // block_m) * block_m
    seg_start = jnp.cumsum(padded) - padded
    csum_excl = jnp.cumsum(counts) - counts
    pos = (
        jnp.take(seg_start, sorted_slot)
        + jnp.arange(m)
        - jnp.take(csum_excl, sorted_slot)
    )
    boundaries = jnp.cumsum(padded)
    tile_slot = jnp.searchsorted(boundaries, jnp.arange(n_tiles) * block_m, side="right")
    tile_slot = jnp.minimum(tile_slot, n_slots - 1).astype(jnp.int32)
    return order, pos, tile_slot, m_pad


def _with_null_slot(a_pool, b_pool, row_slot):
    """Map masked rows (slot < 0) to an appended all-zero adapter slot so
    they receive the base projection only."""
    ap = jnp.concatenate([a_pool, jnp.zeros_like(a_pool[:1])], axis=0)
    bp = jnp.concatenate([b_pool, jnp.zeros_like(b_pool[:1])], axis=0)
    slot = jnp.where(row_slot < 0, a_pool.shape[0], row_slot).astype(jnp.int32)
    return ap, bp, slot


def _gathered_kernel(
    slot_ref, x_ref, w_ref, a_ref, b_ref, s_ref, o_ref, acc_ref, accr_ref, *, nk: int
):
    del slot_ref  # consumed by the BlockSpec index maps
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        accr_ref[...] = jnp.zeros_like(accr_ref)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    accr_ref[...] += jnp.dot(x, a_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        scale = s_ref[0, 0]
        lora = jnp.dot(
            accr_ref[...].astype(b_ref.dtype), b_ref[0],
            preferred_element_type=jnp.float32,
        )
        o_ref[...] = (acc_ref[...] + scale * lora).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "max_segments", "interpret"),
)
def gathered_lora_matmul(
    x: jnp.ndarray,  # (M, K)
    w: jnp.ndarray,  # (K, N) shared base projection
    a_pool: jnp.ndarray,  # (n_slots, K, R)
    b_pool: jnp.ndarray,  # (n_slots, R, N)
    row_slot: jnp.ndarray,  # (M,) int32; -1 = no adapter (base only)
    scale: float = 1.0,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    max_segments: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """One ``pallas_call`` for a mixed-tenant batch.

    The tile→slot map rides in as a scalar-prefetch operand; the A/B
    BlockSpec index maps read it to gather each tile's adapter block
    directly from the pool — no ``(M, K, R)`` materialization ever exists.
    """
    interpret = backend.resolve_interpret(interpret)
    m, kdim = x.shape
    _, n = w.shape
    n_slots, _, r = a_pool.shape
    ap, bp, slot = _with_null_slot(a_pool, b_pool, row_slot)

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    order, pos, tile_slot, m_pad = segment_layout(
        slot, n_slots + 1, block_m=bm, max_segments=max_segments
    )
    xs = jnp.zeros((m_pad, kdim), x.dtype).at[pos].set(jnp.take(x, order, axis=0))

    pad_n, pad_k = (-n) % bn, (-kdim) % bk
    r_pad = _rank_pad(r)
    xp = jnp.pad(xs, ((0, 0), (0, pad_k)))
    wp = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    app = jnp.pad(ap, ((0, 0), (0, pad_k), (0, r_pad)))
    bpp = jnp.pad(bp, ((0, 0), (0, r_pad), (0, pad_n)))
    rp = r + r_pad
    np_, kp = n + pad_n, kdim + pad_k
    nk = kp // bk
    n_tiles = m_pad // bm
    s_arr = jnp.full((1, 1), scale, jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, s_ref: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, s_ref: (k, j)),
            pl.BlockSpec((1, bk, rp), lambda i, j, k, s_ref: (s_ref[i], k, 0)),
            pl.BlockSpec((1, rp, bn), lambda i, j, k, s_ref: (s_ref[i], 0, j)),
            pl.BlockSpec((1, 1), lambda i, j, k, s_ref: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, s_ref: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, rp), jnp.float32),
        ],
    )
    out_sorted = pl.pallas_call(
        functools.partial(_gathered_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, np_), x.dtype),
        interpret=interpret,
    )(tile_slot, xp, wp, app, bpp, s_arr)
    out = jnp.zeros((m, n), x.dtype).at[order].set(
        jnp.take(out_sorted[:, :n], pos, axis=0)
    )
    return out


@functools.partial(jax.jit, static_argnames=("block_m", "max_segments"))
def gathered_lora_matmul_xla(
    x: jnp.ndarray,  # (M, K)
    w: jnp.ndarray,  # (K, N)
    a_pool: jnp.ndarray,  # (n_slots, K, R)
    b_pool: jnp.ndarray,  # (n_slots, R, N)
    row_slot: jnp.ndarray,  # (M,) int32; -1 = no adapter
    scale: float = 1.0,
    *,
    block_m: int = 16,
    max_segments: Optional[int] = None,
) -> jnp.ndarray:
    """Grouped XLA lowering of the same segment layout (CPU fast path).

    Adapters are gathered once per *tile* (``m_pad / block_m`` copies, a
    factor ``block_m`` less HBM traffic than per-row materialization) and
    the LoRA correction runs as two batched GEMMs with real matrix shapes —
    measured 1.2–2.3x over per-request gather at batch >= 16 on CPU.
    """
    m, kdim = x.shape
    n = w.shape[1]
    n_slots = a_pool.shape[0]
    ap, bp, slot = _with_null_slot(a_pool, b_pool, row_slot)
    order, pos, tile_slot, m_pad = segment_layout(
        slot, n_slots + 1, block_m=block_m, max_segments=max_segments
    )
    xs = jnp.zeros((m_pad, kdim), x.dtype).at[pos].set(jnp.take(x, order, axis=0))
    xt = xs.reshape(-1, block_m, kdim)
    at = jnp.take(ap, tile_slot, axis=0).astype(x.dtype)
    bt = jnp.take(bp, tile_slot, axis=0).astype(x.dtype)
    xa = jnp.einsum("tbk,tkr->tbr", xt, at, preferred_element_type=jnp.float32)
    lo = jnp.einsum(
        "tbr,trn->tbn", xa.astype(x.dtype), bt, preferred_element_type=jnp.float32
    ).reshape(m_pad, n)
    lora = jnp.zeros((m, n), lo.dtype).at[order].set(jnp.take(lo, pos, axis=0))
    base = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return (base + scale * lora).astype(x.dtype)
