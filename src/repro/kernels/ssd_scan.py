"""Pallas TPU kernels: the Mamba-2 SSD chunk core, forward and backward.

``ssd_chunk_scan`` is the chunk core of ``models/ssd.py::ssd_chunked``, from
the dt-premultiplied input and the within-chunk cumulative log-decays to the
output and the final state, as a ``jax.custom_vjp`` over two kernels.  Per
chunk of Q positions and head h (cum = cumulative log-decay, h0 the state
entering the chunk, L the causal decay mask):

    L_ij = exp(cum_i - cum_j) (i >= j),   S = C B^T
    y    = (L * S) x + exp(cum) * (C h0)
    h1   = exp(cum_Q) h0 + B^T (exp(cum_Q - cum) * x)

The (Q, Q) decay mask and scores, and their gradients, exist only in VMEM.

Layout.  x, y and dx keep the model's (B, S, H*P) layout; a grid step takes
one chunk of a block of heads that fills the 128 lanes (2 heads at P = 64),
with every head's part of a lane-dense product masked to its own lanes.  B
and C stay (B, S, N), shared by all heads.  The log-decays come in twice,
(B, S, H) and (B, H, S), so a head's column and row are lane- and
sublane-masked reductions of a lane-dense block.

Grid (B, chunks, head blocks), head blocks innermost: a chunk's C B^T is
formed once and shared by all its heads, and the backward sums dB, dC and
the log-decay gradients of a chunk over all heads in their output blocks.
The forward runs the chunks in order and the backward in reverse, each
carrying every head block's (N, lanes) state, or its gradient, in VMEM.  The
forward's residual is the state leaving each chunk, (B, chunks, N, H*P).

Matmul operands: one bf16 pass with float32 accumulation, what XLA's TPU
backend gives the same float32 contraction at the default precision, or
float32 at ``"highest"`` under an ambient ``jax.default_matmul_precision``
asking for more.  Everything else (decays, exp, masks, states, accumulators)
is float32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

LANES = 128
# A ``jax.default_matmul_precision`` under which XLA's TPU backend runs a
# float32 dot as one bf16 pass.
_ONE_PASS = (None, "default", "bfloat16", "fastest")


def heads_per_block(heads: int, head_dim: int) -> Optional[int]:
    """Heads a grid step takes so that its lanes are a multiple of 128, or
    None where the heads cannot be so blocked."""
    if LANES % head_dim == 0:
        hb = LANES // head_dim
    elif head_dim % LANES == 0:
        hb = 1
    else:
        return None
    return hb if heads % hb == 0 else None


def _mxu() -> tuple:
    """(operand dtype, precision) of the kernels' matmuls under the ambient
    ``jax.default_matmul_precision``."""
    if jax.config.jax_default_matmul_precision in _ONE_PASS:
        return jnp.bfloat16, lax.Precision.DEFAULT
    return jnp.float32, lax.Precision.HIGHEST


def _dot(a, b, mxu, contract=((1,), (0,))):
    dtype, precision = mxu
    return lax.dot_general(a.astype(dtype), b.astype(dtype), (contract, ((), ())),
                           precision=precision, preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


class _Block:
    """A grid step's view of its head block: each head's log-decay column
    and row, and per-lane broadcasts of per-head values."""

    def __init__(self, cum_ref, cumt_ref, *, hb: int, p: int):
        j = pl.program_id(2)
        cum, cumt = cum_ref[0], cumt_ref[0]  # (Q, H), (H, Q)
        q, lanes = cum.shape[0], hb * p
        lane = lax.broadcasted_iota(jnp.int32, (1, cum.shape[1]), 1)
        row = lax.broadcasted_iota(jnp.int32, (cumt.shape[0], 1), 0)
        self.hb = hb
        self.cols = [jnp.sum(jnp.where(lane == j * hb + k, cum, 0.0), axis=1, keepdims=True)
                     for k in range(hb)]  # (Q, 1) each
        self.rows = [jnp.sum(jnp.where(row == j * hb + k, cumt, 0.0), axis=0, keepdims=True)
                     for k in range(hb)]  # (1, Q) each
        self.last = [c[q - 1:q, :] for c in self.cols]  # (1, 1): cum at the chunk's end
        self.head = lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // p
        self.causal = (lax.broadcasted_iota(jnp.int32, (q, q), 0)
                       >= lax.broadcasted_iota(jnp.int32, (q, q), 1))

    def decay(self, k):
        """Head k's causal decay mask L (Q, Q)."""
        return jnp.exp(jnp.where(self.causal, self.cols[k] - self.rows[k], -jnp.inf))

    def lanes(self, per_head):
        """Per-head arrays (rows, 1) broadcast to the head's lanes."""
        out = per_head[-1]
        for k in range(self.hb - 2, -1, -1):
            out = jnp.where(self.head == k, per_head[k], out)
        return jnp.broadcast_to(out, (out.shape[0], self.head.shape[1]))

    def only(self, k, a):
        """``a`` (rows, lanes) with every lane but head k's zeroed."""
        return jnp.where(self.head == k, a, 0.0)

    def decays(self):
        """exp(cum) (Q, lanes), exp(cum_Q - cum) (Q, lanes), exp(cum_Q) (1, lanes)."""
        into = jnp.exp(self.lanes(self.cols))
        to_end = jnp.exp(self.lanes([l - c for l, c in zip(self.last, self.cols)]))
        whole = jnp.exp(self.lanes(self.last))
        return into, to_end, whole


def _fwd_kernel(x_ref, cum_ref, cumt_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, s_scr,
                *, hb, p, mxu):
    c, j = pl.program_id(1), pl.program_id(2)
    blk = _Block(cum_ref, cumt_ref, hb=hb, p=p)

    @pl.when(c == 0)
    def _():
        h_scr[j] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        s_scr[...] = _dot(c_ref[0], b_ref[0], mxu, _NT)

    x, bm, cm, s, h = x_ref[0], b_ref[0], c_ref[0], s_scr[...], h_scr[j]
    into, to_end, whole = blk.decays()
    y = into * _dot(cm, h, mxu)
    for k in range(hb):
        y = y + _dot(blk.decay(k) * s, blk.only(k, x), mxu)
    y_ref[0] = y
    h_new = whole * h + _dot(bm, x * to_end, mxu, _TN)
    h_scr[j] = h_new
    hout_ref[0, 0] = h_new


def _bwd_kernel(x_ref, cum_ref, cumt_ref, b_ref, c_ref, hout_ref, g_ref, dhf_ref,
                dx_ref, dcum_ref, dcumt_ref, db_ref, dc_ref, dh_scr, s_scr, ds_scr,
                *, hb, p, mxu):
    c, j = pl.program_id(1), pl.program_id(2)
    first_chunk = c == pl.num_programs(1) - 1  # chunks run in reverse
    blk = _Block(cum_ref, cumt_ref, hb=hb, p=p)

    @pl.when(c == 0)
    def _():
        dh_scr[j] = dhf_ref[0]

    @pl.when(j == 0)
    def _():
        s_scr[...] = _dot(c_ref[0], b_ref[0], mxu, _NT)
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dcum_ref[...] = jnp.zeros_like(dcum_ref)
        dcumt_ref[...] = jnp.zeros_like(dcumt_ref)

    x, g, bm, cm, s = x_ref[0], g_ref[0], b_ref[0], c_ref[0], s_scr[...]
    h0 = jnp.where(first_chunk, 0.0, hout_ref[0, 0])  # the state entering the chunk
    dh1 = dh_scr[j]
    into, to_end, whole = blk.decays()

    # Intra-chunk: y += (L * S) x.
    dx = jnp.zeros_like(x)
    ds = jnp.zeros_like(s)
    dcols, drows = [], []
    for k in range(hb):
        lk = blk.decay(k)
        mk = lk * s
        gk = blk.only(k, g)
        dmk = jnp.where(blk.causal, _dot(gk, blk.only(k, x), mxu, _NT), 0.0)
        dx = dx + _dot(mk, gk, mxu, _TN)
        ds = ds + dmk * lk
        r = dmk * mk  # d(cum_i - cum_j) of each L_ij
        dcols.append(jnp.sum(r, axis=1, keepdims=True))
        drows.append(-jnp.sum(r, axis=0, keepdims=True))
    ds_scr[...] += ds

    # Inter-chunk: y += exp(cum) * (C h0); and h1 = exp(cum_Q) h0 + B^T (x * to_end).
    eg = into * g
    xw = x * to_end
    bd = _dot(bm, dh1, mxu)
    dx = dx + to_end * bd
    dx_ref[0] = dx
    dh_scr[j] = whole * dh1 + _dot(cm, eg, mxu, _TN)
    t_in = eg * _dot(cm, h0, mxu)
    t_st = xw * bd
    t_h = dh1 * h0
    for k in range(hb):
        w = jnp.sum(blk.only(k, t_st), axis=1, keepdims=True)  # (Q, 1)
        at_end = (jnp.sum(w, axis=0, keepdims=True)
                  + jnp.exp(blk.last[k]) * jnp.sum(blk.only(k, t_h), keepdims=True))
        end = lax.broadcasted_iota(jnp.int32, w.shape, 0) == w.shape[0] - 1
        dcols[k] = (dcols[k] + jnp.sum(blk.only(k, t_in), axis=1, keepdims=True) - w
                    + jnp.where(end, at_end, 0.0))
    lane = lax.broadcasted_iota(jnp.int32, (1, dcum_ref.shape[-1]), 1)
    row = lax.broadcasted_iota(jnp.int32, (dcumt_ref.shape[1], 1), 0)
    dcum, dcumt = dcum_ref[0], dcumt_ref[0]
    for k in range(hb):
        dcum = dcum + jnp.where(lane == j * hb + k, dcols[k], 0.0)
        dcumt = dcumt + jnp.where(row == j * hb + k, drows[k], 0.0)
    dcum_ref[0] = dcum
    dcumt_ref[0] = dcumt

    db = _dot(xw, dh1, mxu, _NT)
    dc = _dot(eg, h0, mxu, _NT)

    @pl.when(j == 0)
    def _():
        db_ref[0] = db
        dc_ref[0] = dc

    @pl.when(j > 0)
    def _():
        db_ref[0] += db
        dc_ref[0] += dc

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dsum = ds_scr[...]
        db_ref[0] += _dot(dsum, cm, mxu, _TN)
        dc_ref[0] += _dot(dsum, bm, mxu)


def _specs(s, n, h, q, lanes, reverse):
    nc = s // q
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    seq = pl.BlockSpec((1, q, lanes), lambda b, c, j: (b, at(c), j))
    cum = pl.BlockSpec((1, q, h), lambda b, c, j: (b, at(c), 0))
    cumt = pl.BlockSpec((1, h, q), lambda b, c, j: (b, 0, at(c)))
    bc = pl.BlockSpec((1, q, n), lambda b, c, j: (b, at(c), 0))
    return seq, cum, cumt, bc


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _forward(x, cum, b, c, chunk, hb, interpret):
    bsz, s, hp = x.shape
    h, n = cum.shape[-1], b.shape[-1]
    p, nc = hp // h, s // chunk
    lanes = hb * p
    seq, cum_s, cumt_s, bc = _specs(s, n, h, chunk, lanes, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p, mxu=_mxu()),
        grid=(bsz, nc, h // hb),
        in_specs=[seq, cum_s, cumt_s, bc, bc],
        out_specs=[seq, pl.BlockSpec((1, 1, n, lanes), lambda b, c, j: (b, c, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, hp), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, nc, n, hp), jnp.float32)],
        scratch_shapes=[_vmem((h // hb, n, lanes)), _vmem((chunk, chunk))],
        interpret=backend.resolve_interpret(interpret),
        name="ssd_scan_fwd",
    )(x, cum, jnp.swapaxes(cum, 1, 2), b, c)


def _backward(x, cum, b, c, hout, g, dh_final, chunk, hb, interpret):
    bsz, s, hp = x.shape
    h, n = cum.shape[-1], b.shape[-1]
    p, nc = hp // h, s // chunk
    lanes = hb * p
    seq, cum_s, cumt_s, bc = _specs(s, n, h, chunk, lanes, reverse=True)
    # The state entering chunk c left chunk c - 1; chunk 0's is zero.
    h_in = pl.BlockSpec((1, 1, n, lanes),
                        lambda b_, c_, j: (b_, jnp.maximum(nc - 2 - c_, 0), 0, j))
    dx, dcum, dcumt, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p, mxu=_mxu()),
        grid=(bsz, nc, h // hb),
        in_specs=[seq, cum_s, cumt_s, bc, bc, h_in, seq,
                  pl.BlockSpec((1, n, lanes), lambda b_, c_, j: (b_, 0, j))],
        out_specs=[seq, cum_s, cumt_s, bc, bc],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, hp), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, s, h), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h, s), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, s, n), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, s, n), jnp.float32)],
        scratch_shapes=[_vmem((h // hb, n, lanes)), _vmem((chunk, chunk)),
                        _vmem((chunk, chunk))],
        interpret=backend.resolve_interpret(interpret),
        name="ssd_scan_bwd",
    )(x, cum, jnp.swapaxes(cum, 1, 2), b, c, hout, g, dh_final)
    return dx, dcum + jnp.swapaxes(dcumt, 1, 2), db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _scan(x, cum, b, c, chunk, hb, interpret):
    y, hout = _forward(x, cum, b, c, chunk, hb, interpret)
    return y, hout[:, -1]


def _scan_fwd(x, cum, b, c, chunk, hb, interpret):
    y, hout = _forward(x, cum, b, c, chunk, hb, interpret)
    return (y, hout[:, -1]), (x, cum, b, c, hout)


def _scan_bwd(chunk, hb, interpret, res, cot):
    g, dh_final = cot
    return _backward(*res, g, dh_final, chunk, hb, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_chunk_scan(
    x: jnp.ndarray,  # (B, S, H*P) float32, dt-premultiplied
    cum: jnp.ndarray,  # (B, S, H) float32, cumulative log-decay within each chunk
    b: jnp.ndarray,  # (B, S, N) float32
    c: jnp.ndarray,  # (B, S, N) float32
    *,
    chunk: int,
    interpret: Optional[bool] = None,
) -> tuple:
    """The SSD chunk core from a zero state: (y (B, S, H*P), final state
    (B, N, H*P)), both float32.  S is a multiple of ``chunk``, and the heads
    block (``heads_per_block``).  Differentiable in x, cum, b and c."""
    bsz, s, hp = x.shape
    h = cum.shape[-1]
    hb = heads_per_block(h, hp // h)
    if s % chunk or hb is None:
        raise ValueError(f"ssd_chunk_scan: S={s} (chunk {chunk}), {h} heads of {hp // h}")
    return _scan(x, cum, b, c, chunk, hb, interpret)
