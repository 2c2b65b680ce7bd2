"""jit'd wrappers binding the Pallas kernels into the framework.

Execution mode policy lives in ``repro.kernels.backend``: compiled on TPU,
interpret elsewhere; an explicit ``interpret=True`` is honoured off-TPU only.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels import local_attention as _la
from repro.kernels import lora_matmul as _lm
from repro.kernels import soft_threshold as _st

def soft_threshold(x: jnp.ndarray, t, *, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Kernel-backed shrinkage; reshapes any rank to 2-D tiles."""
    interpret = backend.resolve_interpret(interpret)
    shape = x.shape
    x2 = jnp.atleast_2d(x.reshape(-1, shape[-1]) if x.ndim >= 2 else x.reshape(1, -1))
    out = _st.soft_threshold(x2, t, interpret=interpret)
    return out.reshape(shape)


def lora_matmul(
    x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, scale: float = 1.0,
    *, interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused y = xW + s(xA)B for inputs of any leading rank."""
    interpret = backend.resolve_interpret(interpret)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = _lm.lora_matmul(x2, w, a, b, scale, interpret=interpret)
    return out.reshape(*lead, w.shape[-1])


def gathered_lora_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    a_pool: jnp.ndarray,
    b_pool: jnp.ndarray,
    row_slot: jnp.ndarray,
    scale: float = 1.0,
    *,
    impl: Optional[str] = None,
    max_segments: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pooled multi-adapter y = xW + s(xA_slot)B_slot for any leading rank.

    ``row_slot`` is either per-row (same leading shape as ``x`` minus the
    feature axis) or per-request ``(B,)`` for ``x: (B, S, K)`` — request ids
    broadcast across the sequence axis, and the request count then bounds
    the segment layout (``max_segments``) so pool size never inflates the
    padded batch.  Slot ``-1`` means "no adapter" (base projection only).

    ``impl``: ``"pallas"`` (in-kernel block gather, the TPU path) or
    ``"xla"`` (tile-level gather + batched GEMMs, the CPU fast path);
    ``None`` picks by backend.
    """
    lead = x.shape[:-1]
    rs = jnp.asarray(row_slot, jnp.int32)
    if rs.shape != lead:
        if rs.ndim != 1 or len(lead) < 2 or rs.shape[0] != lead[0]:
            raise ValueError(
                f"row_slot shape {rs.shape} matches neither rows {lead} nor "
                f"requests ({lead[0]},)"
            )
        if max_segments is None:
            max_segments = rs.shape[0]
        rs = jnp.broadcast_to(rs.reshape(rs.shape + (1,) * (len(lead) - 1)), lead)
    rs = rs.reshape(-1)
    x2 = x.reshape(-1, x.shape[-1])
    if impl is None:
        impl = "xla" if backend.resolve_interpret(interpret) else "pallas"
    if impl == "pallas":
        out = _lm.gathered_lora_matmul(
            x2, w, a_pool, b_pool, rs, scale,
            max_segments=max_segments, interpret=interpret,
        )
    elif impl == "xla":
        out = _lm.gathered_lora_matmul_xla(
            x2, w, a_pool, b_pool, rs, scale, max_segments=max_segments
        )
    else:
        raise ValueError(f"unknown impl {impl!r} (want 'pallas' or 'xla')")
    return out.reshape(*lead, w.shape[-1])


def local_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, window: int = 0,
    causal: bool = True, interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(B, S, H, D) x (B, S, H, D) sliding-window attention (per-head fused)."""
    interpret = backend.resolve_interpret(interpret)
    if q.ndim == 4:
        bsz, s, h, d = q.shape
        fold = lambda t: jnp.transpose(t, (0, 2, 1, 3)).reshape(bsz * h, s, d)
        out = _la.local_attention(
            fold(q), fold(k), fold(v), window=window, causal=causal, interpret=interpret
        )
        return jnp.transpose(out.reshape(bsz, h, s, d), (0, 2, 1, 3))
    return _la.local_attention(q, k, v, window=window, causal=causal, interpret=interpret)

