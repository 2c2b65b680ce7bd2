"""Subspace-sketch compressed uplinks (DESIGN.md §12).

FedRPCA's premise is that client LoRA deltas share a dominant common
subspace — and the server's warm RPCA carry already *is* an estimate of
that subspace (``BucketCarry.v``, the carried right-eigenbasis, together
with the converged low-rank iterate ``BucketCarry.l``).  So instead of
shipping a dense ``(d1, )`` column per module per client every round, a
client can project its delta onto the broadcast basis and ship

    ``(coefficients (r,), sparse residual (top-k values + indices))``

per (module, client) column — ``r + 2k`` numbers instead of ``d1``.

The codec here is the *bucket-layout* realization of that contract: it
operates directly on the packed ``(B, padded_vec, n_clients)`` bucket
tensors the engine aggregates, so the decode writes straight into the
layout ``robust_pca_bucket`` consumes and no per-client dense delta is
ever materialized outside the codec.  Three properties are load-bearing:

* **Exact at full coverage.**  The residual values shipped are the RAW
  delta entries at the top-|residual| positions (not the residuals), and
  the decode scatter *sets* them (``at[...].set``), so ``k == d1``
  reconstructs the input bit-for-bit — IEEE ``a + (m - a)`` is not ``m``,
  but "overwrite with m" is.

* **Dense-fallback gate.**  ``Sketch.energy_frac`` measures the delta
  energy the sketch *drops* (residual energy beyond the top-k, relative
  to the delta's own energy).  Cold rounds (zero/invalid basis: the
  projection captures nothing) and basis-drift rounds (clients moved off
  the carried subspace) score high and degrade to the exact dense path;
  the engine applies the gate as a ``jnp.where`` so the traced program is
  shape-static and a tripped gate is bitwise the dense round.

* **Masked columns stay zero.**  Packed buckets zero masked client
  columns; their coefficients, residuals and scattered values are all
  exactly zero, so cohort padding remains inert through the codec.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import rpca as rpca_lib

#: Bytes per float32 / int32 element — the uplink wire format.
_BYTES_F32 = 4
_BYTES_I32 = 4

#: Default residual budget per (module, client) column.
DEFAULT_K = 64

#: Default dense-fallback gate: maximum fraction of a bucket's delta
#: energy the sketch may drop before the round degrades to dense.
DEFAULT_ENERGY_TOL = 0.3

UPLINK_MODES = ("dense", "sketch")


class UplinkConfig(NamedTuple):
    """Static uplink codec configuration (part of the aggregation plan).

    ``mode="dense"`` is the identity uplink — the engine never enters the
    codec and the traced program is bit-for-bit the uncompressed path.
    ``mode="sketch"`` encodes each client column as ``r`` basis
    coefficients plus a ``k``-entry sparse residual, gated per bucket
    tier by ``energy_tol`` (see module docstring).
    """

    mode: str = "dense"
    k: int = DEFAULT_K
    energy_tol: float = DEFAULT_ENERGY_TOL

    @property
    def active(self) -> bool:
        return self.mode == "sketch"


def parse_uplink(spec) -> UplinkConfig:
    """Parse an ``--uplink`` CLI spec into an ``UplinkConfig``.

    Accepted forms: ``"dense"``, ``"sketch"``, ``"sketch:<k>"``,
    ``"sketch:<k>:<energy_tol>"``, an existing ``UplinkConfig`` (returned
    unchanged), or ``None`` (dense).
    """
    if spec is None:
        return UplinkConfig()
    if isinstance(spec, UplinkConfig):
        return spec
    parts = str(spec).split(":")
    mode = parts[0]
    if mode not in UPLINK_MODES:
        raise ValueError(
            f"unknown uplink mode: {mode!r} (expected one of {UPLINK_MODES})"
        )
    if mode == "dense":
        if len(parts) > 1:
            raise ValueError(f"dense uplink takes no parameters: {spec!r}")
        return UplinkConfig()
    k = int(parts[1]) if len(parts) > 1 and parts[1] else DEFAULT_K
    if k < 1:
        raise ValueError(f"uplink sketch k must be >= 1, got {k}")
    tol = float(parts[2]) if len(parts) > 2 and parts[2] else DEFAULT_ENERGY_TOL
    if not 0.0 <= tol <= 1.0:
        raise ValueError(f"uplink energy_tol must be in [0, 1], got {tol}")
    if len(parts) > 3:
        raise ValueError(f"malformed uplink spec: {spec!r}")
    return UplinkConfig(mode="sketch", k=k, energy_tol=tol)


class Sketch(NamedTuple):
    """One bucket's encoded uplink payload.

    ``coef``  (B, r, C) f32 — basis coefficients per module per client.
    ``vals``  (B, C, k) f32 — RAW delta entries at the top-|residual|
              positions (see module docstring: set-semantics exactness).
    ``idx``   (B, C, k) i32 — d1-axis positions of ``vals``.
    ``energy_frac`` (B,) f32 — fraction of each module's delta energy the
              sketch drops (residual energy beyond the top-k / ||m||^2).
    """

    coef: jnp.ndarray
    vals: jnp.ndarray
    idx: jnp.ndarray
    energy_frac: jnp.ndarray


def uplink_basis(carry_l: jnp.ndarray, carry_v: jnp.ndarray) -> jnp.ndarray:
    """Derive the broadcast d1-side basis from a bucket's RPCA carry.

    The carry stores the d2-side (client-side) eigenbasis ``v`` (B, d2, r)
    and the converged low-rank iterate ``l`` (B, d1, d2); the d1-side
    column space those two imply is ``span(l @ v)``, orthonormalized with
    the same batched CholeskyQR the subspace SVT uses.  An invalid/cold
    carry (``l == 0``) degrades to a zero basis — projections capture
    nothing, ``energy_frac`` saturates, and the dense-fallback gate trips,
    which is exactly the cold-round contract.
    """
    z = jnp.einsum("bdc,bcr->bdr", carry_l.astype(jnp.float32),
                   carry_v.astype(jnp.float32))
    return rpca_lib._orthonormalize(z)


def encode_delta(m: jnp.ndarray, basis: jnp.ndarray, k: int) -> Sketch:
    """Encode a (B, d1, C) bucket against a (B, d1, r) orthonormal basis.

    Per (module, client) column: ``r`` projection coefficients plus the
    ``k`` raw entries with the largest reconstruction residual.  ``k`` is
    clipped to ``d1``; at ``k == d1`` the decode is bitwise the input.
    """
    b, d1, c = m.shape
    m32 = m.astype(jnp.float32)
    kk = min(int(k), d1)
    coef = jnp.einsum("bdr,bdc->brc", basis, m32)
    resid = m32 - jnp.einsum("bdr,brc->bdc", basis, coef)
    resid_t = jnp.swapaxes(resid, 1, 2)  # (B, C, d1)
    _, idx = jax.lax.top_k(jnp.abs(resid_t), kk)
    # Ship the RAW delta entries at those positions, not the residuals:
    # decode overwrites, so full coverage is exact (no a + (m - a) drift).
    vals = jnp.take_along_axis(jnp.swapaxes(m32, 1, 2), idx, axis=-1)
    # Sum the dropped entries themselves rather than (total - kept): the
    # difference of two differently ordered f32 sums leaves epsilon residue,
    # whereas this is exactly zero at k == d1 and monotone in k.
    dropped = jnp.put_along_axis(
        resid_t * resid_t, idx, 0.0, axis=-1, inplace=False
    )
    m_sq = jnp.sum(m32 * m32, axis=(1, 2))
    energy_frac = jnp.sum(dropped, axis=(1, 2)) / jnp.maximum(m_sq, 1e-12)
    return Sketch(coef=coef, vals=vals, idx=idx, energy_frac=energy_frac)


def decode_into_bucket(sketch: Sketch, basis: jnp.ndarray) -> jnp.ndarray:
    """Decode a ``Sketch`` straight into the packed (B, d1, C) bucket layout.

    Reconstruction = basis @ coef, with the shipped raw entries scattered
    over it by SET (not add) — see ``encode_delta``.
    """
    b, d1, _ = basis.shape
    c = sketch.coef.shape[-1]
    approx = jnp.einsum("bdr,brc->bdc", basis, sketch.coef)
    approx_t = jnp.swapaxes(approx, 1, 2)  # (B, C, d1)
    bi = jnp.arange(b)[:, None, None]
    ci = jnp.arange(c)[None, :, None]
    approx_t = approx_t.at[bi, ci, sketch.idx].set(sketch.vals)
    return jnp.swapaxes(approx_t, 1, 2)


def sketch_bytes_per_client(n_modules: int, r: int, k: int) -> float:
    """Wire bytes one client ships for one bucket under the sketch codec:
    per module, ``r`` f32 coefficients + ``k`` f32 values + ``k`` i32
    indices."""
    return float(n_modules) * (_BYTES_F32 * (r + k) + _BYTES_I32 * k)


def dense_bytes_per_client(true_dims) -> float:
    """Wire bytes one client ships for one bucket dense: the true
    (unpadded) f32 payload — padding rows are never on the wire."""
    return float(_BYTES_F32) * float(sum(int(d) for d in true_dims))


def basis_bytes(n_modules: int, d1: int, r: int) -> float:
    """Downlink bytes for one bucket's broadcast basis (counted once per
    round — the basis multicast is shared by every client)."""
    return float(_BYTES_F32) * float(n_modules) * float(d1) * float(r)
