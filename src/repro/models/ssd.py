"""Mamba-2 SSD (state-space duality) mixer — chunked dual form + decode step.

Follows Dao & Gu (2024, arXiv:2405.21060): the selective SSM

    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t x_t^T      (per head)
    y_t = C_t . h_t + D x_t

is evaluated in O(S) with chunkwise duality: within a chunk of length Q the
output is a masked (semiseparable) attention-like contraction; across chunks
a small recurrent state (H, P, N) is propagated.  The cross-chunk recurrence
uses ``lax.associative_scan`` (log-depth — TPU-friendly; a sequential scan
would serialize 2048 steps at 500k context).

``ssd_chunked`` is the pure-jnp form: the CPU path and the oracle of
``ssd_pallas``, which runs the chunk core through the Pallas kernels of
``repro.kernels.ssd_scan`` on a TPU.  ``apply_ssd`` takes the kernels
wherever Pallas compiles for the backend and the heads block into 128 lanes.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import backend, ssd_scan
from repro.models import layers
from repro.models.kvcache import SSMState


def ssd_dims(cfg) -> dict:
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return dict(
        d_inner=d_inner,
        n_heads=n_heads,
        head_dim=cfg.ssm_head_dim,
        state=cfg.ssm_state,
        conv_dim=d_inner + 2 * cfg.ssm_state,  # conv over [x, B, C]
    )


def init_ssd(key, cfg, dtype=jnp.float32):
    dims = ssd_dims(cfg)
    k_in, k_conv, k_dt, k_out = jax.random.split(key, 4)
    d = cfg.d_model
    d_in_proj = dims["d_inner"] + dims["conv_dim"] + dims["n_heads"]  # z, xBC, dt
    p = {
        "in_proj": layers.init_dense(k_in, d, d_in_proj, dtype=dtype),
        "conv_w": jax.random.normal(k_conv, (cfg.conv_width, dims["conv_dim"]), dtype) * 0.1,
        "conv_b": jnp.zeros((dims["conv_dim"],), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, dims["n_heads"]).astype(jnp.float32)),
        "dt_bias": jnp.asarray(
            jnp.log(jnp.exp(jnp.linspace(1e-3, 1e-1, dims["n_heads"])) - 1.0), jnp.float32
        ),
        "D": jnp.ones((dims["n_heads"],), jnp.float32),
        "norm": {"scale": jnp.ones((dims["d_inner"],), dtype)},
        "out_proj": layers.init_dense(k_out, dims["d_inner"], d, dtype=dtype),
    }
    del k_dt
    return p


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv over seq: x (B,S,C), w (K,C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):  # K is 4: unrolled adds beat a conv op at this width
        out = out + xp[:, i : i + x.shape[1], :] * w[i][None, None, :]
    return jax.nn.silu(out + b[None, None, :])


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """Lower-triangular pairwise segment sums: out[..., i, j] = sum_{j<k<=i} a_k."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # (..., i, j) = cs_i - cs_j
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jnp.ndarray,  # (B, S, H, P)  (already multiplied by nothing; dt applied here)
    dt: jnp.ndarray,  # (B, S, H) positive
    a_log: jnp.ndarray,  # (H,)  A = -exp(a_log)
    b_mat: jnp.ndarray,  # (B, S, N)  (single group)
    c_mat: jnp.ndarray,  # (B, S, N)
    d_skip: jnp.ndarray,  # (H,)
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk

    a = -jnp.exp(a_log)  # (H,) negative
    da = dt * a[None, None, :]  # (B, S, H) log-decay increments
    xdt = x * dt[..., None]  # dt-premultiplied input

    # Reshape into chunks.
    xc = jnp.reshape(xdt, (bsz, nc, chunk, h, p))
    dac = jnp.transpose(jnp.reshape(da, (bsz, nc, chunk, h)), (0, 1, 3, 2))  # (B,nc,H,Q)
    bc = jnp.reshape(b_mat, (bsz, nc, chunk, n))
    cc = jnp.reshape(c_mat, (bsz, nc, chunk, n))

    # --- intra-chunk (dual quadratic form) ---
    l_mask = jnp.exp(_segsum(dac))  # (B,nc,H,Q,Q), lower-triangular decay
    scores = jnp.einsum("bcin,bcjn->bcij", cc, bc)  # (B,nc,Q,Q)
    y_intra = jnp.einsum("bchij,bcij,bcjhp->bcihp", l_mask, scores, xc)

    # --- chunk states: contribution of each chunk to the running state ---
    cum = jnp.cumsum(dac, axis=-1)  # (B,nc,H,Q)
    decay_to_end = jnp.exp(cum[..., -1:] - cum)  # (B,nc,H,Q)
    states = jnp.einsum("bchj,bcjn,bcjhp->bchpn", decay_to_end, bc, xc)  # (B,nc,H,P,N)

    # --- inter-chunk recurrence via associative scan over chunks ---
    chunk_decay = jnp.exp(cum[..., -1])  # (B,nc,H) total decay of each chunk

    def combine(left, right):
        d1, s1 = left
        d2, s2 = right
        return d1 * d2, s1 * d2[..., None, None] + s2

    _, states_inclusive = jax.lax.associative_scan(combine, (chunk_decay, states), axis=1)
    final_state = states_inclusive[:, -1]
    # State *entering* each chunk = inclusive scan shifted right by one.
    h_prev = jnp.concatenate(
        [jnp.zeros_like(final_state)[:, None], states_inclusive[:, :-1]], axis=1
    )  # (B,nc,H,P,N)

    # --- inter-chunk output ---
    in_decay = jnp.exp(cum)  # decay from chunk start to position i (inclusive)
    y_inter = jnp.einsum("bcin,bchpn,bchi->bcihp", cc, h_prev, in_decay)

    y = y_intra + y_inter
    y = jnp.reshape(y, (bsz, s + pad, h, p))[:, :s]
    y = y + x[:, :s] * d_skip[None, None, :, None]
    return y, final_state


def ssd_pallas(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H) positive
    a_log: jnp.ndarray,  # (H,)
    b_mat: jnp.ndarray,  # (B, S, N)
    c_mat: jnp.ndarray,  # (B, S, N)
    d_skip: jnp.ndarray,  # (H,)
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``ssd_chunked`` with the chunk core in the Pallas kernels: the dt
    premultiplication, the log-decays, their within-chunk cumsum and the D
    skip stay here, in XLA."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    pad = (-s) % chunk
    xdt = jnp.reshape(x * dt[..., None], (bsz, s, h * p))
    da = dt * -jnp.exp(a_log)[None, None, :]
    if pad:
        xdt, da, b_mat, c_mat = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                                 for t in (xdt, da, b_mat, c_mat))
    sp = s + pad
    cum = jnp.reshape(jnp.cumsum(jnp.reshape(da, (bsz, sp // chunk, chunk, h)), axis=2),
                      (bsz, sp, h))
    y, h_final = ssd_scan.ssd_chunk_scan(xdt, cum, b_mat, c_mat, chunk=chunk)
    y = jnp.reshape(y, (bsz, sp, h, p))[:, :s] + x * d_skip[None, None, :, None]
    return y, jnp.transpose(jnp.reshape(h_final, (bsz, n, h, p)), (0, 2, 3, 1))


def ssd_decode_step(
    x: jnp.ndarray,  # (B, 1, H, P)
    dt: jnp.ndarray,  # (B, 1, H)
    a_log: jnp.ndarray,
    b_mat: jnp.ndarray,  # (B, 1, N)
    c_mat: jnp.ndarray,  # (B, 1, N)
    d_skip: jnp.ndarray,
    h: jnp.ndarray,  # (B, H, P, N)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    a = -jnp.exp(a_log)
    da = jnp.exp(dt[:, 0] * a[None, :])  # (B, H)
    update = jnp.einsum("bhp,bn->bhpn", (x * dt[..., None])[:, 0], b_mat[:, 0])
    h_new = h * da[..., None, None] + update
    y = jnp.einsum("bn,bhpn->bhp", c_mat[:, 0], h_new)[:, None]
    return y + x * d_skip[None, None, :, None], h_new


def apply_ssd(
    params,
    lora,
    x: jnp.ndarray,  # (B, S, D)
    cfg,
    *,
    state: SSMState | None = None,
    lora_scale: float = 1.0,
    return_state: bool = False,
) -> Tuple[jnp.ndarray, SSMState | None]:
    """Full SSD mixer: in_proj -> conv -> SSD -> gated norm -> out_proj.

    LoRA attaches to in_proj ("q" slot) and out_proj ("v" slot) — the mixer's
    trainable linear maps (DESIGN.md §4 mamba2 row).
    """
    lora = lora or {}
    dims = ssd_dims(cfg)
    h_heads, p_dim, n_state = dims["n_heads"], dims["head_dim"], dims["state"]

    proj = layers.dense(x, params["in_proj"], lora.get("q"), lora_scale)
    z, xbc, dt_raw = jnp.split(
        proj, [dims["d_inner"], dims["d_inner"] + dims["conv_dim"]], axis=-1
    )
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"][None, None, :])

    new_state = state
    if state is None:
        conv_tail = None
        if return_state:  # prefill: keep the last K-1 pre-conv inputs
            conv_tail = xbc[:, -(cfg.conv_width - 1):, :]
            short = cfg.conv_width - 1 - conv_tail.shape[1]
            if short > 0:
                conv_tail = jnp.pad(conv_tail, ((0, 0), (short, 0), (0, 0)))
        xbc = _causal_conv(xbc, params["conv_w"].astype(x.dtype), params["conv_b"])
        xs, b_mat, c_mat = jnp.split(xbc, [dims["d_inner"], dims["d_inner"] + n_state], -1)
        xs = jnp.reshape(xs, (*xs.shape[:2], h_heads, p_dim))
        use_kernels = (not backend.interpret_default()
                       and ssd_scan.heads_per_block(h_heads, p_dim) is not None)
        y, h_final = (ssd_pallas if use_kernels else ssd_chunked)(
            xs.astype(jnp.float32),
            dt,
            params["A_log"],
            b_mat.astype(jnp.float32),
            c_mat.astype(jnp.float32),
            params["D"],
            cfg.ssm_chunk,
        )
        if return_state:
            new_state = SSMState(h=h_final, conv=conv_tail)
    else:
        # Decode: roll the conv window, single-step recurrence.
        conv_in = jnp.concatenate([state.conv, xbc], axis=1)  # (B, K, conv_dim)
        w = params["conv_w"].astype(x.dtype)
        conv_out = jnp.einsum("bkc,kc->bc", conv_in, w) + params["conv_b"]
        xbc1 = jax.nn.silu(conv_out)[:, None]
        xs, b_mat, c_mat = jnp.split(xbc1, [dims["d_inner"], dims["d_inner"] + n_state], -1)
        xs = jnp.reshape(xs, (xs.shape[0], 1, h_heads, p_dim))
        y, h_new = ssd_decode_step(
            xs.astype(jnp.float32),
            dt,
            params["A_log"],
            b_mat.astype(jnp.float32),
            c_mat.astype(jnp.float32),
            params["D"],
            state.h,
        )
        new_state = SSMState(h=h_new, conv=conv_in[:, 1:])

    y = jnp.reshape(y, (*y.shape[:2], dims["d_inner"])).astype(x.dtype)
    # Gated RMSNorm (mamba2): norm(y * silu(z))
    y = layers.apply_norm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = layers.dense(y, params["out_proj"], lora.get("v"), lora_scale)
    return out, new_state


def init_ssm_state(batch: int, cfg, dtype=jnp.float32) -> SSMState:
    dims = ssd_dims(cfg)
    return SSMState(
        h=jnp.zeros((batch, dims["n_heads"], dims["head_dim"], dims["state"]), jnp.float32),
        conv=jnp.zeros((batch, cfg.conv_width - 1, dims["conv_dim"]), dtype),
    )
