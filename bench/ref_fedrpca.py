"""Plain FedRPCA reference, written from the paper and not from the program.

FedRPCA (arXiv:2506.01194, Algorithm 2 and App. B.3): every adapter module
of every client is a column of ``M`` (vec_dim x clients); Robust PCA by the
ADMM of Candes et al. (2011) splits it into a low-rank ``L`` and a sparse
``S`` with

    mu = d1 * d2 / (4 ||M||_1),  lam = 1 / sqrt(max(d1, d2)),  rho = 1 / mu
    L <- SVT_rho(M - S + rho Y);  S <- shrink_{rho lam}(M - L + rho Y)
    Y <- Y + mu (M - L - S)

for a fixed number of iterations, and the merged update is
``mean(L) + beta * mean(S)`` with ``beta = clip(1 / E, 1, 100)``,
``E = ||S 1|| / ||M 1||``.  The SVT takes the exact eigendecomposition of
the small Gram ``X^T X`` in every iteration.  Everything is float32 with
matmuls at ``precision``: "highest", or "bf16x3" for the control, three
bfloat16 products of the split inputs, spelled out so that it means the
same on every backend.

Deltas come as ``{name: (clients, layers, *matrix)}``; each layer's matrix
is one module.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = 1e-12


def modules(leaf: jnp.ndarray) -> jnp.ndarray:
    """(clients, layers, *matrix) -> (layers, vec, clients)."""
    c, layers = leaf.shape[:2]
    return jnp.transpose(jnp.reshape(leaf.astype(jnp.float32), (c, layers, -1)), (1, 2, 0))


def unmodules(upd: jnp.ndarray, shape: tuple) -> jnp.ndarray:
    """(layers, vec) -> (layers, *matrix)."""
    return jnp.reshape(upd, shape)


def round_to(x, dtype):
    """``x`` rounded to ``dtype`` and back to float32.  The barrier keeps
    the TPU compiler from dropping the pair of conversions as excess
    precision, which would leave ``x`` unrounded."""
    return jax.lax.optimization_barrier(x.astype(dtype)).astype(jnp.float32)


def _bf16x3(a, b):
    def split(x):
        hi = round_to(x, jnp.bfloat16)
        return hi, round_to(x - hi, jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    mm = functools.partial(jnp.matmul, precision="highest")
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def matmul(precision: str):
    if precision == "bf16x3":
        return _bf16x3
    return functools.partial(jnp.matmul, precision=precision)


def _shrink(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


@functools.partial(jax.jit, static_argnames=("iters", "precision"))
def rpca(m, l0, s0, y0, *, iters: int, precision: str = "highest"):
    """ADMM on a batch of modules ``m`` (B, d, C) from (l0, s0, y0).
    Returns (L, S, Y, relative residual per module)."""
    _, d1, d2 = m.shape
    mu = d1 * d2 / (4.0 * jnp.maximum(jnp.sum(jnp.abs(m), axis=(1, 2)), EPS))
    rho = (1.0 / mu)[:, None, None]
    lam = 1.0 / jnp.sqrt(float(max(d1, d2)))
    mm = matmul(precision)

    def svt(x):
        g = mm(jnp.swapaxes(x, 1, 2), x)
        w, v = jnp.linalg.eigh(g)
        s = jnp.sqrt(jnp.maximum(w, 0.0))
        coef = jnp.where(s > EPS, _shrink(s, rho[:, :, 0]) / jnp.maximum(s, EPS), 0.0)
        return mm(mm(x, v) * coef[:, None, :], jnp.swapaxes(v, 1, 2))

    def body(_, state):
        _, s, y = state
        l = svt(m - s + rho * y)
        s = _shrink(m - l + rho * y, rho * lam)
        y = y + mu[:, None, None] * (m - l - s)
        return l, s, y

    l, s, y = jax.lax.fori_loop(0, iters, body, (l0, s0, y0))
    res = jnp.sqrt(jnp.sum((m - l - s) ** 2, axis=(1, 2)))
    return l, s, y, res / jnp.maximum(jnp.sqrt(jnp.sum(m * m, axis=(1, 2))), EPS)


@jax.jit
def merge(m, l, s):
    """FedRPCA's merged update of each module: (B, d)."""
    e = jnp.linalg.norm(jnp.sum(s, axis=-1), axis=-1) / jnp.maximum(
        jnp.linalg.norm(jnp.sum(m, axis=-1), axis=-1), EPS)
    beta = jnp.clip(1.0 / jnp.maximum(e, EPS), 1.0, 100.0)
    return jnp.mean(l, axis=-1) + beta[:, None] * jnp.mean(s, axis=-1)


def aggregate(deltas: dict, *, iters: int, precision: str = "highest", warm=None,
              gate: float = 1.0):
    """FedRPCA over a delta dict.  ``warm`` is the previous aggregation's
    ``{name: (L, S, Y)}``: it is the starting point when every module's
    relative residual ``||M - L - S|| / ||M||`` is at most ``gate`` (a cold
    start scores 1), else every module starts from zero.

    Returns (update {name: (layers, *matrix)}, state {name: (L, S, Y)},
    the largest relative residual over modules)."""
    ms = {k: modules(v) for k, v in deltas.items()}
    use_warm = False
    if warm is not None:
        errs = [jnp.max(jnp.linalg.norm(ms[k] - warm[k][0] - warm[k][1], axis=(1, 2))
                        / jnp.maximum(jnp.linalg.norm(ms[k], axis=(1, 2)), EPS))
                for k in ms]
        use_warm = bool(max(float(e) for e in errs) <= gate)
    upd, state, res_max = {}, {}, 0.0
    for k, m in ms.items():
        if use_warm:
            l0, s0, y0 = warm[k]
        else:
            l0 = s0 = y0 = jnp.zeros_like(m)
        l, s, y, res = rpca(m, l0, s0, y0, iters=iters, precision=precision)
        upd[k] = unmodules(merge(m, l, s), deltas[k].shape[1:])
        state[k] = (l, s, y)
        res_max = max(res_max, float(jnp.max(res)))
    return upd, state, res_max
