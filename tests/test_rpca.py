"""Robust-PCA: recovery, SVT equivalence, and algebraic properties."""
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    batched_robust_pca,
    robust_pca,
    robust_pca_bucket,
    robust_pca_fixed_iters,
    soft_threshold,
    svt_gram,
    svt_svd,
)


def planted(n, m, rank, sparsity, scale=5.0, seed=0):
    rng = np.random.default_rng(seed)
    low = rng.normal(size=(n, rank)) @ rng.normal(size=(rank, m))
    sp = np.zeros((n, m))
    mask = rng.random((n, m)) < sparsity
    sp[mask] = scale * rng.normal(size=mask.sum())
    return low, sp


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _dots(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "dot_general"]


def _long_dots(jaxpr, vec):
    """The dot_generals with an operand that carries the ``vec`` dimension."""
    return [e for e in _dots(jaxpr) if any(vec in v.aval.shape for v in e.invars)]


class TestSVT:
    @pytest.mark.parametrize("shape,pad", [
        pytest.param((64, 8), 0, id="shape0"),
        pytest.param((8, 64), 0, id="shape1"),
        pytest.param((128, 128), 0, id="shape2"),
        pytest.param((33, 7), 0, id="shape3"),
        pytest.param((33, 7), 31, id="padded_rows"),
    ])
    def test_gram_matches_svd(self, shape, pad, rng):
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        x = jnp.concatenate([x, jnp.zeros((pad, shape[1]), jnp.float32)])
        for t in (0.0, 0.5, 3.0, 100.0):
            a, b = svt_gram(x, t), svt_svd(x, t)
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-3)
            assert not np.any(np.asarray(a[shape[0]:]))

    @pytest.mark.parametrize("dims,vec,cols", [([64, 40, 17], 64, 6), ([5, 3, 1], 5, 8)])
    def test_batched_padded_bucket(self, dims, vec, cols, rng):
        """The bucket loop's SVT stage (its first iteration's L, the SVT of
        M at threshold 1/mu): zero-padded rows stay exactly zero and the
        true rows match the unpadded per-matrix call, in the tall bucket
        and in the wide one (whose per-matrix call transposes)."""
        x = np.zeros((len(dims), vec, cols), np.float32)
        for i, d in enumerate(dims):
            x[i, :d] = rng.normal(size=(d, cols))
        for t in (0.3, 1.0, 2.0):
            out = np.asarray(robust_pca_bucket(
                jnp.asarray(x), jnp.asarray(dims), n_iter=1, mu=1.0 / t).low_rank)
            for i, d in enumerate(dims):
                assert not np.any(out[i, d:])
                np.testing.assert_allclose(
                    out[i, :d], svt_gram(jnp.asarray(x[i, :d]), t), atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("shape", [(64, 6), (6, 64)])
    def test_gram_streams_x_twice(self, shape):
        """The shrink is one projector: the long side feeds the Gram and
        X @ P and nothing else, and P is one C x C product."""
        jaxpr = jax.make_jaxpr(lambda x: svt_gram(x, 0.5))(jnp.ones(shape)).jaxpr
        gram, proj = _long_dots(jaxpr, 64)
        assert gram.invars[0] is gram.invars[1] is proj.invars[0]
        assert gram.outvars[0].aval.shape == (6, 6)
        assert proj.outvars[0].aval.shape == (64, 6)
        assert len(_dots(jaxpr)) == 3

    def test_svt_zero_threshold_identity(self, rng):
        x = jnp.asarray(rng.normal(size=(50, 10)), jnp.float32)
        np.testing.assert_allclose(svt_gram(x, 0.0), x, atol=1e-4)

    def test_svt_large_threshold_zero(self, rng):
        x = jnp.asarray(rng.normal(size=(50, 10)), jnp.float32)
        np.testing.assert_allclose(svt_gram(x, 1e6), jnp.zeros_like(x), atol=1e-5)


class TestBucketLoopStructure:
    @pytest.mark.parametrize("tol", [None, 1e-6])
    def test_gram_loop_streams_state_twice(self, tol):
        """Each gram-mode ADMM iteration runs two matmuls over the vec
        dimension, both reading X = M - S + rho Y: the Gram and X @ P.  L
        reaches the tail through a barrier, so that XLA cannot fuse the
        tail into X @ P and keep X, L and M - L live at once."""
        m = jnp.ones((3, 64, 6), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda m: robust_pca_bucket(m, n_iter=4, tol=tol, svt_mode="gram").low_rank
        )(m).jaxpr
        (loop,) = [e for e in _eqns(jaxpr) if e.primitive.name in ("scan", "while")]
        body = loop.params["jaxpr" if tol is None else "body_jaxpr"].jaxpr
        gram, proj = _long_dots(body, 64)
        assert gram.invars[0] is gram.invars[1] is proj.invars[0]
        assert gram.outvars[0].aval.shape == (3, 6, 6)
        assert proj.outvars[0].aval.shape == (3, 64, 6)
        assert len(_dots(body)) == 3
        (user,) = [e for e in body.eqns if proj.outvars[0] in e.invars]
        assert user.primitive.name == "optimization_barrier"


class TestRPCA:
    def test_planted_recovery(self):
        low, sp = planted(512, 16, rank=2, sparsity=0.05)
        res = robust_pca(jnp.asarray(low + sp, jnp.float32), max_iter=500)
        assert res.residual < 1e-6
        assert np.linalg.norm(res.low_rank - low) / np.linalg.norm(low) < 0.08
        assert np.linalg.norm(res.sparse - sp) / np.linalg.norm(sp) < 0.12

    def test_reconstruction_invariant(self, rng):
        """M = L + S must hold at the stopping tolerance."""
        m = jnp.asarray(rng.normal(size=(128, 12)), jnp.float32)
        res = robust_pca(m, max_iter=300, tol=1e-6)
        resid = jnp.linalg.norm(m - res.low_rank - res.sparse) / jnp.linalg.norm(m)
        assert float(resid) < 1e-5

    def test_sparse_is_sparse(self):
        low, sp = planted(256, 16, rank=1, sparsity=0.03)
        res = robust_pca(jnp.asarray(low + sp, jnp.float32), max_iter=400)
        frac_nonzero = float(jnp.mean((jnp.abs(res.sparse) > 1e-3).astype(jnp.float32)))
        assert frac_nonzero < 0.15  # close to the 3% planted support

    def test_low_rank_is_low_rank(self):
        low, sp = planted(256, 16, rank=2, sparsity=0.03)
        res = robust_pca(jnp.asarray(low + sp, jnp.float32), max_iter=400)
        s = jnp.linalg.svd(res.low_rank, compute_uv=False)
        energy_top2 = float(jnp.sum(s[:2] ** 2) / jnp.maximum(jnp.sum(s**2), 1e-12))
        assert energy_top2 > 0.95

    def test_fixed_iters_matches_whileloop(self, rng):
        m = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        a = robust_pca_fixed_iters(m, n_iter=100)
        b = robust_pca(m, max_iter=100, tol=0.0)
        np.testing.assert_allclose(a.low_rank, b.low_rank, atol=1e-5)
        np.testing.assert_allclose(a.sparse, b.sparse, atol=1e-5)

    def test_batched(self, rng):
        ms = jnp.asarray(rng.normal(size=(5, 64, 8)), jnp.float32)
        res = batched_robust_pca(ms, n_iter=50)
        single = robust_pca_fixed_iters(ms[2], n_iter=50)
        np.testing.assert_allclose(res.low_rank[2], single.low_rank, atol=1e-5)

    def test_zero_matrix(self):
        m = jnp.zeros((32, 4), jnp.float32)
        res = robust_pca_fixed_iters(m, n_iter=10)
        assert np.all(np.isfinite(res.low_rank)) and np.all(np.isfinite(res.sparse))

    def test_jit_and_grad_safe(self, rng):
        m = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)
        out = jax.jit(lambda x: robust_pca_fixed_iters(x, n_iter=20).low_rank)(m)
        assert np.all(np.isfinite(out))


@settings(max_examples=20, deadline=None)
@given(
    t=st.floats(0.0, 5.0),
    n=st.integers(4, 60),
    m=st.integers(2, 12),
)
def test_soft_threshold_properties(t, n, m):
    rng = np.random.default_rng(42)
    x = jnp.asarray(rng.normal(size=(n, m)) * 3, jnp.float32)
    y = soft_threshold(x, t)
    # shrinkage: |y| <= max(|x| - t, 0), sign preserved or zeroed
    assert np.all(np.abs(y) <= np.maximum(np.abs(x) - t, 0) + 1e-6)
    assert np.all((y == 0) | (np.sign(y) == np.sign(x)))
    # 1-Lipschitz in t around 0: t=0 is identity
    np.testing.assert_allclose(soft_threshold(x, 0.0), x, atol=1e-7)
