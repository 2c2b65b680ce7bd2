"""Warm-started subspace-iteration SVT: single-call parity, ADMM warm-start
carry, rank adaptation, masked-cohort correctness, the fused Pallas sweep
tail, and engine parity for every method in both svt modes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    METHODS,
    AggregatorConfig,
    aggregate,
    robust_pca,
    robust_pca_fixed_iters,
    subspace_init,
    svt_gram,
    svt_subspace,
    svt_subspace_step,
    svt_svd,
)
from repro.core import rpca as rpca_lib
from repro.kernels import ref, svt_subspace as svt_kernel


def planted_bucket(rng, b, d, nc, rank=2, sparsity=0.05):
    """FedRPCA-structured bucket: shared low-rank core + sparse outliers."""
    low = rng.normal(size=(b, d, rank)) @ rng.normal(size=(b, rank, nc))
    spikes = rng.random((b, d, nc)) < sparsity
    sp = np.where(spikes, 5.0 * rng.normal(size=(b, d, nc)), 0.0)
    return jnp.asarray(low + sp, jnp.float32)


def planted_tree(rng, nc, rank=2):
    mk = lambda *s: jnp.asarray(
        np.moveaxis(np.asarray(planted_bucket(rng, 1, int(np.prod(s[1:])), nc, rank))[0], -1, 0)
        .reshape(nc, *s[1:]), jnp.float32,
    )
    return {
        "blocks": {"attn": {"A": mk(nc, 4, 6, 8), "B": mk(nc, 4, 8, 6)}},
        "head": mk(nc, 12, 4),
        "odd": mk(nc, 5, 10),
    }


class TestSVTSubspaceSingle:
    def test_cold_start_matches_gram_and_svd(self, rng):
        """Cold call = exact eigh path: parity with svt_gram / svt_svd."""
        x = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        for t in (0.5, 3.0, 100.0):
            res = svt_subspace(x, t)
            assert bool(res.fell_back)
            np.testing.assert_allclose(res.low_rank, svt_gram(x, t), atol=2e-5)
            np.testing.assert_allclose(res.low_rank, svt_svd(x, t), atol=5e-4, rtol=1e-3)

    def test_warm_call_exactly_low_rank(self, rng):
        """On an exactly-low-rank matrix the warm sweeps path (no fallback)
        reproduces the exact SVT."""
        u = rng.normal(size=(64, 2))
        w = rng.normal(size=(2, 12))
        x = jnp.asarray(u @ w, jnp.float32)
        cold = svt_subspace(x, 1.0)
        # small perturbation within the same column space
        x2 = jnp.asarray(u @ (w + 0.01 * rng.normal(size=w.shape)), jnp.float32)
        warm = svt_subspace(x2, 1.0, cold.v)
        assert not bool(warm.fell_back)
        np.testing.assert_allclose(warm.low_rank, svt_gram(x2, 1.0), atol=1e-4)
        assert int(warm.n_live) <= 3

    def test_saturation_falls_back(self, rng):
        """Dense spectrum above the threshold saturates the carried width and
        trips the exact fallback — the result stays exact, never truncated."""
        x = jnp.asarray(10.0 * rng.normal(size=(64, 8)), jnp.float32)
        cold = svt_subspace(x, 0.1, rank=2)
        warm = svt_subspace(x, 0.1, cold.v, rank=2)
        assert bool(warm.fell_back)
        # The exact path and svt_gram both form X (V c V^T) with the same
        # projector, one batched and one not, so only the order in which a
        # backend sums their matmuls may differ; that f32 error scales with
        # |x|.  x is 10x the unit data of the cold-start test, so its atol of
        # 2e-5 becomes 2e-4 here; a truncated SVT would be off by whole
        # singular values (~10).
        np.testing.assert_allclose(warm.low_rank, svt_gram(x, 0.1), atol=2e-4)

    def test_rejects_non_2d(self, rng):
        with pytest.raises(ValueError, match="2-D"):
            svt_subspace(jnp.zeros((2, 3, 4)), 1.0)


class TestWarmStartCarry:
    """svt_subspace_step threaded across ADMM-style iterations."""

    def _drive(self, ms, n_iter, rank=8, collect=None):
        """Hand-rolled subspace-mode ADMM loop (mirrors robust_pca_bucket)."""
        b, d1, nc = ms.shape
        dims_f = jnp.full((b,), d1, jnp.float32)
        abs_sum = jnp.sum(jnp.abs(ms), axis=(1, 2))
        mu = dims_f * nc / (4.0 * jnp.maximum(abs_sum, 1e-12))
        rho = 1.0 / mu
        thresh = rho / jnp.sqrt(jnp.maximum(dims_f, float(nc)))
        sub = subspace_init(ms, rank)
        l = s = y = jnp.zeros_like(ms)
        for it in range(n_iter):
            p, sub, fell = svt_subspace_step(rho, sub, cold=(it == 0))
            x = ms - s + rho[:, None, None] * y
            l = jnp.einsum("bdc,bce->bde", x, p)
            s = rpca_lib.soft_threshold(ms - l + rho[:, None, None] * y, thresh[:, None, None])
            y = y + mu[:, None, None] * (ms - l - s)
            x2 = ms - s + rho[:, None, None] * y
            sub = sub._replace(g=jnp.einsum("bdc,bde->bce", x2, x2))
            if collect is not None:
                collect(it, sub, bool(fell))
        return l, s, sub

    def test_basis_stays_near_orthonormal(self, rng):
        """CholeskyQR is semi-orthogonal (orthogonality loss scales with the
        squared condition of Z, and dead directions ride on jitter), so the
        carry must stay *near* orthonormal — never drift or blow up."""
        ms = planted_bucket(rng, 3, 48, 16)

        def check(it, sub, fell):
            vtv = np.asarray(jnp.einsum("bnr,bns->brs", sub.v, sub.v))
            r = vtv.shape[-1]
            diag = vtv[:, np.arange(r), np.arange(r)]
            off = vtv - diag[:, :, None] * np.eye(r)
            # dead (jitter-dominated) directions sag a little below unit
            # norm; live directions stay unit and everything stays bounded
            assert diag.min() > 0.8 and diag.max() < 1.05, diag
            assert np.abs(off).max() < 0.05, np.abs(off).max()

        self._drive(ms, 20, collect=check)

    def test_carry_loop_matches_bucket_driver(self, rng):
        """The hand-rolled carry loop == robust_pca_bucket(svt_mode=subspace):
        the warm-start state threads identically through the fori_loop."""
        ms = planted_bucket(rng, 3, 48, 16)
        l, s, _ = self._drive(ms, 30)
        res = rpca_lib.robust_pca_bucket(ms, n_iter=30, svt_mode="subspace")
        np.testing.assert_allclose(np.asarray(l), np.asarray(res.low_rank), atol=1e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(res.sparse), atol=1e-5)

    def test_warm_iterations_stop_falling_back(self, rng):
        """After the ADMM burn-in the eigh fallback stops firing — the whole
        point of the warm start."""
        ms = planted_bucket(rng, 3, 64, 16)
        fallbacks = []
        self._drive(ms, 30, collect=lambda it, sub, fell: fallbacks.append(fell))
        assert not any(fallbacks[-10:]), f"late-iteration fallbacks: {fallbacks}"
        assert all(fallbacks[:2])  # cold start + burn-in are exact

    def test_rank_adaptation_monotone_tail(self, rng):
        """The live-rank schedule tracks the post-shrink spectrum: it starts
        saturated during burn-in, is non-increasing once warm iterations
        begin, and settles at the planted rank (+ threshold stragglers)."""
        ms = planted_bucket(rng, 3, 64, 16, rank=2, sparsity=0.0)
        lives = []
        self._drive(ms, 30, collect=lambda it, sub, fell: lives.append(int(jnp.max(sub.n_live))))
        warm = lives[10:]
        assert all(a >= b for a, b in zip(warm, warm[1:])), f"non-monotone tail: {lives}"
        assert lives[-1] <= 4
        assert lives[0] >= lives[-1]


class TestBucketSubspaceMode:
    @pytest.mark.parametrize("nc", [8, 16])
    def test_matches_gram_mode(self, nc, rng):
        ms = planted_bucket(rng, 4, 64, nc)
        a = rpca_lib.robust_pca_bucket(ms, n_iter=40, svt_mode="gram")
        b = rpca_lib.robust_pca_bucket(ms, n_iter=40, svt_mode="subspace")
        np.testing.assert_allclose(b.low_rank, a.low_rank, atol=2e-4)
        np.testing.assert_allclose(b.sparse, a.sparse, atol=2e-4)

    def test_random_inputs_fall_back_to_exact(self, rng):
        """Dense-spectrum inputs ride the exact path throughout — bit-tight
        agreement with gram mode, never a truncated result."""
        ms = jnp.asarray(rng.normal(size=(3, 48, 8)), jnp.float32)
        a = rpca_lib.robust_pca_bucket(ms, n_iter=30, svt_mode="gram")
        b = rpca_lib.robust_pca_bucket(ms, n_iter=30, svt_mode="subspace")
        np.testing.assert_allclose(b.low_rank, a.low_rank, atol=1e-5)

    def test_padded_rows_stay_zero(self, rng):
        ms = planted_bucket(rng, 3, 40, 8)
        padded = jnp.pad(ms, ((0, 0), (0, 24), (0, 0)))
        res = rpca_lib.robust_pca_bucket(
            padded, jnp.full((3,), 40, jnp.int32), n_iter=30, svt_mode="subspace"
        )
        assert float(jnp.abs(res.low_rank[:, 40:]).max()) == 0.0
        assert float(jnp.abs(res.sparse[:, 40:]).max()) == 0.0
        # zero rows leave the Gram untouched, so the padded run follows the
        # unpadded one exactly (same carry, same fallback decisions)
        want = rpca_lib.robust_pca_bucket(
            ms, jnp.full((3,), 40, jnp.int32), n_iter=30, svt_mode="subspace"
        )
        np.testing.assert_allclose(res.low_rank[:, :40], want.low_rank, atol=1e-5)

    def test_masked_matches_dense_subcohort(self, rng):
        ms = planted_bucket(rng, 3, 40, 5)
        garbage = 100.0 * jnp.asarray(rng.normal(size=(3, 40, 3)), jnp.float32)
        padded = jnp.concatenate([ms, garbage], axis=-1)
        mask = jnp.asarray([1, 1, 1, 1, 1, 0, 0, 0], jnp.float32)
        got = rpca_lib.robust_pca_bucket(padded, client_mask=mask, n_iter=30,
                                         svt_mode="subspace", true_cols=5)
        want = rpca_lib.robust_pca_bucket(ms, n_iter=30, svt_mode="subspace")
        # true_cols caps the padded call's carried width by the live column
        # count, so both sides run r = (5+1)//2 = 3; the subspace
        # approximations may still differ by up to the fallback tolerance
        # (different static d2) — not bit-tight like gram mode.
        np.testing.assert_allclose(got.low_rank[..., :5], want.low_rank, atol=1e-3)
        np.testing.assert_allclose(got.sparse[..., :5], want.sparse, atol=1e-3)
        # inactive columns exactly zero (no eigh/projector leakage)
        assert float(jnp.abs(got.low_rank[..., 5:]).max()) == 0.0
        assert float(jnp.abs(got.sparse[..., 5:]).max()) == 0.0

    def test_tol_mode(self, rng):
        ms = planted_bucket(rng, 3, 48, 8)
        got = rpca_lib.robust_pca_bucket(ms, n_iter=100, tol=1e-5, svt_mode="subspace")
        want = rpca_lib.robust_pca_bucket(ms, n_iter=100, tol=1e-5, svt_mode="gram")
        np.testing.assert_allclose(got.low_rank, want.low_rank, atol=2e-4)
        # SVT approximation may shift the trip count by a step or two
        assert np.all(np.abs(np.asarray(got.n_iter) - np.asarray(want.n_iter)) <= 2)

    def test_single_matrix_wrappers(self, rng):
        ms = planted_bucket(rng, 1, 64, 8)[0]
        a = robust_pca_fixed_iters(ms, n_iter=30, svt_mode="subspace")
        b = rpca_lib.robust_pca_bucket(ms[None], n_iter=30, svt_mode="subspace")
        np.testing.assert_array_equal(np.asarray(a.low_rank), np.asarray(b.low_rank[0]))
        w = robust_pca(ms, max_iter=60, tol=1e-5, svt_mode="subspace")
        g = robust_pca(ms, max_iter=60, tol=1e-5, svt_mode="gram")
        np.testing.assert_allclose(w.low_rank, g.low_rank, atol=2e-4)

    def test_unknown_mode_rejected(self, rng):
        with pytest.raises(ValueError, match="svt_mode"):
            rpca_lib.robust_pca_bucket(jnp.zeros((1, 8, 4)), svt_mode="lanczos")


class TestFusedSweepTail:
    """kernels/svt_subspace.py vs the jnp oracle, and inside the bucket loop."""

    def _inputs(self, rng, b, d, nc):
        m, s, y = (jnp.asarray(rng.normal(size=(b, d, nc)), jnp.float32) for _ in range(3))
        p = jnp.asarray(rng.normal(size=(b, nc, nc)), jnp.float32)
        rho = jnp.asarray(rng.uniform(0.5, 2.0, b), jnp.float32)
        return m, s, y, p, rho, 1.0 / rho, rho * 0.1

    @pytest.mark.parametrize("b,d,nc", [(3, 64, 8), (2, 100, 12), (1, 1, 1)])
    @pytest.mark.parametrize("block_vec", [32, 512])
    def test_sweep(self, b, d, nc, block_vec, rng):
        m, s, y, p, rho, mu, th = self._inputs(rng, b, d, nc)
        got = svt_kernel.subspace_apply(
            m, s, y, p, rho, mu, th, block_vec=block_vec, interpret=True
        )
        want = ref.svt_subspace_apply_ref(m, s, y, p, rho, mu, th)
        for g, w, name in zip(got, want, ("L", "S", "Y", "rsq", "G")):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-4,
                                       rtol=1e-4, err_msg=name)

    def test_gram_accumulation_tiling_invariant(self, rng):
        """The next-iteration Gram accumulator must not depend on block_vec."""
        m, s, y, p, rho, mu, th = self._inputs(rng, 2, 250, 6)
        g_small = svt_kernel.subspace_apply(m, s, y, p, rho, mu, th,
                                            block_vec=16, interpret=True)[4]
        g_full = svt_kernel.subspace_apply(m, s, y, p, rho, mu, th,
                                           block_vec=512, interpret=True)[4]
        np.testing.assert_allclose(g_small, g_full, rtol=1e-4, atol=1e-3)

    def test_client_mask(self, rng):
        m, s, y, p, rho, mu, th = self._inputs(rng, 2, 40, 8)
        mask = jnp.asarray([1, 1, 1, 1, 1, 0, 0, 0], jnp.float32)
        got = svt_kernel.subspace_apply(m, s, y, p, rho, mu, th, mask=mask, interpret=True)
        want = ref.svt_subspace_apply_ref(m, s, y, p, rho, mu, th, mask=mask)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-4, rtol=1e-4)
        # masked columns of S'/Y' exactly zero
        assert float(jnp.abs(got[1][:, :, 5:]).max()) == 0.0
        assert float(jnp.abs(got[2][:, :, 5:]).max()) == 0.0

    def test_bucket_fused_matches_jnp(self, rng):
        ms = planted_bucket(rng, 3, 64, 8)
        plain = rpca_lib.robust_pca_bucket(ms, n_iter=30, svt_mode="subspace")
        fused = rpca_lib.robust_pca_bucket(
            ms, n_iter=30, svt_mode="subspace", fused_tail=True, interpret=True
        )
        np.testing.assert_allclose(fused.low_rank, plain.low_rank, atol=2e-5)
        np.testing.assert_allclose(fused.sparse, plain.sparse, atol=2e-5)

    def test_bucket_fused_masked(self, rng):
        ms = planted_bucket(rng, 2, 48, 8)
        mask = jnp.asarray([1, 1, 1, 1, 1, 1, 0, 0], jnp.float32)
        plain = rpca_lib.robust_pca_bucket(ms, client_mask=mask, n_iter=20,
                                           svt_mode="subspace")
        fused = rpca_lib.robust_pca_bucket(
            ms, client_mask=mask, n_iter=20, svt_mode="subspace",
            fused_tail=True, interpret=True,
        )
        np.testing.assert_allclose(fused.low_rank, plain.low_rank, atol=2e-5)
        np.testing.assert_allclose(fused.sparse, plain.sparse, atol=2e-5)


SVT_TOL = dict(atol=5e-4, rtol=1e-4)


def assert_trees_close(a, b, **tol):
    tol = tol or SVT_TOL
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32), **tol
        ),
        a,
        b,
    )


METHOD_CONFIGS = [
    pytest.param(AggregatorConfig(method="fedavg"), id="fedavg"),
    pytest.param(AggregatorConfig(method="task_arithmetic", beta=2.5), id="task_arithmetic"),
    pytest.param(AggregatorConfig(method="ties", ties_keep=0.2), id="ties"),
    pytest.param(AggregatorConfig(method="fedexp"), id="fedexp"),
    pytest.param(AggregatorConfig(method="dare", dare_drop=0.5), id="dare"),
    pytest.param(AggregatorConfig(method="fedrpca", rpca_iters=25), id="fedrpca"),
]


class TestEngineParityBothModes:
    """Packed == reference for every method under both svt modes, dense,
    masked and weighted (fedrpca is the only consumer of svt_mode; the rest
    prove the flag is inert for them)."""

    @pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
    @pytest.mark.parametrize("cfg", METHOD_CONFIGS)
    def test_dense(self, cfg, svt_mode, rng):
        tree = planted_tree(rng, 6)
        cfg = cfg.replace(svt_mode=svt_mode)
        key = jax.random.PRNGKey(7)
        want = aggregate(tree, cfg, engine="reference", key=key)
        got = aggregate(tree, cfg, engine="packed", key=key)
        assert_trees_close(want, got)

    @pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
    @pytest.mark.parametrize("cfg", METHOD_CONFIGS)
    def test_masked_weighted(self, cfg, svt_mode, rng):
        tree = planted_tree(rng, 8)
        cfg = cfg.replace(svt_mode=svt_mode)
        key = jax.random.PRNGKey(3)
        mask = (jnp.arange(8) < 5).astype(jnp.float32)
        w = jnp.asarray(rng.uniform(0.5, 2.0, 8), jnp.float32)
        want = aggregate(tree, cfg, engine="reference", key=key, mask=mask, weights=w)
        got = aggregate(tree, cfg, engine="packed", key=key, mask=mask, weights=w)
        assert_trees_close(want, got)

    def test_all_methods_covered(self):
        assert {p.values[0].method for p in METHOD_CONFIGS} == set(METHODS)

    @pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
    def test_masked_equals_dense_subcohort(self, svt_mode, rng):
        tree = planted_tree(rng, 8)
        cfg = AggregatorConfig(method="fedrpca", rpca_iters=20, svt_mode=svt_mode)
        mask = (jnp.arange(8) < 5).astype(jnp.float32)
        got = aggregate(tree, cfg, engine="packed", mask=mask)
        take = jax.tree_util.tree_map(lambda x: x[:5], tree)
        want = aggregate(take, cfg, engine="packed", mask=jnp.ones(5))
        if svt_mode == "subspace":
            # The mask is dynamic, so the 8-slot call carries width
            # r = ceil(8/2) = 4 while the true 5-cohort carries
            # r = ceil(5/2) = 3: two different subspace approximations of
            # the same split, close but not bit-tight (plan_aggregation's
            # static cohort_size hint is how the fed path pins them equal).
            assert_trees_close(want, got, rtol=1e-4, atol=2e-3)
        else:
            assert_trees_close(want, got)

    def test_unknown_svt_mode_rejected(self, rng):
        tree = planted_tree(rng, 4)
        with pytest.raises(ValueError, match="svt_mode"):
            aggregate(tree, AggregatorConfig(svt_mode="lanczos"))


class TestImportanceWeightedRPCA:
    """weighting="data_size_rpca": weights shape the subspace, both engines."""

    @pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
    def test_cross_engine(self, svt_mode, rng):
        tree = planted_tree(rng, 6)
        cfg = AggregatorConfig(method="fedrpca", rpca_iters=15,
                               weighting="data_size_rpca", svt_mode=svt_mode)
        w = jnp.asarray(rng.uniform(0.5, 2.0, 6), jnp.float32)
        want = aggregate(tree, cfg, engine="reference", weights=w)
        got = aggregate(tree, cfg, engine="packed", weights=w)
        assert_trees_close(want, got)

    def test_masked_equals_dense(self, rng):
        tree = planted_tree(rng, 8)
        cfg = AggregatorConfig(method="fedrpca", rpca_iters=15, weighting="data_size_rpca")
        w = jnp.asarray(rng.uniform(0.5, 2.0, 8), jnp.float32)
        mask = (jnp.arange(8) < 5).astype(jnp.float32)
        got = aggregate(tree, cfg, engine="packed", mask=mask, weights=w)
        take = jax.tree_util.tree_map(lambda x: x[:5], tree)
        want = aggregate(take, cfg, engine="packed", mask=jnp.ones(5), weights=w[:5])
        assert_trees_close(want, got)

    def test_uniform_weights_match_plain(self, rng):
        """Equal weights x n_eff = 1 -> the column scaling is a no-op."""
        tree = planted_tree(rng, 6)
        base = AggregatorConfig(method="fedrpca", rpca_iters=15)
        plain = aggregate(tree, base, engine="packed")
        scaled = aggregate(tree, base.replace(weighting="data_size_rpca"),
                           engine="packed", weights=jnp.ones(6))
        assert_trees_close(plain, scaled, atol=5e-6, rtol=1e-5)

    def test_weights_shape_the_subspace(self, rng):
        """Heavily up-weighting one client must change the recovered
        low-rank component, not just the final mean."""
        tree = {"w": planted_bucket(rng, 1, 24, 6).transpose(0, 2, 1).reshape(6, 4, 6)}
        w_skew = jnp.asarray([10.0, 1, 1, 1, 1, 1], jnp.float32)
        cfg_scale = AggregatorConfig(method="fedrpca", rpca_iters=25,
                                     weighting="data_size_rpca")
        cfg_mean = AggregatorConfig(method="fedrpca", rpca_iters=25,
                                    weighting="data_size")
        a = aggregate(tree, cfg_scale, engine="packed", weights=w_skew)
        b = aggregate(tree, cfg_mean, engine="packed", weights=w_skew)
        assert float(jnp.max(jnp.abs(a["w"] - b["w"]))) > 1e-4
