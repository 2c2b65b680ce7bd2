"""Mesh-sharded aggregation (DESIGN.md §10): shard-count invariance.

The contract under test: sharding a bucket over host devices is a pure
execution-layout choice.  The RPCA loop shards the vec rows and, in both
SVT modes, exchanges only (B, C, C) Grams and projectors.  Every method,
both SVT modes, masked cohorts, RAGGED cohorts (d2 % shards != 0), the
shard-local fused Pallas tail (``rpca_fused_tail``), and cross-round carry
must produce the same numbers at 1, 2, and 4 shards (bitwise at one shard,
fp32-allclose beyond, where only the collective reduction order differs),
and the warm-carry path must stay eigh-fallback-free under sharding exactly
as it is on one device.

The multi-device half of the suite needs 4 forced host devices
(XLA_FLAGS=--xla_force_host_platform_device_count=4 — the CI mesh job
sets it; conftest.py deliberately never does) and self-skips otherwise,
so the tier-1 run stays single-device.  The loop's layout test and its
parity test run on four forced host devices in a subprocess, so they run
in tier-1 too.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AggregatorConfig, AggSession, aggregate
from repro.core import rpca as rpca_lib
from repro.core.engine import plan_aggregation
from repro.launch import costmodel
from repro.launch.mesh import client_shard_count, make_debug_mesh, make_host_mesh
from repro.models import partitioning

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4",
)


def planted_bucket(rng, b=2, d=24, nc=8):
    """Low-rank core + sparse spikes: the FedRPCA workload model."""
    u = rng.normal(size=(b, d, 2))
    w = rng.normal(size=(b, 2, nc))
    sp = np.where(rng.random((b, d, nc)) < 0.05,
                  5.0 * rng.normal(size=(b, d, nc)), 0.0)
    return jnp.asarray(u @ w + sp, jnp.float32)


def round_trees(rng, nc=8, rounds=4, drift=0.02):
    """Correlated multi-round deltas (drifting shared core + persistent
    spikes) — the regime where warm carry rounds stay fallback-free."""
    shapes = {"A": (4, 6, 8), "head": (12, 4)}
    cores, spikes = {}, {}
    for k, s in shapes.items():
        d = int(np.prod(s))
        cores[k] = (rng.normal(size=(d, 2)), rng.normal(size=(2, nc)))
        supp = rng.random((d, nc)) < 0.05
        spikes[k] = np.where(supp, 5.0 * rng.normal(size=(d, nc)), 0.0)
    out = []
    for _t in range(rounds):
        tree = {}
        for k, s in shapes.items():
            u, w = cores[k]
            w_t = w + drift * rng.normal(size=w.shape)
            sp_t = spikes[k] * (1.0 + 0.05 * rng.normal(size=spikes[k].shape))
            tree[k] = jnp.asarray((u @ w_t + sp_t).T.reshape(nc, *s), jnp.float32)
        out.append(tree)
    return out


def session_cfg(**kw):
    base = dict(
        method="fedrpca", rpca_iters=60, rpca_fixed_iters=False, rpca_tol=1e-5,
        svt_mode="subspace", carry_mode="subspace",
    )
    base.update(kw)
    return AggregatorConfig(**base)


def assert_trees_close(a, b, atol=1e-4, rtol=1e-4):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            atol=atol, rtol=rtol,
        ),
        a, b,
    )


class TestSingleDevice:
    """Always-run half: the one-shard path and the static plumbing."""

    @pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
    def test_one_shard_delegates_bitwise(self, rng, svt_mode):
        """At one client shard the mesh call must BE the one-device loop
        (called directly, before any shard_map), not a 1-shard shard_map
        of it — pinned bitwise, not allclose."""
        m = planted_bucket(rng)
        ref = rpca_lib.robust_pca_bucket(m, n_iter=15, svt_mode=svt_mode)
        for mesh in (None, make_debug_mesh()):
            got = rpca_lib.robust_pca_bucket(
                m, mesh=mesh, n_iter=15, svt_mode=svt_mode
            )
            assert np.array_equal(np.asarray(ref.low_rank), np.asarray(got.low_rank))
            assert np.array_equal(np.asarray(ref.sparse), np.asarray(got.sparse))

    def test_plan_normalizes_one_shard_mesh(self, rng):
        """A 1-client-shard mesh IS the single-device path: the plan pins
        mesh=None so downstream jit caches can never split on it."""
        tree = {"w": jnp.asarray(rng.normal(size=(8, 4, 8)), jnp.float32)}
        plan = plan_aggregation(tree, AggregatorConfig(method="fedrpca"),
                                mesh=make_debug_mesh())
        assert plan.mesh is None

    def test_make_host_mesh_validates(self):
        with pytest.raises(ValueError):
            make_host_mesh(0)
        with pytest.raises(RuntimeError, match="xla_force_host_platform_device_count"):
            make_host_mesh(4096)

    def test_shard_count_helpers_agree(self):
        meshes = [None, make_debug_mesh()]
        if jax.device_count() >= 2:
            meshes.append(make_host_mesh(2))
        for mesh in meshes:
            assert client_shard_count(mesh) == rpca_lib.mesh_client_shards(mesh)

    def test_bucket_carry_pspecs_match_layout(self):
        """The specs the sharded loop maps over: vec-row-sharded bucket and
        l/s/y, replicated basis and scalars; two client axes split the rows
        over both."""
        P = jax.sharding.PartitionSpec
        row, specs = rpca_lib.bucket_pspecs(("data",))
        assert isinstance(specs, rpca_lib.BucketCarry)
        assert row == P(None, "data", None)
        assert specs.l == row and specs.s == row and specs.y == row
        for rep in (specs.v, specs.n_live, specs.n_eff, specs.valid,
                    specs.fall_count, specs.hit):
            assert rep == P()
        row2, _ = rpca_lib.bucket_pspecs(("pod", "data"))
        assert row2 == P(None, ("pod", "data"), None)

    def test_mesh_agg_costs_sanity(self):
        kw = dict(n_modules=8, padded_vec=64, cohort=64, rpca_iters=20)
        with pytest.raises(ValueError):
            costmodel.mesh_agg_costs(shards=0, cohort=64, n_modules=8,
                                     padded_vec=64)
        c1 = costmodel.mesh_agg_costs(shards=1, **kw)
        c4 = costmodel.mesh_agg_costs(shards=4, **kw)
        warm4 = costmodel.mesh_agg_costs(shards=4, warm=True, **kw)
        cold4 = costmodel.mesh_agg_costs(shards=4, warm=False, **kw)
        assert c1["us"] > 0 and c4["us"] > 0
        # Sharding's guaranteed win: per-device resident footprint.
        assert c4["peak_bytes_per_shard"] < c1["peak_bytes_per_shard"]
        # Warm rounds skip the gather + replicated Gram/eigh burn-in.
        assert warm4["us"] < cold4["us"]
        assert warm4["gather_bytes"] == 0.0
        # One shard has nobody to talk to.
        assert c1["allreduce_bytes"] == 0.0
        cross = costmodel.mesh_crossover_shards(
            n_modules=8, padded_vec=64, cohort=512
        )
        assert cross is None or (cross & (cross - 1)) == 0

    def test_mesh_agg_costs_ragged_fused_overlap(self):
        """Ragged cohorts cost the padded slice; fused cuts local HBM
        traffic at the same FLOPs."""
        # 65 clients over 3 shards no longer refuses: it pads to 66 and
        # charges ceil(65 / 3) = 22 local columns, same as cohort 66.
        ragged = costmodel.mesh_agg_costs(shards=3, cohort=65, n_modules=8,
                                          padded_vec=64)
        padded = costmodel.mesh_agg_costs(shards=3, cohort=66, n_modules=8,
                                          padded_vec=64)
        assert ragged["local_hbm_bytes"] == padded["local_hbm_bytes"]
        kw = dict(n_modules=8, padded_vec=64, cohort=64, shards=4,
                  rpca_iters=20)
        base = costmodel.mesh_agg_costs(**kw)
        fused = costmodel.mesh_agg_costs(fused_tail=True, **kw)
        assert fused["local_hbm_bytes"] < base["local_hbm_bytes"]
        assert fused["local_flops"] == base["local_flops"]

    def test_padded_cohort_helper(self):
        assert partitioning.padded_cohort(8, 4) == 8
        assert partitioning.padded_cohort(7, 4) == 8
        assert partitioning.padded_cohort(65, 3) == 66
        assert partitioning.padded_cohort(1, 4) == 4
        with pytest.raises(ValueError):
            partitioning.padded_cohort(8, 0)


METHOD_CONFIGS = [
    pytest.param(AggregatorConfig(method="fedavg"), id="fedavg"),
    pytest.param(AggregatorConfig(method="task_arithmetic", beta=2.5),
                 id="task_arithmetic"),
    pytest.param(AggregatorConfig(method="ties", ties_keep=0.2), id="ties"),
    pytest.param(AggregatorConfig(method="fedexp"), id="fedexp"),
    pytest.param(AggregatorConfig(method="dare", dare_drop=0.5), id="dare"),
    pytest.param(AggregatorConfig(method="fedrpca", rpca_iters=25,
                                  svt_mode="subspace"), id="fedrpca-subspace"),
    pytest.param(AggregatorConfig(method="fedrpca", rpca_iters=25), id="fedrpca-gram"),
    pytest.param(
        AggregatorConfig(method="fedrpca", rpca_fixed_iters=False,
                         rpca_tol=1e-4, rpca_iters=50),
        id="fedrpca-tol",
    ),
]


@needs4
class TestShardInvariance:
    """Multi-device half: 1 vs 2 vs 4 shards must agree fp32-allclose."""

    def _tree(self, rng, nc=8):
        mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
        return {"A": mk(nc, 4, 6, 8), "head": mk(nc, 12, 4)}

    @pytest.mark.parametrize("cfg", METHOD_CONFIGS)
    def test_methods_masked(self, cfg, rng):
        """Every method, masked partial-participation cohort: packed engine
        on a 2- and 4-shard mesh matches the unsharded packed run and the
        reference oracle."""
        tree = self._tree(rng)
        mask = jnp.asarray([1, 1, 0, 1, 1, 1, 0, 1], jnp.float32)
        key = jax.random.PRNGKey(3)
        ref = aggregate(tree, cfg, engine="reference", mask=mask, key=key)
        base = aggregate(tree, cfg, engine="packed", mask=mask, key=key)
        assert_trees_close(ref, base, atol=1e-5, rtol=1e-5)
        for shards in (2, 4):
            got = aggregate(tree, cfg, engine="packed", mask=mask, key=key,
                            mesh=make_host_mesh(shards))
            assert_trees_close(base, got, atol=1e-5, rtol=1e-5)

    def test_tol_mode_trip_counts_match(self, rng):
        """Tolerance-driven ADMM must take the SAME number of iterations
        sharded and not: the while-condition reduces over a psum'd
        residual, so the trip count is a sharp invariance probe."""
        m = planted_bucket(rng, b=3, d=32, nc=8)
        ref = rpca_lib.robust_pca_bucket(m, n_iter=50, tol=1e-4,
                                         svt_mode="subspace")
        for shards in (2, 4):
            got = rpca_lib.robust_pca_bucket(
                m, mesh=make_host_mesh(shards), n_iter=50, tol=1e-4,
                svt_mode="subspace",
            )
            assert np.array_equal(np.asarray(ref.n_iter), np.asarray(got.n_iter))
            np.testing.assert_allclose(np.asarray(ref.low_rank),
                                       np.asarray(got.low_rank),
                                       atol=1e-5, rtol=1e-5)

    def test_plan_accepts_ragged_and_fused(self, rng):
        """The PR 7 refusals are now capabilities: ragged cohorts shard as
        they are (the loop splits vec rows), and the fused Pallas tail runs
        shard-locally — both plan clean on a multi-shard mesh."""
        mesh = make_host_mesh(2)
        odd = {"w": jnp.asarray(rng.normal(size=(7, 4, 8)), jnp.float32)}
        plan = plan_aggregation(odd, AggregatorConfig(method="fedrpca"),
                                mesh=mesh)
        assert plan.mesh is mesh
        even = {"w": jnp.asarray(rng.normal(size=(8, 4, 8)), jnp.float32)}
        plan = plan_aggregation(
            even,
            AggregatorConfig(method="fedrpca", rpca_fused_tail=True),
            mesh=mesh,
        )
        assert plan.mesh is mesh

    def test_reference_engine_refuses_mesh(self, rng):
        tree = self._tree(rng)
        with pytest.raises(ValueError, match="reference engine"):
            aggregate(tree, AggregatorConfig(method="fedrpca"),
                      engine="reference", mesh=make_host_mesh(2))


@needs4
class TestShardedCarry:
    """Cross-round carry under sharding: warm equivalence and the
    zero-fallback contract."""

    def _run(self, mesh, trees):
        sess = AggSession(session_cfg(), mesh=mesh)
        outs, falls, hits = [], [], []
        for tree in trees:
            out, diag = sess.step(tree)
            outs.append(jax.tree_util.tree_map(np.asarray, out))
            falls.append(int(diag.scalars["fallback_count"]))
            hits.append(float(diag.scalars["carry_hit_rate"]))
        return outs, falls, hits

    def test_warm_carry_equivalent_across_shard_counts(self, rng):
        trees = round_trees(rng, nc=8, rounds=4)
        base_outs, base_falls, _ = self._run(None, trees)
        for shards in (2, 4):
            outs, falls, _ = self._run(make_host_mesh(shards), trees)
            assert falls == base_falls
            for a, b in zip(base_outs, outs):
                assert_trees_close(a, b)

    def test_gram_full_carry_equivalent_across_shard_counts(self, rng):
        """Gram mode with the full L/S/Y carry: the row-sharded loop takes
        and returns the carry, on a ragged cohort, as one device does."""
        trees = round_trees(rng, nc=7, rounds=3)

        def run(mesh):
            sess = AggSession(session_cfg(svt_mode="gram", carry_mode="full"),
                              mesh=mesh)
            outs, hits = [], []
            for tree in trees:
                out, diag = sess.step(tree)
                outs.append(jax.tree_util.tree_map(np.asarray, out))
                hits.append(float(diag.scalars["carry_hit_rate"]))
            return outs, hits

        base_outs, base_hits = run(None)
        for shards in (2, 4):
            outs, hits = run(make_host_mesh(shards))
            assert hits == base_hits
            for a, b in zip(base_outs, outs):
                assert_trees_close(a, b)

    def test_warm_rounds_fallback_free_sharded(self, rng):
        """The acceptance bar: on correlated rounds, the 4-shard warm path
        reuses the carried subspace every round — zero eigh fallbacks and a
        full carry hit rate, exactly like one device."""
        trees = round_trees(rng, nc=8, rounds=4)
        _, falls, hits = self._run(make_host_mesh(4), trees)
        assert all(f == 0 for f in falls[1:])
        assert all(h == 1.0 for h in hits[1:])


@needs4
class TestRaggedCohorts:
    """d2 % shards != 0: the loop splits vec rows, so a ragged cohort
    shards as it is — results must match the unsharded run."""

    def _tree(self, rng, nc):
        mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
        return {"A": mk(nc, 4, 6, 8), "head": mk(nc, 12, 4)}

    @pytest.mark.parametrize("cfg", METHOD_CONFIGS)
    @pytest.mark.parametrize("shards", [2, 4])
    def test_methods_ragged_masked(self, cfg, shards, rng):
        """Every method on a 7-client cohort (ragged at both shard counts)
        with a partial-participation mask on top: sharded matches the
        unsharded packed engine fp32-allclose."""
        tree = self._tree(rng, nc=7)
        mask = jnp.asarray([1, 1, 0, 1, 1, 1, 1], jnp.float32)
        key = jax.random.PRNGKey(3)
        base = aggregate(tree, cfg, engine="packed", mask=mask, key=key)
        got = aggregate(tree, cfg, engine="packed", mask=mask, key=key,
                        mesh=make_host_mesh(shards))
        assert_trees_close(base, got, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("shards", [2, 4])
    @pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
    def test_rpca_ragged_matches_unsharded(self, shards, svt_mode, rng):
        m = planted_bucket(rng, b=3, d=32, nc=7)
        ref = rpca_lib.robust_pca_bucket(m, n_iter=20, svt_mode=svt_mode)
        got = rpca_lib.robust_pca_bucket(
            m, mesh=make_host_mesh(shards), n_iter=20, svt_mode=svt_mode
        )
        np.testing.assert_allclose(np.asarray(ref.low_rank),
                                   np.asarray(got.low_rank),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ref.sparse),
                                   np.asarray(got.sparse),
                                   atol=1e-5, rtol=1e-5)

    def test_padded_columns_contribute_zero(self, rng):
        """The zero-contribution invariant: a masked (= padded) column's
        CONTENT must be unobservable — garbage behind the mask decomposes
        bitwise identically to zeros behind the mask.  If a masked column
        leaked into any psum / Gram / n_eff term, the 1e3-scaled garbage
        would move the result."""
        m7 = planted_bucket(rng, b=2, d=24, nc=7)
        zeros = jnp.zeros((2, 24, 1), jnp.float32)
        garbage = 1e3 * jnp.asarray(rng.normal(size=(2, 24, 1)), jnp.float32)
        cmask = jnp.asarray([1, 1, 1, 1, 1, 1, 1, 0], jnp.float32)
        mesh = make_host_mesh(4)
        ref = rpca_lib.robust_pca_bucket(
            jnp.concatenate([m7, zeros], axis=-1), mesh=mesh, n_iter=20,
            svt_mode="subspace", client_mask=cmask,
        )
        got = rpca_lib.robust_pca_bucket(
            jnp.concatenate([m7, garbage], axis=-1), mesh=mesh, n_iter=20,
            svt_mode="subspace", client_mask=cmask,
        )
        assert np.array_equal(np.asarray(ref.low_rank), np.asarray(got.low_rank))
        assert np.array_equal(np.asarray(ref.sparse), np.asarray(got.sparse))
        # The masked column itself comes out exactly zero.
        assert np.all(np.asarray(got.sparse[:, :, 7:]) == 0.0)

    def test_ragged_warm_carry(self, rng):
        """Cross-round carry on a ragged cohort: same outputs and the same
        zero-fallback warm trajectory at 1 / 2 / 4 shards (the carried
        eigenbasis round-trips through the padded layout).  nc=9 stays
        ragged at both shard counts while leaving the rank cap
        (r = ceil(9/2) = 5) headroom above the planted rank-2 core; the
        ceil cap keeps nc=7 (r=4) fallback-free too now —
        tests/test_uplink.py::test_odd_cohort_warm_fallback_free pins
        that directly."""
        trees = round_trees(rng, nc=9, rounds=4)

        def run(mesh):
            sess = AggSession(session_cfg(), mesh=mesh)
            outs, falls = [], []
            for tree in trees:
                out, diag = sess.step(tree)
                outs.append(jax.tree_util.tree_map(np.asarray, out))
                falls.append(int(diag.scalars["fallback_count"]))
            return outs, falls

        base_outs, base_falls = run(None)
        for shards in (2, 4):
            outs, falls = run(make_host_mesh(shards))
            assert falls == base_falls
            assert all(f == 0 for f in falls[1:])
            for a, b in zip(base_outs, outs):
                assert_trees_close(a, b)


@needs4
class TestShardedFusedTail:
    """rpca_fused_tail under sharding: the Pallas ADMM / sweep tails run
    shard-locally on vec-row slices, their partial sums psum-reduced — the
    same numbers as the unsharded fused run."""

    @pytest.mark.parametrize("shards", [2, 4])
    @pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
    @pytest.mark.parametrize("nc", [8, 7])
    def test_fused_matches_unsharded(self, shards, svt_mode, nc, rng):
        m = planted_bucket(rng, b=3, d=32, nc=nc)
        ref = rpca_lib.robust_pca_bucket(m, n_iter=20, svt_mode=svt_mode,
                                         fused_tail=True)
        got = rpca_lib.robust_pca_bucket(
            m, mesh=make_host_mesh(shards), n_iter=20, svt_mode=svt_mode,
            fused_tail=True,
        )
        np.testing.assert_allclose(np.asarray(ref.low_rank),
                                   np.asarray(got.low_rank),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(ref.sparse),
                                   np.asarray(got.sparse),
                                   atol=2e-4, rtol=2e-4)

    def test_one_shard_fused_delegates_bitwise(self, rng):
        m = planted_bucket(rng)
        ref = rpca_lib.robust_pca_bucket(m, n_iter=15, svt_mode="subspace",
                                         fused_tail=True)
        got = rpca_lib.robust_pca_bucket(
            m, mesh=make_debug_mesh(), n_iter=15, svt_mode="subspace",
            fused_tail=True,
        )
        assert np.array_equal(np.asarray(ref.low_rank), np.asarray(got.low_rank))
        assert np.array_equal(np.asarray(ref.sparse), np.asarray(got.sparse))

    def test_fused_warm_carry_fallback_free(self, rng):
        """Warm-carry rounds through the fused sharded tail (ragged cohort
        — nc=9): zero eigh fallbacks after round 0 and outputs matching the
        unfused sharded session."""
        trees = round_trees(rng, nc=9, rounds=4)
        mesh = make_host_mesh(4)

        def run(**kw):
            sess = AggSession(session_cfg(**kw), mesh=mesh)
            outs, falls = [], []
            for tree in trees:
                out, diag = sess.step(tree)
                outs.append(jax.tree_util.tree_map(np.asarray, out))
                falls.append(int(diag.scalars["fallback_count"]))
            return outs, falls

        base_outs, _ = run()
        outs, falls = run(rpca_fused_tail=True)
        assert all(f == 0 for f in falls[1:])
        for a, b in zip(base_outs, outs):
            assert_trees_close(a, b, atol=5e-4, rtol=5e-4)


#: A collective in compiled HLO text, whatever its result type (a tuple too).
COLLECTIVE = re.compile(
    r"= (.+?) (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


@needs4
@pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
def test_sharded_collectives_run_under_agg_psum(svt_mode):
    """Every collective of the sharded ADMM loop carries the ``agg.psum``
    scope inside ``agg.admm`` in its HLO ``op_name``: what a device trace's
    collective time is attributed by.  The only cross-shard work outside
    the loop is the tail's per-module reductions (``agg.tail``)."""
    from repro.launch import steps as steps_lib

    tree = round_trees(np.random.default_rng(0), rounds=1)[0]
    cfg = AggregatorConfig(method="fedrpca", rpca_iters=3, svt_mode=svt_mode)
    step = steps_lib.make_agg_step(cfg, mesh=make_host_mesh(4))
    text = jax.jit(step).lower(tree).compile().as_text()
    collectives = [ln for ln in text.splitlines() if COLLECTIVE.search(ln)]
    assert any("agg.admm/" in ln for ln in collectives)
    for ln in collectives:
        assert re.search(r'op_name="[^"]*(agg\.admm/[^"]*agg\.psum|agg\.tail/)', ln), ln


#: Compiles the sharded agg step on four forced host devices and prints its
#: HLO text: the layout test runs in the tier-1 process, which has one
#: device.
LAYOUT_RUN = """
import sys
import jax
from repro.core import AggregatorConfig
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from test_mesh_agg import ragged_layout_tree

cfg = AggregatorConfig(method="fedrpca", rpca_iters=3, svt_mode=sys.argv[1],
                       rpca_fused_tail=sys.argv[2] == "fused")
step = steps.make_agg_step(cfg, mesh=make_host_mesh(4))
print(jax.jit(step).lower(ragged_layout_tree()).compile().as_text())
"""


def ragged_layout_tree():
    """7 clients, one bucket of 5 modules at vec 256: neither the cohort
    nor the module count is a multiple of 4."""
    rng = np.random.default_rng(0)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return {"A": mk(7, 4, 16, 16), "head": mk(7, 16, 16)}


def run_on_four_devices(script: str, *args: str) -> str:
    """Run ``script`` in a fresh Python on four forced host devices, with
    this directory importable; returns its stdout."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([src, here])}
    p = subprocess.run([sys.executable, "-c", script, *args], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


@pytest.mark.parametrize("tail", ["xla", "fused"])
@pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
def test_sharded_loop_exchanges_grams_not_rows(svt_mode, tail):
    """Compiled on four devices, the sharded ADMM loop exchanges only
    (B, C, C) blocks in both SVT modes: no collective inside its ``while``
    carries more than ``B_pad * C * C`` floats or the bucket's vec length,
    and each device's eigh batch is ``B_pad / 4`` modules (the subspace
    mode's Ritz eighs are ``r x r``)."""
    from repro.core.engine import pack

    (b, vec), = pack(ragged_layout_tree())[1].bucket_dims.values()
    c, shards = 7, 4
    b_pad = shards * -(-b // shards)
    r = rpca_lib.subspace_rank(c, AggregatorConfig().svt_rank)
    assert (b, c) == (5, 7) and vec > c
    text = run_on_four_devices(LAYOUT_RUN, svt_mode, tail)
    loop = [ln for ln in text.splitlines()
            if re.search(r'op_name="[^"]*agg\.admm/[^"]*/while/', ln)]
    dims = lambda ty: [[int(d) for d in s.split(",") if d]
                       for s in re.findall(r"[a-z]+[0-9]*\[([0-9,]*)\]", ty)]
    kinds = set()
    for ln in loop:
        m = COLLECTIVE.search(ln)
        if m is None:
            continue
        kinds.add(m.group(2))
        shapes = dims(m.group(1))
        assert sum(int(np.prod(s)) for s in shapes) <= b_pad * c * c, ln
        assert not any(vec in s or vec // shards in s for s in shapes), ln
    assert {"reduce-scatter", "all-gather"} <= kinds
    eighs = [dims(ln.split(" custom-call(")[0])[0] for ln in loop
             if " custom-call(" in ln and "/eigh" in ln]
    full = [b_pad // shards, c, c]
    ritz = [b_pad // shards, r, r] if svt_mode == "subspace" else full
    assert full in eighs and all(s in (full, ritz) for s in eighs), eighs


#: Runs robust_pca_bucket on four forced host devices and on one, on the
#: same warm-carried rounds of a ragged bucket, for every SVT mode and tail;
#: prints one JSON line per case with the largest differences.
PARITY_RUN = """
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import rpca
from repro.launch.mesh import make_host_mesh

B, D1, C, R, ITERS = 5, 30, 7, 8, 40
rng = np.random.default_rng(0)
u, w = rng.normal(size=(B, D1, 2)), rng.normal(size=(B, 2, C))
spikes = np.where(rng.random((B, D1, C)) < 0.05, 5.0 * rng.normal(size=(B, D1, C)), 0.0)
rounds = [jnp.asarray(u @ (w + 0.02 * rng.normal(size=w.shape)) + spikes, jnp.float32)
          for _ in range(3)]
mesh = make_host_mesh(4)
for mode in ("gram", "subspace"):
    for fused in (False, True):
        def run(mesh):
            fn = jax.jit(lambda m, c: rpca.robust_pca_bucket(
                m, mesh=mesh, n_iter=ITERS, tol=1e-5, svt_mode=mode,
                fused_tail=fused, svt_rank=R, carry=c, return_carry=True))
            carry, outs = rpca.init_bucket_carry(B, D1, C, R), []
            for m in rounds:
                res, carry = fn(m, carry)
                outs.append((np.asarray(res.low_rank), np.asarray(res.sparse),
                             np.asarray(res.n_iter).tolist(), int(carry.fall_count),
                             float(carry.hit)))
            return outs
        one, four = run(None), run(mesh)
        print(json.dumps({
            "case": f"{mode}-{'fused' if fused else 'xla'}",
            "l": max(float(np.max(np.abs(a[0] - b[0]))) for a, b in zip(one, four)),
            "s": max(float(np.max(np.abs(a[1] - b[1]))) for a, b in zip(one, four)),
            "n_iter": [[a[2], b[2]] for a, b in zip(one, four)],
            "falls": [[a[3], b[3]] for a, b in zip(one, four)],
            "hits": [[a[4], b[4]] for a, b in zip(one, four)],
        }))
"""


@pytest.fixture(scope="module")
def four_device_parity():
    import json

    out = run_on_four_devices(PARITY_RUN)
    return {d["case"]: d for d in map(json.loads, out.splitlines())}


@pytest.mark.parametrize("tail", ["xla", "fused"])
@pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
def test_sharded_loop_matches_one_device(four_device_parity, svt_mode, tail):
    """Four shards against one device, on three warm-carried rounds of a
    ragged bucket (7 clients, 5 modules, 30 vec rows, none a multiple of
    4): the same L and S to float32 round-off, the same iteration counts
    and the same exact-eigh fallback counts, with the carry warm after the
    first round."""
    got = four_device_parity[f"{svt_mode}-{tail}"]
    assert got["l"] <= 1e-4 and got["s"] <= 1e-4, got
    for one, four in got["n_iter"] + got["falls"] + got["hits"]:
        assert one == four, got
    assert [one for one, _ in got["hits"]] == [0.0, 1.0, 1.0], got
