"""Compressed uplinks + heterogeneous client ranks (DESIGN.md §12).

Covers the wire codec contract end to end: the sketch round-trip is
bitwise at full coverage, cold/gated rounds are bit-for-bit the dense
path, warm rounds engage the codec and cut ``bytes_up``, the
energy-fraction gate trips on planted basis drift, final accuracy stays
allclose to dense at k << d1*d2 across every method on both engines, the
per-client rank masks are the equal-uniform-rank zero-padding oracle by
construction, and the odd-cohort (nc=7) warm carry is fallback-free
under the ceil rank cap.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import METHODS, AggregatorConfig, aggregate
from repro.core import engine as engine_lib
from repro.core import rpca as rpca_lib
from repro.core.aggregators import rpca_diag_summary
from repro.core.engine import AggSession
from repro.fed import FedRunConfig, LocalSpec, run_simulation, synth
from repro.fed import partition as partition_lib
from repro.fed import sketch as sketch_lib
from repro.launch import costmodel
from repro.optim import make_optimizer


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def round_trees(rng, nc=8, rounds=4, drift=0.02):
    """Correlated multi-round deltas (drifting shared rank-2 core plus
    persistent sparse spikes) — the regime where the carried basis
    captures the bulk of each round's delta."""
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
        method="fedrpca", rpca_iters=40, svt_mode="subspace",
        carry_mode="subspace",
    )
    base.update(kw)
    return AggregatorConfig(**base)


def tree_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


# ---------------------------------------------------------------------------
# parse_uplink
# ---------------------------------------------------------------------------


class TestParseUplink:
    def test_defaults(self):
        assert sketch_lib.parse_uplink(None).mode == "dense"
        assert not sketch_lib.parse_uplink("dense").active
        c = sketch_lib.parse_uplink("sketch")
        assert c.active and c.k == sketch_lib.DEFAULT_K
        assert c.energy_tol == sketch_lib.DEFAULT_ENERGY_TOL

    def test_explicit(self):
        c = sketch_lib.parse_uplink("sketch:16:0.5")
        assert (c.mode, c.k, c.energy_tol) == ("sketch", 16, 0.5)
        assert sketch_lib.parse_uplink("sketch:16").k == 16

    def test_passthrough(self):
        c = sketch_lib.UplinkConfig(mode="sketch", k=8, energy_tol=0.1)
        assert sketch_lib.parse_uplink(c) is c

    @pytest.mark.parametrize("bad", [
        "dense:4", "sketch:0", "sketch:-1", "sketch:4:2.0", "sketch:4:-0.1",
        "sketch:4:0.1:9", "foo", "",
    ])
    def test_rejects(self, bad):
        with pytest.raises(ValueError):
            sketch_lib.parse_uplink(bad)


# ---------------------------------------------------------------------------
# Codec round-trip
# ---------------------------------------------------------------------------


class TestCodec:
    def _basis(self, rng, b, d1, r):
        raw = jnp.asarray(rng.normal(size=(b, d1, r)), jnp.float32)
        return rpca_lib._orthonormalize(raw)

    def test_roundtrip_bitwise_full_k(self, rng):
        """k = d1 ships every residual position's RAW entry, so decode
        overwrites the projection with the original bytes — bitwise."""
        m = jnp.asarray(rng.normal(size=(3, 24, 6)), jnp.float32)
        basis = self._basis(rng, 3, 24, 4)
        s = sketch_lib.encode_delta(m, basis, 24)
        m_hat = sketch_lib.decode_into_bucket(s, basis)
        assert np.array_equal(np.asarray(m_hat), np.asarray(m))
        # energy_frac sums the dropped entries, and at full k none are.
        assert float(jnp.max(s.energy_frac)) == 0.0

    def test_partial_k_energy_monotone(self, rng):
        m = jnp.asarray(rng.normal(size=(2, 32, 5)), jnp.float32)
        basis = self._basis(rng, 2, 32, 3)
        fracs = [
            float(jnp.max(sketch_lib.encode_delta(m, basis, k).energy_frac))
            for k in (2, 8, 16, 32)
        ]
        assert fracs == sorted(fracs, reverse=True)
        assert fracs[-1] == 0.0

    def test_pure_low_rank_exact(self, rng):
        """A delta living entirely in the carried basis reconstructs from
        the coefficients alone (fp32-allclose; top-k only sweeps noise)."""
        b, d1, c, r = 2, 40, 6, 3
        basis = self._basis(rng, b, d1, r)
        coef = jnp.asarray(rng.normal(size=(b, r, c)), jnp.float32)
        m = jnp.einsum("bdr,brc->bdc", basis, coef)
        s = sketch_lib.encode_delta(m, basis, 4)
        m_hat = sketch_lib.decode_into_bucket(s, basis)
        np.testing.assert_allclose(
            np.asarray(m_hat), np.asarray(m), atol=1e-5, rtol=1e-5
        )
        assert float(jnp.max(s.energy_frac)) < 1e-6

    def test_energy_frac_is_the_decode_error(self, rng):
        """The gate metric must be exactly what it claims: the per-module
        reconstruction error energy as a fraction of the delta energy —
        computed analytically on the encoder side, without a decode."""
        m = jnp.asarray(rng.normal(size=(3, 30, 5)), jnp.float32)
        basis = self._basis(rng, 3, 30, 4)
        s = sketch_lib.encode_delta(m, basis, 6)
        m_hat = sketch_lib.decode_into_bucket(s, basis)
        err = np.asarray(m_hat - m, np.float64)
        want = (err**2).sum(axis=(1, 2)) / (np.asarray(m, np.float64)**2).sum(
            axis=(1, 2)
        )
        np.testing.assert_allclose(
            np.asarray(s.energy_frac, np.float64), want, atol=1e-5, rtol=1e-3
        )

    def test_byte_model(self):
        # The bench geometry (2 modules of vec 1024, basis rank 8, k=64):
        # sketch must beat dense by >= 4x, the perf-gate bar.
        dense = sketch_lib.dense_bytes_per_client([1024] * 2)
        sk = sketch_lib.sketch_bytes_per_client(2, 8, 64)
        assert dense / sk >= 4.0
        assert sketch_lib.basis_bytes(4, 512, 4) == 4 * 4 * 512 * 4


# ---------------------------------------------------------------------------
# Engine gate: cold/tripped rounds are bitwise the dense path
# ---------------------------------------------------------------------------


class TestEngineGate:
    def _run(self, trees, uplink=None):
        cfg = session_cfg()
        plan = engine_lib.plan_aggregation(trees[0], cfg, uplink=uplink)
        carry = engine_lib.init_agg_carry(plan)
        outs, scalars = [], []
        for t in trees:
            out, carry, diag = engine_lib.aggregate_planned(
                plan, t, carry, with_diagnostics=True
            )
            outs.append(jax.tree_util.tree_map(np.asarray, out))
            scalars.append(
                {k: float(v) for k, v in rpca_diag_summary(diag).items()}
            )
        return outs, scalars

    def test_dense_mode_is_the_no_codec_plan(self, rng):
        trees = round_trees(rng, rounds=2)
        cfg = session_cfg()
        assert engine_lib.plan_aggregation(trees[0], cfg, uplink="dense").uplink is None
        assert engine_lib.plan_aggregation(trees[0], cfg, uplink=None).uplink is None

    def test_cold_round_bitwise_dense(self, rng):
        """Round 0 has no carried basis -> the gate trips -> the sketch
        plan's output is bit-for-bit the dense plan's."""
        trees = round_trees(rng, rounds=1)
        dense, _ = self._run(trees)
        sk, sc = self._run(trees, uplink="sketch:8:0.9")
        assert tree_equal(dense[0], sk[0])
        assert sc[0]["uplink_hit_rate"] == 0.0
        assert sc[0]["uplink_dense_falls"] >= 1.0

    def test_zero_tol_gates_every_round_bitwise(self, rng):
        """energy_tol=0 can never accept a lossy sketch, so the whole
        multi-round session is bit-for-bit the dense session."""
        trees = round_trees(rng, rounds=3)
        dense, _ = self._run(trees)
        sk, sc = self._run(trees, uplink="sketch:8:0.0")
        for d, s in zip(dense, sk):
            assert tree_equal(d, s)
        assert all(s["uplink_hit_rate"] == 0.0 for s in sc)

    def test_warm_rounds_engage_and_cut_bytes(self, rng):
        trees = round_trees(rng, rounds=4)
        _, sc = self._run(trees, uplink="sketch:16:0.9")
        assert sc[0]["uplink_hit_rate"] == 0.0  # cold
        assert all(s["uplink_hit_rate"] == 1.0 for s in sc[1:])
        dense_bytes = sc[0]["bytes_up"]
        assert all(s["bytes_up"] < dense_bytes for s in sc[1:])

    def test_gate_trips_on_planted_basis_drift(self, rng):
        """Warm the carry on one subspace, then feed a round drawn from a
        fresh core: the residual energy blows past the tolerance and that
        round degrades to dense — while an aligned round sketches."""
        trees = round_trees(rng, rounds=3)
        aligned = trees[2]
        drifted = round_trees(np.random.default_rng(99), rounds=1)[0]

        cfg = session_cfg()
        plan = engine_lib.plan_aggregation(trees[0], cfg, uplink="sketch:8:0.3")
        carry = engine_lib.init_agg_carry(plan)
        for t in trees[:2]:
            _, carry, _ = engine_lib.aggregate_planned(
                plan, t, carry, with_diagnostics=True
            )

        _, _, diag_a = engine_lib.aggregate_planned(
            plan, aligned, carry, with_diagnostics=True
        )
        assert float(rpca_diag_summary(diag_a)["uplink_hit_rate"]) == 1.0

        out_d, _, diag_d = engine_lib.aggregate_planned(
            plan, drifted, carry, with_diagnostics=True
        )
        assert float(rpca_diag_summary(diag_d)["uplink_hit_rate"]) == 0.0
        # The tripped round is bit-for-bit the dense plan fed the same
        # carry state.
        plan_dense = engine_lib.plan_aggregation(trees[0], cfg)
        out_ref, _, _ = engine_lib.aggregate_planned(
            plan_dense, drifted, carry, with_diagnostics=True
        )
        assert tree_equal(out_ref, out_d)


# ---------------------------------------------------------------------------
# Odd-cohort rank cap (the nc=7 warm-carry fallback fix)
# ---------------------------------------------------------------------------


class TestOddCohortRankCap:
    def test_subspace_rank_ceil(self):
        assert rpca_lib.subspace_rank(7, 8) == 4
        assert rpca_lib.subspace_rank(9, 8) == 5
        assert rpca_lib.subspace_rank(8, 8) == 4
        assert rpca_lib.subspace_rank(2, 8) == 1
        assert rpca_lib.subspace_rank(1, 8) == 1
        assert rpca_lib.subspace_rank(16, 3) == 3  # rank cap still binds

    def test_true_cols_caps_below_padded_width(self):
        assert rpca_lib.subspace_rank(8, 8, true_cols=5) == 3
        assert rpca_lib.subspace_rank(8, 8, true_cols=8) == 4
        assert rpca_lib.subspace_rank(8, 8, true_cols=1) == 1

    @pytest.mark.parametrize("nc", [7, 9])
    def test_odd_cohort_warm_fallback_free(self, nc, rng):
        """The documented nc=7 failure mode: under the floor cap (r=3) the
        planted rank-2-plus-spikes workload saturated the carried width and
        every warm round fell back to eigh.  The ceil cap (r=4) leaves
        headroom — warm rounds run fallback-free, like even cohorts."""
        trees = round_trees(rng, nc=nc, rounds=4)
        sess = AggSession(session_cfg())
        falls = []
        for t in trees:
            _, diag = sess.step(t)
            falls.append(int(diag.scalars["fallback_count"]))
        assert all(f == 0 for f in falls[1:]), falls

    def test_costmodel_matches_engine_cap(self):
        """costmodel's analytic r must track rpca.subspace_rank exactly
        (both sides of the ceil fix), visible through the sketch byte
        model: bytes scale with r."""
        for cohort in (2, 5, 7, 8, 9, 16):
            r_engine = rpca_lib.subspace_rank(cohort, 8)
            got = costmodel.uplink_costs(
                n_modules=1, padded_vec=256, cohort=cohort, svt_rank=8, k=16,
            )
            want = 1 * (r_engine * 4 + 16 * 8)
            assert got["sketch_bytes_per_client"] == want, (cohort, r_engine)
            assert costmodel.mesh_agg_costs(
                n_modules=2, padded_vec=64, cohort=cohort, shards=1,
            )["us"] > 0


# ---------------------------------------------------------------------------
# Simulation parity: every method x both engines, sketch vs dense
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sim_task():
    return synth.make_synth_task(
        n_clients=8, n_per_client=24, d_in=32, d_feat=32, alpha=0.4, seed=3
    )


def _sim_cfg(method, engine, rounds=3, **kw):
    agg_kw = dict(method=method, rpca_iters=8)
    if method == "fedrpca" and engine == "packed":
        agg_kw.update(svt_mode="subspace", carry_mode="subspace")
    defaults = dict(
        aggregator=AggregatorConfig(**agg_kw),
        local=LocalSpec(
            loss_fn=lambda base, lora, batch: synth.loss_fn(
                base, lora, batch, 2.0
            ),
            optimizer=make_optimizer("adam", 1e-2),
            local_steps=2,
            batch_size=8,
            lr=1e-2,
        ),
        rounds=rounds,
        engine=engine,
    )
    defaults.update(kw)
    return FedRunConfig(**defaults)


def _run_sim(task, cfg):
    eval_fn = lambda lora: synth.accuracy(
        task.base, lora, task.test_x, task.test_y, task.lora_scale
    )
    logs = []
    with warnings.catch_warnings():
        # Non-carrying combos degrade sketch -> dense with a warning; the
        # degradation itself is what the parity assertions check.
        warnings.simplefilter("ignore")
        lora, hist = run_simulation(
            task.base, synth.init_lora(task), task.client_x, task.client_y,
            cfg, eval_fn, log_fn=lambda r, d: logs.append(d),
        )
    return lora, hist, logs


class TestSimulationParity:
    @pytest.mark.parametrize("engine", ["packed", "reference"])
    @pytest.mark.parametrize("method", sorted(METHODS))
    def test_sketch_matches_dense_accuracy(self, method, engine, sim_task):
        """--uplink sketch:8 (k << d1*d2) lands within fp32-allclose of the
        dense run's final accuracy for every method on both engines.  Only
        the carrying packed fedrpca path actually sketches; every other
        combo degrades to dense and must match bit-for-bit."""
        dense_cfg = _sim_cfg(method, engine)
        sketch_cfg = _sim_cfg(method, engine, uplink="sketch:8:0.9")
        lora_d, hist_d, _ = _run_sim(sim_task, dense_cfg)
        lora_s, hist_s, logs_s = _run_sim(sim_task, sketch_cfg)
        sketches = method == "fedrpca" and engine == "packed"
        if sketches:
            assert any(d.get("uplink_hit_rate", 0.0) > 0.0 for d in logs_s)
            np.testing.assert_allclose(hist_s[-1], hist_d[-1], atol=0.01)
        else:
            assert tree_equal(lora_d, lora_s)
            np.testing.assert_array_equal(hist_d, hist_s)

    def test_sketch_pipeline_runs(self, sim_task):
        cfg = _sim_cfg(
            "fedrpca", "packed", uplink="sketch:8:0.9",
            pipeline=True, staleness=2,
        )
        _, hist, logs = _run_sim(sim_task, cfg)
        assert np.isfinite(hist).all()
        assert all("bytes_up" in d for d in logs)


# ---------------------------------------------------------------------------
# Wire byte counters
# ---------------------------------------------------------------------------


class TestWireCounters:
    def test_counters_logged_every_round(self, sim_task):
        _, _, logs = _run_sim(sim_task, _sim_cfg("fedavg", "packed"))
        assert logs and all(
            d["bytes_up"] > 0 and d["bytes_down"] > 0 for d in logs
        )

    def test_sketch_cuts_bytes_up(self, sim_task):
        _, _, dense_logs = _run_sim(sim_task, _sim_cfg("fedrpca", "packed"))
        _, _, sk_logs = _run_sim(
            sim_task, _sim_cfg("fedrpca", "packed", uplink="sketch:8:0.9")
        )
        dense_up = dense_logs[-1]["bytes_up"]
        warm = [d for d in sk_logs if d.get("uplink_hit_rate", 0.0) == 1.0]
        assert warm, "no warm sketch round engaged"
        assert all(d["bytes_up"] < dense_up for d in warm)
        # Sketch rounds pay the basis multicast on top of the model cast.
        assert all(d["bytes_down"] > dense_logs[-1]["bytes_down"] for d in warm)

    def test_costmodel_reduction(self):
        got = costmodel.uplink_costs(
            n_modules=2, padded_vec=1024, cohort=16, svt_rank=8, k=64,
        )
        assert got["reduction_vs_dense"] >= 4.0
        assert got["sketch_wins"]
        blended = costmodel.uplink_costs(
            n_modules=2, padded_vec=512, cohort=16, svt_rank=8, k=64,
            dense_rounds_frac=0.5,
        )
        assert blended["reduction_vs_dense"] < got["reduction_vs_dense"]
        assert blended["effective_bytes_per_client"] > got["effective_bytes_per_client"]


# ---------------------------------------------------------------------------
# Heterogeneous per-client ranks
# ---------------------------------------------------------------------------


class TestClientRanks:
    def test_parse_cycles_and_validates(self):
        got = partition_lib.parse_client_ranks("8,4", 5, 8)
        assert got.tolist() == [8, 4, 8, 4, 8]
        assert partition_lib.parse_client_ranks([2, 3], 3, 4).tolist() == [2, 3, 2]
        with pytest.raises(ValueError):
            partition_lib.parse_client_ranks("16", 4, 8)  # > template rank
        with pytest.raises(ValueError):
            partition_lib.parse_client_ranks("0,4", 4, 8)
        with pytest.raises(ValueError):
            partition_lib.parse_client_ranks("", 4, 8)
        with pytest.raises(ValueError):
            partition_lib.parse_client_ranks("a,b", 4, 8)

    def test_infer_lora_rank(self, sim_task):
        lora = synth.init_lora(sim_task)
        assert partition_lib.infer_lora_rank(lora) == sim_task.lora_rank
        with pytest.raises(ValueError):
            partition_lib.infer_lora_rank({"W": jnp.zeros((3, 3))})

    def test_masks_are_the_zero_padding_oracle(self, sim_task, rng):
        """mask * delta must equal the delta a rank-r_i client would ship
        zero-padded into the uniform layout: rank slices >= r_i exactly
        zero, slices < r_i bitwise untouched."""
        lora = synth.init_lora(sim_task)
        ranks = partition_lib.parse_client_ranks("4,2,1", 8, 4)
        masks = partition_lib.client_rank_masks(lora, ranks)
        deltas = jax.tree_util.tree_map(
            lambda x: jnp.asarray(
                rng.normal(size=(8,) + x.shape), jnp.float32
            ),
            lora,
        )
        masked = jax.tree_util.tree_map(
            lambda d, mk: d * mk.astype(d.dtype), deltas, masks
        )
        # Manual oracle: zero-pad each client's rank axis beyond rank_i.
        a = np.asarray(deltas["A"]).copy()  # (8, d_in, r)
        b = np.asarray(deltas["B"]).copy()  # (8, r, d_feat)
        for i, r in enumerate(ranks.tolist()):
            a[i, :, r:] = 0.0
            b[i, r:, :] = 0.0
        np.testing.assert_array_equal(np.asarray(masked["A"]), a)
        np.testing.assert_array_equal(np.asarray(masked["B"]), b)

    def test_masked_aggregation_is_rank_declaration_invariant(self, sim_task, rng):
        """Declared client_ranks are a descriptor: the aggregation of
        already-masked deltas is bitwise identical whether or not the plan
        knows the declaration (the equal-uniform-rank oracle equality)."""
        lora = synth.init_lora(sim_task)
        ranks = partition_lib.parse_client_ranks("4,2", 8, 4)
        masks = partition_lib.client_rank_masks(lora, ranks)
        deltas = jax.tree_util.tree_map(
            lambda x: jnp.asarray(
                rng.normal(size=(8,) + x.shape), jnp.float32
            ),
            lora,
        )
        masked = jax.tree_util.tree_map(
            lambda d, mk: d * mk.astype(d.dtype), deltas, masks
        )
        cfg = session_cfg(rpca_iters=10)
        plan_plain = engine_lib.plan_aggregation(masked, cfg)
        plan_decl = engine_lib.plan_aggregation(
            masked, cfg, client_ranks=ranks.tolist()
        )
        assert plan_decl.spec.client_ranks == tuple(ranks.tolist())
        out_plain, _, _ = engine_lib.aggregate_planned(
            plan_plain, masked, engine_lib.init_agg_carry(plan_plain),
            with_diagnostics=True,
        )
        out_decl, _, _ = engine_lib.aggregate_planned(
            plan_decl, masked, engine_lib.init_agg_carry(plan_decl),
            with_diagnostics=True,
        )
        assert tree_equal(out_plain, out_decl)

    def test_full_rank_declaration_is_a_bitwise_noop(self, sim_task):
        """client_ranks all equal to the template rank multiplies every
        delta by exactly 1.0 — IEEE-exact, so the run is bit-for-bit the
        undeclared run."""
        cfg_plain = _sim_cfg("fedrpca", "packed")
        cfg_full = _sim_cfg("fedrpca", "packed", client_ranks="4")
        lora_p, hist_p, _ = _run_sim(sim_task, cfg_plain)
        lora_f, hist_f, _ = _run_sim(sim_task, cfg_full)
        assert tree_equal(lora_p, lora_f)
        np.testing.assert_array_equal(hist_p, hist_f)

    def test_hetero_ranks_run_end_to_end(self, sim_task):
        cfg = _sim_cfg(
            "fedrpca", "packed", client_ranks="4,2,1",
            uplink="sketch:8:0.9",
        )
        lora, hist, logs = _run_sim(sim_task, cfg)
        assert np.isfinite(hist).all()
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(lora))
        assert all("bytes_up" in d for d in logs)
