"""Async buffered round pipeline (DESIGN.md §8, §11).

The load-bearing guarantee: the staleness=0 pipeline is *bit-for-bit* the
synchronous round driver — same compiled phases, same dispatch order, same
scale — for every aggregation method on both engines.  On top of that:
staleness>=1 runs land scaled updates in dispatch order (land-time
composition, K-deep past the double buffer) and still converge, the
cross-round carry hands off between in-flight dispatches, the split
launch-layer step pair composes back to the monolithic ``fed_train_step``,
and the aggregation session checkpoint round-trips with its carry.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import METHODS, AggregatorConfig
from repro.core import engine as engine_lib
from repro.fed import (
    FedRunConfig,
    InFlightQueue,
    LocalSpec,
    init_round_state,
    make_round_phases,
    rounds_to_reach,
    run_rounds,
    run_simulation,
    stale_scale,
    synth,
)
from repro.optim import make_optimizer


@pytest.fixture(scope="module")
def task():
    return synth.make_synth_task(n_clients=6, n_per_client=32, alpha=0.3, seed=2)


def spec_for(task, **kw):
    defaults = dict(
        loss_fn=lambda base, lora, b: synth.loss_fn(base, lora, b, task.lora_scale),
        optimizer=make_optimizer("adam", 1e-2),
        local_steps=2,
        batch_size=16,
        lr=1e-2,
    )
    defaults.update(kw)
    return LocalSpec(**defaults)


def cfg_for(task, method="fedrpca", rounds=2, **kw):
    agg_kw = {"rpca_iters": 8} if method == "fedrpca" else {}
    return FedRunConfig(
        aggregator=AggregatorConfig(method=method, **agg_kw),
        local=spec_for(task),
        rounds=rounds,
        seed=0,
        **kw,
    )


def eval_fn_for(task):
    return lambda lora: synth.accuracy(
        task.base, lora, task.test_x, task.test_y, task.lora_scale
    )


def run(task, cfg, **kw):
    return run_simulation(
        task.base, synth.init_lora(task), task.client_x, task.client_y, cfg,
        eval_fn_for(task), **kw,
    )


def assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestInFlightQueue:
    def test_depth_zero_passes_through(self):
        q = InFlightQueue(0)
        assert q.pop_ready() is None
        assert q.push("a") == "a"
        assert len(q) == 0

    def test_depth_one_holds_one(self):
        q = InFlightQueue(1)
        assert q.push("a") is None
        assert len(q) == 1
        assert q.pop_ready() == "a"
        assert q.push("b") is None
        assert list(q.drain()) == ["b"]

    def test_pop_only_when_full(self):
        q = InFlightQueue(2)
        q.push("a")
        assert q.pop_ready() is None  # below the bound: keep overlapping
        q.push("b")
        assert q.pop_ready() == "a"

    def test_overfull_push_raises(self):
        q = InFlightQueue(1)
        q.push("a")
        with pytest.raises(RuntimeError):
            q.push("b")

    def test_negative_depth_rejected(self):
        with pytest.raises(ValueError):
            InFlightQueue(-1)

    def test_stale_scale(self):
        assert stale_scale(0) == 1.0
        assert stale_scale(1) == 0.5
        assert stale_scale(3) == 0.25
        with pytest.raises(ValueError):
            stale_scale(-1)


class TestStalenessZeroBitwise:
    """staleness=0 pipeline == synchronous driver, bit for bit."""

    @pytest.mark.parametrize("engine", ["packed", "reference"])
    @pytest.mark.parametrize("method", METHODS)
    def test_all_methods_both_engines(self, task, method, engine):
        cfg = cfg_for(task, method=method, engine=engine)
        lora_sync, hist_sync = run(task, cfg)
        piped = dataclasses.replace(cfg, pipeline=True, staleness=0)
        lora_pipe, hist_pipe = run(task, piped)
        np.testing.assert_array_equal(hist_sync, hist_pipe)
        assert_trees_equal(lora_sync, lora_pipe)

    def test_carry_session_staleness_zero_bitwise(self, task):
        agg = AggregatorConfig(
            method="fedrpca", rpca_iters=8, svt_mode="subspace",
            carry_mode="subspace",
        )
        cfg = FedRunConfig(
            aggregator=agg, local=spec_for(task), rounds=3, seed=0, pipeline=False
        )
        lora_sync, hist_sync = run(task, cfg)
        lora_pipe, hist_pipe = run(
            task, dataclasses.replace(cfg, pipeline=True, staleness=0)
        )
        np.testing.assert_array_equal(hist_sync, hist_pipe)
        assert_trees_equal(lora_sync, lora_pipe)

    def test_partial_participation_staleness_zero_bitwise(self, task):
        cfg = cfg_for(task, rounds=3, clients_per_round=4)
        lora_sync, hist_sync = run(task, cfg, n_active=3)
        lora_pipe, hist_pipe = run(
            task, dataclasses.replace(cfg, pipeline=True, staleness=0), n_active=3
        )
        np.testing.assert_array_equal(hist_sync, hist_pipe)
        assert_trees_equal(lora_sync, lora_pipe)


class TestPipelinedRounds:
    def test_rounds_land_in_order_with_timers(self, task):
        cfg = cfg_for(task, rounds=5, pipeline=True, staleness=1)
        logs = []
        _, hist = run(task, cfg, log_fn=lambda r, d: logs.append((r, d)))
        assert [r for r, _ in logs] == list(range(5))
        assert len(hist) == 5
        for _, d in logs:
            assert {"t_local_s", "t_agg_s", "t_overlap_s"} <= set(d)
            assert d["t_local_s"] >= 0 and d["t_agg_s"] >= 0
            assert d["t_overlap_s"] >= 0

    def test_spans_nest_and_match_timers(self, task, tmp_path):
        """Under a profiler, every round leaves its ``fed.*`` host spans,
        nested as the driver runs them, and ``fed.local`` / ``fed.land``
        cover what ``t_local_s`` / ``t_agg_s`` time."""
        from jax.profiler import ProfileData

        rounds = 3
        cfg = cfg_for(task, rounds=rounds, pipeline=True, staleness=1)
        logs = []
        with jax.profiler.trace(str(tmp_path)):
            run(task, cfg, log_fn=lambda r, d: logs.append(d))
        (path,) = tmp_path.glob("**/*.xplane.pb")
        spans = {}
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("fed."):
                        spans.setdefault(e.name, []).append((e.start_ns, e.end_ns))
        spans = {k: sorted(v) for k, v in spans.items()}
        assert {k: len(v) for k, v in spans.items()} == {
            k: rounds for k in ("fed.round", "fed.local", "fed.agg.dispatch",
                                "fed.agg.worker", "fed.land", "fed.land.wait",
                                "fed.land.apply", "fed.on_round")}

        def inside(child, parents):
            return any(ps <= child[0] and child[1] <= pe for ps, pe in parents)

        for name in ("fed.local", "fed.agg.dispatch"):
            assert all(inside(c, spans["fed.round"]) for c in spans[name])
        for name in ("fed.land.wait", "fed.land.apply"):
            assert all(inside(c, spans["fed.land"]) for c in spans[name])
        # Rounds 0 and 1 land inside the next round's iteration; the last in
        # the drain, after the loop.
        assert [inside(c, spans["fed.round"]) for c in spans["fed.land"]] == [
            True, True, False]
        # The callback runs after its landing, not within it.
        assert not any(inside(c, spans["fed.land"]) for c in spans["fed.on_round"])
        for (s, e), d in zip(spans["fed.local"], logs):
            assert abs((e - s) * 1e-9 - d["t_local_s"]) < 2e-3
        for (s, e), d in zip(spans["fed.land"], logs):
            assert abs((e - s) * 1e-9 - d["t_agg_s"]) < 2e-3

    def test_staleness_one_converges(self, task):
        """Delayed, damped updates must not wreck convergence (the
        acceptance bound: rounds_to_reach within +1 of synchronous)."""
        cfg = cfg_for(task, rounds=10)
        _, hist_sync = run(task, cfg)
        _, hist_pipe = run(task, dataclasses.replace(cfg, pipeline=True, staleness=1))
        assert hist_pipe[-1] >= hist_sync[-1] - 0.05
        assert rounds_to_reach(hist_pipe) <= rounds_to_reach(hist_sync) + 1

    def test_carry_hands_off_between_inflight_dispatches(self, task):
        agg = AggregatorConfig(
            method="fedrpca", rpca_iters=8, svt_mode="subspace",
            carry_mode="subspace",
        )
        cfg = FedRunConfig(
            aggregator=agg, local=spec_for(task), rounds=4, seed=0,
            pipeline=True, staleness=1,
        )
        logs = []
        _, hist = run(task, cfg, log_fn=lambda r, d: logs.append(d))
        assert len(hist) == 4
        # The session health scalars ride through the pipelined rounds.
        assert {"fallback_count", "live_rank_mean", "carry_hit_rate"} <= set(logs[-1])

    def test_staleness_one_applies_damped_update(self, task):
        """The agg phase returns the scaled *update* (land-time composition):
        half the scale is exactly half the update, and ``apply`` folds it
        into the global it lands on."""
        cfg = cfg_for(task, rounds=1)
        phases = make_round_phases(
            task.base, task.client_x, task.client_y, cfg,
            lora_template=synth.init_lora(task),
        )
        lora0 = synth.init_lora(task)
        state = init_round_state(lora0, 6, cfg.seed)
        state1, bundle = phases.local(state)
        # The local phase never touches the aggregation-owned buffers.
        assert_trees_equal(state1.lora_global, lora0)
        full, _, _ = phases.agg(state1.agg_carry, bundle, 1.0)
        half, _, _ = phases.agg(state1.agg_carry, bundle, 0.5)
        for f, h in zip(
            jax.tree_util.tree_leaves(full), jax.tree_util.tree_leaves(half)
        ):
            np.testing.assert_allclose(
                np.asarray(h), 0.5 * np.asarray(f), rtol=1e-6, atol=1e-7
            )
        applied = phases.apply(lora0, full)
        expect = jax.tree_util.tree_map(lambda g, u: g + u, lora0, full)
        assert_trees_equal(applied, expect)

    def test_run_rounds_rejects_negative_staleness(self, task):
        cfg = cfg_for(task)
        phases = make_round_phases(task.base, task.client_x, task.client_y, cfg)
        state = init_round_state(synth.init_lora(task), 6, 0)
        with pytest.raises(ValueError):
            run_rounds(phases, state, 1, staleness=-1)

    def test_staleness_k_deep_lands_in_order(self, task):
        """Depths beyond the double buffer compose at land time: rounds
        land in dispatch order, the state stays finite, and the run still
        trains (FedBuff-style K-deep buffering)."""
        cfg = cfg_for(task, rounds=6, pipeline=True, staleness=3)
        logs = []
        lora, hist = run(task, cfg, log_fn=lambda r, d: logs.append(r))
        assert logs == list(range(6))
        assert len(hist) == 6
        for leaf in jax.tree_util.tree_leaves(lora):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_staleness_k_deep_carry_session(self, task):
        """The carry chain threads dispatch-to-dispatch through a K-deep
        queue (not via the landed state) without losing session health."""
        agg = AggregatorConfig(
            method="fedrpca", rpca_iters=8, svt_mode="subspace",
            carry_mode="subspace",
        )
        cfg = FedRunConfig(
            aggregator=agg, local=spec_for(task), rounds=6, seed=0,
            pipeline=True, staleness=3,
        )
        logs = []
        lora, hist = run(task, cfg, log_fn=lambda r, d: logs.append(d))
        assert len(hist) == 6
        assert {"fallback_count", "live_rank_mean", "carry_hit_rate"} <= set(logs[-1])
        for leaf in jax.tree_util.tree_leaves(lora):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_round_zero_lands_undamped(self, task):
        """Round 0 of a pipelined run has tau=0 (nothing in flight when its
        local phase dispatched), so a single pipelined round must equal the
        synchronous round bit for bit — no blanket damping."""
        cfg = cfg_for(task, rounds=1)
        lora_sync, hist_sync = run(task, cfg)
        lora_pipe, hist_pipe = run(
            task, dataclasses.replace(cfg, pipeline=True, staleness=1)
        )
        np.testing.assert_array_equal(hist_sync, hist_pipe)
        assert_trees_equal(lora_sync, lora_pipe)


class TestLaunchStepSplit:
    """make_local_step + make_agg_step compose to the monolithic step."""

    @pytest.fixture(scope="class")
    def lm(self):
        from repro import configs as cfglib
        from repro.data import client_lm_datasets
        from repro.models import init_lora_params, init_params

        cfg = cfglib.get_config("mamba2-130m").reduced()
        key = jax.random.PRNGKey(0)
        base = init_params(key, cfg)
        lora = init_lora_params(jax.random.fold_in(key, 1), cfg)
        tokens, _ = client_lm_datasets(
            4, vocab_size=min(cfg.vocab_size, 512), n_seqs=8, seq_len=32, seed=0
        )
        batch = {
            "tokens": jnp.asarray(tokens[:, :2, :32]),
            "labels": jnp.asarray(tokens[:, :2, 1:33]),
        }
        return cfg, base, lora, batch

    def test_split_composes_to_monolith(self, lm):
        from repro.launch import steps as steps_lib

        cfg, base, lora, batch = lm
        agg = AggregatorConfig(method="fedrpca", rpca_iters=4)
        key = jax.random.PRNGKey(7)
        mono = steps_lib.make_fed_train_step(
            cfg, agg, local_lr=1e-3, local_steps=1, remat=False
        )
        lora_m, metrics_m = jax.jit(mono)(base, lora, batch, key)
        local = jax.jit(steps_lib.make_local_step(cfg, local_lr=1e-3, local_steps=1,
                                                  remat=False))
        aggs = jax.jit(steps_lib.make_agg_step(agg))
        deltas, loss, mask = local(base, lora, batch, key)
        assert mask is None
        upd, metrics_s = aggs(deltas, mask, key)
        lora_s = steps_lib.apply_update(lora, upd)
        np.testing.assert_allclose(
            float(loss), float(metrics_m["loss"]), rtol=1e-6
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(lora_m), jax.tree_util.tree_leaves(lora_s)
        ):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-5, atol=1e-6,
            )

    def test_agg_step_scale_halves_update(self, lm):
        from repro.launch import steps as steps_lib

        cfg, base, lora, batch = lm
        agg = AggregatorConfig(method="fedavg")
        local = jax.jit(steps_lib.make_local_step(cfg, local_lr=1e-3, remat=False))
        aggs = jax.jit(steps_lib.make_agg_step(agg))
        deltas, _, mask = local(base, lora, batch)
        full, _ = aggs(deltas, mask)
        half, _ = aggs(deltas, mask, scale=0.5)
        for f, h in zip(
            jax.tree_util.tree_leaves(full),
            jax.tree_util.tree_leaves(half),
        ):
            np.testing.assert_allclose(
                np.asarray(h, np.float32),
                0.5 * np.asarray(f, np.float32),
                rtol=1e-5, atol=1e-7,
            )


class TestSessionCheckpoint:
    def test_session_checkpoint_roundtrips_carry(self, tmp_path, rng):
        from repro.checkpoint import (
            checkpoint_metadata, restore_checkpoint, save_checkpoint,
        )

        agg = AggregatorConfig(
            method="fedrpca", rpca_iters=6, svt_mode="subspace",
            carry_mode="subspace",
        )
        tree = {
            "w": jnp.asarray(rng.normal(size=(4, 8, 8)), jnp.float32),
        }
        plan = engine_lib.plan_aggregation(tree, agg)
        carry0 = engine_lib.init_agg_carry(plan)
        _, carry, _ = engine_lib.aggregate_planned(
            plan, tree, carry0, with_diagnostics=True
        )
        lora = {"A": jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)}
        save_checkpoint(
            {"lora": lora, "agg_carry": carry}, str(tmp_path), 3,
            metadata={"format": "session", "round": 3, "carry_mode": "subspace"},
        )
        meta = checkpoint_metadata(str(tmp_path))
        assert meta["format"] == "session"
        assert meta["round"] == 3
        restored, _ = restore_checkpoint(
            str(tmp_path), {"lora": lora, "agg_carry": carry0}
        )
        assert_trees_equal(restored["lora"], lora)
        assert_trees_equal(restored["agg_carry"], carry)

    def test_checkpoint_metadata_missing_dir(self, tmp_path):
        from repro.checkpoint import checkpoint_metadata

        with pytest.raises(FileNotFoundError):
            checkpoint_metadata(str(tmp_path / "nope"))
