"""Refusals that keep a chip run honest.

``chip_smoke.py`` never carries on without a TPU, a round degraded to
FedAvg with no fault injected fails ``train.main``, no code path selects
Pallas interpret mode on a TPU backend, the aggregation's matmuls never
take the TPU's one-pass bf16 default, and the compilation cache lives
exactly where ``repro.utils.compile_cache`` says.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AggregatorConfig, aggregate
from repro.core import engine as engine_lib
from repro.kernels import backend
from repro.launch import steps as steps_lib
from repro.launch import train
from repro.utils import compile_cache

ROOT = Path(__file__).resolve().parents[1]
TINY_TRAIN = ["--arch", "mamba2-130m", "--reduced", "--rounds", "1",
              "--clients", "2", "--rpca-iters", "2", "--local-steps", "1",
              "--seq", "16"]


class TestChipSmokeRefuses:
    @pytest.mark.parametrize("where", ["checkout", "alone"])
    def test_exits_nonzero_without_a_tpu(self, where, tmp_path):
        """On the CPU, and in a directory holding only the script, it exits
        non-zero and prints no result line."""
        script = ROOT / "chip_smoke.py"
        if where == "alone":
            script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("PYTHONPATH", None)
        proc = subprocess.run(
            [sys.executable, str(script)], cwd=script.parent, env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "no TPU" in proc.stderr


@pytest.fixture(scope="module")
def smoke_module():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class TestChipSmokePlantedInput:
    """The smoke's planted deltas must drive the subspace SVT off the exact
    eigh, or its Ritz-path kernels would only be compiled, never run."""

    def test_takes_the_ritz_path(self, smoke_module):
        key = jax.random.PRNGKey(0)
        # One stablelm-1.6b Q module at full width: A (2048, 8), B (8, 2048).
        tree = {"A": jax.random.normal(key, (8, 2048, 8)),
                "B": jax.random.normal(key, (8, 8, 2048))}
        deltas = smoke_module.planted(tree)
        ys = jax.tree_util.tree_leaves(deltas)
        ms = sum(float(jnp.sum(y * y)) for y in ys) / sum(y.size for y in ys)
        assert ms == pytest.approx(1.0, rel=1e-5)
        pairs, falls = jax.jit(smoke_module.bucket_decomposition("subspace", False))(deltas)
        assert all(0 < int(f) < smoke_module.RPCA_ITERS for f in falls)
        for low_rank, sparse in pairs:
            assert bool(jnp.all(jnp.isfinite(low_rank)))
            assert bool(jnp.all(jnp.isfinite(sparse)))


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)


class TestTrainMain:
    def test_clean_run_returns_summary(self, no_cache):
        summary = train.main(TINY_TRAIN)
        assert np.isfinite(summary["initial_eval_loss"])
        assert np.isfinite(summary["final_eval_loss"])
        (rnd,) = summary["rounds"]
        assert rnd["round"] == 0 and np.isfinite(rnd["mean_local_loss"])
        assert "degraded" not in rnd and "supervisor_retry" not in rnd
        for leaf in jax.tree_util.tree_leaves(summary["last_deltas"]):
            assert leaf.shape[0] == 2

    def test_last_deltas_are_rank_masked(self, no_cache):
        """With ``--client-ranks``, the summary holds the deltas the round
        aggregated: zero beyond each client's declared rank."""
        from repro.fed import partition as partition_lib

        deltas = train.main(TINY_TRAIN + ["--client-ranks", "1"])["last_deltas"]
        one = jax.tree_util.tree_map(lambda x: x[0], deltas)
        masks = partition_lib.client_rank_masks(
            one, [1, 1], partition_lib.infer_lora_rank(one))
        for d, mk in zip(jax.tree_util.tree_leaves(deltas),
                         jax.tree_util.tree_leaves(masks)):
            assert bool(jnp.any(mk == 0))
            np.testing.assert_array_equal(np.asarray(d * mk), np.asarray(d))

    def test_traces_the_rounds_asked_for_and_logs_compiles(self, no_cache, tmp_path):
        """``--trace-rounds 1:2`` traces round 1 alone (round 0 lands
        before the trace starts), and each round's log line names what
        compiled while it ran: everything in round 0, nothing after."""
        import logging

        from jax.profiler import ProfileData

        lines = []
        handler = logging.Handler()
        handler.emit = lambda rec: lines.append(rec.getMessage())
        logger = logging.getLogger("repro.train")
        logger.addHandler(handler)
        try:
            train.main(TINY_TRAIN + ["--rounds", "3", "--trace-dir", str(tmp_path),
                                     "--trace-rounds", "1:2"])
        finally:
            logger.removeHandler(handler)
        rounds = [ln for ln in lines if ln.startswith("round ")]
        assert len(rounds) == 3
        assert "compiled=" in rounds[0] and "local_step" in rounds[0]
        assert "agg_step" in rounds[0]
        assert not any("compiled=" in ln for ln in rounds[1:])
        (path,) = tmp_path.glob("**/*.xplane.pb")
        names = [e.name for p in ProfileData.from_file(str(path)).planes
                 for line in p.lines for e in line.events if e.name.startswith("fed.")]
        for span in ("fed.round", "fed.local", "fed.agg.dispatch", "fed.land",
                     "fed.land.wait", "fed.land.apply", "fed.on_round"):
            assert names.count(span) == 1, span

    def test_degraded_round_without_faults_exits_1(self, monkeypatch, no_cache):
        """A non-finite FedRPCA update is retried cold, then degraded to
        FedAvg; the final state is finite, yet with no fault injected the
        run must fail."""
        real = steps_lib.make_agg_step

        def nan_fedrpca(agg_cfg=None, **kw):
            step = real(agg_cfg, **kw)
            if agg_cfg.method != "fedrpca":
                return step

            def poisoned(*args, **kwargs):
                upd, metrics = step(*args, **kwargs)
                return jax.tree_util.tree_map(lambda u: u * jnp.nan, upd), metrics

            return poisoned

        monkeypatch.setattr(steps_lib, "make_agg_step", nan_fedrpca)
        with pytest.warns(UserWarning, match="degrading to masked FedAvg"):
            with pytest.raises(SystemExit) as exc:
                train.main(TINY_TRAIN)
        assert exc.value.code == 1


class TestInterpretPolicy:
    def test_cpu_interprets_by_default_and_on_request(self):
        assert backend.resolve_interpret(None) is True
        assert backend.resolve_interpret(True) is True
        assert backend.resolve_interpret(False) is False

    def test_tpu_never_interprets(self, monkeypatch):
        monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
        assert backend.interpret_default() is False
        assert backend.resolve_interpret(None) is False
        with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
            backend.resolve_interpret(True)


class TestAggregationPrecision:
    @pytest.mark.parametrize("engine,svt_mode,fused", [
        ("packed", "gram", False), ("packed", "subspace", False),
        ("packed", "subspace", True), ("reference", "gram", False),
        ("reference", "subspace", False), ("planned", "subspace", False),
    ])
    def test_every_matmul_is_full_f32(self, engine, svt_mode, fused, rng):
        """Stateless engines and the planned session step that the round
        drivers run."""
        tree = {"q": {
            "A": jnp.asarray(rng.normal(size=(6, 3, 8, 4)), jnp.float32),
            "B": jnp.asarray(rng.normal(size=(6, 3, 4, 8)), jnp.float32),
        }}
        cfg = AggregatorConfig(method="fedrpca", rpca_iters=3, svt_mode=svt_mode,
                               rpca_fused_tail=fused)
        if engine == "planned":
            plan = engine_lib.plan_aggregation(tree, cfg.replace(carry_mode="subspace"))
            fn = lambda t: engine_lib.aggregate_planned(plan, t)
        else:
            fn = lambda t: aggregate(t, cfg, engine=engine)
        text = str(jax.make_jaxpr(fn)(tree))
        n_dots = text.count("dot_general[")
        assert n_dots > 0
        # Each pinned dot_general prints (Precision.HIGHEST, Precision.HIGHEST).
        assert text.count("Precision.HIGHEST") == 2 * n_dots


class TestCompileCache:
    def test_env_var_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.compile_cache_dir() == str(tmp_path)

    def test_default_is_fixed_ignored_checkout_path(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")
        ignored = (ROOT / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored

    def test_entries_land_in_the_chosen_dir(self, monkeypatch, tmp_path):
        from jax.experimental.compilation_cache import compilation_cache

        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        saved = {k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
        )}
        try:
            compilation_cache.reset_cache()
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.jit(lambda x: x * 3.0 + 1.0).lower(jnp.ones(7)).compile()
            assert any(tmp_path.iterdir())
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()

