"""The harness on the CPU: name resolution, generators, the chip check and
the result line."""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import gen, harness
from bench import round_cell as rc
from bench.tests import tiny

BENCH = harness.benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_resolves_every_file_by_name(name):
    cell = harness.resolve(name)
    assert name.startswith(cell.config["name"] + ".")
    assert hasattr(cell.reference(), "__file__")
    assert callable(cell.driver().run)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
        assert m["moves"] in e2e
    assert set(cell.traffic["limits"])


def test_new_files_and_entries_resolve(tmp_path):
    """A later cell, traffic mix and metric: new files plus new entries."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "stablelm-1.6b.round-c16", "config": "stablelm-1.6b",
                               "traffic": "round-c16", "chips": 1, "why": "more clients"})
    bench["per_layer"].append({"name": "batch_build_s", "unit": "s", "better": "lower",
                               "source": "program_span", "layer": "round driver",
                               "moves": "round_p90_s", "workloads": ["stablelm-1.6b.round-c16"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.loads((root / "bench/traffic/round-c8.json").read_text())
    traffic["clients"] = 16
    (root / "bench/traffic/round-c16.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/batch_build_s.py").write_text("def read(cell, out):\n    return 1.5\n")
    cell = harness.resolve("stablelm-1.6b.round-c16", bench_dir=str(root / "bench"))
    assert cell.traffic["clients"] == 16
    assert [m["name"] for m in cell.per_layer] == ["batch_build_s"]
    assert cell.reader("batch_build_s").read(cell, None) == 1.5


def test_markov_tokens_deterministic_in_seed():
    tr = {**harness.load_json(f"{harness.BENCH}/traffic/round-c8.json"),
          **tiny.TINY_TRAFFIC["round"]}
    a = gen.MarkovTokens(tr, tiny.SEED).round(3)
    assert a.shape == (4, 2, 17) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < tr["token_rows"]
    np.testing.assert_array_equal(a, gen.MarkovTokens(tr, tiny.SEED).round(3))
    assert not np.array_equal(a, gen.MarkovTokens(tr, tiny.SEED + 1).round(3))
    assert not np.array_equal(a, gen.MarkovTokens(tr, tiny.SEED).round(4))


def test_planted_cohort_deterministic_in_seed():
    tr = harness.load_json(f"{harness.BENCH}/traffic/agg-c20.json")
    tr["clients"] = 6
    shapes = {"q.A": (2, 64, 8), "q.B": (2, 8, 64)}

    def make(seed, k):
        base = gen.planted_base(gen.seed_key(seed), shapes, tr)
        return gen.planted_cohort(base, jax.random.fold_in(gen.seed_key(seed), k), shapes, tr)

    a = make(tiny.SEED, 1)
    assert a["q.A"].shape == (6, 2, 64, 8)
    for k in a:
        np.testing.assert_array_equal(a[k], make(tiny.SEED, 1)[k])
    assert not np.array_equal(a["q.A"], make(tiny.SEED + 1, 1)["q.A"])
    b = make(tiny.SEED, 2)["q.A"]
    # The next round moves the core a little; the sparse support stays.
    assert 0 < float(np.mean(np.abs(b - a["q.A"]))) < 0.2 * float(np.mean(np.abs(a["q.A"])))


def _run_py(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0], "--seed", "5",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    return bool(lines) and lines[-1].lstrip().startswith("{")


def test_run_refuses_without_a_tpu():
    p = _run_py(harness.ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench")
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def _outcome():
    out = harness.Outcome(attempted=7, failed=1, values={"round_s": 0.4, "round_p90_s": 0.5,
                                                         "setup_s": 30.0})
    out.checks = {"loss_gap": (1e-4, 1e-3)}
    out.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                  "memory_peak_bytes": 123}
    out.facts = {"t_local": [0.3, 0.32], "t_agg": [0.01, 0.02], "rounds": 2}
    out.trace = {"busy_s": 0.9, "window_s": 1.0, "exposed_collective_s": 0.0, "chips": 1,
                 "breakdown": {"device_ops": [["jit_local_step/fusion", 0.5]],
                               "idle_gaps": [["bench.batch", 0.01]]}}
    return out


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_keys(traced):
    cell = harness.resolve("stablelm-1.6b.round-c8")
    line = harness.result_line(cell, _outcome(), traced)
    want = ["correct", "attempted", "failed", "metrics", "device"]
    want += ["breakdown", "checks"] if traced else ["checks"]
    assert list(line) == want
    assert line["correct"] is True and line["failed"] == 1
    metrics = line["metrics"]
    if traced:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(metrics) == {m["name"] for m in cell.per_layer}
        assert metrics["local_phase_s"]["value"] == pytest.approx(0.31)
        assert metrics["idle_share.round"]["value"] == pytest.approx(10.0)
    else:
        # A value the benchmark does not name (round_s) stays out of the line.
        assert set(metrics) == {"round_p90_s", "setup_s"}
    assert line["checks"] == {"loss_gap": {"value": 1e-4, "limit": 1e-3}}
    json.dumps(line)


def test_checks_decide_correct():
    out = _outcome()
    out.checks["update_gap"] = (0.2, 0.1)
    assert not out.correct
    out.checks["update_gap"] = (float("nan"), 0.1)
    assert not out.correct
    assert not harness.Outcome().correct


@pytest.mark.parametrize("flag", ["degraded", "supervisor_retry"])
def test_degraded_or_retried_round_counts_as_failed(flag):
    tr = {"check_rounds": 0, "warmup_rounds": 1, "trace_rounds": 10}
    rec = rc.Recorder(tr, 100.0, harness.CompileCounter(), None)
    base = {"mean_local_loss": 1.0, "t_local_s": 0.1, "t_agg_s": 0.0}
    rec(0, None, base)
    rec(1, None, base)
    rec(2, None, {**base, flag: 1.0})
    assert (len(rec.landings), rec.failed) == (2, 1)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"] if w["chips"] == 1])
def test_tiny_run_drives_every_step(name):
    cell = tiny.cell(name)
    out = tiny.drive(cell)
    assert out.attempted > 0 and out.failed == 0
    assert set(out.checks) == set(cell.traffic["limits"])
    assert all(np.isfinite(v) and v < 0.1 for v, _ in out.checks.values())
    e2e = {m["name"] for m in cell.end_to_end}
    assert set(out.values) == e2e
    assert out.facts["compiles_in_window"] == 0


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reference_weights_have_the_programs_shapes(config):
    """The reference builds every weight the program's model has at the
    configuration's sizes (biases and an untied head included), and the
    adapters it trains are the program's."""
    from repro.models import init_lora_params, init_params

    cell = harness.resolve([w["name"] for w in BENCH["workloads"] if w["config"] == config][0])
    cfg, ref = cell.config, cell.reference()
    mcfg = rc.model_config(cfg)
    key = jax.random.PRNGKey(0)
    shapes = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), t)
    lora = jax.eval_shape(lambda: init_lora_params(key, mcfg))
    assert {k: v.shape for k, v in rc.lora_from_program(lora).items()} == ref.lora_shapes(cfg)
    if hasattr(ref, "make_weights"):
        made = jax.eval_shape(lambda: ref.make_weights(cfg, key))
        assert shapes(rc.base_to_program(made["base"])) == shapes(
            jax.eval_shape(lambda: init_params(key, mcfg)))
