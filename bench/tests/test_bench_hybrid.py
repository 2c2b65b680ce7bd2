"""The hybrid round cell and the mesh aggregation cell on the CPU: the
reference against the program's shapes, the FLOP count by hand, planted
faults under whole tiny runs, and the mesh cell on four host devices."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts_hybrid, harness, hybrid_round_cell as hc
from bench.tests import tiny
from repro.models import blocks, init_lora_params, init_params

HYBRID = "granite-4.0-h-small.round-c8-s1k"


def test_reference_builds_every_program_weight():
    """At the configuration's sizes the reference makes every weight the
    program's model has, at its shape, and trains the program's adapters."""
    cell = harness.resolve(HYBRID)
    cfg, ref = cell.config, cell.reference()
    mcfg = hc.model_config(cfg)
    key = jax.random.PRNGKey(0)
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
    base, lora0 = jax.eval_shape(lambda: hc.make_program_weights(ref, cfg, key))
    assert shapes(base) == shapes(jax.eval_shape(lambda: init_params(key, mcfg)))
    lora = jax.eval_shape(lambda: init_lora_params(key, mcfg))
    assert shapes(lora0) == shapes(lora)
    flat = jax.eval_shape(lambda t: hc.lora_view(t, cfg), lora0)["groups"][0]["mixer"]
    assert {f"{k}.{p}": v[p].shape for k, v in flat.items() for p in v} == ref.lora_shapes(cfg)
    # 10.05M adapter numbers a client: experts 7.56M, mamba 2.39M, attention 0.11M.
    assert sum(int(jnp.prod(jnp.array(s))) for s in ref.lora_shapes(cfg).values()) == 10_050_560


def test_round_flops_hand_count():
    cfg = harness.load_json(f"{harness.BENCH}/configs/granite-4.0-h-small.json")
    d, di, v = 4096, 8192, 12544
    mamba = 2 * d * (2 * di + 2 * 128 + 128) + 2 * di * d
    ssd = 2 * 256 * 128 + 2 * 128 * 256 * 64 + 4 * 128 * 64 * 128
    attn = 2 * d * (2 * 4096 + 2 * 1024)
    # 10 of 72 experts a token, 9 held: 1.25 held experts a token.
    moe = 1.25 * 3 * 2 * d * 768 + 3 * 2 * d * 1536 + 2 * d * 72
    matmul = 9 * mamba + attn + 10 * moe + 2 * d * v
    mix = 9 * ssd + 2 * 2 * 1024 * 4096
    lora = (9 * (2 * 8 * (d + 2 * di + 256 + 128) + 2 * 8 * (di + d))
            + 2 * 8 * (d + 4096) + 2 * 8 * (d + 1024)
            + 10 * 1.25 * (2 * 8 * (d + 1536) + 2 * 8 * (768 + d)))
    per_token = (matmul + mix + lora) + (matmul + 2 * mix + 2 * lora)
    traffic = harness.load_json(f"{harness.BENCH}/traffic/round-c8-s1k.json")
    assert counts_hybrid.round_flops(cfg, traffic) == pytest.approx(8 * 2 * 1024 * per_token)
    # About 2.75 GFLOP a token forward, 9.2e13 a round.
    assert sum(counts_hybrid.forward_flops_per_token(cfg, 1024).values()) == pytest.approx(
        2.746e9, rel=1e-3)


def _float32_cell():
    """The tiny hybrid cell computed in float32, held to the cell's limits."""
    c = tiny.cell(HYBRID)
    c.config["torch_dtype"] = "float32"
    return c


def _drop_expert(real):
    def apply(params, lora, x, cfg):
        # Held expert 0's assignments reach no computation.
        params = {**params, "down": params["down"].at[0].set(0.0)}
        lora = {**lora, "out": {**lora["out"], "B": lora["out"]["B"].at[0].set(0.0)}}
        return real(params, lora, x, cfg)

    return apply


@pytest.mark.parametrize("fault", ["none", "dropped_expert", "no_residual_multiplier"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    if fault == "dropped_expert":
        monkeypatch.setattr(blocks.moe, "apply_expert_share",
                            _drop_expert(blocks.moe.apply_expert_share))
    elif fault == "no_residual_multiplier":
        monkeypatch.setattr(blocks, "_residual", lambda x, out, cfg: x + out)
    out = tiny.drive(_float32_cell())
    assert out.attempted > 0 and set(out.checks) == {"loss_gap", "update_gap", "change_gap"}
    assert out.correct == (fault == "none"), out.checks


MESH_RUN = """
import json, sys
sys.path[:0] = [".", "src"]
from bench import harness
from bench.tests import tiny, tiny_hybrid
tiny_hybrid.register()
out = tiny.drive(tiny.cell("deepseek-67b.agg-c40-x4"))
print(json.dumps({"correct": out.correct, "attempted": out.attempted,
                  "compiles": out.facts["compiles_in_window"], "count": out.device["count"],
                  "checks": out.checks}))
"""


def test_mesh_cell_on_four_host_devices():
    """The mesh cell shards its cohort over four devices and its updates
    match the one-device reference."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", MESH_RUN], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["count"] == 4 and got["attempted"] > 0 and got["compiles"] == 0
    assert got["correct"], got["checks"]


def test_mesh_readings_median_module_gap():
    """One module far off moves the whole-tree gap and not the median
    module's; a gap in every module moves both."""
    from bench import agg_mesh_cell

    rng = np.random.default_rng(0)
    ref = {0: {"q.A": rng.normal(size=(5, 4, 3)), "v.B": rng.normal(size=(5, 3, 2))}}
    one_off = {0: {"q.A": ref[0]["q.A"].copy(), "v.B": ref[0]["v.B"] * (1 + 1e-5)}}
    one_off[0]["q.A"][2] *= 1.1
    got = agg_mesh_cell.readings(one_off, ref)
    assert got["update_median_module_gap"] == pytest.approx(1e-5)
    assert got["update_rel_gap"] > 1e-2
    every = {0: {k: v * (1 + 3e-5) for k, v in ref[0].items()}}
    got = agg_mesh_cell.readings(every, ref)
    assert got["update_median_module_gap"] == pytest.approx(3e-5)
    assert got["update_rel_gap"] == pytest.approx(3e-5)


def test_exposed_collective_counts_leaf_operations():
    """A collective inside the sharded loop's ``while`` is exposed when no
    other leaf operation runs beside it; the ``while`` itself is no cover,
    and a fusion that reads the collective's result is compute."""
    from bench import agg_mesh_cell
    from bench import trace

    def chip(i, ops):
        return {"name": f"/device:TPU:{i}", "lines": {
            trace.OPS_LINE: [(n, s, e - s) for n, s, e in ops],
            trace.MODULES_LINE: [("jit_agg_step(1)", 0.0, 1000.0)]}}

    loop = [("while.1", 100.0, 900.0), ("fusion.2", 100.0, 300.0),
            ("%all-gather.3 = f32[8]{0} all-gather(f32[2]{0} %p), dimensions={0}", 300.0, 500.0),
            ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %all-gather.3), kind=kLoop", 500.0, 900.0)]
    hidden = [("fusion.2", 100.0, 600.0), ("all-reduce.3", 0.0, 0.0)]
    planes = [{"name": "/host:CPU", "lines": {"python": [(trace.WINDOW_SPAN, 100.0, 900.0)]}},
              chip(0, loop), chip(1, hidden)]
    assert trace.reduce(planes)["exposed_collective_s"] == 0.0
    assert agg_mesh_cell.exposed_collective_s(planes) == pytest.approx(0.5 * 200e-9)
