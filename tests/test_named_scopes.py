"""Named scopes of the local and aggregation steps, and the ``svt_steps``
counter.

A scope reaches the compiled HLO as the ``op_name`` metadata of every
operation traced under it, which is what a device trace's ``tf_op`` stat
carries (``bench/scopes.py``); the scopes change that metadata and nothing
else.  ``svt_steps`` is the denominator of the exact-eigh fallback share.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs as cfglib
from repro.core import AggregatorConfig, rpca_diag_summary
from repro.core import engine as engine_lib
from repro.launch import steps as steps_lib
from repro.models import init_lora_params, init_params

AGG_SCOPES = {"agg.pack", "agg.admm", "agg.svt", "agg.tail", "agg.unpack"}


def op_names(compiled_text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def scopes(compiled_text: str) -> set:
    return {s for path in op_names(compiled_text)
            for s in re.findall(r"(?:agg|local)\.[a-z.]*[a-z]", path)}


def tree(clients=5):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    return {"q": {"A": jax.random.normal(k[0], (clients, 4, 16)),
                  "B": jax.random.normal(k[1], (clients, 16, 4))},
            "v": {"A": jax.random.normal(k[2], (clients, 4, 16)),
                  "B": jax.random.normal(k[3], (clients, 8, 4))}}


AGG_CONFIGS = {
    "gram": AggregatorConfig(method="fedrpca", rpca_iters=3),
    "subspace": AggregatorConfig(method="fedrpca", rpca_iters=3, svt_mode="subspace"),
    "carry": AggregatorConfig(method="fedrpca", rpca_iters=3, svt_mode="subspace",
                              carry_mode="subspace"),
}


@pytest.mark.parametrize("mode", sorted(AGG_CONFIGS))
def test_agg_step_scopes_reach_op_metadata(mode):
    cfg = AGG_CONFIGS[mode]
    deltas = tree()
    args = (deltas,)
    if cfg.carry_mode != "none":
        carry = engine_lib.init_agg_carry(engine_lib.plan_aggregation(deltas, cfg))
        args = (deltas, None, None, carry)
    text = jax.jit(steps_lib.make_agg_step(cfg)).lower(*args).compile().as_text()
    assert scopes(text) == AGG_SCOPES
    # The SVT step sits inside the ADMM loop.
    assert any(re.search(r"agg\.admm/.*agg\.svt", p) for p in op_names(text))


def test_apply_update_scope():
    lora = tree(1)
    text = jax.jit(steps_lib.apply_update).lower(lora, lora).compile().as_text()
    assert scopes(text) == {"agg.apply"}


def test_local_step_scopes_reach_op_metadata():
    cfg = cfglib.get_config("stablelm-1.6b").reduced()
    key = jax.random.PRNGKey(0)
    base, lora = init_params(key, cfg), init_lora_params(key, cfg)
    batch = {"tokens": jnp.zeros((2, 2, 8), jnp.int32),
             "labels": jnp.zeros((2, 2, 8), jnp.int32)}
    step = steps_lib.make_local_step(cfg, local_lr=1e-3, local_steps=2,
                                     local_optimizer="adam", remat=False)
    text = jax.jit(step).lower(base, lora, batch).compile().as_text()
    assert scopes(text) == {"local.grad", "local.opt", "local.delta"}
    # The backward pass carries the scope of the step that differentiates.
    assert any("local.grad/transpose(" in p for p in op_names(text))


@pytest.mark.parametrize("fixed", [True, False])
def test_svt_steps_counts_the_steps_each_carried_tier_ran(fixed):
    cfg = AggregatorConfig(method="fedrpca", rpca_iters=6, rpca_fixed_iters=fixed,
                           rpca_tol=1e-4, svt_mode="subspace", carry_mode="subspace")
    deltas = tree(6)
    plan = engine_lib.plan_aggregation(deltas, cfg)
    tiers = sum(len(t.tiers()) for t in plan.tiers.values())
    _, _, diag = engine_lib.aggregate_planned(plan, deltas, with_diagnostics=True)
    summary = rpca_diag_summary(diag)
    steps, falls = int(summary["svt_steps"]), int(summary["fallback_count"])
    if fixed:
        assert steps == cfg.rpca_iters * tiers
    else:
        assert 1 <= steps <= cfg.rpca_iters * tiers
    assert 1 <= falls <= steps  # a cold start falls back at least once


def test_hybrid_local_step_scopes_and_counters():
    """The Mamba-2 mixers and the expert layer carry their scopes into the
    compiled local step, which returns the expert counters beside the loss."""
    cfg = cfglib.get_config("granite-4.0-h-small").reduced()
    key = jax.random.PRNGKey(0)
    base, lora = init_params(key, cfg), init_lora_params(key, cfg)
    batch = {"tokens": jnp.zeros((2, 1, 32), jnp.int32),
             "labels": jnp.zeros((2, 1, 32), jnp.int32)}
    step = jax.jit(steps_lib.make_local_step(cfg, local_lr=1e-3, local_steps=2,
                                             local_optimizer="adam"))
    text = step.lower(base, lora, batch).compile().as_text()
    assert {"local.ssd", "local.moe.route", "local.moe.experts",
            "local.moe.shared"} <= scopes(text)
    assert any("local.grad/transpose(" in p and "local.ssd" in p for p in op_names(text))
    _, _, _, counts = step(base, lora, batch)
    assert set(counts) == {"moe_load_max"} and float(counts["moe_load_max"]) >= 1.0
