"""Each fault a cell can have, planted under a whole tiny run on the CPU,
must come out as not correct; so must the control.

Faults: a step that returns its state unchanged; half of the batch (round
cells) or of the cohort (aggregation cells) left out, the mean taken over
the rest; an answer altered where it is produced.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import agg_cell, calibrate, harness, round_cell
from bench.tests import tiny

ROUND, AGG = "stablelm-1.6b.round-c8", "deepseek-67b.agg-c20"


def _unchanged(out):
    return (jax.tree_util.tree_map(jnp.zeros_like, out[0]),) + tuple(out[1:])


def _altered(out):
    leaves, treedef = jax.tree_util.tree_flatten(out[0])
    leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1.0)
    return (jax.tree_util.tree_unflatten(treedef, leaves),) + tuple(out[1:])


def _broken_agg(real, fault):
    def make(*a, **k):
        step = real(*a, **k)

        def broken(deltas, *rest, **kw):
            if fault == "half":
                deltas = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], deltas)
            out = step(deltas, *rest, **kw)
            return {"unchanged": _unchanged, "altered": _altered}.get(fault, lambda o: o)(out)

        broken.carry_on = getattr(step, "carry_on", False)
        return broken

    return make


def _half_batch_local(real):
    def make(*a, **k):
        step = real(*a, **k)

        def broken(base, lora, batch, key=None):
            half = {n: x[:, : x.shape[1] // 2] for n, x in batch.items()}
            return step(base, lora, half, key)

        return broken

    return make


@pytest.mark.parametrize("workload,fault", [
    (ROUND, "unchanged"), (ROUND, "half"), (ROUND, "altered"),
    (AGG, "unchanged"), (AGG, "half"), (AGG, "altered"),
])
def test_fault_is_not_correct(monkeypatch, workload, fault):
    cell = tiny.cell(workload)
    mod = round_cell if cell.traffic["kind"] == "round" else agg_cell
    steps = mod.steps_lib
    if fault == "half" and mod is round_cell:
        monkeypatch.setattr(steps, "make_local_step", _half_batch_local(steps.make_local_step))
    else:
        monkeypatch.setattr(steps, "make_agg_step", _broken_agg(steps.make_agg_step, fault))
    out = tiny.drive(cell)
    assert out.attempted > 0
    assert not out.correct, out.checks


def test_sound_tiny_runs_are_correct_at_their_limits():
    """Without a fault the same tiny runs pass their cell's limits, so the
    failures above are the faults' doing."""
    for name in (ROUND, AGG):
        out = tiny.drive(tiny.cell(name))
        assert out.correct, (name, out.checks)


def test_control_round_is_not_correct():
    cell = tiny.cell(ROUND)
    ref = round_cell.reference(cell, tiny.SEED)
    got = round_cell.readings(round_cell.reference(cell, tiny.SEED, "control"), ref)
    lim = cell.traffic["limits"]
    assert not harness.Outcome(checks={k: (got[k], lim[k]) for k in lim}).correct, got


def test_control_agg_separates_from_the_program():
    """The bf16x3 control at deepseek-67b's real module widths (two of its
    95 layers, 20 clients) fails the cell's limits; the program passes."""
    cell = harness.resolve(AGG)
    cell.config.update(num_hidden_layers=2)
    lim = cell.traffic["limits"]
    ref = agg_cell.reference_updates(cell, tiny.SEED, [0])
    prog = calibrate.program_updates(agg_cell.Program(cell), tiny.SEED, [0])
    control = agg_cell.reference_updates(cell, tiny.SEED, [0], "bf16x3")
    judge = lambda got: harness.Outcome(checks={k: (got[k], lim[k]) for k in lim}).correct
    assert judge(agg_cell.readings(prog, ref))
    assert not judge(agg_cell.readings(control, ref))
