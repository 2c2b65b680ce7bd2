"""The scope reduction (``bench/scopes.py``) on hand-made planes laid out as
a TPU trace is, with a clock offset planted between host and device; on the
benchmark's recorded traces; and its reading of the ``tf_op`` stat from a
serialized XSpace."""
import os

import pytest

from bench import harness, scopes, trace
from bench.tests.test_bench_trace import _planes

DATA = os.path.join(os.path.dirname(__file__), "data")
OFFSET = 1100.0  # ns the planted device clock runs behind the host's


def _scoped_planes():
    """One chip over a window [0, 10000): a local step (two ops under
    local.grad, one under local.opt, one with no scope) then an aggregation
    step (an agg.admm loop holding an agg.svt op) and an apply.  The host
    dispatched each program OFFSET ns after the device shows it starting."""
    ops = [("%fusion.1 = f(a)", 1000.0, 2000.0), ("%fusion.2 = f(b)", 2000.0, 2500.0),
           ("%add.3 = add(c)", 2500.0, 2800.0), ("%copy.4 = copy(d)", 2800.0, 3000.0),
           ("%while.5 = while(e)", 4000.0, 5500.0), ("%fusion.6 = f(g)", 4000.0, 4500.0),
           ("%custom-call.7 = eigh(h)", 4500.0, 5500.0), ("%fusion.8 = f(i)", 7000.0, 7200.0)]
    op_scopes = {
        "%fusion.1 = f(a)": "jit(local_step)/vmap()/local.grad/jvp()/dot_general:",
        "%fusion.2 = f(b)": "jit(local_step)/vmap()/local.grad/transpose(jvp())/mul:",
        "%add.3 = add(c)": "jit(local_step)/vmap()/while/body/local.opt/add:",
        "%fusion.6 = f(g)": "jit(agg_step)/agg.admm/while/body/mul:",
        "%custom-call.7 = eigh(h)": "jit(agg_step)/agg.admm/while/body/agg.svt/vmap(jit(eigh))/eigh:",
        "%fusion.8 = f(i)": "jit(apply_update)/agg.apply/add:",
    }
    mods = [("jit_local_step(11)", 1000.0, 2000.0), ("jit_agg_step(12)", 4000.0, 1500.0),
            ("jit_apply_update(13)", 7000.0, 200.0)]
    host = [("bench.local_dispatch", 1000.0 + OFFSET - 300.0, 1000.0 + OFFSET - 100.0),
            ("bench.agg_dispatch", 4000.0 + OFFSET, 4000.0 + OFFSET + 50.0),
            # Idle gaps on the device: [3000, 4000) and [5500, 7000).  Shifted
            # onto the device clock, fed.land.wait holds the first gap's middle
            # and fed.land.apply the second's; unshifted, neither does.
            ("fed.land", 3000.0 + OFFSET, 7800.0 + OFFSET),
            ("fed.land.wait", 3200.0 + OFFSET, 3800.0 + OFFSET),
            ("fed.land.apply", 6200.0 + OFFSET, 6900.0 + OFFSET)]
    planes = [{"name": "/host:CPU", "lines": {"python": [(trace.WINDOW_SPAN, 0.0, 10000.0)] + [
        (n, s, e - s) for n, s, e in host]}},
        {"name": "/device:TPU:0", "lines": {
            trace.OPS_LINE: [(n, s, e - s) for n, s, e in ops],
            trace.MODULES_LINE: [(n, s, d) for n, s, d in mods]},
         "op_scopes": op_scopes}]
    return planes


@pytest.mark.parametrize("path,scope", [
    ("jit(agg_step)/agg.admm/while/body/agg.svt/eigh:", "agg.svt"),
    ("jit(local_step)/vmap()/local.grad/transpose(jvp())/mul:", "local.grad"),
    ("jit(local_step)/transpose(jvp(local.grad))/dot_general:", "local.grad"),
    ("jit(local_step)/vmap()/while/body/closed_call/add:", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_scope_of_takes_the_innermost_program_scope(path, scope):
    assert scopes.scope_of(path) == scope


def test_scope_times_count_leaf_ops_by_program_and_innermost_scope():
    st = scopes.scope_times(_scoped_planes())
    assert st == {
        "jit_local_step/local.grad": pytest.approx(1500e-9),
        "jit_local_step/local.opt": pytest.approx(300e-9),
        "jit_local_step/unscoped": pytest.approx(200e-9),
        "jit_agg_step/agg.admm": pytest.approx(500e-9),
        "jit_agg_step/agg.svt": pytest.approx(1000e-9),
        "jit_apply_update/agg.apply": pytest.approx(200e-9),
    }
    # Leaf operations only: together they are the chip's busy time.
    red = scopes.reduce(_scoped_planes())
    assert sum(st.values()) == pytest.approx(red["busy_s"])


def test_scope_times_mean_over_chips():
    one = _scoped_planes()
    two = one + [dict(one[1], name="/device:TPU:1", op_scopes={})]
    st = scopes.scope_times(two)
    assert st["jit_agg_step/agg.svt"] == pytest.approx(500e-9)
    assert st["jit_agg_step/unscoped"] == pytest.approx(750e-9)


def test_clock_offset_is_the_largest_lead_of_a_program_over_its_dispatch():
    planes = _scoped_planes()
    assert scopes.clock_offset(planes) == pytest.approx(OFFSET)


def test_no_dispatch_span_reads_no_offset():
    planes = _planes([("fusion.1", 100.0, 400.0)], host=[("bench.batch", 380.0, 520.0)])
    assert scopes.clock_offset(planes) == 0.0


def test_gaps_are_named_after_the_shift_by_program_spans():
    planes = _scoped_planes()
    gaps = scopes.named_gaps(planes, scopes.clock_offset(planes))
    names = {(s, e): n for n, s, e in gaps}
    assert names[(3000.0, 4000.0)] == "fed.land.wait"
    assert names[(5500.0, 7000.0)] == "fed.land.apply"
    unshifted = {(s, e): n for n, s, e in scopes.named_gaps(planes)}
    assert unshifted[(3000.0, 4000.0)] == scopes.NO_SPAN
    assert unshifted[(5500.0, 7000.0)] == "fed.land"
    red = scopes.reduce(planes)
    assert red["clock_offset_s"] == pytest.approx(OFFSET * 1e-9)
    assert red["idle_by_span"]["fed.land.wait"] == pytest.approx(1000e-9)
    assert red["breakdown"]["device_scopes"][0] == [
        "jit_local_step/local.grad", pytest.approx(1500e-9)]


@pytest.mark.parametrize("name", ["v5e_trace.json.gz", "v5e_scoped_trace.json.gz"])
def test_reduce_keeps_every_key_of_the_trace_reduction(name):
    """What ``trace.reduce`` returns, and what the accepted metric readers
    read from it, is the same through ``scopes.reduce``; on the unscoped
    trace, which has no dispatch span and no program span, so are the gap
    names."""
    planes = trace.load_planes(os.path.join(DATA, name))
    old, new = trace.reduce(planes), scopes.reduce(planes)
    for k in ("window_s", "busy_s", "exposed_collective_s", "chips"):
        assert new[k] == old[k]
    assert new["breakdown"]["device_ops"] == old["breakdown"]["device_ops"]
    if name == "v5e_trace.json.gz":
        assert new["breakdown"]["idle_gaps"] == old["breakdown"]["idle_gaps"]
        assert new["clock_offset_s"] == 0.0
    facts = {"rounds": 3, "t_local": [0.3, 0.4], "t_agg": [0.01, 0.02]}
    outs = [harness.Outcome(device={"kind": "TPU v5 lite"}, facts=facts, trace=t)
            for t in (old, new)]
    for workload in ("deepseek-67b.agg-c20", "stablelm-1.6b.round-c8"):
        cell = harness.resolve(workload)
        for m in cell.per_layer:
            reader = cell.reader(m["name"])
            assert reader.read(cell, outs[0]) == reader.read(cell, outs[1])


def test_recorded_scoped_v5e_trace():
    """Three steps on one v5e: a 5 ms host sleep, a jitted local step (a
    differentiated matmul chain) and a jitted aggregation step (a loop of
    eighs), each under its dispatch span (``record_scoped_trace.py``)."""
    planes = trace.load_planes(os.path.join(DATA, "v5e_scoped_trace.json.gz"))
    (dev,) = trace.device_planes(planes)
    paths = dev["op_scopes"].values()
    assert any("local.grad/transpose(" in p for p in paths)  # the backward pass
    st = scopes.scope_times(planes)
    for key in ("jit_local_step/local.grad", "jit_agg_step/agg.svt",
                "jit_agg_step/agg.admm", "jit_agg_step/agg.tail"):
        assert st[key] > 0, key
    # The optimizer step fused into the gradient's last matmul, whose
    # op_name the fusion carries: a fused op counts under one scope.
    assert "jit_local_step/local.opt" not in st
    # What carries no scope is XLA's own: the async copies it inserted.
    t0, t1 = trace.window(planes)
    unscoped = {trace._base(n) for n, _, _ in trace.leaf_ops(trace.op_intervals(dev, t0, t1))
                if scopes.scope_of(dev["op_scopes"].get(n)) == scopes.UNSCOPED}
    assert unscoped == {"copy-start", "copy-done"}
    # Here the device clock read 0.21 ms behind the host's (in the
    # recording of v5e_trace.json.gz, about 1.1 ms): the largest lead of a
    # program over its dispatch span.
    offset = scopes.clock_offset(planes)
    assert offset == pytest.approx(0.210275e6)
    spans = scopes.program_spans(planes)
    mods = sorted((s, trace._base(n)) for n, s, _ in dev["lines"][trace.MODULES_LINE])
    for span, prog in scopes.DISPATCHES.items():
        hosts = sorted(s for n, s, _ in spans if n == span)
        runs = [s for s, n in mods if n == prog]
        assert len(hosts) == len(runs) == 3
        assert min(d - (h - offset) for h, d in zip(hosts, runs)) >= 0.0
    # The host's sleeps name the longest idle gaps, after the shift too.
    gaps = scopes.reduce(planes)["breakdown"]["idle_gaps"]
    assert [k for k, _ in gaps[:3]] == ["bench.host_wait"] * 3


def test_op_scopes_reads_tf_op_from_a_serialized_xspace():
    space = scopes._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((7, "tf_op"), (8, "flops"), (9, "jit(f)/agg.svt/eigh:")):
        dev.stat_metadata.add(key=key).value.name = name
    fused = dev.event_metadata.add(key=1).value
    fused.name = "%fusion.1 = f32[] fusion()"
    fused.stats.add(metadata_id=8)
    fused.stats.add(metadata_id=7, str_value="jit(f)/agg.admm/mul:")
    eigh = dev.event_metadata.add(key=2).value
    eigh.name = "%custom-call.2 = eigh()"
    eigh.stats.add(metadata_id=7, ref_value=9)  # an interned string
    dev.event_metadata.add(key=3).value.name = "%copy-start = copy-start()"
    space.planes.add(name="/host:CPU")
    assert scopes.op_scopes(space.SerializeToString()) == {"/device:TPU:0": {
        "%fusion.1 = f32[] fusion()": "jit(f)/agg.admm/mul:",
        "%custom-call.2 = eigh()": "jit(f)/agg.svt/eigh:"}}
