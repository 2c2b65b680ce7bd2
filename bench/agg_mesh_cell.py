"""Server aggregation of planted cohorts with the client axis sharded over a
mesh: the FL server of an adapter fine-tune whose cohort one chip cannot
hold.

As ``bench/agg_cell.py``, on a ``data`` mesh over the cell's chips (the
traffic's ``mesh``): the cohort is made on the devices already sharded on
its client axis, and the program's aggregation step
(``launch.steps.make_agg_step(mesh=...)``, FedRPCA on the packed engine)
runs the ADMM loop shard-local with its reductions as collectives.  The
base of the planted model and the global adapters are replicated.

Correct: as in the agg cell, a sample of the window's aggregations plus the
last one is recomputed by the plain reference and compared by the relative
Frobenius gap of the update, and by the median module's (``readings``).
The reference runs on one chip one leaf at a time: a whole cohort's RPCA
state at this size does not fit one chip.
"""
from __future__ import annotations

import collections
import functools
import gc
import shutil
import time

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from bench import agg_cell, gen
from bench import trace as trace_lib
from bench.harness import Outcome, device_facts, log
from bench.round_cell import agg_config, lora_from_program, lora_to_program

from repro.launch import steps as steps_lib


def make_mesh(devs, tr: dict) -> Mesh:
    shards = tr["mesh"]["data"]
    if len(devs) != shards:
        raise ValueError(f"the mesh has {shards} shards, the cell {len(devs)} chips")
    return Mesh(np.asarray(devs).reshape(shards, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


class Program(agg_cell.Program):
    """The agg cell's program on a mesh: cohorts made client-sharded,
    everything else replicated."""

    def __init__(self, cell, mesh: Mesh):
        super().__init__(cell)
        tr = cell.traffic
        every = NamedSharding(mesh, P())
        self.replicated = every
        self.agg_step = jax.jit(steps_lib.make_agg_step(agg_config(tr), engine="packed",
                                                        mesh=mesh))
        self.make_base = jax.jit(functools.partial(
            gen.planted_base, shapes=self.shapes, traffic=tr), out_shardings=every)
        self.make_cohort = jax.jit(
            lambda base, key: lora_to_program(
                gen.planted_cohort(base, key, self.shapes, tr), self.targets),
            out_shardings=NamedSharding(mesh, P("data")))


def reference_updates(cell, seed: int, rounds: list, precision: str = "highest") -> dict:
    """``agg_cell.reference_updates`` with each leaf aggregated on its own
    (FedRPCA treats every module alone, so the result is the same)."""
    tr = cell.traffic
    ref = cell.reference()
    shapes = ref.lora_shapes(cell.config)
    base = jax.jit(functools.partial(gen.planted_base, shapes=shapes, traffic=tr))(
        gen.seed_key(seed))
    cohort = jax.jit(lambda b, k: gen.planted_cohort(b, k, shapes, tr))
    out = {}
    for k in rounds:
        deltas = cohort(base, agg_cell.round_key(seed, k))
        out[k] = {}
        for name in sorted(deltas):
            upd, _, _ = ref.aggregate({name: deltas[name]}, iters=tr["aggregator"]["rpca_iters"],
                                      precision=precision)
            out[k].update(jax.device_get(upd))
            del upd
        del deltas
    return out


def readings(prog: dict, ref: dict) -> dict:
    """``agg_cell.readings``, and the largest over the compared rounds of
    the median over modules (a layer of one leaf) of the module's relative
    Frobenius gap.  A few modules whose shrunk singular values sit at the
    threshold move by 1e-4 under any rounding, the program's and the
    control's alike, and set the whole-tree gap; the median reads the
    rounding of the rest."""
    median = 0.0
    for k, r_upd in ref.items():
        gaps = []
        for leaf, r in r_upd.items():
            r = np.asarray(r, np.float64).reshape(r.shape[0], -1)
            d = np.asarray(prog[k][leaf], np.float64).reshape(r.shape) - r
            gaps.extend(np.linalg.norm(d, axis=1) / np.linalg.norm(r, axis=1))
        median = max(median, float(np.median(gaps)))
    return {**agg_cell.readings(prog, ref), "update_median_module_gap": median}


def _collective(op: str) -> bool:
    return trace_lib.is_collective(trace_lib._base(op))


def exposed_collective_s(planes: list) -> float:
    """Seconds of the traced window, mean over chips, in which a collective
    runs and no other leaf operation does.  Leaf operations, because
    ``trace.exposed_collective_ns`` counts the ``while`` operation around
    the sharded ADMM loop as compute, which covers every collective in it."""
    t0, t1 = trace_lib.window(planes)
    devs = trace_lib.device_planes(planes)
    secs = 0.0
    for p in devs:
        ops = trace_lib.leaf_ops(trace_lib.op_intervals(p, t0, t1))
        # An op's name is its HLO text, where a fusion names the collectives
        # it reads: judge the op by its own name.
        coll = trace_lib.merge((s, e) for n, s, e in ops if _collective(n))
        rest = trace_lib.merge((s, e) for n, s, e in ops if not _collective(n))
        secs += trace_lib.total(trace_lib.subtract(coll, rest)) * 1e-9
    return secs / len(devs)


def run(cell, seed: int, seconds: float, trace_dir: str | None, devs, counter, facts_at):
    tr = cell.traffic
    prog = Program(cell, make_mesh(devs, tr))
    base = prog.make_base(gen.seed_key(seed))
    glob = jax.device_put(
        jax.tree_util.tree_map(lambda s: np.zeros(s.shape, np.float32), prog.tree),
        prog.replicated)

    def send(k):
        """Dispatch round ``k``; returns its update and the landed global."""
        nonlocal glob
        with jax.profiler.TraceAnnotation("bench.cohort"):
            deltas = prog.make_cohort(base, agg_cell.round_key(seed, k))
        with jax.profiler.TraceAnnotation("bench.agg_dispatch"):
            upd, _ = prog.agg_step(deltas)
        with jax.profiler.TraceAnnotation("bench.land"):
            glob = prog.apply(glob, upd)
        return upd, glob

    def wait(landed):
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(landed)
        return time.perf_counter()

    for k in range(tr["warmup_rounds"]):
        wait(send(k)[1])
    first = tr["warmup_rounds"]
    picks = {first + i for i in agg_cell.sample(tr, seed)}
    kept = {}
    span = None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
    counter.open = True
    t0 = time.perf_counter()
    landings, sent = [], collections.deque()
    k = first
    while True:
        upd, landed = send(k)
        sent.append(landed)
        if k in picks:
            kept[k] = upd
        last = (k, upd)
        k += 1
        if len(sent) > tr["in_flight"]:
            landings.append(wait(sent.popleft()))
        if (k - first >= tr["trace_rounds"] if trace_dir
                else time.perf_counter() - t0 >= seconds):
            break
    while sent:
        landings.append(wait(sent.popleft()))
    t_end = landings[-1]
    counter.open = False
    if span is not None:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    kept[last[0]] = last[1]
    n = k - first
    out = Outcome(attempted=n, failed=0)
    out.values["setup_s"] = t0 - facts_at
    out.values["agg_s"] = (t_end - t0) / n
    out.facts.update(rounds=n, window_s=t_end - t0, compiles_in_window=counter.in_window)
    gaps = np.diff([t0] + landings)
    log(f"window: {n} aggregations in {t_end - t0:.3f}s on {len(devs)} chips, compiles in "
        f"window {counter.in_window}; longest gaps between landings "
        f"{sorted(gaps.round(4).tolist())[-3:]}")
    out.device = device_facts(devs)
    if trace_dir:
        planes = trace_lib.load_xplane(trace_dir)
        out.trace = trace_lib.reduce(planes)
        out.facts["exposed_collective_s"] = exposed_collective_s(planes)
        shutil.rmtree(trace_dir, ignore_errors=True)
    prog_upd = {k: jax.device_get(lora_from_program(u)) for k, u in kept.items()}
    del prog, base, glob, kept, last, upd, landed
    gc.collect()
    t = time.perf_counter()
    ref = reference_updates(cell, seed, sorted(prog_upd))
    log(f"reference: {len(ref)} aggregations in {time.perf_counter() - t:.1f}s")
    got = readings(prog_upd, ref)
    log(f"not compared: {({k: v for k, v in got.items() if k not in tr['limits']})}")
    for name, lim in tr["limits"].items():
        out.checks[name] = (got[name], lim)
    return out
