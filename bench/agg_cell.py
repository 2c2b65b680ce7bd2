"""Server aggregation of planted cohorts: the FL server of an adapter
fine-tune, which never holds the base model.

Set-up builds the adapter tree's shapes from the configuration, compiles
the program's aggregation step (``launch.steps.make_agg_step``, FedRPCA on
the packed engine) and runs ``warmup_rounds`` rounds.  In the window each
round makes its cohort on the device, aggregates it and lands the update
into the global adapters; ``in_flight`` rounds are dispatched ahead of the
one waited for, so that the chip stays fed while the host stalls.  When
the time is up nothing more is sent, every round sent is waited for, and
the clock is read after that wait.

Correct: a sample of the window's aggregations, drawn from the seed, plus
the last one, is recomputed by the plain reference (``configs/<config>.ref.py``)
from cohorts the generator makes anew.  Compared, each the largest over
the sample: the relative Frobenius gap of the update.  Logged, not
compared: the widest gap of an update entry over the reference update's
RMS (the control reads no higher on it than the program does).
"""
from __future__ import annotations

import collections
import functools
import gc
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench import trace as trace_lib
from bench.harness import Outcome, device_facts, log
from bench.round_cell import agg_config, lora_from_program, lora_to_program, model_config

from repro.launch import steps as steps_lib
from repro.models import init_lora_params


class Program:
    def __init__(self, cell):
        cfg, tr = cell.config, cell.traffic
        self.cell = cell
        self.targets = cfg["lora_targets"]
        self.tree = jax.eval_shape(
            lambda: init_lora_params(jax.random.PRNGKey(0), model_config(cfg)))
        self.shapes = cell.reference().lora_shapes(cfg)
        have = {k: tuple(v.shape) for k, v in lora_from_program(self.tree).items()}
        if have != self.shapes:
            raise ValueError(f"the program's adapter tree {have} is not the "
                             f"configuration's {self.shapes}")
        self.agg_step = jax.jit(steps_lib.make_agg_step(agg_config(tr), engine="packed"))
        self.apply = jax.jit(steps_lib.apply_update)
        self.make_base = jax.jit(functools.partial(
            gen.planted_base, shapes=self.shapes, traffic=tr))
        self.make_cohort = jax.jit(
            lambda base, key: lora_to_program(
                gen.planted_cohort(base, key, self.shapes, tr), self.targets))


def round_key(seed: int, k: int):
    return jax.random.fold_in(gen.seed_key(seed), 1 + k)


def sample(tr: dict, seed: int) -> set:
    """Window rounds whose updates are compared (offsets from the window's
    first round), drawn from the seed."""
    rng = np.random.default_rng([seed, 3])
    return set(int(i) for i in rng.choice(tr["check_span"], tr["check_sample"], replace=False))


def reference_updates(cell, seed: int, rounds: list, precision: str = "highest") -> dict:
    """Round -> reference update ``{leaf: array}`` on the host."""
    tr = cell.traffic
    ref = cell.reference()
    shapes = ref.lora_shapes(cell.config)
    base = jax.jit(functools.partial(gen.planted_base, shapes=shapes, traffic=tr))(
        gen.seed_key(seed))
    cohort = jax.jit(lambda b, k: gen.planted_cohort(b, k, shapes, tr))
    out = {}
    for k in rounds:
        deltas = cohort(base, round_key(seed, k))
        upd, _, _ = ref.aggregate(deltas, iters=tr["aggregator"]["rpca_iters"],
                                  precision=precision)
        out[k] = jax.device_get(upd)
        del deltas, upd
    return out


def readings(prog: dict, ref: dict) -> dict:
    """Largest over the compared rounds of the widest entry gap over the
    reference RMS, and of the relative Frobenius gap."""
    widest = rel = 0.0
    for k, r_upd in ref.items():
        p_upd = prog[k]
        num = sq = sqd = 0.0
        big = 0.0
        for leaf, r in r_upd.items():
            r = np.asarray(r, np.float64)
            d = np.asarray(p_upd[leaf], np.float64) - r
            big = max(big, float(np.max(np.abs(d))))
            sq += float(np.sum(r * r))
            sqd += float(np.sum(d * d))
            num += r.size
        widest = max(widest, big / np.sqrt(sq / num))
        rel = max(rel, np.sqrt(sqd / sq))
    return {"update_widest_gap": float(widest), "update_rel_gap": float(rel)}


def run(cell, seed: int, seconds: float, trace_dir: str | None, devs, counter, facts_at):
    tr = cell.traffic
    prog = Program(cell)
    base = prog.make_base(gen.seed_key(seed))
    glob = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, jnp.float32), prog.tree)

    def send(k):
        """Dispatch round ``k``; returns its update and the landed global."""
        nonlocal glob
        with jax.profiler.TraceAnnotation("bench.cohort"):
            deltas = prog.make_cohort(base, round_key(seed, k))
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
    picks = {first + i for i in sample(tr, seed)}
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
    log(f"window: {n} aggregations in {t_end - t0:.3f}s, compiles in window "
        f"{counter.in_window}; longest gaps between landings "
        f"{sorted(gaps.round(4).tolist())[-3:]}")
    out.device = device_facts(devs)
    if trace_dir:
        out.trace = trace_lib.reduce(trace_lib.load_xplane(trace_dir))
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
