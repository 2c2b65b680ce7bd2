"""Packed-engine vs per-leaf aggregation wall-time, across SVT modes.

Builds delta pytrees with many *separate* module leaves (the non-scan layout
where the per-leaf reference path hurts most: one vmapped ADMM loop, one tiny
eigh and one stack of elementwise ops per leaf) and times one jitted
``aggregate`` call per (method, engine, svt_mode, n_modules, n_clients) cell.

The trees follow the FedRPCA workload model (a shared low-rank signal plus
per-client sparse outliers — the paper's planted structure) rather than raw
Gaussian noise, so the SVT spectrum settles to a low post-shrink rank within
a few ADMM iterations: the regime the warm-started subspace SVT targets.
LoRA shapes span both the 64- and 128-dim canonical vec buckets.

Sweeps module counts 32 / 128 / 512 and client counts 8 / 32 / 100.
Quick mode (BENCH_QUICK=1 or --quick, either entry point) runs only the
32-module, 8/32-client cells.

Multi-round mode (``--rounds N [--carry-mode ...]``) drives an
``AggSession`` over N *correlated* rounds (slowly-drifting shared core +
persistent per-client spikes — the cross-round structure the paper's
observation implies) and reports cold-round vs warm-round wall time plus
the per-round eigh-fallback counts, against the stateless carry_mode="none"
baseline (the PR 3 cold-start path).

Pipeline mode (``--rounds N`` rides along) additionally drives the REAL
federated phases (``fed.make_round_phases`` + ``fed.pipeline.run_rounds``)
over N rounds on the synthetic FedRPCA task at 8 and 32 clients, timing
the synchronous schedule (staleness=0) against the async double-buffered
pipeline (staleness=1) — the wall-clock overlap win of hiding each round's
client local phase inside the previous round's still-running RPCA split
(DESIGN.md §8).  The cells use a server-bound regime (the paper's: RPCA
dominates the round), where the win is the point of the pipeline.

Mesh mode (``--mesh``) adds the mesh-sharded aggregation cells of
DESIGN.md §10: the packed client axis split over 1/2/4 forced host
devices (XLA_FLAGS is preset before jax loads — run from the CLI), cold
round vs warm-carry rounds at 32–512 packed clients, each against the
``costmodel.mesh_agg_costs`` roofline prediction.  On a one-core CI host
the devices share the core, so the cells demonstrate the memory-headroom
envelope (peak resident bytes per shard), not a wall-clock speedup.

Output contract:
  * CSV rows (stdout): name,us_per_call,derived — derived carries the
    packed speedup vs reference and, for svt_mode=subspace, the speedup vs
    the gram-mode cell.
  * ``BENCH_agg.json`` (path overridable via BENCH_AGG_JSON): machine-
    readable, schema-versioned: {"schema_version": 7, "records": [...]}
    with single-call records {method, engine, svt_mode, n_modules,
    n_clients, masked, us_per_call, spread, compile_s} (interleaved
    min-of-N; spread = (max-min)/min across trials), multi-round records
    {mode: "multi_round", carry_mode, round_type: cold|warm, rounds,
    fallbacks, ...}, pipeline records {mode: "pipeline", staleness,
    n_clients, rounds, us_per_round, speedup_vs_sync}, and serving records
    (``--serve``) {mode: "serve", path: gathered|per_request|merged,
    n_adapters, batch, speedup_vs_per_request, predicted_speedup}, and mesh
    records (``--mesh``) {mode: "mesh", shards, n_clients, round_type,
    fused, fallbacks, predicted_us, predicted_peak_bytes,
    vs_1shard} — uploaded as a CI artifact so the perf trajectory is
    tracked across PRs.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _preset_host_devices(argv: list[str]) -> None:
    """Force 4 host devices BEFORE the first jax import when ``--mesh`` is
    requested (XLA fixes the device count at backend init, so this cannot
    wait until argparse runs after the imports below)."""
    if "--mesh" not in argv:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()


_preset_host_devices(sys.argv[1:])

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import common  # noqa: E402
from repro.core import AggregatorConfig, AggSession, aggregate  # noqa: E402

#: BENCH_agg.json schema version: 2 added the top-level envelope and the
#: multi-round (cross-round carry) records; 3 added the async round
#: pipeline records (mode="pipeline": staleness 0 vs 1 wall clock); 4 added
#: the multi-tenant serving records (mode="serve": gathered-pool vs
#: per-request-gather vs merged adapter-count x batch throughput cells);
#: 5 added the mesh-sharded aggregation records (mode="mesh": 1/2/4 host-
#: device shard sweeps, cold + warm-carry, measured vs
#: costmodel.mesh_agg_costs-predicted wall time and peak bytes); 6 added
#: the fault-tolerance records (mode="faults": rounds-to-target and final
#: accuracy under 0/10/30% scale-corruption with the quarantine on vs
#: off, DESIGN.md §11); 7 made the single-call cells interleaved min-of-N
#: (adding the "spread" trial-dispersion field) and added the sharded
#: fused-tail mesh variants (mode="mesh" records grew "fused"/"overlap"
#: booleans: shard-local Pallas ADMM tail, chunked-psum comm/compute
#: overlap, DESIGN.md §10); 8 added the compressed-uplink records
#: (mode="uplink": dense vs sketch:<k> bytes-per-round, final accuracy,
#: rounds-to-target, and reduction_vs_dense on warm rounds, DESIGN.md §12).
SCHEMA_VERSION = 8

MODULE_COUNTS = (32, 128, 512)
CLIENT_COUNTS = (8, 32, 100)
RPCA_ITERS = 40
# Four LoRA shapes spanning the 64- and 128-dim canonical vec buckets.
SHAPES = ((4, 16), (8, 8), (8, 16), (4, 32))
# Cheap non-RPCA methods included so the JSON covers the method axis.
SIMPLE_METHODS = ("fedavg", "ties")

RECORDS: list[dict] = []


def make_tree(n_modules: int, n_clients: int, seed: int = 0, rank: int = 2,
              sparsity: float = 0.05) -> dict:
    """Planted FedRPCA deltas: shared low-rank core + per-client sparse."""
    rng = np.random.default_rng(seed)
    tree = {}
    for i in range(n_modules):
        shape = SHAPES[i % len(SHAPES)]
        d = int(np.prod(shape))
        low = rng.normal(size=(d, rank)) @ rng.normal(size=(rank, n_clients))
        spikes = rng.random((d, n_clients)) < sparsity
        sparse = np.where(spikes, 5.0 * rng.normal(size=(d, n_clients)), 0.0)
        mats = (low + sparse).T.reshape(n_clients, *shape)
        tree[f"layer{i:03d}"] = jnp.asarray(mats, jnp.float32)
    return tree


def record(name: str, us: float, derived: str, **meta) -> None:
    common.emit(name, us, derived)
    RECORDS.append({**meta, "us_per_call": round(us, 1)})


def time_fn(fn, *args, repeats: int = 3) -> tuple[float, float]:
    """Returns (seconds_per_call, compile_seconds)."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / repeats, compile_s


def time_interleaved(fns: dict, trials: int = 5) -> dict:
    """Interleaved min-of-N across variants (the pipeline cells' estimator).

    Compiles every variant once, then alternates single timed calls across
    all of them for ``trials`` passes — on a shared CPU a slow machine
    phase hits every variant equally instead of biasing whichever cell ran
    during it (the v6 masked-vs-dense "overhead" was exactly such an
    artifact).  ``fns`` maps name -> (jitted_fn, args); returns name ->
    (min_secs, spread, compile_secs) with spread = (max - min) / min.
    """
    compiles, times = {}, {name: [] for name in fns}
    for name, (fn, args) in fns.items():
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        compiles[name] = time.perf_counter() - t0
    for _ in range(trials):
        for name, (fn, args) in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times[name].append(time.perf_counter() - t0)
    return {
        name: (min(ts), (max(ts) - min(ts)) / min(ts), compiles[name])
        for name, ts in times.items()
    }


def bench_cell(tree, n_modules: int, n_clients: int) -> None:
    mask = (jnp.arange(n_clients) < max(3 * n_clients // 4, 1)).astype(jnp.float32)

    # One jitted variant per cell; all cells of this (m, c) grid point are
    # timed interleaved so cross-variant ratios are noise-robust.
    fns = {}
    for svt_mode in ("gram", "subspace"):
        cfg = AggregatorConfig(method="fedrpca", rpca_iters=RPCA_ITERS, svt_mode=svt_mode)
        fns[("packed", svt_mode, False)] = (
            jax.jit(lambda t, c=cfg: aggregate(t, c, engine="packed")), (tree,)
        )
        fns[("packed", svt_mode, True)] = (
            jax.jit(lambda t, m, c=cfg: aggregate(t, c, engine="packed", mask=m)),
            (tree, mask),
        )
    rcfg = AggregatorConfig(method="fedrpca", rpca_iters=RPCA_ITERS)
    fns[("reference", "gram", False)] = (
        jax.jit(lambda t: aggregate(t, rcfg, engine="reference")), (tree,)
    )
    timed = time_interleaved(fns)

    secs = {m: timed[("packed", m, False)][0] for m in ("gram", "subspace")}
    for svt_mode in ("gram", "subspace"):
        s, spread, comp = timed[("packed", svt_mode, False)]
        extra = "" if svt_mode == "gram" else f" svt_speedup={secs['gram'] / s:.2f}x"
        record(
            f"agg_fedrpca_packed_{svt_mode}_m{n_modules}_c{n_clients}",
            s * 1e6, f"compile={comp:.2f}s spread={spread:.2f}{extra}",
            method="fedrpca", engine="packed", svt_mode=svt_mode,
            n_modules=n_modules, n_clients=n_clients, masked=False,
            spread=round(spread, 3), compile_s=round(comp, 2),
        )
        ms, mspread, mcomp = timed[("packed", svt_mode, True)]
        record(
            f"agg_fedrpca_masked_{svt_mode}_m{n_modules}_c{n_clients}",
            ms * 1e6, f"overhead_vs_dense={ms / s:.2f}x spread={mspread:.2f}",
            method="fedrpca", engine="packed", svt_mode=svt_mode,
            n_modules=n_modules, n_clients=n_clients, masked=True,
            spread=round(mspread, 3), compile_s=round(mcomp, 2),
        )
    rs, rspread, rcomp = timed[("reference", "gram", False)]
    record(
        f"agg_fedrpca_reference_m{n_modules}_c{n_clients}",
        rs * 1e6,
        f"packed_gram_speedup={rs / secs['gram']:.2f}x "
        f"packed_subspace_speedup={rs / secs['subspace']:.2f}x "
        f"spread={rspread:.2f} compile={rcomp:.2f}s",
        method="fedrpca", engine="reference", svt_mode="gram",
        n_modules=n_modules, n_clients=n_clients, masked=False,
        spread=round(rspread, 3), compile_s=round(rcomp, 2),
    )

    # Cheap methods: one cell per engine for the JSON's method axis,
    # interleaved as their own group (their microsecond scale would vanish
    # inside the fedrpca group's trial cadence).
    mfns = {}
    for method in SIMPLE_METHODS:
        mc = AggregatorConfig(method=method)
        for engine in ("packed", "reference"):
            mfns[(method, engine)] = (
                jax.jit(lambda t, c=mc, e=engine: aggregate(t, c, engine=e)),
                (tree,),
            )
    for (method, engine), (s, spread, comp) in time_interleaved(mfns).items():
        record(
            f"agg_{method}_{engine}_m{n_modules}_c{n_clients}",
            s * 1e6, f"compile={comp:.2f}s spread={spread:.2f}",
            method=method, engine=engine, svt_mode=None,
            n_modules=n_modules, n_clients=n_clients, masked=False,
            spread=round(spread, 3), compile_s=round(comp, 2),
        )


def make_round_trees(n_modules: int, n_clients: int, rounds: int, seed: int = 0,
                     rank: int = 2, sparsity: float = 0.05, drift: float = 0.02):
    """Correlated multi-round deltas: the shared low-rank core drifts slowly
    and the per-client sparse outliers persist on a fixed support (the
    paper's client-specific knowledge) — round t+1's matrix is close to
    round t's ADMM fixed point, the regime the cross-round carry targets."""
    rng = np.random.default_rng(seed)
    cores, spikes, shapes = {}, {}, {}
    for i in range(n_modules):
        shape = SHAPES[i % len(SHAPES)]
        d = int(np.prod(shape))
        shapes[i] = shape
        cores[i] = (rng.normal(size=(d, rank)), rng.normal(size=(rank, n_clients)))
        supp = rng.random((d, n_clients)) < sparsity
        spikes[i] = np.where(supp, 5.0 * rng.normal(size=(d, n_clients)), 0.0)
    out = []
    for _t in range(rounds):
        tree = {}
        for i in range(n_modules):
            u, w = cores[i]
            w_t = w + drift * rng.normal(size=w.shape)
            sp_t = spikes[i] * (1.0 + 0.05 * rng.normal(size=spikes[i].shape))
            tree[f"layer{i:03d}"] = jnp.asarray(
                (u @ w_t + sp_t).T.reshape(n_clients, *shapes[i]), jnp.float32
            )
        out.append(tree)
    return out


def bench_multi_round(rounds: int, carry_mode: str, n_modules: int = 32,
                      n_clients: int = 32) -> None:
    """Cold-round vs warm-round wall time of a cross-round AggSession.

    Both carry modes run tolerance-based ADMM (the carry's payoff is fewer
    iterations to re-converge, which fixed-iteration mode deliberately
    forgoes) at rpca_tol=3e-4 — the tolerance every planted module
    genuinely reaches (the bucket while-loop runs until its *slowest*
    module passes, so a tighter tol would measure one straggler's tail
    stall, not the carry): warm rounds re-converge in < 10 matmul-only
    iterations while cold rounds pay the eigh burn-in plus ~3x the trip
    count; carry_mode="none" is the stateless PR 3 cold-start baseline.
    """
    if rounds < 2:
        raise ValueError(f"multi-round mode needs --rounds >= 2, got {rounds}")
    cfg = AggregatorConfig(
        method="fedrpca", rpca_iters=RPCA_ITERS, rpca_fixed_iters=False,
        rpca_tol=3e-4, svt_mode="subspace", carry_mode=carry_mode,
    )
    trees = make_round_trees(n_modules, n_clients, rounds)
    sess = AggSession(cfg)
    # Round 0 compiles + runs cold; re-time a fresh cold round afterwards.
    t0 = time.perf_counter()
    jax.block_until_ready(sess.step(trees[0])[0])
    compile_s = time.perf_counter() - t0

    def stats(diag):
        if not diag.scalars:  # carry_mode="none": no session health scalars
            return -1, 0.0
        return int(diag.scalars["fallback_count"]), float(diag.scalars["carry_hit_rate"])

    times, falls, hits = [], [], []
    for tree in trees:
        t0 = time.perf_counter()
        out, diag = sess.step(tree)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
        f, h = stats(diag)
        falls.append(f)
        hits.append(h)
    sess.reset()
    t0 = time.perf_counter()
    out, cold_diag = sess.step(trees[0])
    jax.block_until_ready(out)
    cold_s = time.perf_counter() - t0
    cold_falls = stats(cold_diag)[0]
    warm = times[1:]
    warm_s = sum(warm) / len(warm)
    tag = f"m{n_modules}_c{n_clients}"
    record(
        f"agg_round_cold_{carry_mode}_{tag}", cold_s * 1e6,
        f"compile={compile_s:.2f}s cold_fallbacks={cold_falls}",
        mode="multi_round", carry_mode=carry_mode, round_type="cold",
        rounds=rounds, n_modules=n_modules, n_clients=n_clients,
        fallbacks=cold_falls, compile_s=round(compile_s, 2),
    )
    record(
        f"agg_round_warm_{carry_mode}_{tag}", warm_s * 1e6,
        f"cold_to_warm={cold_s / warm_s:.2f}x "
        f"warm_fallbacks={max(falls[1:])} hit_rate={min(hits[1:]):.2f}",
        mode="multi_round", carry_mode=carry_mode, round_type="warm",
        rounds=rounds, n_modules=n_modules, n_clients=n_clients,
        fallbacks=max(falls[1:]), compile_s=round(compile_s, 2),
    )


#: Pipeline cells: client counts of the paper's server-bound sweet spot.
PIPELINE_CLIENTS = (8, 32)


def bench_pipeline(rounds: int, n_clients: int, local_steps: int | None = None) -> None:
    """Synchronous vs async double-buffered federated rounds, end to end.

    Drives the real split phases on the synthetic non-IID task: the local
    phase is the vmapped per-client adam scan, the aggregation phase the
    packed fedrpca step.  The regime is balanced (rpca_iters=40 gram SVT,
    8 local adam steps): the RPCA split and the cohort's local work cost
    the same order of wall clock, so at staleness=1 each local phase
    should hide inside the previous round's in-flight aggregation (the
    ``AggWorker`` thread makes that real on XLA CPU's synchronous
    dispatch).  Reported ``speedup_vs_sync`` is the whole-run wall-clock
    ratio at matched round counts; staleness=0 is bitwise the synchronous
    driver, so its cell doubles as the baseline.
    """
    if rounds < 2:
        raise ValueError(f"pipeline mode needs --rounds >= 2, got {rounds}")
    if local_steps is None:
        local_steps = 8
    from repro.fed import (
        FedRunConfig, LocalSpec, init_round_state, make_round_phases,
        run_rounds, synth,
    )
    from repro.optim import make_optimizer

    task = synth.make_synth_task(
        n_clients=n_clients, n_per_client=64, d_in=128, d_feat=128,
        lora_rank=8, alpha=0.3, seed=0,
    )
    local = LocalSpec(
        loss_fn=lambda base, lora, b: synth.loss_fn(base, lora, b, task.lora_scale),
        optimizer=make_optimizer("adam", 1e-2),
        local_steps=local_steps, batch_size=32, lr=1e-2,
    )
    cfg = FedRunConfig(
        aggregator=AggregatorConfig(method="fedrpca", rpca_iters=RPCA_ITERS),
        local=local, rounds=rounds, seed=0,
    )
    phases = make_round_phases(
        task.base, task.client_x, task.client_y, cfg,
        lora_template=synth.init_lora(task),
    )
    lora0 = synth.init_lora(task)

    def one(staleness: int, n_rounds: int) -> float:
        state = init_round_state(lora0, n_clients, 0)
        t0 = time.perf_counter()
        end = run_rounds(phases, state, n_rounds, staleness=staleness, timers=False)
        jax.block_until_ready(end.lora_global)
        return (time.perf_counter() - t0) / n_rounds

    t0 = time.perf_counter()
    one(0, 2)
    sync_comp = time.perf_counter() - t0
    t0 = time.perf_counter()
    one(1, 2)
    pipe_comp = time.perf_counter() - t0
    # Interleaved min-of-N: on shared CPUs the wall-clock noise dwarfs the
    # effect size; the minimum is the standard noise-robust estimator, and
    # alternating the modes keeps a slow machine phase from biasing one.
    sync_trials, pipe_trials = [], []
    for _ in range(5):
        sync_trials.append(one(0, rounds))
        pipe_trials.append(one(1, rounds))
    sync_s, pipe_s = min(sync_trials), min(pipe_trials)
    tag = f"c{n_clients}"
    record(
        f"fed_round_sync_{tag}", sync_s * 1e6, f"compile={sync_comp:.2f}s",
        mode="pipeline", staleness=0, n_clients=n_clients, rounds=rounds,
        local_steps=local_steps, us_per_round=round(sync_s * 1e6, 1),
        speedup_vs_sync=1.0, compile_s=round(sync_comp, 2),
    )
    record(
        f"fed_round_pipelined_{tag}", pipe_s * 1e6,
        f"overlap_speedup={sync_s / pipe_s:.2f}x",
        mode="pipeline", staleness=1, n_clients=n_clients, rounds=rounds,
        local_steps=local_steps, us_per_round=round(pipe_s * 1e6, 1),
        speedup_vs_sync=round(sync_s / pipe_s, 3),
        compile_s=round(pipe_comp, 2),
    )


#: Serve cells: adapter-count x request-count grid (quick keeps the
#: acceptance-critical >=16 x >=16 corner plus one small cell).
SERVE_ADAPTERS = (4, 16, 64)
SERVE_BATCHES = (4, 16, 64)
SERVE_DIMS = dict(d_in=512, d_out=512, rank=16, seq=4)


def bench_serve(n_adapters: int, batch: int) -> None:
    """Multi-tenant LoRA projection: gathered-pool vs per-request vs merged.

    One LoRA-adapted projection (K=N=512, rank 16, 4 tokens/request — the
    decode-ish regime) with ``batch`` requests spread round-robin over
    ``n_adapters`` tenants.  ``gathered`` is the pool path
    (``kernels.gathered_lora_matmul``: sorted/padded segment layout, tile-
    level adapter gather); ``per_request`` materializes each row's (A, B)
    from the pool first (the old ``serve.gather_adapters`` behavior);
    ``merged`` averages the adapters (single-tenant baseline: a lower bound,
    but it serves every tenant the same adapter).  The costmodel's
    ``serve_gather_costs`` entry predicts each cell's crossover.
    """
    from repro.kernels import ops
    from repro.launch.costmodel import serve_gather_costs

    k, n, r, seq = (SERVE_DIMS[x] for x in ("d_in", "d_out", "rank", "seq"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, seq, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    a_pool = jnp.asarray(rng.normal(size=(n_adapters, k, r)), jnp.float32)
    b_pool = jnp.asarray(rng.normal(size=(n_adapters, r, n)), jnp.float32)
    req_slot = jnp.asarray(np.arange(batch) % n_adapters, jnp.int32)

    gathered = jax.jit(
        lambda x, w, ap, bp, s: ops.gathered_lora_matmul(x, w, ap, bp, s, 1.0, impl="xla")
    )

    @jax.jit
    def per_request(x, w, ap, bp, s):
        row_slot = jnp.repeat(s, seq)
        x2 = x.reshape(-1, k)
        ag = jnp.take(ap, row_slot, axis=0)
        bg = jnp.take(bp, row_slot, axis=0)
        xa = jnp.einsum("mk,mkr->mr", x2, ag)
        out = jnp.dot(x2, w, preferred_element_type=jnp.float32)
        return (out + jnp.einsum("mr,mrn->mn", xa, bg)).reshape(batch, seq, n)

    @jax.jit
    def merged(x, w, ap, bp):
        am, bm = jnp.mean(ap, axis=0), jnp.mean(bp, axis=0)
        x2 = x.reshape(-1, k)
        out = jnp.dot(x2, w, preferred_element_type=jnp.float32) + (x2 @ am) @ bm
        return out.reshape(batch, seq, n)

    secs = {}
    secs["per_request"], comp_pr = time_fn(per_request, x, w, a_pool, b_pool, req_slot,
                                           repeats=10)
    secs["gathered"], comp_g = time_fn(gathered, x, w, a_pool, b_pool, req_slot,
                                       repeats=10)
    secs["merged"], comp_m = time_fn(merged, x, w, a_pool, b_pool, repeats=10)
    compile_s = {"per_request": comp_pr, "gathered": comp_g, "merged": comp_m}

    predicted = serve_gather_costs(
        n_requests=batch, seq_len=seq, n_adapters=n_adapters,
        d_in=k, d_out=n, rank=r,
    )["gathered_vs_per_request"]
    tag = f"a{n_adapters}_b{batch}"
    for path, s in secs.items():
        speedup = secs["per_request"] / s
        extra = (
            f" speedup_vs_per_request={speedup:.2f}x predicted={predicted:.2f}x"
            if path == "gathered" else ""
        )
        record(
            f"serve_{path}_{tag}", s * 1e6, extra.strip(),
            mode="serve", path=path, n_adapters=n_adapters, batch=batch,
            seq=seq, rank=r,
            speedup_vs_per_request=round(speedup, 3),
            predicted_speedup=round(predicted, 3) if path == "gathered" else None,
            compile_s=round(compile_s[path], 2),
        )


#: Mesh cells: host-device shard counts x packed-client cohorts.  The
#: 512-client column is the acceptance cell (the cohort where one device's
#: resident footprint is at its worst and 4-way sharding pays); quick mode
#: keeps the small cohorts so CI still exercises every shard count.
MESH_SHARDS = (1, 2, 4)
MESH_CLIENTS = (32, 128, 512)
MESH_CLIENTS_QUICK = (32, 64)
MESH_MODULES = 16
MESH_ITERS = 20
MESH_ROUNDS = 3


def _mesh_predicted(n_modules: int, cohort: int, shards: int, warm: bool,
                    fused: bool = False) -> dict:
    """Costmodel envelope for one mesh cell, summed over the two canonical
    vec buckets SHAPES populates (64 and 128, half the modules each); the
    per-call dispatch overhead is counted once."""
    from repro.launch.costmodel import MESH_DISPATCH_US, mesh_agg_costs

    buckets = {64: 0, 128: 0}
    for i in range(n_modules):
        buckets[int(np.prod(SHAPES[i % len(SHAPES)]))] += 1
    parts = [
        mesh_agg_costs(
            n_modules=count, padded_vec=vec, cohort=cohort, shards=shards,
            rpca_iters=MESH_ITERS, warm=warm, fused_tail=fused,
        )
        for vec, count in buckets.items() if count
    ]
    return {
        "us": sum(p["us"] for p in parts) - MESH_DISPATCH_US * (len(parts) - 1),
        "peak_bytes_per_shard": max(p["peak_bytes_per_shard"] for p in parts),
        "comm_fraction": max(p["comm_fraction"] for p in parts),
    }


def bench_mesh(shards: int, n_clients: int,
               baseline: "tuple[float, float] | None" = None,
               n_modules: int = MESH_MODULES,
               rounds: int = MESH_ROUNDS,
               fused: bool = False) -> "tuple[float, float] | None":
    """Mesh-sharded aggregation: client axis split over ``shards`` host
    devices (DESIGN.md §10), cold round vs warm-carry rounds, against the
    ``mesh_agg_costs`` roofline prediction.

    On the CI host every "device" is a thread on the same core, so sharding
    buys memory headroom (peak resident bytes / shard), not wall clock —
    the costmodel's ``shared_host_core=True`` default predicts exactly
    that, and the perf gate checks the measured/predicted envelope rather
    than a speedup.  ``baseline`` is the (cold_s, warm_s) of the 1-shard
    cell at the same cohort, for the vs-1-shard ratio in the record.
    Returns this cell's (cold_s, warm_s) so the caller can thread it.

    ``fused=True`` runs the shard-local Pallas ADMM/sweep tail
    (``rpca_fused_tail``); it lands as a boolean in the record so the perf
    gate can pair each variant with its matching costmodel prediction.
    """
    if shards > jax.device_count():
        common.emit(
            f"agg_mesh_s{shards}_c{n_clients}", 0.0,
            f"skipped: need {shards} host devices, have {jax.device_count()} "
            "(run with --mesh from the CLI so XLA_FLAGS is preset)",
        )
        return None
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(shards) if shards > 1 else None
    cfg = AggregatorConfig(
        method="fedrpca", rpca_iters=MESH_ITERS,
        svt_mode="subspace", carry_mode="subspace",
        rpca_fused_tail=fused,
    )
    trees = make_round_trees(n_modules, n_clients, rounds, seed=7)
    sess = AggSession(cfg, mesh=mesh)
    t0 = time.perf_counter()
    jax.block_until_ready(sess.step(trees[0])[0])
    compile_s = time.perf_counter() - t0
    sess.reset()
    t0 = time.perf_counter()
    out, cold_diag = sess.step(trees[0])
    jax.block_until_ready(out)
    cold_s = time.perf_counter() - t0
    warm_times, warm_falls = [], []
    for tree in trees[1:]:
        t0 = time.perf_counter()
        out, diag = sess.step(tree)
        jax.block_until_ready(out)
        warm_times.append(time.perf_counter() - t0)
        warm_falls.append(int(diag.scalars["fallback_count"]))
    warm_s = min(warm_times)
    tag = f"s{shards}_c{n_clients}" + ("_fused" if fused else "")
    for round_type, s, falls, base in (
        ("cold", cold_s, int(cold_diag.scalars["fallback_count"]),
         baseline[0] if baseline else None),
        ("warm", warm_s, max(warm_falls), baseline[1] if baseline else None),
    ):
        pred = _mesh_predicted(n_modules, n_clients, shards,
                               round_type == "warm", fused=fused)
        extra = f" vs_1shard={base / s:.2f}x" if base else ""
        record(
            f"agg_mesh_{round_type}_{tag}", s * 1e6,
            f"predicted={pred['us']:.0f}us envelope={s * 1e6 / pred['us']:.2f}x "
            f"fallbacks={falls} compile={compile_s:.2f}s{extra}",
            mode="mesh", shards=shards, n_clients=n_clients,
            n_modules=n_modules, round_type=round_type, rounds=rounds,
            fused=fused,
            fallbacks=falls, predicted_us=round(pred["us"], 1),
            predicted_peak_bytes=int(pred["peak_bytes_per_shard"]),
            predicted_comm_fraction=round(pred["comm_fraction"], 3),
            vs_1shard=round(base / s, 3) if base else None,
            compile_s=round(compile_s, 2),
        )
    return cold_s, warm_s


def bench_faults(rounds: int, n_clients: int = 16) -> None:
    """Convergence under injected corruption, quarantine on vs off.

    Drives the full fed simulation on the synthetic non-IID task with
    ``corrupt_mode="scale"`` (norm blow-up — finite, so it degrades
    convergence instead of NaN-ing the run, which makes guard-off a
    measurable baseline rather than an instant failure).  Cells:
    corruption 0% (clean reference, guard off) and 10/30% x {quarantine
    on, off}; each records final accuracy, rounds-to-target (R@90), and
    whether the final state stayed finite.
    """
    if rounds < 2:
        raise ValueError(f"faults mode needs --rounds >= 2, got {rounds}")
    from repro.fed import (
        FaultConfig, FedRunConfig, GuardConfig, LocalSpec, rounds_to_reach,
        run_simulation, synth,
    )
    from repro.optim import make_optimizer

    task = synth.make_synth_task(
        n_clients=n_clients, n_per_client=64, d_in=128, d_feat=128,
        lora_rank=8, alpha=0.3, seed=0,
    )
    local = LocalSpec(
        loss_fn=lambda base, lora, b: synth.loss_fn(base, lora, b, task.lora_scale),
        optimizer=make_optimizer("adam", 1e-2),
        local_steps=4, batch_size=32, lr=1e-2,
    )
    lora0 = synth.init_lora(task)

    def eval_fn(lora):
        return synth.accuracy(
            task.base, lora, task.test_x, task.test_y, task.lora_scale
        )

    for corrupt in (0.0, 0.1, 0.3):
        for guard in ((False,) if corrupt == 0.0 else (True, False)):
            faults = (
                None if corrupt == 0.0
                else FaultConfig(corrupt=corrupt, corrupt_mode="scale", seed=0)
            )
            cfg = FedRunConfig(
                aggregator=AggregatorConfig(method="fedrpca", rpca_iters=RPCA_ITERS),
                local=local, rounds=rounds, seed=0,
                faults=faults, guard=GuardConfig() if guard else False,
            )
            t0 = time.perf_counter()
            lora, hist = run_simulation(
                task.base, lora0, task.client_x, task.client_y, cfg, eval_fn
            )
            wall = time.perf_counter() - t0
            finite = all(
                bool(jnp.all(jnp.isfinite(x)))
                for x in jax.tree_util.tree_leaves(lora)
            )
            r90 = rounds_to_reach(np.asarray(hist))
            name = f"faults_c{int(corrupt * 100)}_{'guard' if guard else 'noguard'}"
            record(
                name, wall / rounds * 1e6,
                f"acc={float(hist[-1]):.3f} R@90={r90} finite={finite}",
                mode="faults", corrupt=corrupt, guard=bool(guard),
                n_clients=n_clients, rounds=rounds,
                final_acc=round(float(hist[-1]), 4),
                rounds_to_target=int(r90), finite=bool(finite),
            )


def bench_uplink(rounds: int, n_clients: int = 16, k: int = 64,
                 energy_tol: float = 0.6) -> None:
    """Compressed-uplink convergence and byte cells, dense vs sketch:<k>.

    Drives the full fed simulation with the subspace-carrying FedRPCA
    aggregator (the sketch codec projects onto the carry basis, so carry
    must be on) and compares the legacy dense wire against
    ``sketch:<k>:<tol>``.  Each cell records final accuracy,
    rounds-to-target (R@90), and mean uplink bytes per round; the sketch
    cell additionally records the warm-round reduction factor — dense
    bytes over sketched-round bytes, excluding the cold/gated rounds the
    codec deliberately leaves dense (DESIGN.md §12).  perf_gate's
    ``uplink`` gate holds the warm reduction >= 4x at <= 0.01 accuracy
    cost.

    The task sits in the codec's intended regime: near-IID full-batch
    local SGD, where the cohort deltas share a dominant subspace the
    round-to-round carry basis tracks.  Even there the basis explains
    only ~half of each round's energy (the gradient directions rotate as
    training converges), so the cell runs at energy_tol=0.6 rather than
    the conservative CLI default of 0.3 — at this operating point the
    dropped residual is redundant across rounds and the accuracy cost
    stays inside the 0.01 gate budget, while stochastic-heterogeneous
    tasks (mini-batch Adam, low Dirichlet alpha) spread delta energy too
    flat for top-k and correctly stay gated dense.
    """
    if rounds < 2:
        raise ValueError(f"uplink mode needs --rounds >= 2, got {rounds}")
    from repro.fed import (
        FedRunConfig, LocalSpec, rounds_to_reach, run_simulation, synth,
    )
    from repro.optim import make_optimizer

    # d_in=128, d_feat=128, lora_rank=8 -> two modules on the 1024-entry
    # padded vec bucket: dense wire is 8192 B/client, sketch at r=8/k=64
    # is ~1088 B/client -> ~7.5x on warm rounds.
    task = synth.make_synth_task(
        n_clients=n_clients, n_per_client=64, d_in=128, d_feat=128,
        lora_rank=8, alpha=1.0, noise=0.1, seed=0,
    )
    local = LocalSpec(
        loss_fn=lambda base, lora, b: synth.loss_fn(base, lora, b, task.lora_scale),
        optimizer=make_optimizer("sgd", 10.0),
        local_steps=4, batch_size=64, lr=10.0,
    )
    lora0 = synth.init_lora(task)

    def eval_fn(lora):
        return synth.accuracy(
            task.base, lora, task.test_x, task.test_y, task.lora_scale
        )

    dense_bytes = None
    for uplink in ("dense", f"sketch:{k}:{energy_tol}"):
        per_round: list[dict] = []
        cfg = FedRunConfig(
            aggregator=AggregatorConfig(
                method="fedrpca", rpca_iters=RPCA_ITERS,
                svt_mode="subspace", carry_mode="subspace",
            ),
            local=local, rounds=rounds, seed=0, uplink=uplink,
        )
        t0 = time.perf_counter()
        lora, hist = run_simulation(
            task.base, lora0, task.client_x, task.client_y, cfg, eval_fn,
            log_fn=lambda r, m: per_round.append(m),
        )
        wall = time.perf_counter() - t0
        r90 = rounds_to_reach(np.asarray(hist))
        ups = [m["bytes_up"] for m in per_round if "bytes_up" in m]
        mean_up = float(np.mean(ups)) if ups else 0.0
        hits = [m.get("uplink_hit_rate", 0.0) for m in per_round]
        # Warm-round reduction: dense wire bytes over the bytes of the
        # rounds where the sketch actually engaged (hit_rate == 1).
        warm_ups = [
            u for u, h in zip(ups, hits) if h >= 1.0
        ] if uplink != "dense" else []
        reduction = (
            round(dense_bytes / float(np.mean(warm_ups)), 2)
            if warm_ups and dense_bytes else None
        )
        if uplink == "dense":
            dense_bytes = mean_up
        name = "uplink_dense" if uplink == "dense" else f"uplink_sketch{k}"
        extra = f" reduction={reduction}x" if reduction else ""
        record(
            name, wall / rounds * 1e6,
            f"acc={float(hist[-1]):.3f} R@90={r90} "
            f"bytes_up/round={mean_up:.0f} hit={float(np.mean(hits)):.2f}{extra}",
            mode="uplink", uplink=uplink, n_clients=n_clients, rounds=rounds,
            final_acc=round(float(hist[-1]), 4), rounds_to_target=int(r90),
            bytes_up_per_round=round(mean_up, 1),
            uplink_hit_rate=round(float(np.mean(hits)), 3),
            reduction_vs_dense=reduction,
        )


def main(quick: bool | None = None, rounds: int = 0, carry_mode: str = "subspace",
         serve: bool = False, mesh: bool = False, faults: bool = False,
         uplink: bool = False) -> None:
    quick = common.QUICK if quick is None else quick
    module_counts = (32,) if quick else MODULE_COUNTS
    client_counts = (8, 32) if quick else CLIENT_COUNTS
    for n_modules in module_counts:
        for n_clients in client_counts:
            bench_cell(make_tree(n_modules, n_clients), n_modules, n_clients)
    if rounds:
        # The stateless baseline rides along so the JSON always holds the
        # warm-vs-PR3 comparison at matched settings.
        for mode in dict.fromkeys(("none", carry_mode)):
            bench_multi_round(rounds, mode)
        # Async round pipeline: sync vs staleness-1 overlap, end to end.
        for n_clients in PIPELINE_CLIENTS:
            bench_pipeline(rounds, n_clients)
    if serve:
        cells = (
            ((16, 4), (16, 16), (16, 64)) if quick
            else tuple((a, b) for a in SERVE_ADAPTERS for b in SERVE_BATCHES)
        )
        for n_adapters, batch in cells:
            bench_serve(n_adapters, batch)
    if mesh:
        for n_clients in (MESH_CLIENTS_QUICK if quick else MESH_CLIENTS):
            base = None
            for shards in MESH_SHARDS:
                got = bench_mesh(shards, n_clients, baseline=base)
                if shards == 1:
                    base = got
            # Sharded fused-tail variants (DESIGN.md §10): the shard-local
            # Pallas tail at every multi-device shard count, against the
            # same 1-shard baseline so vs_1shard compares schedules.
            for shards in MESH_SHARDS:
                if shards == 1:
                    continue
                bench_mesh(shards, n_clients, baseline=base, fused=True)
    if faults:
        bench_faults(rounds or 10, n_clients=8 if quick else 16)
    if uplink:
        # Rounds floor: the accuracy-match gate compares FINAL accuracy, so
        # the runs must be past the early transient — 10 rounds converges
        # the 8-client quick task, the 16-client full task needs ~15.
        bench_uplink(max(rounds, 10 if quick else 15),
                     n_clients=8 if quick else 16)
    out_path = os.environ.get("BENCH_AGG_JSON", "BENCH_agg.json")
    with open(out_path, "w") as f:
        json.dump({"schema_version": SCHEMA_VERSION, "records": RECORDS}, f, indent=1)
    print(f"# wrote {len(RECORDS)} records to {out_path} "
          f"(schema v{SCHEMA_VERSION})", flush=True)


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--quick", action="store_true",
        help="CI smoke: smallest module/client cells only",
    )
    parser.add_argument(
        "--rounds", type=int, default=0,
        help="multi-round mode: drive an AggSession over this many "
             "correlated rounds and record cold vs warm wall time (0 = off)",
    )
    parser.add_argument(
        "--carry-mode", default="subspace", choices=["subspace", "full"],
        help="carry mode for the multi-round cells (the stateless 'none' "
             "baseline always rides along)",
    )
    parser.add_argument(
        "--serve", action="store_true",
        help="add multi-tenant serving cells: gathered-pool vs per-request "
             "vs merged across adapter-count x batch",
    )
    parser.add_argument(
        "--mesh", action="store_true",
        help="add mesh-sharded aggregation cells: 1/2/4 host-device shard "
             "sweeps, cold + warm-carry, vs the costmodel envelope "
             "(presets XLA_FLAGS for 4 host devices before jax loads)",
    )
    parser.add_argument(
        "--faults", action="store_true",
        help="add fault-tolerance cells: rounds-to-target under 0/10/30%% "
             "scale-corruption with the quarantine on vs off "
             "(DESIGN.md §11; uses --rounds, default 10)",
    )
    parser.add_argument(
        "--uplink", action="store_true",
        help="add compressed-uplink cells: dense vs sketch:64 bytes-per-"
             "round, final accuracy, and warm-round reduction factor "
             "(DESIGN.md §12; uses --rounds, default 10)",
    )
    args = parser.parse_args()
    main(quick=True if args.quick else None, rounds=args.rounds,
         carry_mode=args.carry_mode, serve=args.serve, mesh=args.mesh,
         faults=args.faults, uplink=args.uplink)
