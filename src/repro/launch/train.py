"""Federated LoRA fine-tuning driver.

Executes the same federated step the dry-run lowers — on the CPU with
reduced configs (``--reduced``), on a TPU at published widths
(``chip_smoke.py`` drives it there).  Per round: every client takes
``--local-steps`` LoRA steps on its own Markov-LM shard, deltas are
aggregated with ``--aggregator`` (FedRPCA by default), checkpoints are
written every ``--ckpt-every`` rounds.

The step runs as its two halves (``steps.make_local_step`` +
``steps.make_agg_step``), each jitted separately, so every round logs
per-phase wall clocks — and ``--pipeline`` overlaps them: round *r*'s
local phase dispatches while up to ``--staleness`` earlier aggregations
are still in flight (FedBuff-style K-deep buffering; updates land in
dispatch order, damped adaptively from the carry residual — DESIGN.md §8,
§11).  ``--staleness 0`` keeps the synchronous schedule.

``--faults`` injects seeded failures (client dropout, stragglers,
delta corruption: ``nan:0.1``, ``dropout:0.2,straggler:0.5``, ...); the
pre-aggregation quarantine (``fed.guard``) switches on with them (force
with ``--guard`` / ``--no-guard``), and the run exits nonzero if the
final state is non-finite or a corrupted column ever escaped the screen.
Without ``--faults`` a round that the land-time supervisor retried or
degraded to FedAvg is a failure too: nothing was injected, so a
non-finite aggregation is a bug, and the run exits 1.

``--trace-dir DIR --trace-rounds A:B`` writes a JAX profiler trace of
rounds A to B-1 to DIR, from the landing of round A-1 (or the start) to
the first local phase after round B-1 has landed: the round driver's ``fed.*`` host spans
(``fed/pipeline.py``) and device operations tagged with the ``local.*`` /
``agg.*`` scopes of the steps (``launch/steps.py``, ``core/``).  Under
``--pipeline`` round A's local phase has run by then.  Each round's log line names
the jitted functions compiled while it ran (``compiled=...``), so a
landing that recompiles shows in the log.

``main`` returns a summary dict: ``initial_eval_loss``,
``final_eval_loss``, ``rounds`` (one dict of scalar diagnostics and phase
timers per round) and ``last_deltas`` (the last local phase's stacked
client deltas, ``--client-ranks`` masks applied, before any injected
fault), so in-process callers such as ``chip_smoke.py`` can check the run
without parsing logs.

Example (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --reduced \
      --rounds 10 --clients 4 --aggregator fedrpca --pipeline
"""
from __future__ import annotations

import argparse
import os
import sys
import types
from typing import Any, NamedTuple


def _preset_host_devices(argv) -> None:
    """Self-set the host device count for ``--mesh-shards`` N runs.

    jax locks the device count at first init, so the flag must land in
    XLA_FLAGS before the ``import jax`` below (the launch/dryrun.py idiom).
    Peeks at argv instead of argparse because parsing happens long after
    the import; a user-provided XLA_FLAGS with the flag wins.
    """
    n = 0
    for i, a in enumerate(argv):
        if a == "--mesh-shards" and i + 1 < len(argv):
            n = int(argv[i + 1])
        elif a.startswith("--mesh-shards="):
            n = int(a.split("=", 1)[1])
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )


_preset_host_devices(sys.argv[1:])

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.checkpoint import checkpoint_metadata, restore_checkpoint, save_checkpoint
from repro.core import (
    CARRY_MODES, ENGINES, METHODS, SVT_MODES, WEIGHTINGS, AggregatorConfig,
)
from repro.core import engine as engine_lib
from repro.data import client_lm_datasets
from repro.fed import faults as faults_lib
from repro.fed import guard as guard_lib
from repro.fed import partition as partition_lib
from repro.fed import sketch as sketch_lib
from repro.fed.pipeline import run_rounds
from repro.launch import steps as steps_lib
from repro.models import init_lora_params, init_params, loss_fn
from repro.utils import get_logger
from repro.utils.compile_cache import enable_compile_cache

log = get_logger("train")


class _CliState(NamedTuple):
    """The driver's buffer for ``fed.pipeline.run_rounds`` (same surface as
    the simulation ``RoundState``: the scheduler only touches
    ``lora_global`` / ``agg_carry`` via ``_replace``)."""

    lora_global: Any
    agg_carry: Any
    round_idx: int


class _CliBundle(NamedTuple):
    """Local-phase hand-off of the CLI driver (needs only ``loss_mean`` for
    the scheduler's timers; the rest feeds the agg step)."""

    deltas: Any
    mask: Any
    round_key: Any
    loss_mean: Any
    fault_slots: Any = None  # injected-corruption marker (fed.faults)


def build_batches(client_tokens: np.ndarray, per_client: int, seq: int, rng: np.random.Generator):
    """Sample one round's (M, per_client, S) token/label batch."""
    m, n_seqs, _ = client_tokens.shape
    idx = rng.integers(0, n_seqs, size=(m, per_client))
    seqs = np.take_along_axis(client_tokens, idx[:, :, None], axis=1)
    return {
        "tokens": jnp.asarray(seqs[:, :, :seq]),
        "labels": jnp.asarray(seqs[:, :, 1 : seq + 1]),
    }


def evaluate(base, lora, cfg, test_tokens: np.ndarray, batch: int = 8) -> float:
    tokens = jnp.asarray(test_tokens[:batch, :-1])
    labels = jnp.asarray(test_tokens[:batch, 1:])
    loss, _ = loss_fn(base, lora, {"tokens": tokens, "labels": labels}, cfg, remat=False)
    return float(loss)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="mamba2-130m", help="architecture id")
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config (CPU)")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--per-client-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-lr", type=float, default=1e-3)
    ap.add_argument("--local-optimizer", default="adam", choices=["sgd", "adam"])
    ap.add_argument("--aggregator", default="fedrpca", choices=list(METHODS))
    ap.add_argument("--engine", default="packed", choices=list(ENGINES),
                    help="server aggregation engine (packed = bucketed batched)")
    ap.add_argument("--clients-per-round", type=int, default=0,
                    help="partial participation: sample this many clients per "
                         "round via a shape-static validity mask (0 = all)")
    ap.add_argument("--weighting", default="uniform", choices=list(WEIGHTINGS),
                    help="client aggregation weights: uniform mean, "
                         "data-size-weighted (true FedAvg), or data_size_rpca "
                         "(weights column-scale M before the RPCA split)")
    ap.add_argument("--rpca-iters", type=int, default=30)
    ap.add_argument("--rpca-fused-tail", action="store_true",
                    help="route the RPCA elementwise tail through the fused "
                         "Pallas kernels (packed engine; under --mesh-shards "
                         "the kernels run shard-locally on each shard's "
                         "column slice — DESIGN.md §10)")
    ap.add_argument("--mesh-overlap", action="store_true",
                    help="sharded aggregation: chunk the bucket axis so each "
                         "chunk's sweep/tail all-reduce overlaps the next "
                         "chunk's compute (no-op without --mesh-shards > 1; "
                         "off reproduces the unchunked schedule bit-for-bit)")
    ap.add_argument("--svt-mode", default="gram", choices=list(SVT_MODES),
                    help="RPCA SVT step: per-iteration eigh (gram) or "
                         "warm-started subspace iteration (subspace)")
    ap.add_argument("--svt-rank", type=int, default=8,
                    help="subspace SVT: carried eigenbasis width cap")
    ap.add_argument("--svt-sweeps", type=int, default=2,
                    help="subspace SVT: power sweeps per ADMM iteration")
    ap.add_argument("--carry-mode", default="none", choices=list(CARRY_MODES),
                    help="cross-round aggregation session carry: persist "
                         "per-bucket subspace/ADMM warm-start state so warm "
                         "rounds skip the RPCA cold start (packed engine, "
                         "fedrpca; subspace carry needs --svt-mode subspace)")
    ap.add_argument("--uplink", default="dense",
                    help="client->server wire codec (DESIGN.md §12): 'dense' "
                         "(full f32 deltas, the legacy wire bit-for-bit) or "
                         "'sketch[:k[:energy_tol]]' — project each delta onto "
                         "the server's carried RPCA basis and ship basis "
                         "coefficients + a top-k sparse residual, gated back "
                         "to dense on cold/basis-drift rounds; needs "
                         "--carry-mode != none (packed fedrpca)")
    ap.add_argument("--client-ranks", default=None,
                    help="heterogeneous per-client LoRA ranks: comma list "
                         "cycled over the cohort (e.g. '8,4,2'); each "
                         "client's delta is zero-masked beyond its declared "
                         "rank before aggregation (DESIGN.md §12)")
    ap.add_argument("--pipeline", action="store_true",
                    help="async double-buffered round pipeline: dispatch each "
                         "round's local phase while the previous round's "
                         "aggregation is still in flight (DESIGN.md §8)")
    ap.add_argument("--staleness", type=int, default=1,
                    help="pipeline depth bound: how many aggregation "
                         "dispatches may stay in flight (0 = synchronous "
                         "schedule; landed updates are damped adaptively "
                         "from the carry residual, FedAsync fallback)")
    ap.add_argument("--faults", default=None,
                    help="seeded fault injection spec (fed.faults.parse): "
                         "comma-separated name:value terms, e.g. 'nan:0.1' "
                         "(10%% NaN-corrupted clients), "
                         "'dropout:0.2,straggler:0.5,delay:2.0'")
    ap.add_argument("--guard", dest="guard", action="store_true", default=None,
                    help="force the pre-aggregation quarantine on "
                         "(default: on exactly when --faults is set)")
    ap.add_argument("--no-guard", dest="guard", action="store_false",
                    help="force the pre-aggregation quarantine off")
    ap.add_argument("--mesh-shards", type=int, default=0,
                    help="shard the aggregation's packed client axis across "
                         "this many mesh shards (DESIGN.md §10; 0/1 = single "
                         "device, bitwise the legacy round; sets "
                         "--xla_force_host_platform_device_count on CPU "
                         "automatically). Packed engine only — the reference "
                         "engine runs replicated with a warning")
    ap.add_argument("--heterogeneity", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--trace-dir", default=None,
                    help="write a JAX profiler trace of the --trace-rounds "
                         "rounds here: the fed.* host spans and the local.* "
                         "/ agg.* scopes of the device operations")
    ap.add_argument("--trace-rounds", default=None, metavar="A:B",
                    help="rounds to trace with --trace-dir: A up to but not "
                         "including B (default: every round)")
    args = ap.parse_args(argv)

    carry_on = (
        args.carry_mode != "none" and args.engine == "packed"
        and args.aggregator == "fedrpca"
    )
    if args.carry_mode != "none" and not carry_on:
        # The cross-round carry exists only on the packed fedrpca path; a
        # silently inert flag would report cold-start numbers as if they
        # were warm — refuse instead.
        ap.error(
            f"--carry-mode {args.carry_mode} has no effect with "
            f"--engine {args.engine} / --aggregator {args.aggregator}: the "
            "cross-round aggregation session exists only for --engine packed "
            "--aggregator fedrpca; drop --carry-mode (or set it to none)"
        )
    if args.staleness < 0:
        ap.error(f"--staleness must be >= 0, got {args.staleness}")
    trace_from, trace_to = 0, args.rounds
    if args.trace_rounds is not None:
        if args.trace_dir is None:
            ap.error("--trace-rounds needs --trace-dir")
        try:
            trace_from, trace_to = (int(x) for x in args.trace_rounds.split(":"))
        except ValueError:
            ap.error(f"--trace-rounds takes A:B, got {args.trace_rounds!r}")
        if not 0 <= trace_from < trace_to:
            ap.error(f"--trace-rounds A:B needs 0 <= A < B, got {args.trace_rounds!r}")
    uplink_cfg = sketch_lib.parse_uplink(args.uplink)
    if uplink_cfg.active and not carry_on:
        # The sketch basis IS the carried RPCA subspace; without a carry
        # there is never a basis to project onto, so every round would
        # gate to dense anyway — run dense and say so.
        log.warning(
            "--uplink %s needs --carry-mode != none (packed fedrpca) for a "
            "basis to project onto; running dense", args.uplink,
        )
        uplink_cfg = None
    if args.mesh_shards < 0:
        ap.error(f"--mesh-shards must be >= 0, got {args.mesh_shards}")
    mesh = None
    if args.mesh_shards > 1:
        if args.engine != "packed":
            log.warning(
                "--mesh-shards %d with --engine %s: the reference engine is "
                "the single-device parity oracle; running the aggregation "
                "replicated", args.mesh_shards, args.engine,
            )
        else:
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh(args.mesh_shards)
            log.info("aggregation client axis sharded over %d host devices",
                     args.mesh_shards)
    fault_model = None
    if args.faults:
        fcfg = faults_lib.parse(args.faults, seed=args.seed)
        if fcfg.active:
            fault_model = faults_lib.FaultModel(fcfg)
            log.info("fault injection on: %s", fcfg)
    guard_on = fault_model is not None if args.guard is None else args.guard
    enable_compile_cache()
    guard_cfg = guard_lib.GuardConfig() if guard_on else None

    cfg = cfglib.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    log.info("arch=%s layers=%d d_model=%d vocab=%d", cfg.name, cfg.n_layers, cfg.d_model,
             cfg.vocab_size)

    client_tokens, test = client_lm_datasets(
        args.clients, vocab_size=min(cfg.vocab_size, 512), n_seqs=32,
        seq_len=args.seq, heterogeneity=args.heterogeneity, seed=args.seed,
    )

    key = jax.random.PRNGKey(args.seed)
    base = init_params(key, cfg)
    lora = init_lora_params(jax.random.fold_in(key, 1), cfg)

    # Heterogeneous per-client ranks: each client's delta is zero-masked
    # beyond its declared rank before it reaches the wire/aggregation —
    # bitwise the equal-uniform-rank oracle over zero-padded deltas
    # (DESIGN.md §12).
    ranks_all = None
    rank_masks = None
    if args.client_ranks:
        lora_rank = partition_lib.infer_lora_rank(lora)
        ranks_all = partition_lib.parse_client_ranks(
            args.client_ranks, args.clients, lora_rank
        )
        rank_masks = partition_lib.client_rank_masks(lora, ranks_all, lora_rank)
        log.info("heterogeneous client ranks: %s (template rank %d)",
                 ranks_all.tolist(), lora_rank)

    agg = AggregatorConfig(
        method=args.aggregator, rpca_iters=args.rpca_iters, weighting=args.weighting,
        svt_mode=args.svt_mode, svt_rank=args.svt_rank, svt_sweeps=args.svt_sweeps,
        carry_mode=args.carry_mode,
        rpca_fused_tail=args.rpca_fused_tail, mesh_overlap=args.mesh_overlap,
        guard_energy_k=guard_cfg.energy_k if guard_cfg is not None else 0.0,
    )
    # Cross-round aggregation session: the carry pytree is initialized once
    # from the plan (zeros deltas with the round's client axis) so every
    # round shares one compiled step, then threads through the jitted step.
    carry = None
    agg_plan = None
    if carry_on:
        example = jax.tree_util.tree_map(
            lambda x: jnp.zeros((args.clients,) + x.shape, x.dtype), lora
        )
        agg_plan = engine_lib.plan_aggregation(
            example, agg, mesh=mesh, uplink=uplink_cfg,
            client_ranks=None if ranks_all is None else ranks_all.tolist(),
        )
        carry = engine_lib.init_agg_carry(agg_plan)

    start_round = 0
    if args.resume and args.ckpt_dir:
        meta = checkpoint_metadata(args.ckpt_dir)
        if meta.get("format") == "session":
            # Session checkpoint: the aggregation carry (and round counter)
            # resume alongside the LoRA tree, so a warm session stays warm.
            if not carry_on:
                raise ValueError(
                    f"checkpoint under {args.ckpt_dir} is an aggregation-"
                    "session checkpoint (it carries AggCarry state), but this "
                    "run has the carry disabled; rerun with --carry-mode "
                    f"{meta.get('carry_mode', 'subspace')} (packed fedrpca)"
                )
            restored, meta = restore_checkpoint(
                args.ckpt_dir, {"lora": lora, "agg_carry": carry}
            )
            lora, carry = restored["lora"], restored["agg_carry"]
        else:
            if carry_on:
                log.warning(
                    "resuming a carry-mode run from a legacy LoRA-only "
                    "checkpoint: the aggregation session cold-starts"
                )
            lora, meta = restore_checkpoint(args.ckpt_dir, lora)
        start_round = int(meta.get("round", meta.get("step", 0)))
        log.info("resumed from round %s", start_round)

    # Synthetic client shards all hold n_seqs sequences; real pipelines pass
    # partition sizes here (fed.partition.data_size_weights).
    client_sizes = np.full(args.clients, client_tokens.shape[1], np.float64)
    local_step = jax.jit(
        steps_lib.make_local_step(
            cfg, local_lr=args.local_lr, local_steps=args.local_steps,
            local_optimizer=args.local_optimizer, remat=False,
            clients_per_round=args.clients_per_round,
        )
    )
    agg_step = jax.jit(
        steps_lib.make_agg_step(
            agg, engine=args.engine,
            client_weights=client_sizes / client_sizes.sum(),
            mesh=mesh, uplink=uplink_cfg,
        )
    )

    depth = args.staleness if args.pipeline else 0

    # The CLI driver reuses the fed.pipeline scheduler (InFlightQueue +
    # AggWorker thread + per-tau stale scale live in ONE place) through the
    # same duck-typed phase surface the simulation uses.  The local phase
    # builds its round's batch from a per-round generator — seeded by
    # (seed, round) rather than a shared stream, so a resumed run consumes
    # exactly the batches an uninterrupted run would have seen.
    last_deltas = [None]  # the newest local phase's deltas, for the summary
    # The profiler: None before, "on", "ending" once round B-1 has landed
    # (it stops at the next local phase, when that round's spans are
    # closed), "done".
    tracing = [None]

    def start_trace():
        jax.profiler.start_trace(args.trace_dir)
        tracing[0] = "on"

    def cli_local(state: _CliState, n_active=None):
        del n_active
        r = state.round_idx
        if tracing[0] == "ending":
            jax.profiler.stop_trace()
            tracing[0] = "done"
        batch = build_batches(
            client_tokens, args.per_client_batch, args.seq,
            np.random.default_rng((args.seed, 1000 + r)),
        )
        round_key = jax.random.fold_in(key, 1000 + r)
        # An expert share's counters, a fourth output, are not logged.
        deltas, loss, mask = local_step(base, state.lora_global, batch, round_key)[:3]
        if rank_masks is not None:
            # Zero each client's delta beyond its declared rank — what a
            # rank-r_i client would actually have trained and shipped.
            deltas = jax.tree_util.tree_map(
                lambda d, mk: d * mk.astype(d.dtype), deltas, rank_masks
            )
        last_deltas[0] = deltas
        fault_slots = None
        if fault_model is not None:
            if mask is None:
                mask = jnp.ones((args.clients,), jnp.float32)
            deltas, mask, fault_slots = fault_model.inject(r, deltas, mask)
        bundle = _CliBundle(deltas=deltas, mask=mask, round_key=round_key,
                            loss_mean=loss, fault_slots=fault_slots)
        return state._replace(round_idx=r + 1), bundle

    screen_jit = (
        jax.jit(lambda d, m: guard_lib.screen(d, m, guard_cfg))
        if guard_cfg is not None else None
    )

    def _screen(bundle: _CliBundle):
        deltas, mask2 = bundle.deltas, bundle.mask
        sflags, sdiags = None, {}
        if screen_jit is not None:
            if mask2 is None:
                mask2 = jnp.ones((args.clients,), jnp.float32)
            deltas, mask2, g = screen_jit(deltas, mask2)
            sflags = g.pop("flags")
            sdiags = g
        return deltas, mask2, sflags, sdiags

    def _finite(tree):
        return jnp.all(jnp.stack([
            jnp.all(jnp.isfinite(leaf))
            for leaf in jax.tree_util.tree_leaves(tree)
        ])).astype(jnp.float32)

    def _fault_diags(upd, sflags, bundle: _CliBundle, sdiags):
        diags = dict(sdiags)
        diags["update_finite"] = _finite(upd)
        if bundle.fault_slots is not None:
            diags["fault_injected"] = jnp.sum(bundle.fault_slots)
            if sflags is not None:
                diags["fault_caught"] = jnp.sum(sflags * bundle.fault_slots)
        return diags

    # Wire accounting (DESIGN.md §12), logged beside the phase timers: a
    # dense f32 delta costs 4 bytes/param per participating client; the
    # sketch codec emits its exact ``bytes_up`` / ``bytes_down_basis``
    # through the engine diags.  ``bytes_down`` is the update broadcast
    # (counted once — multicast) plus, on sketch rounds, the basis cast.
    per_client_bytes = 4.0 * sum(
        int(np.prod(np.shape(leaf))) for leaf in jax.tree_util.tree_leaves(lora)
    )

    def _wire_metrics(metrics, mask2):
        m = dict(metrics)
        n_eff = float(args.clients) if mask2 is None else float(jnp.sum(mask2))
        if "bytes_up" not in m:
            m["bytes_up"] = per_client_bytes * n_eff
        m["bytes_down"] = per_client_bytes + float(m.pop("bytes_down_basis", 0.0))
        return m

    def cli_agg(agg_carry, bundle: _CliBundle, scale):
        deltas, mask2, sflags, sdiags = _screen(bundle)
        if carry_on:
            upd, metrics, new_carry = agg_step(
                deltas, mask2, bundle.round_key, agg_carry, scale
            )
        else:
            upd, metrics = agg_step(deltas, mask2, bundle.round_key, scale=scale)
            new_carry = agg_carry
        metrics = _wire_metrics(metrics, mask2)
        return upd, new_carry, {**metrics, **_fault_diags(upd, sflags, bundle, sdiags)}

    def cli_cold_carry():
        return engine_lib.init_agg_carry(agg_plan) if agg_plan is not None else None

    # Degradation floor for the land-time supervisor: plain masked FedAvg
    # over the screened deltas, carry-free.
    fallback_step = jax.jit(
        steps_lib.make_agg_step(
            agg.replace(method="fedavg", carry_mode="none", guard_energy_k=0.0),
            engine=args.engine,
            client_weights=client_sizes / client_sizes.sum(),
            mesh=mesh,
        )
    )

    def cli_fallback(bundle: _CliBundle, scale):
        deltas, mask2, sflags, sdiags = _screen(bundle)
        upd, _ = fallback_step(deltas, mask2, bundle.round_key, scale=scale)
        diags = {**_wire_metrics({}, mask2),
                 **_fault_diags(upd, sflags, bundle, sdiags), "degraded": 1.0}
        return upd, cli_cold_carry(), diags

    phases = types.SimpleNamespace(
        local=cli_local, agg=cli_agg, prep_state=lambda s: s,
        apply=jax.jit(steps_lib.apply_update),
        fallback=cli_fallback, cold_carry=cli_cold_carry,
    )

    fault_totals = {"injected": 0.0, "caught": 0.0, "escapes": 0.0,
                    "degraded": 0.0, "retries": 0.0}
    round_log = []
    compiled = []  # jitted functions compiled since the last landing

    def on_compile(event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    def on_round(r, state: _CliState, diags):
        rg = start_round + r  # global round index (resume offset)
        fault_totals["injected"] += float(diags.get("fault_injected", 0.0))
        fault_totals["caught"] += float(diags.get("fault_caught", 0.0))
        if "screen_clean" in diags and float(diags["screen_clean"]) == 0.0:
            fault_totals["escapes"] += 1.0
        fault_totals["degraded"] += float(diags.get("degraded", 0.0))
        fault_totals["retries"] += float(diags.get("supervisor_retry", 0.0))
        timers = {k: diags.get(k, 0.0) for k in ("t_local_s", "t_agg_s", "t_overlap_s")}
        round_log.append({"round": rg, **{k: float(v) for k, v in diags.items()}})
        extra = "".join(
            f"  {k}={float(v):.3g}" for k, v in diags.items()
            if k != "mean_local_loss" and not k.startswith("t_")
        )
        if compiled:
            extra += f"  compiled={','.join(compiled)}"
            compiled.clear()
        log.info(
            "round %03d  local_loss=%.4f%s  t_local=%.2fs t_agg=%.2fs "
            "t_overlap=%.2fs", rg, float(diags["mean_local_loss"]), extra,
            timers["t_local_s"], timers["t_agg_s"], timers["t_overlap_s"],
        )
        if tracing[0] == "on" and rg >= trace_to - 1:
            tracing[0] = "ending"
        elif args.trace_dir and tracing[0] is None and rg == trace_from - 1:
            start_trace()
        if args.ckpt_dir and (rg + 1) % args.ckpt_every == 0:
            if carry_on:
                save_checkpoint(
                    {"lora": state.lora_global, "agg_carry": state.agg_carry},
                    args.ckpt_dir, rg + 1,
                    metadata={"arch": cfg.name, "round": rg + 1,
                              "format": "session", "carry_mode": args.carry_mode},
                )
            else:
                save_checkpoint(
                    state.lora_global, args.ckpt_dir, rg + 1,
                    metadata={"arch": cfg.name, "round": rg + 1},
                )

    initial_loss = evaluate(base, lora, cfg, test.tokens)
    log.info("initial eval loss %.4f", initial_loss)
    if depth:
        log.info("pipeline on: staleness bound %d", depth)
    if args.trace_dir and trace_from <= start_round < trace_to:
        start_trace()
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        state = run_rounds(
            phases, _CliState(lora, carry, start_round),
            max(args.rounds - start_round, 0), staleness=depth, on_round=on_round,
        )
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        if tracing[0] in ("on", "ending"):
            jax.profiler.stop_trace()
    lora = state.lora_global
    if fault_model is not None or guard_cfg is not None:
        inj, caught = fault_totals["injected"], fault_totals["caught"]
        log.info(
            "fault summary: injected=%d caught=%d (%.0f%%) screen_escapes=%d "
            "supervisor_retries=%d degraded_rounds=%d",
            int(inj), int(caught), 100.0 * caught / max(inj, 1.0),
            int(fault_totals["escapes"]), int(fault_totals["retries"]),
            int(fault_totals["degraded"]),
        )
        if fault_totals["escapes"]:
            log.error("quarantine escape: a screened round was not finite")
            sys.exit(1)
    if fault_model is None and (fault_totals["degraded"] or fault_totals["retries"]):
        log.error(
            "no fault was injected, yet %d round(s) were retried and %d "
            "degraded to FedAvg: the aggregation produced non-finite output",
            int(fault_totals["retries"]), int(fault_totals["degraded"]),
        )
        sys.exit(1)
    final_finite = all(
        bool(jnp.all(jnp.isfinite(leaf)))
        for leaf in jax.tree_util.tree_leaves(lora)
    )
    if not final_finite:
        log.error("final global LoRA state is non-finite")
        sys.exit(1)
    final_loss = evaluate(base, lora, cfg, test.tokens)
    log.info("final eval loss %.4f", final_loss)
    return {
        "initial_eval_loss": initial_loss,
        "final_eval_loss": final_loss,
        "rounds": round_log,
        "last_deltas": last_deltas[0],
    }


if __name__ == "__main__":
    main()
