"""Server round loop: broadcast -> vmapped local runs -> aggregate -> update.

The per-round computation is a pair of independently dispatchable jitted
phases (``make_round_phases``): a *local phase* — broadcast + vmapped client
runs, emitting stacked deltas — and an *aggregation phase* — the planned
aggregation step consuming/producing the cross-round ``AggCarry`` and
applying the update.  ``make_round_fn`` composes the two back-to-back (the
synchronous driver, numerically the legacy single-jit round); the async
double-buffered driver in ``repro.fed.pipeline`` dispatches round *r*'s
local phase while round *r-1*'s RPCA split is still in flight (DESIGN.md
§8).  The mesh execution path in ``repro.launch.train`` replaces the vmap
with client-axis sharding, but the aggregation code (``repro.core``) is
byte-identical in both.

Partial participation is *shape-static*: instead of gathering the sampled
cohort to a ``|S|``-sized stack (which re-traces the whole jitted round for
every distinct cohort size), the round samples a random permutation, takes a
fixed ``canonical_cohort_size(clients_per_round)`` prefix of client slots,
and marks the first ``n_active`` of them valid with a client mask.  The mask
and (optionally data-size) weights thread through ``aggregate`` and the
state scatter, so one compilation serves every cohort size that shares a
canonical bucket — ``n_active`` is a traced scalar argument of the round
function (see tests/test_cohort.py's retrace regression test).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AggregatorConfig, aggregate
from repro.core import engine as engine_lib
from repro.core.aggregators import (
    CARRY_MODES, WEIGHTINGS, client_flag_vector, rpca_diag_summary,
)
from repro.core import stacking
from repro.fed import faults as faults_lib
from repro.fed import guard as guard_lib
from repro.fed.client import LocalSpec, make_local_fn
from repro.utils.pytree import tree_zeros_like

PyTree = Any


class RoundState(NamedTuple):
    lora_global: PyTree
    scaffold_c: PyTree
    scaffold_ci: PyTree  # (M, ...) per-client variates
    prev_local: PyTree  # (M, ...) previous-round local models (MOON)
    rng: jnp.ndarray
    # Plain-int default: no device array (or backend init) at import time;
    # init_round_state sets the concrete int32 counter.
    round_idx: Any = 0
    # Cross-round aggregation carry (engine AggCarry: per-bucket subspace /
    # ADMM warm-start state, DESIGN.md §7).  Empty tuple when
    # carry_mode="none"; make_round_fn's wrapper initializes it from the
    # session plan before the first jitted call so the carried pytree
    # structure — and therefore the compiled round — is stable from round 0.
    agg_carry: Any = ()


class LocalBundle(NamedTuple):
    """One local phase's hand-off to the aggregation phase.

    ``deltas`` are the stacked per-slot client deltas; ``mask``/``weights``
    are the cohort validity mask and per-client aggregation weights (None on
    the dense/unweighted paths — static per round function, so both phases
    compile one program each); ``agg_key`` is the round's aggregation PRNG
    key, split from the same stream as the legacy monolithic round so the
    pipelined and synchronous drivers consume identical randomness;
    ``loss_mean`` is the masked mean of the clients' final local losses.
    """

    deltas: PyTree
    mask: Any
    weights: Any
    agg_key: jnp.ndarray
    loss_mean: jnp.ndarray
    # Clients whose deltas the fault model corrupted this round ((cohort,)
    # float32; None with fault injection off) — lets the aggregation phase
    # report how many injected faults the quarantine caught.
    fault_slots: Any = None


class RoundPhases:
    """The split server round: two independently dispatchable jitted phases.

    ``local(state, n_active=None) -> (state', LocalBundle)`` runs the
    broadcast + vmapped client optimization plus every piece of round
    bookkeeping that does NOT depend on the aggregation result (SCAFFOLD
    variate scatter, MOON prev-model scatter, RNG advance, round counter);
    ``state'`` keeps the *input* ``lora_global`` and ``agg_carry``
    untouched, so a pipelined driver may dispatch the next local phase
    before the previous aggregation lands.

    ``agg(agg_carry, bundle, scale) -> (scaled_update, carry', diags)``
    consumes a bundle (possibly several rounds stale) and returns the
    *scaled update* — NOT the applied state.  Decoupling the update from
    the base it lands on is what enables the FedBuff-style K-deep
    in-flight queue: the driver composes updates at land time via
    ``apply(lora_global, scaled_update) -> lora'``, so an update computed
    K rounds ago still lands on the *current* global model.  ``scale=1.0``
    reproduces the legacy unscaled apply bit-for-bit (IEEE multiplication
    by 1.0 is exact, and splitting ``g + s*u`` into ``s*u`` then ``g + su``
    does not change the float ops — XLA does not contract them into an
    FMA); the pipelined driver passes the staleness-corrected scale.

    ``fallback(bundle, scale) -> (scaled_update, cold_carry, diags)`` is
    the degradation ladder's last rung: plain masked FedAvg over the
    (screened) deltas, used by the driver's supervisor when the real
    aggregation produced a non-finite update even after a cold-carry
    retry.  ``cold_carry()`` returns the bitwise-cold carry for that retry.

    The synchronous driver (``make_round_fn``) composes the phases back to
    back; ``repro.fed.pipeline.run_rounds`` overlaps them.  Both consume
    the *same* compiled phases, which is what makes the staleness=0
    pipeline bitwise identical to the synchronous path.
    """

    def __init__(self, local, agg, *, cohort_pad, plan, prep_state, cache_size,
                 apply=None, fallback=None, cold_carry=None):
        self.local = local
        self.agg = agg
        self.cohort_pad = cohort_pad
        self.plan = plan
        self.prep_state = prep_state
        self.cache_size = cache_size
        self.apply = apply
        self.fallback = fallback
        self.cold_carry = cold_carry


@dataclasses.dataclass(frozen=True)
class FedRunConfig:
    aggregator: AggregatorConfig
    local: LocalSpec
    rounds: int
    seed: int = 0
    clients_per_round: int = 0  # 0 = full participation (the paper's setting)
    engine: str = "packed"  # "packed" (bucketed batched engine) | "reference"
    sampler: str = "uniform"  # client sampler (see SAMPLERS)
    # Async double-buffered round pipeline (repro.fed.pipeline): overlap each
    # round's local phase with the previous round's still-running RPCA.
    # ``pipeline=False`` is the classic synchronous loop; ``staleness`` bounds
    # the in-flight aggregation dispatches when the pipeline is on (0 = the
    # synchronous schedule, bit-for-bit — same phases, same order).
    pipeline: bool = False
    staleness: int = 1
    # Fault tolerance (DESIGN.md §11).  ``faults`` is a
    # ``fed.faults.FaultConfig`` (None = no injection); ``guard`` controls
    # the pre-aggregation quarantine: None = auto (on exactly when faults
    # are injected), a ``fed.guard.GuardConfig`` = on with those
    # thresholds, False = force off.  Both default to the legacy
    # bit-for-bit round.
    faults: Any = None
    guard: Any = None
    # Shard the packed client axis of the aggregation across a device mesh
    # (DESIGN.md §10).  0/1 = single-device (bitwise the legacy round);
    # n > 1 builds launch.mesh.make_host_mesh(n) — the process must have
    # been started with XLA_FLAGS=--xla_force_host_platform_device_count>=n
    # (or a real backend with >= n devices).  Packed engine only: the
    # reference engine is the single-device parity oracle and runs
    # replicated with a warning.
    mesh_shards: int = 0
    # Compressed uplink codec (DESIGN.md §12): "dense" (the legacy wire,
    # bit-for-bit), "sketch[:k[:energy_tol]]", or a fed.sketch.UplinkConfig.
    # Sketch mode needs a carrying fedrpca plan (packed engine) — the codec
    # projects client deltas onto the carried basis; otherwise it degrades
    # to dense with a warning.
    uplink: Any = "dense"
    # Heterogeneous per-client LoRA ranks (DESIGN.md §12): None = uniform,
    # else a fed.partition.parse_client_ranks spec (comma string or int
    # sequence, cycled over the cohort).  Client i's delta is zero-masked
    # beyond rank_i before aggregation — bitwise the equal-uniform-rank
    # oracle whose low-rank clients padded with zeros.
    client_ranks: Any = None


def init_round_state(lora_init: PyTree, n_clients: int, seed: int) -> RoundState:
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_clients, *x.shape)), lora_init
    )
    return RoundState(
        lora_global=lora_init,
        scaffold_c=tree_zeros_like(lora_init),
        scaffold_ci=tree_zeros_like(stacked),
        prev_local=stacked,
        rng=jax.random.PRNGKey(seed),
        round_idx=jnp.asarray(0, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Pluggable client samplers (shape-static: every sampler fills the same
# cohort_pad slots; only the cohort indices and the validity mask vary)
# ---------------------------------------------------------------------------

#: Built-in sampler kinds for ``FedRunConfig.sampler`` / ``make_sampler``.
SAMPLERS = ("uniform", "trace", "size_weighted")


def make_sampler(
    kind: str,
    n_clients: int,
    cohort_pad: int,
    *,
    availability=None,
    weights=None,
) -> Callable:
    """Build a jit-safe client sampler: ``(key, round_idx) -> (cohort,
    slot_valid)`` with ``cohort`` a (cohort_pad,) int32 index vector and
    ``slot_valid`` a (cohort_pad,) float32 per-slot validity factor.

    * ``uniform`` — prefix of a random permutation (a uniform sample
      without replacement; the legacy stream, bit-identical).
    * ``trace`` — fixed availability trace: ``availability`` is a
      ``(n_clients,)`` or ``(rounds, n_clients)`` 0/1 array; the round's
      row (cycled by ``round_idx``) restricts sampling to available
      clients, uniformly.  Available clients sort first, so ``slot_valid``
      zeroes any slot beyond the round's availability head-count — rounds
      with fewer available clients than requested shrink n_eff instead of
      aggregating stale deltas.
    * ``size_weighted`` — without-replacement sampling proportional to
      ``weights`` (e.g. local data sizes) via the Gumbel-top-k trick.

    All samplers share one compiled round: the outputs are shape-static
    and ``round_idx`` is a traced scalar.
    """
    if kind == "uniform":

        def sample(key, round_idx):
            del round_idx
            cohort = jax.random.permutation(key, n_clients)[:cohort_pad]
            return cohort, jnp.ones((cohort_pad,), jnp.float32)

        return sample
    if kind == "size_weighted":
        if weights is None:
            raise ValueError("sampler='size_weighted' requires client weights")
        logw = jnp.log(jnp.maximum(jnp.asarray(weights, jnp.float32), 1e-12))

        def sample(key, round_idx):
            del round_idx
            u = jax.random.uniform(key, (n_clients,), minval=1e-12, maxval=1.0)
            gumbel = -jnp.log(-jnp.log(u))
            cohort = jax.lax.top_k(logw + gumbel, cohort_pad)[1]
            return cohort, jnp.ones((cohort_pad,), jnp.float32)

        return sample
    if kind == "trace":
        if availability is None:
            raise ValueError("sampler='trace' requires an availability trace")
        avail = jnp.asarray(availability, jnp.float32)
        if avail.ndim == 1:
            avail = avail[None]
        if avail.shape[-1] != n_clients:
            raise ValueError(
                f"availability trace covers {avail.shape[-1]} clients, "
                f"expected {n_clients}"
            )

        def sample(key, round_idx):
            row = avail[round_idx % avail.shape[0]]
            # Available clients draw a uniform score in [0, 1); unavailable
            # ones score below it — top_k puts available clients first.
            score = jnp.where(row > 0, jax.random.uniform(key, (n_clients,)), -1.0)
            cohort = jax.lax.top_k(score, cohort_pad)[1]
            return cohort, (row[cohort] > 0).astype(jnp.float32)

        return sample
    raise ValueError(f"unknown sampler: {kind!r} (expected one of {SAMPLERS})")


def make_round_phases(
    base: PyTree, data_x, data_y, cfg: FedRunConfig, client_weights=None,
    availability=None, lora_template: PyTree | None = None,
) -> RoundPhases:
    """Build the split server round: independently dispatchable phases.

    Same arguments and validation as ``make_round_fn`` (which composes the
    returned phases into the synchronous round); see its docstring for the
    weighting / sampler / carry semantics.  The returned ``RoundPhases``
    carries two jitted functions plus the session plan, the canonical
    cohort size, the carry-initializing ``prep_state``, and a combined
    ``cache_size`` retrace counter.
    """
    local_fn = make_local_fn(cfg.local)
    n_clients = data_x.shape[0]

    sample_size = cfg.clients_per_round or n_clients
    if not 0 < sample_size <= n_clients:
        raise ValueError(
            f"clients_per_round={cfg.clients_per_round} out of range for {n_clients} clients"
        )
    partial = sample_size < n_clients
    # Canonical padded cohort: power-of-two slots, so cohort sizes 5/7/8 of
    # 16 clients all run the same compiled round with 8 slots.
    cohort_pad = min(stacking.canonical_cohort_size(sample_size), n_clients)

    if cfg.aggregator.weighting not in WEIGHTINGS:
        raise ValueError(
            f"unknown weighting: {cfg.aggregator.weighting!r} (expected one of {WEIGHTINGS})"
        )
    use_weights = cfg.aggregator.weighting in ("data_size", "data_size_rpca")
    w_all = None
    if use_weights:
        if client_weights is None:
            raise ValueError(
                f"weighting={cfg.aggregator.weighting!r} requires "
                "client_weights (e.g. fed.partition.data_size_weights); "
                "refusing to silently fall back to uniform"
            )
        w_all = jnp.asarray(client_weights, jnp.float32)

    if cfg.sampler not in SAMPLERS:
        raise ValueError(f"unknown sampler: {cfg.sampler!r} (expected one of {SAMPLERS})")
    # Full participation never samples: skip building (and validating the
    # inputs of) a sampler that would never be invoked.
    sampler = (
        make_sampler(
            cfg.sampler, n_clients, cohort_pad,
            availability=availability, weights=client_weights,
        )
        if partial
        else None
    )

    # Fault model + update quarantine (DESIGN.md §11).  The guard defaults
    # to on exactly when faults are injected; ``cfg.guard=False`` forces it
    # off (chaos baselines), a GuardConfig forces it on.  ``agg_cfg`` folds
    # the sparse-energy threshold into the aggregator so both engines score
    # and down-weight suspect clients inside the RPCA split itself.
    fault_model = None
    if cfg.faults is not None and cfg.faults.active:
        fault_model = faults_lib.FaultModel(cfg.faults)
    guard_cfg = cfg.guard
    if guard_cfg is None:
        guard_cfg = guard_lib.GuardConfig() if fault_model is not None else None
    elif guard_cfg is False:
        guard_cfg = None
    agg_cfg = cfg.aggregator
    if guard_cfg is not None and guard_cfg.energy_k > 0:
        agg_cfg = cfg.aggregator.replace(guard_energy_k=guard_cfg.energy_k)
    deadline_cohort = False
    if fault_model is not None and cfg.faults.straggler > 0 and partial:
        # Deadline-based cohort formation: over-sample candidates from the
        # configured sampler, seat the earliest simulated arrivals, zero
        # this round's stragglers, and buffer late arrivals into the next
        # round's cohort head.
        n_cand = min(2 * cohort_pad, n_clients)
        inner = make_sampler(
            cfg.sampler, n_clients, n_cand,
            availability=availability, weights=client_weights,
        )
        sampler = faults_lib.make_deadline_sampler(
            fault_model, inner, n_clients, cohort_pad
        )
        deadline_cohort = True

    if cfg.aggregator.carry_mode not in CARRY_MODES:
        raise ValueError(
            f"unknown carry_mode: {cfg.aggregator.carry_mode!r} "
            f"(expected one of {CARRY_MODES})"
        )
    # Cross-round carry: packed-engine fedrpca only (the reference engine
    # is the stateless parity oracle and ignores carry_mode).
    carry_on = (
        cfg.aggregator.carry_mode != "none"
        and cfg.engine == "packed"
        and cfg.aggregator.method == "fedrpca"
    )
    mesh = None
    if cfg.mesh_shards > 1:
        if cfg.engine != "packed":
            warnings.warn(
                f"mesh_shards={cfg.mesh_shards} with engine="
                f"{cfg.engine!r}: the reference engine is the single-device "
                "parity oracle; running the aggregation replicated",
                stacklevel=2,
            )
        else:
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh(cfg.mesh_shards)
    # Heterogeneous per-client ranks (DESIGN.md §12): static 0/1 masks
    # zeroing each client's delta beyond its declared rank, applied in the
    # local phase before the bundle ships — so the aggregation sees exactly
    # the bytes an equal-uniform-rank oracle with zero-padded low-rank
    # clients would see.
    rank_masks = None
    ranks_all = None
    if cfg.client_ranks is not None:
        if lora_template is None:
            raise ValueError(
                "client_ranks needs the LoRA structure to build the rank "
                "masks: pass lora_template= (e.g. the lora_init given to "
                "init_round_state)"
            )
        from repro.fed import partition as partition_lib

        r_dim = partition_lib.infer_lora_rank(lora_template)
        ranks_all = partition_lib.parse_client_ranks(
            cfg.client_ranks, n_clients, r_dim
        )
        rank_masks = partition_lib.client_rank_masks(
            lora_template, ranks_all, r_dim
        )
    uplink_cfg = None
    if cfg.uplink is not None:
        from repro.fed import sketch as sketch_lib

        uplink_cfg = sketch_lib.parse_uplink(cfg.uplink)
        if uplink_cfg.active and not carry_on:
            warnings.warn(
                "uplink sketch mode needs a carrying packed-engine fedrpca "
                "round (the codec projects onto the carried basis); running "
                "dense",
                stacklevel=2,
            )
            uplink_cfg = None
    plan = None
    if carry_on:
        if lora_template is None:
            raise ValueError(
                f"carry_mode={cfg.aggregator.carry_mode!r} needs the LoRA "
                "structure to plan the session: pass lora_template= (e.g. "
                "the lora_init given to init_round_state)"
            )
        slots = cohort_pad if partial else n_clients
        example = jax.tree_util.tree_map(
            lambda x: jnp.zeros((slots,) + jnp.shape(x), jnp.asarray(x).dtype),
            lora_template,
        )
        plan = engine_lib.plan_aggregation(
            example, agg_cfg, mesh=mesh, uplink=uplink_cfg,
            client_ranks=None if ranks_all is None else ranks_all.tolist(),
        )

    @jax.jit
    def local_phase(state: RoundState, n_active=None):
        rng, sub, pick, agg_key = jax.random.split(state.rng, 4)
        if partial:
            # Shape-static partial participation: the sampler fills the
            # fixed cohort_pad slots, of which the first n_active (further
            # restricted by the sampler's own slot validity, e.g. an
            # availability trace) are valid.
            na = sample_size if n_active is None else jnp.clip(n_active, 1, cohort_pad)
            cohort, slot_valid = sampler(pick, state.round_idx)
            mask = (jnp.arange(cohort_pad) < na).astype(jnp.float32) * slot_valid
        else:
            cohort = jnp.arange(n_clients)
            mask = None
        take = lambda t: jax.tree_util.tree_map(lambda x: x[cohort], t)
        client_rngs = jax.random.split(sub, cohort_pad if partial else n_clients)
        local_args = (
            base,
            state.lora_global,
            data_x[cohort],
            data_y[cohort],
            client_rngs,
            state.scaffold_c,
            take(state.scaffold_ci),
            take(state.prev_local),
        )
        if partial:
            # Masked slots early-exit the local phase (zero delta, untouched
            # variates) instead of optimizing a client that won't aggregate.
            results = jax.vmap(
                local_fn, in_axes=(None, None, 0, 0, 0, None, 0, 0, 0)
            )(*local_args, mask)
        else:
            results = jax.vmap(
                local_fn, in_axes=(None, None, 0, 0, 0, None, 0, 0)
            )(*local_args)
        stacked_deltas = results.delta  # leaves: (cohort_pad, ...)
        if rank_masks is not None:
            # Zero each client's delta beyond its declared rank (bitwise
            # the uniform-rank oracle over zero-padded low-rank deltas).
            stacked_deltas = jax.tree_util.tree_map(
                lambda d, mk: d * mk[cohort].astype(d.dtype),
                stacked_deltas, rank_masks,
            )
        weights = w_all[cohort] if use_weights else None

        if mask is None:
            n_eff = float(n_clients)
            bmask = lambda x: 1.0
            scatter = lambda full, part: jax.tree_util.tree_map(
                lambda f, p: f.at[cohort].set(p), full, part
            )
            loss_mean = jnp.mean(results.final_loss)
        else:
            n_eff = jnp.maximum(jnp.sum(mask), 1.0)
            bmask = lambda x: mask.reshape((cohort_pad,) + (1,) * (x.ndim - 1))
            # Only valid slots write back: masked padding keeps old state.
            scatter = lambda full, part: jax.tree_util.tree_map(
                lambda f, p: f.at[cohort].set(jnp.where(bmask(p) > 0, p, f[cohort])),
                full,
                part,
            )
            loss_mean = jnp.sum(mask * results.final_loss) / n_eff
        new_ci = scatter(state.scaffold_ci, results.new_ci)
        new_prev = scatter(state.prev_local, results.lora)
        new_c = state.scaffold_c
        if cfg.local.scaffold:
            # c <- c + |S|/M * mean_S(ci_new - ci_old)   (SCAFFOLD eq. 5)
            frac = n_eff / n_clients
            delta_ci = jax.tree_util.tree_map(
                lambda new, old: jnp.sum(bmask(new) * (new - old[cohort]), axis=0) / n_eff,
                results.new_ci,
                state.scaffold_ci,
            )
            new_c = jax.tree_util.tree_map(
                lambda c, d: c + frac * d, state.scaffold_c, delta_ci
            )
        # lora_global and agg_carry pass through UNCHANGED: the aggregation
        # phase owns both, so a pipelined driver can dispatch the next local
        # phase before the previous aggregation lands.
        new_state = RoundState(
            lora_global=state.lora_global,
            scaffold_c=new_c,
            scaffold_ci=new_ci,
            prev_local=new_prev,
            rng=rng,
            round_idx=state.round_idx + 1,
            agg_carry=state.agg_carry,
        )
        bundle_mask = mask
        fault_slots = None
        if fault_model is not None or guard_cfg is not None:
            # Fault/guard rounds are always masked rounds: injection and
            # quarantine fold losses into the validity mask, so the full-
            # participation None-mask fast path materializes all-ones.
            if bundle_mask is None:
                bundle_mask = jnp.ones((n_clients,), jnp.float32)
        if fault_model is not None:
            # Inject on the pre-increment round counter so a given (seed,
            # round) always plants the same faults, resume included.
            stacked_deltas, bundle_mask, fault_slots = fault_model.inject(
                state.round_idx, stacked_deltas, bundle_mask,
                stragglers=not deadline_cohort,
            )
        bundle = LocalBundle(
            deltas=stacked_deltas, mask=bundle_mask, weights=weights,
            agg_key=agg_key, loss_mean=loss_mean, fault_slots=fault_slots,
        )
        return new_state, bundle

    def _screen_bundle(bundle: LocalBundle):
        # Layer-one quarantine: fold non-finite / norm-outlier clients into
        # the validity mask and zero their columns (where-select — a mask
        # multiply cannot sanitize NaN).
        deltas, mask2 = bundle.deltas, bundle.mask
        sflags = None
        sdiags = {}
        if guard_cfg is not None:
            deltas, mask2, g = guard_lib.screen(deltas, mask2, guard_cfg)
            sflags = g.pop("flags")
            sdiags = g
        return deltas, mask2, sflags, sdiags

    def _update_diags(scaled, sflags, eflags, bundle: LocalBundle, sdiags):
        diags = dict(sdiags)
        finite = jnp.stack([
            jnp.all(jnp.isfinite(leaf))
            for leaf in jax.tree_util.tree_leaves(scaled)
        ])
        diags["update_finite"] = jnp.all(finite).astype(jnp.float32)
        if bundle.fault_slots is not None:
            flags = sflags
            if eflags is not None:
                flags = eflags if flags is None else jnp.maximum(flags, eflags)
            injected = bundle.fault_slots
            diags["fault_injected"] = jnp.sum(injected)
            if flags is not None:
                diags["fault_caught"] = jnp.sum(flags * injected)
        return diags

    def _wire_diags(diags, deltas, mask2):
        # Per-round wire accounting (DESIGN.md §12), logged beside the
        # phase timers: sketch-uplink engines already emitted exact
        # ``bytes_up`` / ``bytes_down_basis`` scalars; every other path
        # defaults to the dense f32 wire (per-client payload x live
        # cohort).  ``bytes_down`` is the update broadcast (counted once —
        # multicast) plus, on sketch rounds, the basis multicast.
        per_client = 4.0 * sum(
            int(np.prod(l.shape[1:])) for l in jax.tree_util.tree_leaves(deltas)
        )
        n_eff_r = (
            float(n_clients) if mask2 is None else jnp.maximum(jnp.sum(mask2), 0.0)
        )
        if "bytes_up" not in diags:
            diags["bytes_up"] = per_client * n_eff_r
        diags["bytes_down"] = per_client + diags.pop("bytes_down_basis", 0.0)
        return diags

    @jax.jit
    def agg_phase(agg_carry, bundle: LocalBundle, scale):
        deltas, mask2, sflags, sdiags = _screen_bundle(bundle)
        agg_kw = dict(
            engine=cfg.engine, key=bundle.agg_key, mask=mask2,
            weights=bundle.weights, mesh=mesh,
        )
        new_carry = agg_carry
        eflags = None
        if plan is not None:
            update, new_carry, ediag = engine_lib.aggregate_planned(
                plan, deltas, agg_carry, key=bundle.agg_key,
                mask=mask2, weights=bundle.weights, with_diagnostics=True,
            )
            rpca_diags = rpca_diag_summary(ediag)
            eflags = client_flag_vector(ediag)
        elif agg_cfg.method == "fedrpca":
            update, ediag = aggregate(
                deltas, agg_cfg, with_diagnostics=True, **agg_kw
            )
            rpca_diags = rpca_diag_summary(ediag)
            eflags = client_flag_vector(ediag)
        else:
            update = aggregate(deltas, agg_cfg, **agg_kw)
            rpca_diags = {}
        scaled = jax.tree_util.tree_map(lambda u: scale * u, update)
        diags = {
            **rpca_diags,
            **_update_diags(scaled, sflags, eflags, bundle, sdiags),
        }
        diags = _wire_diags(diags, deltas, mask2)
        return scaled, new_carry, diags

    @jax.jit
    def apply_phase(lora_global, scaled_update):
        return jax.tree_util.tree_map(
            lambda g, su: g + su, lora_global, scaled_update
        )

    def cold_carry():
        return engine_lib.init_agg_carry(plan) if plan is not None else ()

    # Degradation floor: plain masked FedAvg over the screened deltas, no
    # RPCA, no energy guard — the last rung of the supervisor ladder.
    fedavg_cfg = agg_cfg.replace(method="fedavg", guard_energy_k=0.0)

    @jax.jit
    def fallback_phase(bundle: LocalBundle, scale):
        deltas, mask2, sflags, sdiags = _screen_bundle(bundle)
        update = aggregate(
            deltas, fedavg_cfg, engine=cfg.engine, key=bundle.agg_key,
            mask=mask2, weights=bundle.weights, mesh=mesh,
        )
        scaled = jax.tree_util.tree_map(lambda u: scale * u, update)
        diags = {
            **_update_diags(scaled, sflags, None, bundle, sdiags),
            "degraded": jnp.asarray(1.0, jnp.float32),
        }
        diags = _wire_diags(diags, deltas, mask2)
        return scaled, cold_carry(), diags

    def guard_n_active(n_active):
        # Eager guard: a concrete out-of-range n_active is a caller bug —
        # fail loudly instead of silently clipping into the valid range
        # (tracer arguments keep the traced jnp.clip inside local_phase).
        if isinstance(n_active, (int, np.integer)):
            na = int(n_active)
            if not partial:
                raise ValueError(
                    f"n_active={na} passed to a full-participation round "
                    "(set clients_per_round to enable partial participation)"
                )
            if not 1 <= na <= cohort_pad:
                raise ValueError(
                    f"n_active={na} out of range for the canonical cohort of "
                    f"{cohort_pad} slots (expected 1 <= n_active <= {cohort_pad})"
                )

    def prep_state(state: RoundState) -> RoundState:
        if plan is not None and isinstance(state.agg_carry, tuple) and not state.agg_carry:
            # First call of a carry session: materialize the empty carry so
            # every round shares one pytree structure (and one compile).
            state = state._replace(agg_carry=engine_lib.init_agg_carry(plan))
        return state

    def local(state: RoundState, n_active=None):
        guard_n_active(n_active)
        return local_phase(prep_state(state), n_active)

    return RoundPhases(
        local,
        agg_phase,
        cohort_pad=cohort_pad,
        plan=plan,
        prep_state=prep_state,
        cache_size=lambda: max(local_phase._cache_size(), agg_phase._cache_size()),
        apply=apply_phase,
        fallback=fallback_phase,
        cold_carry=cold_carry,
    )


def make_round_fn(
    base: PyTree, data_x, data_y, cfg: FedRunConfig, client_weights=None,
    availability=None, lora_template: PyTree | None = None,
) -> Callable:
    """Returns fn: (RoundState, n_active=None) -> (RoundState, diagnostics).

    The synchronous round driver: composes ``make_round_phases``'s local
    and aggregation phases back to back with ``scale=1.0`` (the async
    driver in ``repro.fed.pipeline`` overlaps the same phases instead).

    ``client_weights`` are per-client data sizes (or any nonnegative
    weights, e.g. ``fed.partition.data_size_weights``); they feed the
    aggregation when ``cfg.aggregator.weighting`` is "data_size" /
    "data_size_rpca", and the sampler when ``cfg.sampler ==
    "size_weighted"``.  ``availability`` is the 0/1 trace for
    ``cfg.sampler == "trace"`` (see ``make_sampler``).

    With partial participation, ``n_active`` overrides the cohort size at
    call time: every in-range value shares the single compiled round, only
    the validity mask changes.  ``None`` uses ``cfg.clients_per_round``; a
    concrete out-of-range value raises eagerly at call time (the jitted
    path keeps a traced clip for tracer arguments).  Masked cohort slots
    early-exit their local phase (``make_local_fn``'s ``active`` argument)
    and return exact zero deltas.

    ``cfg.aggregator.carry_mode != "none"`` (packed engine, fedrpca) makes
    the round a cross-round aggregation session: ``lora_template`` (one
    client's LoRA structure, e.g. the ``lora_init`` passed to
    ``init_round_state``) is required to build the trace-time ``AggPlan``,
    and the per-bucket warm-start carry rides on ``RoundState.agg_carry``
    through the jitted round — same pytree structure every round, so the
    carry adds zero extra compiles.
    """
    phases = make_round_phases(
        base, data_x, data_y, cfg, client_weights=client_weights,
        availability=availability, lora_template=lora_template,
    )

    def round_fn(state: RoundState, n_active=None):
        state, bundle = phases.local(state, n_active)
        upd, new_carry, rpca_diags = phases.agg(state.agg_carry, bundle, 1.0)
        state = state._replace(
            lora_global=phases.apply(state.lora_global, upd),
            agg_carry=new_carry,
        )
        return state, {"mean_local_loss": bundle.loss_mean, **rpca_diags}

    round_fn._cache_size = phases.cache_size
    round_fn.cohort_pad = phases.cohort_pad
    round_fn.agg_plan = phases.plan
    round_fn.phases = phases
    return round_fn


def run_simulation(
    base: PyTree,
    lora_init: PyTree,
    data_x,
    data_y,
    cfg: FedRunConfig,
    eval_fn: Callable[[PyTree], float],
    *,
    eval_every: int = 1,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    client_weights=None,
    availability=None,
    n_active: Optional[int] = None,
):
    """Runs ``cfg.rounds`` rounds; returns (final lora, accuracy history).

    ``n_active`` overrides the per-round cohort size (partial participation
    only); it is validated eagerly against the canonical cohort here — an
    out-of-range value is a configuration bug, not something to clip.  With
    ``cfg.aggregator.carry_mode != "none"`` the rounds form one aggregation
    session: the warm-start carry rides on the round state, and the carry
    health diagnostics (``fallback_count``, ``live_rank_mean``,
    ``carry_hit_rate``) flow to ``log_fn`` beside the accuracy.

    Every run drives ``pipeline.run_rounds`` over the split phases:
    ``cfg.pipeline=False`` runs the staleness-0 (synchronous) schedule;
    ``cfg.pipeline=True`` overlaps each round's local phase with the
    previous round's in-flight aggregation, bounded by ``cfg.staleness``.
    Per-round phase timers (``t_local_s`` / ``t_agg_s`` / ``t_overlap_s``)
    ride to ``log_fn`` beside the accuracy either way, so the pipeline win
    is visible straight from the logs.
    """
    from repro.fed import pipeline as pipeline_lib

    n_clients = data_x.shape[0]
    state = init_round_state(lora_init, n_clients, cfg.seed)
    phases = make_round_phases(
        base, data_x, data_y, cfg, client_weights=client_weights,
        availability=availability, lora_template=lora_init,
    )
    if n_active is not None and not 1 <= int(n_active) <= phases.cohort_pad:
        raise ValueError(
            f"n_active={n_active} out of range for the canonical cohort of "
            f"{phases.cohort_pad} slots"
        )
    staleness = cfg.staleness if cfg.pipeline else 0
    history = []

    def on_round(r, round_state, diags):
        if (r + 1) % eval_every == 0 or r == cfg.rounds - 1:
            acc = float(eval_fn(round_state.lora_global))
            history.append(acc)
            if log_fn:
                log_fn(r, {"acc": acc, **{k: float(v) for k, v in diags.items()}})

    state = pipeline_lib.run_rounds(
        phases, state, cfg.rounds, staleness=staleness, n_active=n_active,
        on_round=on_round,
    )
    return state.lora_global, np.asarray(history)


def rounds_to_reach(history: np.ndarray, frac: float = 0.9) -> int:
    """R@90-style metric: 1-based count of rounds until frac * final accuracy.

    Returns -1 on an empty history.  When the target is never reached (only
    possible with a negative final accuracy, since final >= frac * final
    whenever final >= 0 and frac <= 1) returns ``len(history)`` — the same
    value as first reaching the target on the final round, so treat the
    maximum as "took all rounds (or never converged)", an upper bound.
    """
    if len(history) == 0:
        return -1
    target = frac * history[-1]
    hits = np.flatnonzero(history >= target)
    return int(hits[0]) + 1 if len(hits) else len(history)
