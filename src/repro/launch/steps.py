"""Step functions lowered by the dry-run and executed by train.py / serve.py.

``fed_train_step`` is the paper's full workload on the mesh: per-client local
LoRA optimization (clients = the ("pod","data") mesh axes, vmapped) followed
by the server aggregation (FedRPCA or a baseline) computed redundantly on
every device from the all-gathered client deltas — deltas are LoRA-sized
(r*(d_in+d_out) per module), so the gather is tiny next to the base model.

The step is built from two independently dispatchable halves —
``make_local_step`` (client local phase, emitting deltas) and
``make_agg_step`` (server aggregation + apply, threading the cross-round
``AggCarry``) — which ``make_fed_train_step`` composes into the classic
monolith for the dry-run/mesh path, and ``launch/train.py`` drives
separately so the async round pipeline (DESIGN.md §8) can overlap round
*r*'s local phase with round *r-1*'s still-running RPCA.

``prefill_step`` / ``serve_step`` are the serving pair: full-sequence prefill
emitting decode caches, and single-token decode against those caches.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import AggregatorConfig, aggregate
from repro.core import engine as engine_lib
from repro.core.aggregators import CARRY_MODES, rpca_diag_summary
from repro.models import model as model_lib
from repro.models import moe
from repro.utils.pytree import tree_add, tree_scale

PyTree = Any

_EXTRA_KEYS = ("vision_embeds", "encoder_frames", "positions")


def _reduce(stats: dict) -> dict:
    """Expert counters (``moe.COUNTERS``) stacked on leading axes -> one
    scalar each."""
    return {k: moe.COUNTERS[k][1](v) for k, v in stats.items()}


def make_local_step(
    cfg,
    *,
    local_lr: float = 1e-4,
    local_steps: int = 1,
    local_optimizer: str = "sgd",
    remat: bool = True,
    microbatch: int = 1,
    clients_per_round: int = 0,
) -> Callable:
    """Client half of the federated step, independently dispatchable.

    ``(base, lora_global, batch, agg_key=None) -> (deltas, loss, mask)``:
    the vmapped per-client local LoRA optimization, the cohort validity
    mask (None under full participation — sampled from ``agg_key`` when
    ``clients_per_round`` > 0, with masked slots early-exiting), and the
    masked mean of the client losses.  It never reads aggregation output,
    so the async pipeline can dispatch it against a global that is still
    missing the in-flight round's update.

    An expert-share model (``cfg.experts_held``) returns a fourth output,
    the expert layer's counters over every layer, local step and client:
    ``moe_load_max``, the busiest held expert over the held mean, the worst.

    ``microbatch`` > 1 splits each client's batch into that many slices and
    accumulates LoRA grads over a scan — activation residency drops by the
    same factor (the llama4 §Perf fit fix) at no extra FLOPs.
    """

    counters = tuple(moe.COUNTERS) if cfg.experts_held else ()

    def client_update(base, lora_global, client_batch):
        def full_loss(l, b):
            total, parts = model_lib.loss_fn(base, l, b, cfg, remat=remat)
            return total, {k: parts[k] for k in counters}

        if microbatch > 1:
            def local_loss_grad(l, b):
                def slice_batch(x):
                    per = x.shape[0]
                    assert per % microbatch == 0, (per, microbatch)
                    return jnp.reshape(x, (microbatch, per // microbatch, *x.shape[1:]))

                mb = jax.tree_util.tree_map(slice_batch, b)

                def acc(carry, mb_i):
                    loss_acc, g_acc = carry
                    (loss_i, st_i), g_i = jax.value_and_grad(full_loss, has_aux=True)(l, mb_i)
                    g_acc = jax.tree_util.tree_map(lambda a, gi: a + gi, g_acc, g_i)
                    return (loss_acc + loss_i, g_acc), st_i

                zeros = jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), l
                )
                (loss, g), st = jax.lax.scan(acc, (jnp.zeros(()), zeros), mb)
                inv = 1.0 / microbatch
                return (loss * inv, _reduce(st)), jax.tree_util.tree_map(lambda x: x * inv, g)
        else:
            def local_loss_grad(l, b):
                return jax.value_and_grad(full_loss, has_aux=True)(l, b)

        if local_optimizer == "adam":
            from repro.optim import adam
            from repro.optim.optimizers import apply_updates

            opt = adam(local_lr)
            with jax.named_scope("local.opt"):
                state = opt.init(lora_global)

            def one(carry, _):
                lora, state = carry
                with jax.named_scope("local.grad"):
                    (loss, st), g = local_loss_grad(lora, client_batch)
                with jax.named_scope("local.opt"):
                    upd, state = opt.update(g, state, lora)
                    return (apply_updates(lora, upd), state), (loss, st)

            (lora, _), (losses, stats) = jax.lax.scan(
                one, (lora_global, state), None, length=local_steps
            )
        else:
            # Plain SGD local steps.
            def one(lora, _):
                with jax.named_scope("local.grad"):
                    (loss, st), g = local_loss_grad(lora, client_batch)
                with jax.named_scope("local.opt"):
                    return tree_add(lora, tree_scale(g, -local_lr)), (loss, st)

            lora, (losses, stats) = jax.lax.scan(one, lora_global, None, length=local_steps)
        with jax.named_scope("local.delta"):
            delta = jax.tree_util.tree_map(lambda a, b: a - b, lora, lora_global)
            return delta, losses[-1], _reduce(stats)

    def local_step(base, lora_global, batch, agg_key=None):
        extras = {k: batch[k] for k in _EXTRA_KEYS if k in batch}
        m = batch["tokens"].shape[0]
        mask = None
        if clients_per_round > m:
            raise ValueError(
                f"clients_per_round={clients_per_round} exceeds the batch's "
                f"{m} client slots"
            )
        if clients_per_round and clients_per_round < m:
            if agg_key is None:
                raise ValueError("clients_per_round > 0 requires an agg_key per round")
            perm = jax.random.permutation(jax.random.fold_in(agg_key, 0x5EED), m)
            mask = jnp.zeros((m,), jnp.float32).at[perm[:clients_per_round]].set(1.0)

        def client_fn(tokens, labels, *extra_vals):
            b = {"tokens": tokens, "labels": labels}
            b.update(dict(zip(extras.keys(), extra_vals)))
            return client_update(base, lora_global, b)

        if mask is None:
            deltas, losses, stats = jax.vmap(client_fn)(
                batch["tokens"], batch["labels"], *extras.values()
            )
        else:
            # Masked-slot early exit, mirroring fed/server.py: unsampled
            # clients return exact zero deltas / zero loss under lax.cond
            # instead of running a local scan whose output is discarded.
            # Under vmap/SPMD the cond lowers to a select (both branches
            # lower), so the saving is semantic there; per-device dispatch
            # with a scalar predicate skips the branch outright.
            def gated_fn(active, tokens, labels, *extra_vals):
                def run(_):
                    delta, loss, st = client_fn(tokens, labels, *extra_vals)
                    return delta, loss.astype(jnp.float32), st

                def skip(_):
                    return (
                        jax.tree_util.tree_map(jnp.zeros_like, lora_global),
                        jnp.zeros((), jnp.float32),
                        {k: jnp.zeros((), jnp.float32) for k in counters},
                    )

                return jax.lax.cond(active > 0, run, skip, None)

            deltas, losses, stats = jax.vmap(gated_fn)(
                mask, batch["tokens"], batch["labels"], *extras.values()
            )
        with jax.named_scope("local.delta"):
            if mask is None:
                loss = jnp.mean(losses)
            else:
                loss = jnp.sum(mask * losses) / jnp.maximum(jnp.sum(mask), 1.0)
        if counters:
            return deltas, loss, mask, _reduce(stats)
        return deltas, loss, mask

    return local_step


@jax.named_scope("agg.apply")
def apply_update(lora_global: PyTree, scaled_update: PyTree) -> PyTree:
    """Land-time composition: fold an already-scaled update into the global.

    The aggregation step returns the scaled *update*, not the applied
    state (so K-deep in-flight aggregations can land in dispatch order
    without overwriting each other — DESIGN.md §11); this is the single
    apply they all compose through.  Multiplying the update by exactly 1.0
    upstream is IEEE-exact, so the synchronous schedule stays bit-for-bit
    the legacy ``lora + update``.
    """
    return jax.tree_util.tree_map(
        lambda g, su: g + su, lora_global, scaled_update
    )


def make_agg_step(
    agg_cfg: Optional[AggregatorConfig] = None,
    *,
    engine: str = "packed",
    client_weights=None,
    mesh=None,
    uplink=None,
) -> Callable:
    """Server half of the federated step, independently dispatchable.

    ``(deltas, mask=None, agg_key=None[, agg_carry], scale=1.0)
    -> (scaled_update, metrics[, new_carry])``: aggregate the stacked
    client deltas and return ``scale * update`` for the caller to land via
    ``apply_update`` (land-time composition — the driver may hold several
    aggregations in flight, so the step must not bake in the global it was
    dispatched from).  ``scale=1.0`` is bit-for-bit the legacy unscaled
    update; the async pipeline passes the staleness-corrected damping for
    updates landing behind.  ``client_weights`` are per-client data sizes,
    used when ``agg_cfg.weighting`` is data-size based.

    ``agg_cfg.carry_mode != "none"`` (packed engine, fedrpca) makes the
    step a cross-round aggregation session: it threads the ``agg_carry``
    argument/return (build the initial one with
    ``engine.init_agg_carry(engine.plan_aggregation(example, agg_cfg))``
    over a zeros delta tree, as ``launch/train.py`` does) and its metrics
    grow the carry health scalars.  With carry off the return arity drops
    the carry, matching the legacy contract.

    ``mesh`` shards the packed client axis of the aggregation across the
    mesh's client axes (packed engine only — DESIGN.md §10); one-shard
    meshes are normalized away, keeping the single-device trace bitwise.
    Ragged cohorts (clients not divisible by the shard count) are padded
    with masked zero columns inside the sharded loop, and
    ``agg_cfg.rpca_fused_tail`` / ``agg_cfg.mesh_overlap`` select the
    shard-local fused Pallas tail and the chunked-psum overlap schedule.

    ``uplink`` selects the client->server wire codec (DESIGN.md §12) —
    None/"dense" is the exact legacy wire; "sketch[:k[:tol]]" (or an
    ``UplinkConfig``) turns on the carry-basis sketch codec inside the
    session plan, with its byte counters riding the metrics.  Sketch
    requires the cross-round carry (it projects onto the carried basis).
    """
    agg_cfg = agg_cfg or AggregatorConfig()
    if agg_cfg.carry_mode not in CARRY_MODES:
        raise ValueError(
            f"unknown carry_mode: {agg_cfg.carry_mode!r} (expected one of {CARRY_MODES})"
        )
    carry_on = (
        agg_cfg.carry_mode != "none"
        and engine == "packed"
        and agg_cfg.method == "fedrpca"
    )
    if mesh is not None and engine != "packed":
        from repro.core.rpca import mesh_client_shards

        if mesh_client_shards(mesh) > 1:
            raise ValueError(
                "mesh-sharded aggregation requires engine='packed' (the "
                "reference engine is the single-device parity oracle)"
            )
        mesh = None
    use_weights = agg_cfg.weighting in ("data_size", "data_size_rpca")
    if use_weights and client_weights is None:
        raise ValueError(
            f"weighting={agg_cfg.weighting!r} requires client_weights; "
            "refusing to silently fall back to uniform"
        )
    w_clients = None if client_weights is None else jnp.asarray(client_weights, jnp.float32)

    def agg_step(deltas, mask=None, agg_key=None, agg_carry=None, scale=1.0):
        weights = w_clients if use_weights else None
        # agg_key varies the stochastic aggregators (dare) across rounds;
        # None keeps the step a pure function of the deltas.
        if carry_on:
            # Plan at trace time from the deltas' own structure (static),
            # thread the cross-round carry, and surface the session health
            # in the metrics so training logs show carry regressions.
            plan = engine_lib.plan_aggregation(
                deltas, agg_cfg, mesh=mesh, uplink=uplink
            )
            update, new_carry, ediag = engine_lib.aggregate_planned(
                plan, deltas, agg_carry, key=agg_key, mask=mask,
                weights=weights, with_diagnostics=True,
            )
            with jax.named_scope("agg.tail"):
                scaled = jax.tree_util.tree_map(lambda u: scale * u, update)
            return scaled, rpca_diag_summary(ediag), new_carry
        update = aggregate(
            deltas, agg_cfg, engine=engine, key=agg_key, mask=mask, weights=weights,
            mesh=mesh,
        )
        with jax.named_scope("agg.tail"):
            scaled = jax.tree_util.tree_map(lambda u: scale * u, update)
        return scaled, {}

    agg_step.carry_on = carry_on
    return agg_step


def make_fed_train_step(
    cfg,
    agg_cfg: Optional[AggregatorConfig] = None,
    *,
    local_lr: float = 1e-4,
    local_steps: int = 1,
    local_optimizer: str = "sgd",
    remat: bool = True,
    microbatch: int = 1,
    engine: str = "packed",
    clients_per_round: int = 0,
    client_weights=None,
) -> Callable:
    """(base, lora_global, batch) -> (new_lora_global, metrics).

    The classic monolithic federated step — ``make_local_step`` composed
    with ``make_agg_step`` in one traceable function, which the dry-run
    lowers and the mesh executes.  ``launch/train.py --pipeline`` drives
    the two halves separately instead so the aggregation can run one round
    behind (DESIGN.md §8).

    ``batch`` leaves carry a leading client axis: tokens/labels
    (M, per_client, S); frontend stubs likewise.

    ``engine`` selects the server aggregation engine: "packed" lowers one
    batched call per shape bucket (the production path — the compiled
    program holds one RPCA loop per bucket instead of one per LoRA leaf);
    "reference" keeps the per-leaf path for parity runs.

    ``clients_per_round`` > 0 enables mask-based partial participation: the
    client axis is mesh-sharded, so instead of gathering a sub-cohort the
    step samples a validity mask over the M slots from ``agg_key`` (required
    in that case) and the aggregation excludes masked clients — the compiled
    program stays shape-static.  ``client_weights`` are per-client data
    sizes, used when ``agg_cfg.weighting == "data_size"``.

    ``agg_cfg.carry_mode != "none"`` (packed engine, fedrpca) turns the
    step into a cross-round aggregation session: it gains a trailing
    ``agg_carry`` argument and return value and its metrics grow the carry
    health scalars (see ``make_agg_step``).  With carry off the signature
    and return arity are unchanged.
    """
    local_step = make_local_step(
        cfg, local_lr=local_lr, local_steps=local_steps,
        local_optimizer=local_optimizer, remat=remat, microbatch=microbatch,
        clients_per_round=clients_per_round,
    )
    agg_step = make_agg_step(agg_cfg, engine=engine, client_weights=client_weights)

    def fed_train_step(base, lora_global, batch, agg_key=None, agg_carry=None):
        # An expert share's counters, a fourth output, are not reported here.
        deltas, loss, mask = local_step(base, lora_global, batch, agg_key)[:3]
        if agg_step.carry_on:
            upd, metrics, new_carry = agg_step(deltas, mask, agg_key, agg_carry)
            return apply_update(lora_global, upd), {"loss": loss, **metrics}, new_carry
        upd, metrics = agg_step(deltas, mask, agg_key)
        return apply_update(lora_global, upd), {"loss": loss, **metrics}

    return fed_train_step


def make_prefill_step(cfg) -> Callable:
    """(base, lora, batch) -> (next_token_logits, caches)."""

    def prefill_step(base, lora, batch):
        logits, caches, _ = model_lib.forward(
            base, lora, batch, cfg, mode="prefill", remat=False
        )
        return logits, caches

    return prefill_step


def make_serve_step(cfg) -> Callable:
    """(base, lora, tokens (B,1), caches, cache_index) -> (logits, caches)."""

    def serve_step(base, lora, tokens, caches, cache_index):
        return model_lib.decode_step(base, lora, tokens, caches, cache_index, cfg)

    return serve_step


def make_single_train_step(cfg, *, lr: float = 1e-4, remat: bool = True) -> Callable:
    """Non-federated LoRA train step (one SGD step) — utility/baseline."""

    def train_step(base, lora, batch):
        loss, g = jax.value_and_grad(
            lambda l: model_lib.loss_fn(base, l, batch, cfg, remat=remat)[0]
        )(lora)
        return tree_add(lora, tree_scale(g, -lr)), loss

    return train_step
