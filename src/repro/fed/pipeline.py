"""Async buffered round pipeline (DESIGN.md §8, §11).

The server's RPCA split dominates round wall time, and since PR 4 it is a
re-entrant session step: the ``AggPlan`` is fixed at trace time and the
``AggCarry`` threads in/out of every call.  That makes the aggregation
*independently dispatchable* — round *r*'s local phase does not read round
*r-1*'s update until it lands — so this module overlaps the two:

    dispatch local_r            (reads the global missing the last s updates)
    land    agg_{r-s}           (apply the oldest in-flight update)
    dispatch agg_r              (chained on the previous dispatch's carry)

``staleness`` bounds the number of in-flight aggregation dispatches — a
FedBuff-style K-deep buffer.  With ``staleness=0`` every update lands
before the next local phase is dispatched — the synchronous schedule,
bit-for-bit (the same compiled phases run in the same order with the same
``scale=1.0``).  With ``staleness=K>0`` the global a local phase reads is
at most *K* updates behind.  The aggregation phase returns the *scaled
update*, not the applied state; ``run_rounds`` composes updates into the
global at land time (``phases.apply``), which is what lets K in-flight
aggregations land in dispatch order without overwriting each other.  The
per-update damping is driven adaptively from the landed carry residual
(``AdaptiveStaleScale``), falling back to the FedAsync ``stale_scale``.

Landing is also where the fault supervisor lives (DESIGN.md §11): a
non-finite aggregation output never reaches the global — it is retried
once with a bitwise-cold carry, then degraded to plain masked FedAvg
(``phases.fallback``) with a loud diagnostic.

The round state is buffered: the driver's ``state`` buffer advances
through local phases (RNG, variates, round counter) while the in-flight
queue holds the pending scaled updates each aggregation dispatch will
land.  The aggregation carry threads dispatch-to-dispatch through the
worker futures (each dispatch chains on the previous dispatch's carry,
not the last landed one).  The dispatches run on a dedicated ``AggWorker``
thread: XLA CPU's dispatch executes synchronously on the calling thread,
so without the worker the "overlap" would silently serialize — with it,
the client matmuls genuinely hide inside the eigh-bound RPCA loop
(~1.4-1.7x per-round wall clock on the 2-core CPU container,
``benchmarks/agg_engine_bench.py`` pipeline cells); on asynchronous
backends (TPU streams) the worker is a cheap pass-through.

``InFlightQueue`` and ``AggWorker`` are the bare scheduling primitives;
``run_rounds`` is the simulation driver over ``fed.server.RoundPhases``;
``launch/train.py`` reuses both for the mesh step pair.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

PyTree = Any

_RES_EPS = 1e-12


def stale_scale(staleness: int) -> float:
    """FedAsync-style polynomial staleness weight: 1 / (1 + tau).

    An update aggregated from deltas computed against a global ``tau``
    updates old is damped toward the current iterate; ``tau = 0`` returns
    exactly 1.0, so the synchronous path is bit-for-bit unscaled (IEEE
    multiplication by 1.0 is exact).
    """
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    return 1.0 / (1.0 + staleness)


class AdaptiveStaleScale:
    """Residual-driven staleness damping (DESIGN.md §11).

    The fixed FedAsync weight ``1/(1+tau)`` damps every stale update the
    same no matter how turbulent training currently is.  The carry
    residual surfaced by ``rpca_diag_summary`` (``rpca_residual_max``) is
    a direct read on that turbulence: when the RPCA split converges
    cleanly the residual is small and a stale update is still
    well-aligned — damp less; when the residual spikes the update is
    stale *and* noisy — damp more.  This tracker keeps a host-side EMA of
    the landed residuals and scales the tau term by the
    current-to-typical ratio, clipped to [0.25, 4.0] so the weight stays
    within 4x of the FedAsync baseline either way.

    ``tau = 0`` always returns exactly 1.0 (the synchronous bitwise
    contract); before any residual has landed — or for methods that
    report none — it falls back to ``stale_scale``.
    """

    def __init__(self, decay: float = 0.9):
        self.decay = decay
        self.ema: Optional[float] = None
        self.last: Optional[float] = None

    def observe(self, diags: dict) -> None:
        res = diags.get("rpca_residual_max")
        if res is None:
            return
        res = float(res)
        if not (res == res and abs(res) != float("inf")):
            return  # a non-finite residual must not poison the EMA
        self.last = res
        self.ema = res if self.ema is None else (
            self.decay * self.ema + (1.0 - self.decay) * res
        )

    def scale_for(self, tau: int) -> float:
        if tau == 0:
            return 1.0
        if self.ema is None or self.last is None:
            return stale_scale(tau)
        ratio = self.last / max(self.ema, _RES_EPS)
        ratio = min(max(ratio, 0.25), 4.0)
        return 1.0 / (1.0 + tau * ratio)


class InFlightQueue:
    """Bounded FIFO of in-flight dispatches — the staleness bound.

    The landing order matters: updates land in dispatch order (FIFO), and
    the caller pops *before* dispatching (``pop_ready``) and enqueues
    *after* (``push``).  ``depth=0`` degenerates to the synchronous
    schedule: ``pop_ready`` is always None, ``push`` hands the item
    straight back to be landed, and nothing ever stays in flight.
    ``depth=K`` keeps up to K aggregations in flight (FedBuff-style
    K-deep buffering — composable because the agg phase returns updates,
    not applied states).  ``drain()`` yields the stragglers at end of
    training.
    """

    def __init__(self, depth: int):
        if depth < 0:
            raise ValueError(f"queue depth must be >= 0, got {depth}")
        self.depth = depth
        self._q: deque = deque()

    def __len__(self) -> int:
        return len(self._q)

    def pop_ready(self):
        """Oldest entry when the queue sits at its bound (land it before
        dispatching past the staleness budget), else None."""
        if self.depth and len(self._q) >= self.depth:
            return self._q.popleft()
        return None

    def push(self, item):
        """Enqueue a fresh dispatch.  Returns the item itself at depth 0
        (land immediately — the synchronous schedule), else None."""
        if self.depth == 0:
            return item
        if len(self._q) >= self.depth:
            raise RuntimeError(
                "InFlightQueue full: pop_ready() and land the oldest entry "
                "before dispatching a new one"
            )
        self._q.append(item)
        return None

    def drain(self):
        while self._q:
            yield self._q.popleft()


class AggWorker:
    """One worker thread that runs the aggregation dispatches in order.

    On backends whose dispatch executes synchronously on the calling
    thread (XLA CPU — ``jitted_fn(x)`` returns only after the computation
    ran), issuing the aggregation from the driver thread would serialize
    it against the next round's local phase no matter how the schedule is
    arranged.  The worker is what makes the overlap real there: the main
    thread runs local phases while this thread runs the RPCA split, and
    the single-worker FIFO preserves the carry chain ordering (a dispatch
    reading the previous dispatch's carry future never blocks — its
    predecessor already ran).  On genuinely asynchronous backends (TPU
    streams) the worker is a cheap pass-through.  ``submit`` returns a
    ``concurrent.futures.Future``; worker exceptions surface at
    ``result()`` (i.e. when the round lands).
    """

    def __init__(self):
        self._ex = ThreadPoolExecutor(max_workers=1, thread_name_prefix="agg-phase")

    def submit(self, fn, *args) -> Future:
        return self._ex.submit(fn, *args)

    def close(self):
        self._ex.shutdown(wait=True)


@jax.jit
def _default_apply(lora_global, scaled_update):
    """Land-time composition for duck-typed phases without ``apply``."""
    return jax.tree_util.tree_map(
        lambda g, su: g + su, lora_global, scaled_update
    )


class _InFlight(NamedTuple):
    """One dispatched aggregation awaiting landing."""

    round_idx: int
    loss_mean: Any  # the round's local-loss scalar (future)
    out: Any  # (scaled_update, agg_carry', diags) — or a Future of it
    bundle: Any  # the round's LocalBundle (kept for supervisor retries)
    scale: Any  # the round's staleness damping (kept for retries)
    t_local: float  # local phase dispatch -> ready, seconds
    t_dispatch: float  # perf_counter timestamp of the agg dispatch


def run_rounds(
    phases,
    state,
    rounds: int,
    *,
    staleness: int = 0,
    n_active: Optional[int] = None,
    scale: Optional[float] = None,
    on_round: Optional[Callable[[int, Any, dict], None]] = None,
    timers: bool = True,
):
    """Drive ``rounds`` server rounds over split phases with a staleness bound.

    ``phases`` is a ``fed.server.RoundPhases`` (or anything with the same
    ``local`` / ``agg`` / ``prep_state`` surface); ``state`` the initial
    ``RoundState``.  ``staleness=0`` lands every aggregation before the next
    local phase dispatches — the synchronous schedule, bitwise identical to
    ``make_round_fn``'s composition.  ``staleness=K>0`` keeps up to K
    aggregations in flight (FedBuff-style buffering): each dispatch chains
    on the *previous dispatch's* carry through the worker futures, while
    the scaled updates land into the global in dispatch order via
    ``phases.apply`` — land-time composition is what makes depths beyond
    the double buffer sound.

    Each round's landed update is damped by its *actual* staleness ``tau``
    (how many updates were in flight when its local phase dispatched):
    exactly 1.0 at ``tau = 0`` (round 0 of a pipelined run lands undamped,
    and the synchronous schedule is bitwise unscaled), else an adaptive
    residual-driven weight (``AdaptiveStaleScale`` — falls back to
    ``stale_scale`` before any residual has landed).  Passing ``scale``
    overrides the per-round damping with a constant.

    Landing runs the fault supervisor: when the round's diagnostics report
    a non-finite scaled update (``update_finite == 0``), the aggregation
    is retried once with a bitwise-cold carry (``phases.cold_carry``), and
    if still non-finite degraded to plain masked FedAvg
    (``phases.fallback``) — both loud (``warnings.warn`` + the
    ``supervisor_retry`` / ``degraded`` diagnostics).  Duck-typed phases
    without those attributes skip the ladder.

    ``on_round(r, state, diags)`` fires once per round *in round order*, at
    the moment round ``r``'s update has landed in ``state.lora_global`` —
    under the pipeline that is one iteration (per unit of staleness) after
    its local phase ran, and the final rounds land in the drain.  ``diags``
    carries the round's aggregation diagnostics plus, when ``timers`` is
    on, the per-phase wall clocks:

      * ``t_local_s`` — local phase dispatch -> outputs ready;
      * ``t_agg_s`` — host time *blocked* on the aggregation when landing
        it (the synchronous path blocks for the full RPCA; a healthy
        pipeline shows ~0 here);
      * ``t_overlap_s`` — aggregation in-flight time hidden behind
        subsequent local work (dispatch-to-ready latency minus the blocked
        wait; 0 by construction when synchronous).

    The same intervals are host spans on the profiler's clock
    (``jax.profiler.TraceAnnotation``; next to free when no profiler is
    active): ``fed.round`` per loop iteration; ``fed.local`` is the
    interval ``t_local_s`` times; ``fed.land`` the one ``t_agg_s`` times,
    with children ``fed.land.wait`` (the in-flight result) and
    ``fed.land.apply`` (apply and its block); ``fed.agg.dispatch`` the
    aggregation dispatch, ``fed.agg.worker`` its run on the worker thread,
    and ``fed.on_round`` the callback.
    """
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    queue = InFlightQueue(staleness)
    # The worker thread is what overlaps the phases on synchronous-dispatch
    # backends (see AggWorker); the synchronous schedule stays inline on
    # the driver thread — zero threading, bitwise the composed round.
    worker = AggWorker() if staleness else None
    adaptive = AdaptiveStaleScale()
    apply_fn = getattr(phases, "apply", None) or _default_apply
    cold_carry = getattr(phases, "cold_carry", None)
    fallback = getattr(phases, "fallback", None)
    # The carry chain head: the most recent dispatch's Future, which the
    # next dispatch reads its carry from.  A one-slot list so land() can
    # sever the chain after a supervisor intervention (everything still in
    # flight descends from the bad carry; the next dispatch must restart
    # from the repaired state-level carry instead).
    chain: list = [None]

    def land(entry: _InFlight, state):
        with TraceAnnotation("fed.land"):
            t0 = time.perf_counter()
            with TraceAnnotation("fed.land.wait"):
                out = entry.out.result() if isinstance(entry.out, Future) else entry.out
            upd, new_carry, diags = out
            finite = diags.get("update_finite")
            if finite is not None and float(finite) == 0.0:
                # Supervisor ladder (DESIGN.md §11): a non-finite update never
                # reaches the global.  A poisoned carry is the usual culprit —
                # retry bitwise-cold first, then give up on RPCA entirely.
                extra = {}
                if cold_carry is not None:
                    warnings.warn(
                        f"round {entry.round_idx}: non-finite aggregation "
                        "output; retrying with a cold carry"
                    )
                    upd, new_carry, diags = phases.agg(
                        cold_carry(), entry.bundle, entry.scale
                    )
                    extra["supervisor_retry"] = 1.0
                    finite = diags.get("update_finite")
                if finite is not None and float(finite) == 0.0 and fallback is not None:
                    warnings.warn(
                        f"round {entry.round_idx}: aggregation still non-finite "
                        "after the cold-carry retry; degrading to masked FedAvg"
                    )
                    upd, new_carry, diags = fallback(entry.bundle, entry.scale)
                diags = {**diags, **extra}
                chain[0] = None
            with TraceAnnotation("fed.land.apply"):
                new_lora = apply_fn(state.lora_global, upd)
                if timers:
                    jax.block_until_ready(new_lora)
            now = time.perf_counter()
        t_agg = now - t0
        adaptive.observe(diags)
        state = state._replace(lora_global=new_lora, agg_carry=new_carry)
        if on_round is not None:
            diags = {"mean_local_loss": entry.loss_mean, **diags}
            if timers:
                diags["t_local_s"] = entry.t_local
                diags["t_agg_s"] = t_agg
                diags["t_overlap_s"] = max(0.0, (now - entry.t_dispatch) - t_agg)
            with TraceAnnotation("fed.on_round"):
                on_round(entry.round_idx, state, diags)
        return state

    def dispatch(state, bundle, round_scale):
        if worker is None:
            return phases.agg(state.agg_carry, bundle, round_scale)
        prev = chain[0]
        carry0 = state.agg_carry

        def work():
            # Single FIFO worker: prev was submitted earlier, so it has
            # already run and result() never blocks — this is how one
            # carry chain threads through K out-of-state dispatches.
            with TraceAnnotation("fed.agg.worker"):
                carry = prev.result()[1] if prev is not None else carry0
                out = phases.agg(carry, bundle, round_scale)
                jax.block_until_ready(out[0])  # materialize on the worker
                return out

        fut = worker.submit(work)
        chain[0] = fut
        return fut

    state = phases.prep_state(state)
    try:
        for r in range(rounds):
            with TraceAnnotation("fed.round"):
                # This round's actual staleness: how many updates its local
                # phase's global is missing right now.  Round 0 has tau=0
                # even in a pipelined run, so its update lands undamped.
                tau = len(queue)
                round_scale = adaptive.scale_for(tau) if scale is None else scale
                with TraceAnnotation("fed.local"):
                    t0 = time.perf_counter()
                    # The local phase reads the CURRENT buffer: with
                    # aggregations in flight, its lora_global is up to
                    # `staleness` updates behind.
                    state, bundle = phases.local(state, n_active)
                    if timers:
                        jax.block_until_ready(bundle.loss_mean)
                    t_local = time.perf_counter() - t0
                # Land the oldest in-flight aggregation BEFORE dispatching
                # this round's: the dispatch budget frees up and the landed
                # carry is current in case the supervisor severed the chain.
                oldest = queue.pop_ready()
                if oldest is not None:
                    state = land(oldest, state)
                with TraceAnnotation("fed.agg.dispatch"):
                    out = dispatch(state, bundle, round_scale)
                landed = queue.push(
                    _InFlight(
                        r, bundle.loss_mean, out, bundle, round_scale,
                        t_local, time.perf_counter(),
                    )
                )
                if landed is not None:
                    state = land(landed, state)
        for entry in queue.drain():
            state = land(entry, state)
    finally:
        if worker is not None:
            worker.close()
    return state
