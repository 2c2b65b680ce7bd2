"""Plain reference of the pipelined federated round (DESIGN.md §8, §11).

Round ``r``: every client trains from the current global; then, with
``staleness`` K, the oldest aggregation lands once K are in flight, and
round ``r``'s aggregation is dispatched, chained on the previous dispatch's
RPCA state.  An update is damped by its staleness ``tau`` (aggregations in
flight when its local phase began): 1 at ``tau = 0``; ``1 / (1 + tau)``
before any residual has landed; else ``1 / (1 + tau * ratio)`` with
``ratio`` the last landed RPCA residual over their exponential mean (decay
0.9), clipped to [0.25, 4].
"""
from __future__ import annotations

from collections import deque

import jax


def follow(local_phase, aggregate, lora0: dict, rounds: int, staleness: int) -> dict:
    """Run ``rounds`` rounds and land them all.

    ``local_phase(r, lora) -> (deltas, loss)``; ``aggregate(deltas, warm) ->
    (update, state, residual)``.  Returns the losses, the global after each
    landing (``landed[i]`` after the i-th) and the scales used.
    """
    glob = lora0
    queue = deque()
    ema = last = None
    warm = None
    out = {"losses": [], "landed": [], "scales": []}

    def land(entry):
        nonlocal glob, ema, last
        upd, scale, res = entry
        glob = jax.tree_util.tree_map(lambda g, u: g + scale * u, glob, upd)
        out["landed"].append(glob)
        last = res
        ema = res if ema is None else 0.9 * ema + 0.1 * res

    for r in range(rounds):
        tau = len(queue)
        if tau == 0:
            scale = 1.0
        elif ema is None:
            scale = 1.0 / (1.0 + tau)
        else:
            ratio = min(max(last / max(ema, 1e-12), 0.25), 4.0)
            scale = 1.0 / (1.0 + tau * ratio)
        deltas, loss = local_phase(r, glob)
        out["losses"].append(float(loss))
        if staleness and len(queue) >= staleness:
            land(queue.popleft())
        upd, warm, res = aggregate(deltas, warm)
        out["scales"].append(scale)
        queue.append((upd, scale, res))
        if not staleness:
            land(queue.popleft())
    while queue:
        land(queue.popleft())
    return out
