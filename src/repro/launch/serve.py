"""Multi-tenant LoRA serving driver: adapter pool + request scheduler.

Requests carry adapter IDs; the scheduler co-batches across tenants, resolves
IDs to pool slots (``repro.serve.AdapterPool``), and the jitted prefill /
decode loop gathers each request's adapter leaf-wise from the resident pool
(the batched branch of ``layers.dense``) — one forward pass per mixed-tenant
batch, no adapter re-stacking per request.

The old behavior (``--n-adapters > 1`` silently serving the *averaged*
adapter) is gone: per-request selection is the default, and the averaged
path must be asked for explicitly with ``--merged`` (it warns loudly).

Example (CPU):
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced \
      --batch 4 --prompt-len 16 --gen 8 --n-adapters 3 --pool-slots 8
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.models import (
    decode_step,
    extend_caches,
    forward,
    init_lora_params,
    init_params,
)
from repro.serve import AdapterPool, adapter_view
from repro.utils import get_logger
from repro.utils.compile_cache import enable_compile_cache

log = get_logger("serve")


def gather_adapters(stacked_lora, request_ids: jnp.ndarray):
    """Deprecated per-request adapter materialization (O(batch) HBM traffic).

    Kept only as the bench baseline; serving goes through ``AdapterPool`` +
    ``adapter_view`` (leaf-wise slot gather inside the jitted forward).
    """
    return jax.tree_util.tree_map(
        lambda leaf: jnp.take(leaf, request_ids, axis=0), stacked_lora
    )


def merge_adapter_means(stacked_lora):
    """Legacy single-tenant fallback: average the adapter sets."""
    return jax.tree_util.tree_map(lambda leaf: jnp.mean(leaf, axis=0), stacked_lora)


@dataclass
class Request:
    """One serving request: a prompt bound to a tenant's adapter."""

    request_id: int
    adapter_id: object
    tokens: np.ndarray  # (prompt_len,) int32


@dataclass
class RequestScheduler:
    """FIFO co-batching across tenants.

    ``next_batch`` takes up to ``batch_size`` queued requests regardless of
    tenant (the pool path makes mixed batches free) and resolves their
    adapter ids to slots — which also feeds the pool's LRU/traffic keys.
    """

    pool: AdapterPool
    batch_size: int
    queue: List[Request] = field(default_factory=list)

    def submit(self, request: Request):
        if request.adapter_id not in self.pool:
            raise KeyError(
                f"request {request.request_id}: adapter {request.adapter_id!r} "
                "not resident — publish() it before submitting"
            )
        self.queue.append(request)

    def next_batch(self) -> Optional[tuple]:
        if not self.queue:
            return None
        take, self.queue = self.queue[: self.batch_size], self.queue[self.batch_size:]
        tokens = jnp.asarray(np.stack([r.tokens for r in take]), jnp.int32)
        slots = self.pool.acquire([r.adapter_id for r in take])
        return take, tokens, slots


def _make_batch(cfg, tokens, rng):
    batch = {"tokens": tokens}
    b = tokens.shape[0]
    if cfg.frontend == "vision":
        batch["vision_embeds"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_vision_tokens, cfg.d_model)), jnp.dtype(cfg.dtype)
        )
    if cfg.frontend == "audio":
        batch["encoder_frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.encoder_seq, cfg.d_model)), jnp.dtype(cfg.dtype)
        )
    return batch


def serve_batch(base, pool, scheduler, cfg, *, gen: int, rng, prefill_fn, decode_fn):
    """Drain one batch from the scheduler: prefill + greedy decode."""
    item = scheduler.next_batch()
    if item is None:
        return None
    requests, tokens, slots = item
    batch = _make_batch(cfg, tokens, rng)
    logits, caches = prefill_fn(base, pool.pooled, slots, batch)
    caches = extend_caches(caches, gen, cfg)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    generated = [tok]
    prompt_len = tokens.shape[1]
    for i in range(gen - 1):
        logits, caches = decode_fn(
            base, pool.pooled, slots, tok, caches, jnp.asarray(prompt_len + i)
        )
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        generated.append(tok)
    return requests, jnp.concatenate(generated, axis=1)


def make_serving_fns(cfg):
    """Jitted prefill/decode over (base, pooled, slots, ...).

    The pool tree is an argument (never closed over) so a hot-swap publish
    between calls reuses the same executable — see the donation contract in
    ``repro.serve.pool``.
    """

    @jax.jit
    def prefill(base, pooled, slots, batch):
        lora = adapter_view(pooled, slots)
        return forward(base, lora, batch, cfg, mode="prefill", remat=False)[:2]

    @jax.jit
    def decode(base, pooled, slots, tok, caches, idx):
        lora = adapter_view(pooled, slots)
        return decode_step(base, lora, tok, caches, idx, cfg)

    return prefill, decode


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--n-adapters", type=int, default=1)
    ap.add_argument("--pool-slots", type=int, default=0,
                    help="adapter pool capacity (0 = fit --n-adapters exactly)")
    ap.add_argument("--merged", action="store_true",
                    help="legacy path: serve the MEAN of all adapters "
                         "(every tenant gets the same averaged adapter)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = cfglib.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.encoder_decoder:
        log.info("enc-dec arch: prompts are decoder prefixes over stub audio frames")

    key = jax.random.PRNGKey(args.seed)
    base = init_params(key, cfg)
    adapters = [
        init_lora_params(jax.random.fold_in(key, 10 + i), cfg)
        for i in range(args.n_adapters)
    ]

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(args.batch, args.prompt_len)
    ).astype(np.int32)

    if args.merged:
        log.warning(
            "--merged: serving the MEAN of %d adapters — every request gets the "
            "same averaged adapter.  This is the legacy fallback, not "
            "per-request selection; drop --merged for the pool path.",
            args.n_adapters,
        )
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *adapters)
        lora = merge_adapter_means(stacked)
        batch = _make_batch(cfg, jnp.asarray(prompts), rng)
        prefill = jax.jit(
            lambda base, lora, b: forward(base, lora, b, cfg, mode="prefill", remat=False)[:2]
        )
        t0 = time.time()
        logits, caches = prefill(base, lora, batch)
        caches = extend_caches(caches, args.gen, cfg)
        log.info("prefill %d x %d tokens: %.2fs", args.batch, args.prompt_len,
                 time.time() - t0)
        decode = jax.jit(
            lambda base, lora, tok, caches, idx: decode_step(base, lora, tok, caches, idx, cfg)
        )
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        generated = [tok]
        t0 = time.time()
        for i in range(args.gen - 1):
            logits, caches = decode(base, lora, tok, caches, jnp.asarray(args.prompt_len + i))
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            generated.append(tok)
        out = jnp.concatenate(generated, axis=1)
        log.info("sample continuation (req 0): %s", np.asarray(out[0]).tolist())
        return out

    # Pool path (default): publish adapters, schedule requests by tenant id.
    n_slots = args.pool_slots or args.n_adapters
    pool = AdapterPool(adapters[0], n_slots)
    for i, tree in enumerate(adapters):
        pool.publish(f"tenant-{i}", tree)
    log.info("adapter pool: %d/%d slots resident (writer traces: %d)",
             len(pool), pool.n_slots, pool.retrace_count)

    scheduler = RequestScheduler(pool, args.batch)
    for i in range(args.batch):
        scheduler.submit(Request(
            request_id=i,
            adapter_id=f"tenant-{i % args.n_adapters}",
            tokens=prompts[i],
        ))

    prefill_fn, decode_fn = make_serving_fns(cfg)
    t0 = time.time()
    result = serve_batch(
        base, pool, scheduler, cfg, gen=args.gen, rng=rng,
        prefill_fn=prefill_fn, decode_fn=decode_fn,
    )
    requests, out = result
    dt = time.time() - t0
    log.info(
        "served %d requests across %d tenants: %d tokens/req in %.2fs "
        "(%.1f tok/s aggregate)",
        len(requests), min(args.n_adapters, args.batch), args.gen, dt,
        len(requests) * args.gen / max(dt, 1e-9),
    )
    for r, row in zip(requests[:4], np.asarray(out)):
        log.info("request %d (adapter %s): %s", r.request_id, r.adapter_id,
                 row.tolist())
    return out


if __name__ == "__main__":
    main()
