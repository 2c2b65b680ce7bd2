#!/usr/bin/env python3
"""On-chip smoke test: the federated FedRPCA round on a TPU, in one process.

One chip (no arguments):
  1. device check — the first JAX device must be a TPU, else exit 1;
  2. SSD kernels — the Pallas chunk kernels (``models/ssd.py::ssd_pallas``)
     against ``ssd_chunked`` at granite-4.0-h-small's mixer widths under
     ``vmap`` over 8 clients: the output, the final state and the
     gradients in x, dt, A_log, B and C, each as its largest error relative
     to ``ssd_chunked`` at ``"highest"``.  At ``"highest"`` the kernels must
     be within ``SSD_TOL`` of it; at the default precision no further from
     it than ``SSD_SLACK`` times ``ssd_chunked``'s own default-precision
     error;
  3. training — ``repro.launch.train.main`` in-process on stablelm-1.6b at
     full published width: 8 clients, 4 rounds of 2 local Adam steps,
     packed FedRPCA with subspace SVT, the cross-round subspace carry and
     the staleness-1 pipeline;
  4. aggregation parity — the fused Pallas tail, the XLA tail and the
     per-leaf reference engine, under both SVT modes, on the last round's
     real client deltas (aggregated update and the RPCA decomposition (L, S)
     of the packed bucket); fused vs XLA (L, S) also on planted low-rank +
     sparse deltas of the same tree.

``--four-chips``: training with the aggregation sharded over four chips
(``--mesh-shards 4 --rpca-fused-tail``, 2 rounds), then, through
``robust_pca_bucket(mesh=...)`` with the bucket's vec rows split four ways,
the sharded fused subspace aggregation (real deltas) and decomposition
(both inputs), and the sharded gram ones with the XLA tail, against the
single-device ones on chip 0.

Real deltas have live rank 8 = the cohort, so every ADMM iteration takes the
exact-eigh SVT.  The planted deltas (rank 2 + 1% spikes) let the subspace
SVT take its Ritz path after some exact iterations; the smoke requires that
it did (exact-eigh count below the iteration count), so the fused kernel
``subspace_apply`` runs with a Ritz projector too, on one chip and on each
of four.

Fails (exit 1, no JSON line) on a non-finite loss, a retried or degraded
round, a fused kernel that did not compile to a TPU custom call, a Ritz path
not taken on the planted deltas, an output that moves by more than
``NOISE_CAP`` of its scale on a 1-ulp perturbation of its input, or a
mismatch beyond the fixed tolerances below.  On success the last stdout line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# --seq 96, not 128: compiled for a v5e, the local step at seq 128 needs
# 16.4 GiB of arguments + temporaries (logits-bound), more than the chip's
# 16 GiB, and on a v5e train.main could not load it (12.82 GiB of
# temporaries asked, 12.47 GiB free).  Every width is the published one.
TRAIN_ARGS = [
    "--arch", "stablelm-1.6b", "--clients", "8", "--per-client-batch", "4",
    "--seq", "96", "--local-steps", "2", "--aggregator", "fedrpca",
    "--engine", "packed", "--svt-mode", "subspace", "--carry-mode", "subspace",
    "--pipeline", "--staleness", "1",
]
RPCA_ITERS = 30

# Fixed tolerances per input; an input a table leaves out is not compared.
# Both inputs are at unit RMS (FedRPCA is scale-equivariant); differences of
# aggregated updates are divided by the reference update's RMS, those of the
# decomposition (L, S) are in units of the input's RMS, which is 1.
#
# Real deltas: the CPU parity tests' tolerances, whose inputs are O(1).
# Adaptive beta ~ 1/E (~50 here) amplifies S's rounding ~50x in the update,
# so the fused and XLA tails are also compared on (L, S), as the tests do.
#
# Planted deltas: on a TPU v5e one program's own (L, S) moves by up to
# 3.6e-4 under a 1-ulp change of this input, exact-eigh gram mode included,
# so (L, S) is held to 1e-3, the subspace SVT's fallback tolerance, at which
# test_svt_subspace holds subspace programs that gate differently.  Their
# updates are not compared: beta ~ 1/E amplifies that further.
TOL_AGG = {"real": dict(atol=5e-4, rtol=1e-4)}  # test_svt_subspace SVT_TOL
PLANTED_DECOMP = dict(atol=1e-3, rtol=0.0)
TOL_DECOMP = {
    "real": dict(atol=2e-5, rtol=0.0),  # test_svt_subspace fused tail
    "planted": PLANTED_DECOMP,
}
TOL_SHARDED_AGG = {"real": dict(atol=5e-4, rtol=5e-4)}  # test_mesh_agg sessions
TOL_SHARDED_DECOMP = {
    "real": dict(atol=2e-4, rtol=2e-4),  # test_mesh_agg fused, (L, S)
    "planted": PLANTED_DECOMP,
}
# Largest change of an output, in its scale, under a 1-ulp perturbation of
# the deltas.  On a TPU v5e, full-precision matmuls measured 3.6e-4 at most
# (planted (L, S)); the TPU's one-pass bf16 default measured 0.11-1.58,
# which this catches before any parity check.
NOISE_CAP = 1e-3
# Planted deltas: rank-2 client structure plus 1% spikes of 2x its scale.
PLANTED_RANK, PLANTED_DENSITY, PLANTED_SPIKE = 2, 1e-2, 2.0
# The SSD check: granite-4.0-h-small's mixer (128 heads of 64, state 128,
# 256-token chunks) on 8 clients' 1,024 tokens.  Both paths at "highest" sum
# the same float32 products in other orders; at the default precision both
# round their matmul operands to bf16, in other places.
SSD_SHAPE = dict(clients=8, seq=1024, heads=128, head_dim=64, state=128)
SSD_CHUNK, SSD_TOL, SSD_SLACK = 256, 1e-4, 2.0


class Smoke:
    def __init__(self):
        self.failures = []
        self.compile_s = 0.0

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            print(f"FAIL: {what}", flush=True)

    def on_duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def run_training(smoke: Smoke, extra: list, rounds: int) -> dict | None:
    from repro.launch import train

    argv = TRAIN_ARGS + ["--rounds", str(rounds)] + extra
    print("train argv:", " ".join(argv), flush=True)
    t0 = time.perf_counter()
    try:
        summary = train.main(argv)
    except SystemExit as e:
        smoke.check(False, f"train.main exited with code {e.code}")
        return None
    print(f"train wall {time.perf_counter() - t0:.3f}s", flush=True)
    losses = [summary["initial_eval_loss"], summary["final_eval_loss"]]
    print(f"eval loss initial={losses[0]!r} final={losses[1]!r}", flush=True)
    smoke.check(len(summary["rounds"]) == rounds,
                f"{len(summary['rounds'])} rounds landed, expected {rounds}")
    for d in summary["rounds"]:
        keys = ("mean_local_loss", "t_local_s", "t_agg_s", "t_overlap_s",
                "fallback_count", "carry_hit_rate", "degraded",
                "supervisor_retry")
        print("round", json.dumps({"round": d["round"],
                                   **{k: d[k] for k in keys if k in d}}),
              flush=True)
        losses.append(d["mean_local_loss"])
        smoke.check(not d.get("degraded") and not d.get("supervisor_retry"),
                    f"round {d['round']} was retried or degraded")
    smoke.check(all(math.isfinite(v) for v in losses), f"non-finite loss in {losses}")
    return summary


def host64(tree) -> list:
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def rms(leaves: list) -> float:
    return math.sqrt(sum(float(np.sum(y * y)) for y in leaves) / sum(y.size for y in leaves))


def max_diff(a: list, b: list) -> float:
    return max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))


def unit_rms(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    inv = 1.0 / jnp.sqrt(sq / sum(x.size for x in leaves))
    return jax.tree_util.tree_map(lambda x: (x * inv).astype(jnp.float32), tree)


def ulp_noise(tree):
    """``tree`` with every entry moved by about one float32 ulp."""
    key = jax.random.PRNGKey(7)
    return jax.tree_util.tree_map(
        lambda x: x * (1.0 + 2.0**-23 * jax.random.normal(key, x.shape)), tree)


@jax.jit
def planted(tree):
    """Deltas shaped like ``tree`` whose client columns are rank
    ``PLANTED_RANK`` plus sparse spikes: RPCA's own model, made on the device
    from a fixed seed with no matmul (sums of outer products)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for i, x in enumerate(leaves):
        ku, kw, ks, kv = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(11), i), 4)
        clients, vec = x.shape[0], math.prod(x.shape[1:])
        u = jax.random.normal(ku, (clients, PLANTED_RANK))
        w = jax.random.normal(kw, (PLANTED_RANK, vec))
        low = sum(u[:, k, None] * w[None, k] for k in range(PLANTED_RANK))
        spikes = jax.random.bernoulli(ks, PLANTED_DENSITY, (clients, vec)) * (
            PLANTED_SPIKE * jax.random.normal(kv, (clients, vec)))
        out.append((low + spikes).reshape(x.shape))
    return unit_rms(jax.tree_util.tree_unflatten(treedef, out))


class Runs:
    """Compile each program once and run it on every input and on the
    input's 1-ulp perturbation.  A program returns ``(output, falls)``;
    ``falls`` (exact-eigh iteration counts, or ``()``) is kept per input.
    Outputs are kept on the host with the scale their differences are
    measured in: the output's RMS, or 1 (the input's RMS) for ``unit``."""

    def __init__(self, smoke: Smoke, inputs: dict):
        self.smoke = smoke
        self.inputs = {k: (x, ulp_noise(x)) for k, x in inputs.items()}
        self.out, self.scale, self.falls, self.sharding = {}, {}, {}, {}

    def __call__(self, name: str, fn, *, kernel: bool = False, unit: bool = False,
                 only: str | None = None) -> str:
        """Run ``fn`` on every input, or on input ``only``; returns its
        compiled HLO text."""
        inputs = {only: self.inputs[only]} if only else self.inputs
        compiled = jax.jit(fn).lower(next(iter(inputs.values()))[0]).compile()
        text = compiled.as_text()
        if kernel:
            self.smoke.check("tpu_custom_call" in text,
                             f"{name}: the fused tail did not compile to a TPU kernel")
        for inp, (x, x_noisy) in inputs.items():
            out, falls = jax.block_until_ready(compiled(x))
            self.sharding[name] = jax.tree_util.tree_leaves(out)[0].sharding
            clean = host64(out)
            noisy = host64(compiled(x_noisy)[0])
            key = (name, inp)
            self.out[key] = clean
            self.scale[key] = 1.0 if unit else rms(clean)
            self.falls[key] = [int(f) for f in falls]
            noise = max_diff(noisy, clean) / self.scale[key]
            print(f"run {name} on {inp}: noise={noise!r} falls={self.falls[key]}",
                  flush=True)
            self.smoke.check(noise <= NOISE_CAP,
                             f"{name} on {inp}: a 1-ulp input change moves the "
                             f"output by {noise!r} of its scale (cap {NOISE_CAP})")
        return text

    def compare(self, a: str, b: str, tols: dict) -> None:
        """allclose(out[a], out[b], **tols[input]) on every input ``tols``
        names, both divided by ``b``'s scale."""
        for inp, tol in tols.items():
            scale, diff, ok = self.scale[(b, inp)], 0.0, True
            for x, y in zip(self.out[(a, inp)], self.out[(b, inp)]):
                d = np.abs(x - y) / scale
                diff = max(diff, float(np.max(d)))
                ok &= bool(np.all(d <= tol["atol"] + tol["rtol"] * np.abs(y) / scale))
            print(f"parity {a} vs {b} on {inp}: max_diff={diff!r} scale={scale!r} "
                  f"tol={tol}", flush=True)
            self.smoke.check(ok, f"parity {a} vs {b} on {inp}: {diff!r}, beyond {tol}")

    def took_ritz_path(self, name: str) -> None:
        """The planted input's RPCA left the exact eigh in some iteration."""
        falls = self.falls[(name, "planted")]
        self.smoke.check(all(f < RPCA_ITERS for f in falls),
                         f"{name}: exact eigh in {falls} of {RPCA_ITERS} iterations "
                         "on the planted deltas; the Ritz path never ran")


def aggregation(cfg, **kw):
    from repro.core import aggregate

    return lambda t: (aggregate(t, cfg, **kw), ())


def bucket_decomposition(svt_mode: str, fused: bool, mesh=None):
    """deltas -> ([(L, S)] of their packed buckets, [exact-eigh iterations])."""
    from repro.core import engine, rpca

    kw = dict(mesh=mesh, n_iter=RPCA_ITERS, svt_mode=svt_mode, fused_tail=fused,
              return_carry=True)

    def fn(deltas):
        results = [rpca.robust_pca_bucket(b.data, b.true_dims, **kw)
                   for b in engine.pack(deltas)[0].values()]
        return ([(r.low_rank, r.sparse) for r, _ in results],
                [carry.fall_count for _, carry in results])

    return fn


def aggregation_parity(smoke: Smoke, inputs: dict) -> None:
    from repro.core import AggregatorConfig

    runs = Runs(smoke, inputs)
    for mode in ("subspace", "gram"):
        cfg = AggregatorConfig(method="fedrpca", rpca_iters=RPCA_ITERS, svt_mode=mode)
        fused_cfg = cfg.replace(rpca_fused_tail=True)
        runs(f"{mode}/fused", aggregation(fused_cfg, engine="packed"), kernel=True,
             only="real")
        runs(f"{mode}/xla", aggregation(cfg, engine="packed"), only="real")
        runs(f"{mode}/reference", aggregation(cfg, engine="reference"), only="real")
        runs(f"{mode}/fused-LS", bucket_decomposition(mode, True), kernel=True,
             unit=True)
        runs(f"{mode}/xla-LS", bucket_decomposition(mode, False), unit=True)
        runs.compare(f"{mode}/fused", f"{mode}/xla", TOL_AGG)
        runs.compare(f"{mode}/xla", f"{mode}/reference", TOL_AGG)
        runs.compare(f"{mode}/fused", f"{mode}/reference", TOL_AGG)
        runs.compare(f"{mode}/fused-LS", f"{mode}/xla-LS", TOL_DECOMP)
    runs.took_ritz_path("subspace/fused-LS")
    runs.took_ritz_path("subspace/xla-LS")


def sharded_parity(smoke: Smoke, inputs: dict) -> None:
    from repro.core import AggregatorConfig
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(4)
    chips = {d.id for d in mesh.devices.flat if d.platform == "tpu"}
    print(f"mesh {dict(mesh.shape)} over tpu device ids {sorted(chips)}", flush=True)
    smoke.check(len(chips) == 4, f"mesh covers {len(chips)} distinct TPU chips, not 4")
    cfg = AggregatorConfig(method="fedrpca", rpca_iters=RPCA_ITERS,
                           svt_mode="subspace", rpca_fused_tail=True)
    runs = Runs(smoke, inputs)
    runs("one-chip", aggregation(cfg, engine="packed"), kernel=True, only="real")
    hlo = runs("sharded", aggregation(cfg, engine="packed", mesh=mesh), kernel=True,
               only="real")
    smoke.check("all-gather" in hlo, "the sharded aggregation has no all-gather")
    runs("one-chip-LS", bucket_decomposition("subspace", True), kernel=True, unit=True)
    runs("sharded-LS", bucket_decomposition("subspace", True, mesh), kernel=True,
         unit=True)
    sharding = runs.sharding["sharded-LS"]
    placed = sorted(d.id for d in sharding.device_set)
    print(f"sharded L: sharding {sharding.spec} over device ids {placed}", flush=True)
    smoke.check(len(placed) == 4, f"sharded L lives on {len(placed)} chips, not 4")
    runs.compare("sharded", "one-chip", TOL_SHARDED_AGG)
    runs.compare("sharded-LS", "one-chip-LS", TOL_SHARDED_DECOMP)
    runs.took_ritz_path("one-chip-LS")
    runs.took_ritz_path("sharded-LS")
    # Both modes shard the vec rows and exchange (B, C, C) Grams.
    gram = cfg.replace(svt_mode="gram", rpca_fused_tail=False)
    runs("gram/one-chip", aggregation(gram, engine="packed"), only="real")
    hlo = runs("gram/sharded", aggregation(gram, engine="packed", mesh=mesh), only="real")
    smoke.check("all-gather" in hlo, "the sharded gram aggregation has no all-gather")
    runs("gram/one-chip-LS", bucket_decomposition("gram", False), unit=True)
    runs("gram/sharded-LS", bucket_decomposition("gram", False, mesh), unit=True)
    runs.compare("gram/sharded", "gram/one-chip", TOL_SHARDED_AGG)
    runs.compare("gram/sharded-LS", "gram/one-chip-LS", TOL_SHARDED_DECOMP)


def ssd_inputs(clients, seq, heads, head_dim, state):
    """Granite-like mixer inputs: dt from softplus over granite's dt_bias
    range, A_log over log(1..16), unit-scale x, B and C, a loss weight on
    each output."""
    ks = jax.random.split(jax.random.PRNGKey(5), 7)
    bias = jnp.log(jnp.expm1(jnp.linspace(1e-3, 1e-1, heads)))
    x = jax.random.normal(ks[0], (clients, 1, seq, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (clients, 1, seq, heads)) + bias)
    a_log = jnp.log(jnp.linspace(1.0, 16.0, heads))
    bm = jax.random.normal(ks[2], (clients, 1, seq, state))
    cm = jax.random.normal(ks[3], (clients, 1, seq, state))
    d = jax.random.normal(ks[4], (heads,))
    wy = jax.random.normal(ks[5], x.shape)
    wh = jax.random.normal(ks[6], (clients, 1, heads, head_dim, state))
    return (x, dt, a_log, bm, cm, d), (wy, wh)


def ssd_parity(smoke: Smoke) -> None:
    from repro.models import ssd

    args, (wy, wh) = ssd_inputs(**SSD_SHAPE)
    chunk = SSD_CHUNK

    def outputs(core, precision):
        def per_client(x, dt, bm, cm, a_log, d):
            return core(x, dt, a_log, bm, cm, d, chunk)

        def run(x, dt, a_log, bm, cm, d):
            return jax.vmap(per_client, in_axes=(0, 0, 0, 0, None, None))(x, dt, bm, cm, a_log, d)

        def loss(*a):
            y, h = jax.checkpoint(run)(*a)
            return jnp.sum(y * wy) + jnp.sum(h * wh)

        with jax.default_matmul_precision(precision):
            fwd = jax.jit(run).lower(*args).compile()
            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
        out = dict(zip(("y", "state", "dx", "ddt", "dA_log", "dB", "dC"),
                       (*fwd(*args), *grad(*args))))
        return out, "tpu_custom_call" in fwd.as_text() and "tpu_custom_call" in grad.as_text()

    ref, _ = outputs(ssd.ssd_chunked, "highest")
    runs = {}
    for name, core, precision in (("kernels/highest", ssd.ssd_pallas, "highest"),
                                  ("kernels/default", ssd.ssd_pallas, None),
                                  ("xla/default", ssd.ssd_chunked, None)):
        runs[name], kernel = outputs(core, precision)
        smoke.check(kernel == name.startswith("kernels"),
                    f"ssd {name}: TPU kernels compiled in: {kernel}")
    for name, want in ref.items():
        scale = float(jnp.max(jnp.abs(want)))
        rel = {k: float(jnp.max(jnp.abs(r[name] - want))) / scale for k, r in runs.items()}
        print(f"ssd {name}: scale={scale!r} rel_err={json.dumps(rel)}", flush=True)
        smoke.check(rel["kernels/highest"] <= SSD_TOL,
                    f"ssd {name}: kernels at highest {rel['kernels/highest']!r} > {SSD_TOL}")
        smoke.check(rel["kernels/default"] <= SSD_SLACK * rel["xla/default"],
                    f"ssd {name}: kernels at default precision {rel['kernels/default']!r}, "
                    f"more than {SSD_SLACK} x ssd_chunked's {rel['xla/default']!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the aggregation sharded over four chips "
                         "and its single-chip comparison")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform}); refusing "
              "to run on another backend", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devs)}", file=sys.stderr)
        return 1
    print(f"jax {jax.__version__} device_kind={dev.device_kind} count={len(devs)}",
          flush=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.kernels import backend
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not importable: {e}", file=sys.stderr)
        return 1
    print("compile cache:", enable_compile_cache(), flush=True)
    smoke = Smoke()
    jax.monitoring.register_event_duration_secs_listener(smoke.on_duration)
    smoke.check(not backend.interpret_default(), "Pallas kernels would run interpreted")

    if args.four_chips:
        summary = run_training(smoke, ["--mesh-shards", "4", "--rpca-fused-tail"], 2)
    else:
        ssd_parity(smoke)
        summary = run_training(smoke, [], 4)
    print(f"after training: peak_bytes_in_use={peak_bytes(dev)} "
          f"compile_s={smoke.compile_s:.3f}", flush=True)
    if summary is not None:
        real = unit_rms(summary.pop("last_deltas"))
        inputs = {"real": real, "planted": planted(real)}
        if args.four_chips:
            sharded_parity(smoke, inputs)
        else:
            aggregation_parity(smoke, inputs)
    print(f"end: peak_bytes_in_use={peak_bytes(dev)} compile_s={smoke.compile_s:.3f}",
          flush=True)
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
