"""Federated rounds, driven by the program's own round pipeline.

Set-up makes the weights on the device from the seed, compiles the
program's local step (``launch.steps.make_local_step``) and aggregation
step (``launch.steps.make_agg_step``, FedRPCA on the packed engine), and
drives ``fed.pipeline.run_rounds`` through ``warmup_rounds`` rounds.  The
window opens when the last warm-up round lands and closes at the first
landing ``--seconds`` later; the same ``run_rounds`` call carries on
across it, so the pipeline never drains in between.

Correct: a plain float32 reference (``configs/<config>.ref.py`` with
``ref_fedrpca.py``, and ``ref_rounds.py``) follows the first ``check_rounds``
rounds from the same seed.  Compared, each against its limit: the largest
relative gap of a round's mean local loss; and, by the worst adapter leaf,
the gap between the norms of the first landed update, and of the change of
the global after ``check_rounds`` landings, each over the reference's norm
of that leaf or the median leaf's, whichever is larger.
"""
from __future__ import annotations

import functools
import gc
import shutil
import statistics
import time
import types
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, ref_rounds
from bench import trace as trace_lib
from bench.harness import Outcome, device_facts, log

from repro.config import LoRAConfig, ModelConfig
from repro.core import AggregatorConfig
from repro.core import engine as engine_lib
from repro.fed.pipeline import run_rounds
from repro.launch import steps as steps_lib


class State(NamedTuple):
    lora_global: Any
    agg_carry: Any
    round_idx: int


class Bundle(NamedTuple):
    deltas: Any
    mask: Any
    round_key: Any
    loss_mean: Any


class WindowClosed(Exception):
    pass


# The aggregation's own counts, logged as window means on an earlier line.
AGG_DIAGS = ("fallback_count", "carry_hit_rate", "rpca_residual_max")


def diag_means(diags: list) -> dict:
    host = jax.device_get(diags)
    return {k: statistics.fmean(float(d[k]) for d in host if k in d)
            for k in AGG_DIAGS if any(k in d for d in host)}


def model_config(cfg: dict) -> ModelConfig:
    """The program's configuration object for a dense decoder file."""
    return ModelConfig(
        name=cfg["name"], arch_type="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg.get("head_dim", 0),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        layer_pattern=("attn",), qkv_bias=cfg.get("use_qkv_bias", False),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        rope_pct=cfg.get("partial_rotary_factor", 1.0), ffn_kind="swiglu",
        norm_kind=cfg.get("norm", "rmsnorm"), norm_eps=cfg.get("layer_norm_eps", 1e-6),
        tie_embeddings=cfg.get("tie_word_embeddings", True),
        lora=LoRAConfig(rank=cfg["lora_rank"], alpha=float(cfg["lora_alpha"]),
                        targets=tuple(cfg["lora_targets"])),
        dtype=cfg.get("torch_dtype", "bfloat16"),
    )


def lora_to_program(lo: dict, targets) -> dict:
    mixer = {t: {"A": lo[f"{t}.A"], "B": lo[f"{t}.B"]} for t in targets}
    return {"groups": ({"mixer": mixer},), "tail": ()}


def lora_from_program(tree: dict) -> dict:
    mixer = tree["groups"][0]["mixer"]
    return {f"{t}.{p}": mixer[t][p] for t in mixer for p in ("A", "B")}


def base_to_program(w: dict) -> dict:
    dense = lambda k: {"w": w[k], **({"b": w[f"{k}.bias"]} if f"{k}.bias" in w else {})}
    norm = lambda k: {"scale": w[f"{k}.scale"], "bias": w[f"{k}.bias"]}
    block = {
        "norm1": norm("ln1"),
        "mixer": {"q": dense("wq"), "k": dense("wk"), "v": dense("wv"), "o": dense("wo")},
        "norm2": norm("ln2"),
        "ffn": {"gate": dense("w_gate"), "up": dense("w_up"), "down": dense("w_down")},
    }
    base = {"embed": w["embed"], "final_norm": norm("lnf"), "groups": (block,), "tail": ()}
    if "lm_head" in w:
        base["lm_head"] = w["lm_head"]
    return base


def agg_config(tr: dict) -> AggregatorConfig:
    a = tr["aggregator"]
    return AggregatorConfig(method="fedrpca", rpca_iters=a["rpca_iters"],
                            svt_mode=a["svt_mode"], carry_mode=a["carry_mode"],
                            rpca_fused_tail=a["fused_tail"])


class Program:
    """The compiled steps of one cell, shared by every seed of a process."""

    def __init__(self, cell):
        cfg, tr = cell.config, cell.traffic
        self.cell = cell
        self.mcfg = model_config(cfg)
        self.agg_cfg = agg_config(tr)
        self.local_step = jax.jit(steps_lib.make_local_step(
            self.mcfg, local_lr=tr["local_lr"], local_steps=tr["local_steps"],
            local_optimizer="adam", remat=False))
        self.agg_step = jax.jit(steps_lib.make_agg_step(self.agg_cfg, engine="packed"))
        self.fallback_step = jax.jit(steps_lib.make_agg_step(
            self.agg_cfg.replace(method="fedavg", carry_mode="none"), engine="packed"))
        self.apply = jax.jit(steps_lib.apply_update)
        self.finite = jax.jit(lambda t: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(t)])).astype(jnp.float32))
        self.make_weights = jax.jit(functools.partial(cell.reference().make_weights, cfg))

    def phases(self, base, tokens: gen.MarkovTokens, key, cold_carry):
        """The duck-typed phase surface ``run_rounds`` drives."""

        def local(state: State, n_active=None):
            r = state.round_idx
            with jax.profiler.TraceAnnotation("bench.batch"):
                tok = tokens.round(r)
                batch = {"tokens": jnp.asarray(tok[:, :, :-1]),
                         "labels": jnp.asarray(tok[:, :, 1:])}
            round_key = jax.random.fold_in(key, 1000 + r)
            with jax.profiler.TraceAnnotation("bench.local_dispatch"):
                deltas, loss, mask = self.local_step(base, state.lora_global, batch, round_key)
            return state._replace(round_idx=r + 1), Bundle(deltas, mask, round_key, loss)

        def agg(carry, bundle: Bundle, scale):
            with jax.profiler.TraceAnnotation("bench.agg_dispatch"):
                upd, metrics, new_carry = self.agg_step(
                    bundle.deltas, bundle.mask, bundle.round_key, carry, scale)
                return upd, new_carry, {**metrics, "update_finite": self.finite(upd)}

        def fallback(bundle: Bundle, scale):
            upd, _ = self.fallback_step(bundle.deltas, bundle.mask, bundle.round_key,
                                        scale=scale)
            return upd, cold_carry(), {"update_finite": self.finite(upd), "degraded": 1.0}

        return types.SimpleNamespace(local=local, agg=agg, prep_state=lambda s: s,
                                     apply=self.apply, fallback=fallback,
                                     cold_carry=cold_carry)

    def start(self, seed: int):
        """Weights, tokens, phases and initial state for one seed."""
        cfg, tr = self.cell.config, self.cell.traffic
        key = gen.seed_key(seed)
        made = self.make_weights(key)
        base = base_to_program(made["base"])
        lora0 = lora_to_program(made["lora"], cfg["lora_targets"])
        tokens = gen.MarkovTokens(tr, seed)
        plan = None
        if self.agg_cfg.carry_mode != "none":
            example = jax.tree_util.tree_map(
                lambda x: jnp.zeros((tr["clients"],) + x.shape, x.dtype), lora0)
            plan = engine_lib.plan_aggregation(example, self.agg_cfg)
        cold = lambda: engine_lib.init_agg_carry(plan) if plan is not None else None
        phases = self.phases(base, tokens, key, cold)
        return phases, State(lora0, cold(), 0), made["lora"]


class Recorder:
    """``on_round``: keeps what the checks read and times the window."""

    def __init__(self, tr: dict, seconds: float, counter, trace_dir: str | None):
        self.tr = tr
        self.seconds = seconds
        self.counter = counter
        self.trace_dir = trace_dir
        self.losses = {}
        self.snaps = {}
        self.landings = []
        self.t_local, self.t_agg = [], []
        self.diags = []  # device scalars, read after the window
        self.failed = 0
        self.t0 = None
        self.window_span = None

    def __call__(self, r, state, diags):
        now = time.perf_counter()
        tr = self.tr
        if r < tr["check_rounds"]:
            self.losses[r] = float(diags["mean_local_loss"])
            if r in (0, tr["check_rounds"] - 1):
                self.snaps[r] = jax.device_get(lora_from_program(state.lora_global))
        if r == tr["warmup_rounds"] - 1:
            self.open(now)
            return
        if self.t0 is None:
            return
        self.landings.append(now)
        self.t_local.append(float(diags["t_local_s"]))
        self.t_agg.append(float(diags["t_agg_s"]))
        self.diags.append({k: diags[k] for k in AGG_DIAGS if k in diags})
        self.failed += int(bool(diags.get("degraded") or diags.get("supervisor_retry")))
        if self.trace_dir:
            if len(self.landings) >= tr["trace_rounds"]:
                raise WindowClosed
        elif now - self.t0 >= self.seconds:
            raise WindowClosed

    def open(self, now):
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)
            self.window_span = jax.profiler.TraceAnnotation("bench.window")
            self.window_span.__enter__()
        self.counter.open = True
        self.t0 = time.perf_counter() if self.trace_dir else now

    def close(self):
        self.counter.open = False
        if self.window_span is not None:
            self.window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()


def program_rounds(prog: Program, seed: int, rounds: int, rec: Recorder) -> None:
    """Drive the program for at most ``rounds`` rounds; the recorder may
    close the window earlier."""
    phases, state, _ = prog.start(seed)
    try:
        run_rounds(phases, state, rounds, staleness=prog.cell.traffic["staleness"],
                   on_round=rec)
    except WindowClosed:
        pass
    finally:
        rec.close()


def reference(cell, seed: int, variant: str = "reference") -> dict:
    """The reference's first ``check_rounds`` rounds.  ``variant``:
    "reference" (float32, highest); "control" (model matmuls in float8,
    RPCA matmuls as three bfloat16 passes); "half_batch" (the reference with
    each client's loss taken over half its sequences)."""
    cfg, tr = cell.config, cell.traffic
    ref = cell.reference()
    made = jax.jit(functools.partial(ref.make_weights, cfg))(gen.seed_key(seed))
    tokens = gen.MarkovTokens(tr, seed)
    dot = ref.fp8 if variant == "control" else ref.highest
    precision = "bf16x3" if variant == "control" else "highest"
    carry = tr["aggregator"]["carry_mode"] != "none"

    def local(r, lora):
        tok = tokens.round(r)
        if variant == "half_batch":
            tok = tok[:, : tok.shape[1] // 2]
        return ref.local_phase(cfg, tr, made["base"], lora, tok, dot)

    def aggregate(deltas, warm):
        return ref.aggregate(deltas, iters=tr["aggregator"]["rpca_iters"],
                             precision=precision, warm=warm if carry else None)

    out = ref_rounds.follow(local, aggregate, made["lora"], tr["check_rounds"],
                            tr["staleness"])
    host = jax.device_get
    return {"losses": out["losses"], "lora0": host(made["lora"]),
            "first": host(out["landed"][0]), "after": host(out["landed"][-1])}


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers of a program (or stand-in) run against the
    reference.  ``prog`` has ``losses``, ``first`` and ``after``."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))

    def worst_leaf(p_tree, r_tree, skip=()):
        lo = ref["lora0"]
        pn = {k: float(np.linalg.norm(np.asarray(p_tree[k], np.float64) - lo[k])) for k in lo}
        rn = {k: float(np.linalg.norm(np.asarray(r_tree[k], np.float64) - lo[k])) for k in lo}
        med = statistics.median(rn.values())
        return max(abs(pn[k] - rn[k]) / max(rn[k], med) for k in lo if k not in skip)

    first_ref = {k: float(np.linalg.norm(np.asarray(ref["first"][k], np.float64)
                                         - ref["lora0"][k])) for k in ref["lora0"]}
    med = statistics.median(first_ref.values())
    still = [k for k, v in first_ref.items() if v < 1e-3 * med]
    return {"loss_gap": loss_gap,
            "update_gap": worst_leaf(prog["first"], ref["first"]),
            "change_gap": worst_leaf(prog["after"], ref["after"], skip=still)}


def run(cell, seed: int, seconds: float, trace_dir: str | None, devs, counter, facts_at):
    """One run of a round cell: set-up, window, then the reference."""
    tr = cell.traffic
    prog = Program(cell)
    rec = Recorder(tr, seconds, counter, trace_dir)
    program_rounds(prog, seed, 10**9, rec)
    t_end = rec.landings[-1]
    n = len(rec.landings)
    out = Outcome(attempted=n, failed=rec.failed)
    out.values["setup_s"] = rec.t0 - facts_at
    gaps = np.diff([rec.t0] + rec.landings)
    out.values["round_p90_s"] = statistics.quantiles(gaps, n=10)[-1]
    out.facts.update(rounds=n, window_s=t_end - rec.t0, t_local=rec.t_local,
                     t_agg=rec.t_agg, compiles_in_window=counter.in_window)
    log(f"window: {n} rounds in {t_end - rec.t0:.3f}s (mean {(t_end - rec.t0) / n:.6f}s), "
        f"compiles in window "
        f"{counter.in_window}, failed {rec.failed}; longest gaps between landings "
        f"{sorted(gaps.round(4).tolist())[-3:]}")
    log(f"aggregation diagnostics, window means: {diag_means(rec.diags)}")
    out.device = device_facts(devs)
    if trace_dir:
        out.trace = trace_lib.reduce(trace_lib.load_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    prog_res = {"losses": [rec.losses[r] for r in range(tr["check_rounds"])],
                "first": rec.snaps[0], "after": rec.snaps[tr["check_rounds"] - 1]}
    del prog, rec
    gc.collect()
    t = time.perf_counter()
    ref = reference(cell, seed)
    log(f"reference: {time.perf_counter() - t:.1f}s")
    for k, v in readings(prog_res, ref).items():
        out.checks[k] = (v, tr["limits"][k])
    return out
