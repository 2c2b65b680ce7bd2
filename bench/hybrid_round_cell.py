"""Federated rounds of a hybrid Mamba-2 / attention model with an expert
share (granite-4.0-h-small), driven by the program's own round pipeline.

The round is the one of ``bench/round_cell.py`` (its ``Recorder``,
``program_rounds``, ``reference`` and ``readings``): set-up makes the
weights on the device from the seed, compiles the local step
(``launch.steps.make_local_step`` with remat per block and the expert
layer's counters) and the aggregation step (FedRPCA on the packed engine),
and drives ``fed.pipeline.run_rounds`` through ``warmup_rounds`` rounds and
then the window.  The configuration is the chip's share of an
expert-parallel deployment: the router spans every expert, the chip holds
``num_local_experts`` of them, and the vocabulary is its slice.

Correct: the plain float32 reference (``configs/<config>.ref.py``) follows
the first ``check_rounds`` rounds from the same seed, compared by
``loss_gap``, ``update_gap`` and ``change_gap`` as in the round cell.  The
expert layer is dropless by construction (every held expert runs over every
token), so a dropped expert shows only as a gap to the reference, where the
planted-fault test finds it.  A traced run adds the device time per program scope
(``bench/scopes.py``) to what the per-layer readers read.
"""
from __future__ import annotations

import gc
import shutil
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, round_cell, scopes
from bench.harness import Outcome, device_facts, log

from repro.config import LoRAConfig, ModelConfig
from repro.core import engine as engine_lib
from repro.launch import steps as steps_lib

MIXERS = {"mamba": "ssd", "attention": "attn"}
# The program's LoRA slots of each mixer (``models/blocks.init_block_lora``)
# under the reference's names.
MIXER_LORA = {"mamba": {"in": "q", "out": "v"}, "attention": {"q": "q", "v": "v"}}
EXPERT_LORA = {"e_in": "in", "e_out": "out"}


def kinds(cfg: dict) -> list:
    return cfg["layer_types"][: cfg["num_hidden_layers"]]


def model_config(cfg: dict) -> ModelConfig:
    """The program's configuration object for a granite hybrid file: one
    pattern group of ``num_hidden_layers`` layers."""
    if cfg["mamba_n_groups"] != 1 or cfg["mamba_n_heads"] * cfg["mamba_d_head"] != (
            cfg["mamba_expand"] * cfg["hidden_size"]):
        raise ValueError("the SSD mixer holds one group of B, C and d_inner = heads x head size")
    share = cfg["expert_share"]
    return ModelConfig(
        name=cfg["name"], arch_type="hybrid", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        layer_pattern=tuple(MIXERS[k] for k in kinds(cfg)),
        use_rope=cfg["position_embedding_type"] != "nope",
        attention_multiplier=cfg["attention_multiplier"],
        n_experts=share["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["num_local_experts"], expert_offset=share["first_held"],
        shared_d_ff=cfg["shared_intermediate_size"],
        router_aux_weight=0.0, ssm_state=cfg["mamba_d_state"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_expand=cfg["mamba_expand"], ssm_chunk=cfg["mamba_chunk_size"],
        conv_width=cfg["mamba_d_conv"], norm_kind="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        lora=LoRAConfig(rank=cfg["lora_rank"], alpha=float(cfg["lora_alpha"]), targets=("q", "v")),
        dtype=cfg["torch_dtype"],
    )


def base_to_program(w: dict, cfg: dict) -> dict:
    """The reference's flat weights as the program's tree: one pattern
    group, each slot's leaves with a leading group axis of 1."""
    one = lambda x: x[None]
    dense = lambda k: {"w": one(w[k])}
    slots = []
    for i, kind in enumerate(kinds(cfg)):
        p = lambda name: w[f"L{i}.{name}"]
        if kind == "mamba":
            mixer = {"in_proj": dense(f"L{i}.in_proj"), "out_proj": dense(f"L{i}.out_proj"),
                     "norm": {"scale": one(p("gnorm"))},
                     **{k: one(p(k)) for k in ("conv_w", "conv_b", "A_log", "dt_bias", "D")}}
        else:
            mixer = {t: dense(f"L{i}.w{t}") for t in ("q", "k", "v", "o")}
        slots.append({
            "norm1": {"scale": one(p("norm1"))}, "mixer": mixer,
            "norm2": {"scale": one(p("norm2"))},
            "moe": {"router": dense(f"L{i}.router"), "gate": one(p("e_gate")),
                    "up": one(p("e_up")), "down": one(p("e_down")),
                    "shared": {t: dense(f"L{i}.s_{t}") for t in ("gate", "up", "down")}},
        })
    return {"embed": w["embed"], "final_norm": {"scale": w["normf"]}, "groups": tuple(slots),
            "tail": ()}


def lora_to_program(lo: dict, cfg: dict) -> dict:
    slots = []
    for i, kind in enumerate(kinds(cfg)):
        ab = lambda name, f=lambda x: x: {p: f(lo[f"L{i}.{name}.{p}"]) for p in ("A", "B")}
        slots.append({"mixer": {slot: ab(t) for t, slot in MIXER_LORA[kind].items()},
                      "moe": {slot: ab(t, lambda x: x[None]) for t, slot in EXPERT_LORA.items()}})
    return {"groups": tuple(slots), "tail": ()}


def lora_view(tree: dict, cfg: dict) -> dict:
    """The program's adapters in the layout ``round_cell.lora_from_program``
    reads, under the reference's leaf names."""
    mixer = {}
    for i, (kind, slot) in enumerate(zip(kinds(cfg), tree["groups"])):
        for t, s in MIXER_LORA[kind].items():
            mixer[f"L{i}.{t}"] = slot["mixer"][s]
        for t, s in EXPERT_LORA.items():
            mixer[f"L{i}.{t}"] = {p: x[0] for p, x in slot["moe"][s].items()}
    return {"groups": ({"mixer": mixer},), "tail": ()}


def make_program_weights(ref, cfg: dict, key) -> tuple:
    """The reference's weights from ``key`` as the program's (base, adapters)."""
    made = ref.make_weights(cfg, key)
    return base_to_program(made["base"], cfg), lora_to_program(made["lora"], cfg)


class Program(round_cell.Program):
    """The round cell's program with this model: remat per block, and the
    local step's expert counters kept, one entry a round."""

    def __init__(self, cell):
        cfg, tr = cell.config, cell.traffic
        self.cell = cell
        self.mcfg = model_config(cfg)
        self.agg_cfg = round_cell.agg_config(tr)
        step = jax.jit(steps_lib.make_local_step(
            self.mcfg, local_lr=tr["local_lr"], local_steps=tr["local_steps"],
            local_optimizer="adam", remat=True))
        self.counters = []

        def local_step(*args):
            deltas, loss, mask, counts = step(*args)
            self.counters.append(counts)
            return deltas, loss, mask

        self.local_step = local_step
        self.agg_step = jax.jit(steps_lib.make_agg_step(self.agg_cfg, engine="packed"))
        self.fallback_step = jax.jit(steps_lib.make_agg_step(
            self.agg_cfg.replace(method="fedavg", carry_mode="none"), engine="packed"))
        self.apply = jax.jit(steps_lib.apply_update)
        self.finite = jax.jit(lambda t: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(t)])).astype(jnp.float32))
        ref = cell.reference()
        self.make_weights = jax.jit(lambda key: make_program_weights(ref, cfg, key))

    def start(self, seed: int):
        cfg, tr = self.cell.config, self.cell.traffic
        key = gen.seed_key(seed)
        base, lora0 = self.make_weights(key)
        tokens = gen.MarkovTokens(tr, seed)
        plan = None
        if self.agg_cfg.carry_mode != "none":
            example = jax.tree_util.tree_map(
                lambda x: jnp.zeros((tr["clients"],) + x.shape, x.dtype), lora0)
            plan = engine_lib.plan_aggregation(example, self.agg_cfg)
        cold = lambda: (engine_lib.init_agg_carry(plan)
                        if plan is not None else None)
        return self.phases(base, tokens, key, cold), round_cell.State(lora0, cold(), 0), None

    def counts(self) -> dict:
        """The local step's counters over every round run: the busiest held
        expert's load ratio, the worst."""
        host = jax.device_get(self.counters)
        return {"moe_load_max": float(max(c["moe_load_max"] for c in host))}


class Recorder(round_cell.Recorder):
    """The round cell's recorder; its adapter snapshots read this model's
    tree under the reference's leaf names."""

    def __init__(self, cell, seconds, counter, trace_dir):
        super().__init__(cell.traffic, seconds, counter, trace_dir)
        self.cfg = cell.config

    def __call__(self, r, state, diags):
        if r in (0, self.tr["check_rounds"] - 1):
            state = state._replace(lora_global=lora_view(state.lora_global, self.cfg))
        return super().__call__(r, state, diags)


def run(cell, seed: int, seconds: float, trace_dir: str | None, devs, counter, facts_at):
    """One run of the cell: set-up, window, then the reference."""
    tr = cell.traffic
    prog = Program(cell)
    rec = Recorder(cell, seconds, counter, trace_dir)
    round_cell.program_rounds(prog, seed, 10**9, rec)
    t_end = rec.landings[-1]
    n = len(rec.landings)
    out = Outcome(attempted=n, failed=rec.failed)
    out.values["setup_s"] = rec.t0 - facts_at
    gaps = np.diff([rec.t0] + rec.landings)
    out.values["round_p90_s"] = statistics.quantiles(gaps, n=10)[-1]
    counts = prog.counts()
    out.facts.update(rounds=n, window_s=t_end - rec.t0, t_local=rec.t_local, t_agg=rec.t_agg,
                     compiles_in_window=counter.in_window, **counts)
    log(f"window: {n} rounds in {t_end - rec.t0:.3f}s (mean {(t_end - rec.t0) / n:.6f}s), "
        f"compiles in window {counter.in_window}, failed {rec.failed}; longest gaps "
        f"between landings {sorted(gaps.round(4).tolist())[-3:]}")
    log(f"aggregation diagnostics, window means: {round_cell.diag_means(rec.diags)}; "
        f"expert counters over the run: {counts}")
    out.device = device_facts(devs)
    if trace_dir:
        out.trace = scopes.reduce(scopes.load_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    prog_res = {"losses": [rec.losses[r] for r in range(tr["check_rounds"])],
                "first": rec.snaps[0], "after": rec.snaps[tr["check_rounds"] - 1]}
    del prog, rec
    gc.collect()
    t = time.perf_counter()
    ref = round_cell.reference(cell, seed)
    log(f"reference: {time.perf_counter() - t:.1f}s")
    for k, v in round_cell.readings(prog_res, ref).items():
        out.checks[k] = (v, tr["limits"][k])
    return out
