"""granite-4.0-h-small against its plain reference, on the CPU at a small
size: one whole 10-layer period (mamba x5, attention, mamba x4) at d 256,
8 experts top-3, vocab 512, in float32, on seeded random weights.

The reference (``bench/configs/granite-4.0-h-small.ref.py``) computes the
SSM in its quadratic form over the whole sequence and the program chunk by
chunk, and the two sum in different orders; in float32 that leaves gaps of
a few 1e-6 of the logits' scale, so the tolerances below are 1e-4 of each
compared quantity's largest entry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import hybrid_round_cell as hc
from repro.core import AggregatorConfig, aggregate
from repro.core.engine import pack, unpack
from repro.core.stacking import infer_layer_axes
from repro.models import forward, loss_fn, moe

SEQ = 64  # 4 SSD chunks of 16


def config(held=2, first=2):
    cfg = harness.load_json(f"{harness.BENCH}/configs/granite-4.0-h-small.json")
    cfg.update(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=16, mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=16,
               intermediate_size=64, shared_intermediate_size=128, num_local_experts=held,
               expert_share={"router_experts": 8, "first_held": first},
               num_experts_per_tok=3, vocab_size=512, torch_dtype="float32")
    return cfg


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(f"{harness.BENCH}/configs/granite-4.0-h-small.ref.py",
                               "ref_granite_test")


def weights(ref, cfg, seed=0):
    """Reference weights with every adapter nonzero, so each one acts."""
    made = ref.make_weights(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), len(made["lora"])))
    lora = {k: v + 0.05 * jax.random.normal(next(keys), v.shape) if k.endswith(".B") else v
            for k, v in made["lora"].items()}
    return made["base"], lora


def tokens(cfg, seed=0):
    return jax.random.randint(jax.random.PRNGKey(100 + seed), (SEQ + 1,), 0, cfg["vocab_size"])


def close(got, want, rel=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * np.max(np.abs(want)))


def flat(tree, cfg):
    mixer = hc.lora_view(tree, cfg)["groups"][0]["mixer"]
    return {f"{k}.{p}": v[p] for k, v in mixer.items() for p in v}


def test_logits_loss_and_lora_grads_match_the_reference(ref):
    cfg = config()
    base, lora = weights(ref, cfg)
    mcfg = hc.model_config(cfg)
    pbase, plora = hc.base_to_program(base, cfg), hc.lora_to_program(lora, cfg)
    tok = tokens(cfg)
    batch = {"tokens": tok[None, :-1], "labels": tok[None, 1:]}
    with jax.default_matmul_precision("highest"):
        logits, _, stats = jax.jit(lambda l: forward(pbase, l, batch, mcfg, remat=True))(plora)
        close(logits[0], jax.jit(lambda l: ref.logits_of(cfg, base, l, tok[:-1]))(lora))
        (loss, parts), grads = jax.jit(jax.value_and_grad(
            lambda l: loss_fn(pbase, l, batch, mcfg), has_aux=True))(plora)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda l: ref.loss(cfg, base, l, tok[None, :-1], tok[None, 1:])))(lora)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert set(stats) == {"aux", "moe_load_max"} and float(parts["moe_load_max"]) >= 1.0
    got_g = flat(grads, cfg)
    assert set(got_g) == set(want_g)
    for k in want_g:
        close(got_g[k], want_g[k])


def _layer_weights(ref, cfg, seed=0):
    """One layer's MoE weights and expert adapters, reference names."""
    base, lora = weights(ref, cfg, seed)
    pick = lambda t: {k[3:]: v for k, v in t.items() if k.startswith("L0.")}
    return pick(base), pick(lora)


def _program_share(p, lo, first, held, shared):
    """The program's parameters of a share: the reference's held experts
    [first, first + held), counted within the arrays given."""
    cut = lambda x: x[first: first + held]
    params = {"router": {"w": p["router"]}, "gate": cut(p["e_gate"]), "up": cut(p["e_up"]),
              "down": cut(p["e_down"])}
    if shared:
        params["shared"] = {t: {"w": p[f"s_{t}"]} for t in ("gate", "up", "down")}
    adapters = {s: {ab: cut(lo[f"{t}.{ab}"]) for ab in ("A", "B")}
                for t, s in hc.EXPERT_LORA.items()}
    return params, adapters


def test_shares_sum_to_the_uncut_layer(ref):
    """Four shares of 2 experts each, the shared expert counted once, add up
    to the reference's layer holding all 8."""
    whole = config(held=8, first=0)
    p, lo = _layer_weights(ref, whole)
    h = jax.random.normal(jax.random.PRNGKey(7), (SEQ, whole["hidden_size"]))
    scale = whole["lora_alpha"] / whole["lora_rank"]
    with jax.default_matmul_precision("highest"):
        want = ref._moe(whole, ref.dims(whole), p, lo, h, scale, ref.highest)
        total = 0.0
        for s in range(4):
            mcfg = hc.model_config(config(held=2, first=2 * s))
            params, adapters = _program_share(p, lo, 2 * s, 2, shared=s == 0)
            out, _ = moe.apply_expert_share(params, adapters, h[None], mcfg)
            total = total + out[0]
    close(total, want)


def test_dropless_under_routing_skewed_onto_one_expert(ref):
    """Every token routes to held expert 2 (and two more): the layer still
    matches the reference, which computes every routed assignment, and the
    load ratio reads the skew."""
    cfg = config()
    p, lo = _layer_weights(ref, cfg)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (SEQ, cfg["hidden_size"])))
    p["router"] = p["router"].at[:, 2].set(1.0)
    mcfg = hc.model_config(cfg)
    params, adapters = _program_share(p, lo, 0, 2, shared=True)
    with jax.default_matmul_precision("highest"):
        out, stats = moe.apply_expert_share(params, adapters, h[None], mcfg)
        want = ref._moe(cfg, ref.dims(cfg), p, lo, h, cfg["lora_alpha"] / cfg["lora_rank"],
                        ref.highest)
    route = jax.lax.top_k(h @ p["router"], 3)[1]
    assert bool(jnp.all(jnp.any(route == 2, axis=1)))
    load = jnp.array([jnp.sum(route == 2), jnp.sum(route == 3)], jnp.float32)
    assert float(stats["moe_load_max"]) == pytest.approx(float(load.max() / load.mean()))
    close(out[0], want)


def _expert_tree(clients=5, seed=0):
    """Client deltas with expert adapter leaves (clients, groups, experts, d, r)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"moe": {"in": {"A": jax.random.normal(ks[0], (clients, 1, 3, 32, 4)),
                           "B": jax.random.normal(ks[1], (clients, 1, 3, 4, 24))}},
            "mixer": {"q": {"A": jax.random.normal(ks[2], (clients, 1, 32, 4)),
                            "B": jax.random.normal(ks[3], (clients, 1, 4, 48))}}}


def test_expert_leaves_pack_as_groups_times_experts_modules():
    tree = _expert_tree()
    assert infer_layer_axes(tree["moe"]["in"]["A"]) == 2
    buckets, spec = pack(tree)
    n_modules = sum(b.data.shape[0] for b in buckets.values())
    assert n_modules == 2 * 3 + 2 * 1
    out = unpack(spec, {k: jnp.mean(b.data, axis=-1) for k, b in buckets.items()})
    jax.tree_util.tree_map(lambda o, x: close(o, jnp.mean(x, axis=0), 1e-6), out, tree)


def test_packed_fedrpca_matches_the_per_leaf_path_on_expert_leaves():
    tree = _expert_tree(seed=1)
    cfg = AggregatorConfig(method="fedrpca", rpca_iters=10)
    packed = aggregate(tree, cfg, engine="packed")
    per_leaf = aggregate(tree, cfg, engine="reference")
    jax.tree_util.tree_map(lambda a, b: close(a, b, 1e-5), packed, per_leaf)
