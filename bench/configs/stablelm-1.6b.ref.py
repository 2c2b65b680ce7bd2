"""Plain float32 reference of the stablelm-1.6b configuration as it is run,
with LoRA on Q and V: weights from the seed, the forward pass and loss, and
one client's local Adam steps.  Imports nothing of the program.

The model (hf:stabilityai/stablelm-2-1_6b): token embedding; per layer a
pre-LayerNorm causal multi-head attention with rotary embedding on the
first quarter of each head (rotate-half layout), then a pre-LayerNorm SwiGLU
MLP, both residual; a final LayerNorm and the output head.  The file
``stablelm-1.6b.json`` holds the published sizes: a bias on Q, K and V, an
untied output head, LayerNorm epsilon 1e-5.  LoRA: ``W x + (alpha / r) B^T A^T x``
with ``A`` (d_in, r) and ``B`` (r, d_out).

Every matmul goes through ``dot``: float32 at ``"highest"`` precision for the
reference, or a lower precision for the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.ref_fedrpca import aggregate  # noqa: F401  the server's merge
from bench.ref_fedrpca import round_to


def highest(eq, a, b):
    return jnp.einsum(eq, a, b, precision="highest")


def _fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding, passed straight through in
    the backward pass."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = round_to(x / s, jnp.float8_e4m3fn) * s
    return x + jax.lax.stop_gradient(q - x)


def fp8(eq, a, b):
    """The control's matmul: both inputs rounded to float8, one step below
    the configuration's bfloat16."""
    return jnp.einsum(eq, _fp8(a), _fp8(b), precision="highest")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"], hd=hd, ff=cfg["intermediate_size"],
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"], r=cfg["lora_rank"])


def lora_shapes(cfg: dict) -> dict:
    n = dims(cfg)
    out = {"q": n["h"] * n["hd"], "v": n["kv"] * n["hd"]}
    shapes = {}
    for t in cfg["lora_targets"]:
        shapes[f"{t}.A"] = (n["layers"], n["d"], n["r"])
        shapes[f"{t}.B"] = (n["layers"], n["r"], out[t])
    return shapes


def make_weights(cfg: dict, key) -> dict:
    """Base weights (bfloat16, as served) and the initial adapters (float32:
    ``A`` ~ N(0, 1/d_in), ``B`` = 0).  Projections are U(-1/sqrt(d_in),
    1/sqrt(d_in)), the embedding, head and Q/K/V biases N(0, 0.02^2), norms
    the identity."""
    n = dims(cfg)
    bf = jnp.bfloat16
    lay, d, ff = n["layers"], n["d"], n["ff"]
    qd, kvd = n["h"] * n["hd"], n["kv"] * n["hd"]
    proj = {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    keys = jax.random.split(key, len(proj) + 5)
    w = {"embed": (0.02 * jax.random.normal(keys[0], (n["vocab"], d))).astype(bf)}
    if not cfg["tie_word_embeddings"]:
        w["lm_head"] = (0.02 * jax.random.normal(keys[1], (d, n["vocab"]))).astype(bf)
    for k, (name, (din, dout)) in zip(keys[2:], proj.items()):
        lim = 1.0 / math.sqrt(din)
        w[name] = jax.random.uniform(k, (lay, din, dout), jnp.float32, -lim, lim).astype(bf)
    if cfg["use_qkv_bias"]:
        for k, name in zip(jax.random.split(keys[-2], 3), ("wq", "wk", "wv")):
            w[f"{name}.bias"] = (0.02 * jax.random.normal(k, (lay, proj[name][1]))).astype(bf)
    for ln in ("ln1", "ln2"):
        w[f"{ln}.scale"] = jnp.ones((lay, d), jnp.float32)
        w[f"{ln}.bias"] = jnp.zeros((lay, d), jnp.float32)
    w["lnf.scale"] = jnp.ones((d,), jnp.float32)
    w["lnf.bias"] = jnp.zeros((d,), jnp.float32)
    lora = {}
    ka = jax.random.split(keys[-1], len(cfg["lora_targets"]))
    for k, t in zip(ka, cfg["lora_targets"]):
        shp = lora_shapes(cfg)
        lora[f"{t}.A"] = jax.random.normal(k, shp[f"{t}.A"]) / math.sqrt(d)
        lora[f"{t}.B"] = jnp.zeros(shp[f"{t}.B"], jnp.float32)
    return {"base": w, "lora": lora}


def _layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, pct, theta):
    """Rotary embedding on the first ``pct`` of each head, rotate-half."""
    s, hd = x.shape[1], x.shape[-1]
    rot = int(hd * pct) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def loss(cfg: dict, base: dict, lora: dict, tokens, labels, dot=highest):
    """Mean next-token cross-entropy of one batch (batch, seq), float32."""
    n = dims(cfg)
    eps = cfg["layer_norm_eps"]
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    b, s = tokens.shape
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(base["embed"])[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def adapted(h, wname, t, p, lo):
        y = dot("bsd,de->bse", h, f32(p[wname]))
        if f"{wname}.bias" in p:
            y = y + f32(p[f"{wname}.bias"])
        if f"{t}.A" in lo:
            y = y + scale * dot("bsr,re->bse", dot("bsd,dr->bsr", h, lo[f"{t}.A"]), lo[f"{t}.B"])
        return y

    def layer(x, xs):
        p, lo = xs
        h = _layernorm(x, p["ln1.scale"], p["ln1.bias"], eps)
        q = adapted(h, "wq", "q", p, lo).reshape(b, s, n["h"], n["hd"])
        k = adapted(h, "wk", "k", p, lo).reshape(b, s, n["kv"], n["hd"])
        v = adapted(h, "wv", "v", p, lo).reshape(b, s, n["kv"], n["hd"])
        q = _rope(q, cfg["partial_rotary_factor"], cfg["rope_theta"])
        k = _rope(k, cfg["partial_rotary_factor"], cfg["rope_theta"])
        group = n["h"] // n["kv"]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        sc = dot("bqhd,bkhd->bhqk", q, k) / math.sqrt(n["hd"])
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = dot("bhqk,bkhd->bqhd", pr, v).reshape(b, s, n["h"] * n["hd"])
        x = x + dot("bse,ed->bsd", o, f32(p["wo"]))
        h2 = _layernorm(x, p["ln2.scale"], p["ln2.bias"], eps)
        g = jax.nn.silu(dot("bsd,df->bsf", h2, f32(p["w_gate"])))
        u = dot("bsd,df->bsf", h2, f32(p["w_up"]))
        return x + dot("bsf,fd->bsd", g * u, f32(p["w_down"])), None

    per_layer = {k: v for k, v in base.items()
                 if k not in ("embed", "lm_head", "lnf.scale", "lnf.bias")}
    x, _ = jax.lax.scan(layer, x, (per_layer, lora))
    x = _layernorm(x, base["lnf.scale"], base["lnf.bias"], eps)
    head = f32(base["lm_head"]) if "lm_head" in base else f32(base["embed"]).T
    logits = dot("bsd,dv->bsv", x, head)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


@functools.partial(jax.jit, static_argnames=("cfg_items", "steps", "lr", "dot"))
def _local(cfg_items, base, lora, tokens, *, steps, lr, dot):
    cfg = dict(cfg_items)

    def client(tok):
        inp, lab = tok[:, :-1], tok[:, 1:]
        m = jax.tree_util.tree_map(jnp.zeros_like, lora)
        v = jax.tree_util.tree_map(jnp.zeros_like, lora)
        p = lora
        last = jnp.zeros(())
        for t in range(1, steps + 1):
            last, g = jax.value_and_grad(lambda l: loss(cfg, base, l, inp, lab, dot))(p)
            m = jax.tree_util.tree_map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
            v = jax.tree_util.tree_map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
            p = jax.tree_util.tree_map(
                lambda w, a, b: w - lr * (a / (1 - 0.9 ** t)) / (jnp.sqrt(b / (1 - 0.999 ** t)) + 1e-8),
                p, m, v)
        return jax.tree_util.tree_map(jnp.subtract, p, lora), last

    deltas, losses = jax.lax.map(client, tokens)
    return deltas, jnp.mean(losses)


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, list, bool))))


def local_phase(cfg: dict, traffic: dict, base: dict, lora: dict, tokens, dot=highest):
    """Every client's local Adam steps from ``lora``: (deltas {name:
    (clients, ...)}, mean over clients of the last step's loss)."""
    return _local(_freeze(cfg), base, lora, jnp.asarray(tokens),
                  steps=traffic["local_steps"], lr=traffic["local_lr"], dot=dot)
