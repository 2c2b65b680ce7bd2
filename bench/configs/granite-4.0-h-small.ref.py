"""Plain float32 reference of the granite-4.0-h-small configuration as it is
run: weights from the seed, the forward pass and loss of the chip's expert
share, and one client's local Adam steps.  Imports nothing of the program.

The model (hf:ibm-granite/granite-4.0-h-small, ``GraniteMoeHybridForCausalLM``):

    x = embed[tokens] * embedding_multiplier
    per layer (``layer_types``: mamba or attention):
        x = x + residual_multiplier * mixer(rmsnorm(x))
        h = rmsnorm(x)
        r = h @ W_router                  # over every expert of the model
        top = top_k(r, k); g = softmax(r[top])
        moe = sum_{e in top, e held} g_e * (silu(h Wg_e) * (h Wu_e)) Wd_e
        x = x + residual_multiplier * (moe + (silu(h Sg) * (h Su)) Sd)
    logits = rmsnorm(x) @ embed.T / logits_scaling

Attention is causal GQA with no positional encoding and the scores scaled
by ``attention_multiplier``.  The Mamba-2 mixer: in_proj -> [z | xBC | dt];
causal depthwise conv (with bias) over xBC, silu; dt = softplus(dt +
dt_bias), A = -exp(A_log); the SSM output in its quadratic (dual) form over
the whole sequence, y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<u<=t} dt_u A)
dt_s x_s + D x_t, which the program computes chunk by chunk; gated RMSNorm
rmsnorm(y * silu(z)); out_proj.

The chip's share (``expert_share`` in the configuration): the router spans
``router_experts`` experts, the chip holds ``num_local_experts`` of them from
``first_held`` on, and the layer's result is their part plus the shared
expert; the vocabulary is the configuration's slice.  LoRA, ``W x + (alpha /
r) B^T A^T x`` with ``A`` (d_in, r) and ``B`` (r, d_out), on attention Q and
V, the mamba in_proj and out_proj, and each held expert's input linear
``[Wg | Wu]`` and output linear ``Wd``.

Base weights are in the configuration's dtype (bfloat16, as served); each
layer's are upcast inside that layer, under ``jax.checkpoint``, so the
reference never holds a float32 copy of the model.  Every matmul goes through ``dot``: float32 at ``"highest"``
for the reference, or float8 inputs for the control.
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
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    di = cfg["mamba_expand"] * d
    share = cfg["expert_share"]
    return dict(
        d=d, h=h, kv=cfg["num_key_value_heads"], hd=d // h, vocab=cfg["vocab_size"],
        kinds=tuple(cfg["layer_types"][: cfg["num_hidden_layers"]]),
        di=di, mh=cfg["mamba_n_heads"], mp=cfg["mamba_d_head"], n=cfg["mamba_d_state"],
        conv=cfg["mamba_d_conv"], conv_dim=di + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"],
        ff=cfg["intermediate_size"], sff=cfg["shared_intermediate_size"],
        held=cfg["num_local_experts"], first=share["first_held"],
        router=share["router_experts"], top=cfg["num_experts_per_tok"], r=cfg["lora_rank"])


def lora_shapes(cfg: dict) -> dict:
    """Adapter leaves ``L<i>.<target>.<A|B>``, each with a leading module
    axis: one module for a mixer projection, one per held expert."""
    n = dims(cfg)
    d, r = n["d"], n["r"]
    mixer = {"mamba": {"in": (d, 2 * n["di"] + 2 * n["n"] + n["mh"]), "out": (n["di"], d)},
             "attention": {"q": (d, n["h"] * n["hd"]), "v": (d, n["kv"] * n["hd"])}}
    shapes = {}
    for i, kind in enumerate(n["kinds"]):
        for t, (din, dout) in mixer[kind].items():
            shapes[f"L{i}.{t}.A"], shapes[f"L{i}.{t}.B"] = (1, din, r), (1, r, dout)
        for t, (din, dout) in {"e_in": (d, 2 * n["ff"]), "e_out": (n["ff"], d)}.items():
            shapes[f"L{i}.{t}.A"] = (n["held"], din, r)
            shapes[f"L{i}.{t}.B"] = (n["held"], r, dout)
    return shapes


def weight_shapes(cfg: dict) -> dict:
    """Base weights: name -> (shape, kind) with kind "proj" (U(-1/sqrt(fan_in),
    1/sqrt(fan_in)) over the second-to-last axis), "embed" (N(0, 0.02^2)),
    "conv" (N(0, 0.1^2)), "bias" (N(0, 0.02^2)), all in the configuration's
    dtype; or, in float32, "ones", "A_log" (log U(1, 16)) and "dt_bias"
    (softplus^-1 of log-uniform dt in [1e-3, 0.1])."""
    n = dims(cfg)
    d = n["d"]
    w = {"embed": ((n["vocab"], d), "embed"), "normf": ((d,), "ones")}
    for i, kind in enumerate(n["kinds"]):
        p = f"L{i}."
        w[p + "norm1"] = w[p + "norm2"] = ((d,), "ones")
        if kind == "mamba":
            w[p + "in_proj"] = ((d, 2 * n["di"] + 2 * n["n"] + n["mh"]), "proj")
            w[p + "conv_w"] = ((n["conv"], n["conv_dim"]), "conv")
            w[p + "conv_b"] = ((n["conv_dim"],), "bias")
            w[p + "A_log"] = ((n["mh"],), "A_log")
            w[p + "dt_bias"] = ((n["mh"],), "dt_bias")
            w[p + "D"] = ((n["mh"],), "ones")
            w[p + "gnorm"] = ((n["di"],), "ones")
            w[p + "out_proj"] = ((n["di"], d), "proj")
        else:
            qd, kvd = n["h"] * n["hd"], n["kv"] * n["hd"]
            for name, shape in {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd),
                                "wo": (qd, d)}.items():
                w[p + name] = (shape, "proj")
        w[p + "router"] = ((d, n["router"]), "proj")
        w[p + "e_gate"] = w[p + "e_up"] = ((n["held"], d, n["ff"]), "proj")
        w[p + "e_down"] = ((n["held"], n["ff"], d), "proj")
        w[p + "s_gate"] = w[p + "s_up"] = ((d, n["sff"]), "proj")
        w[p + "s_down"] = ((n["sff"], d), "proj")
    return w


def make_weights(cfg: dict, key) -> dict:
    """Base weights (``weight_shapes``, matrices in the configuration's
    ``torch_dtype``) and the initial adapters (float32: ``A`` ~ N(0, 1/d_in),
    ``B`` = 0)."""
    dt, f32 = jnp.dtype(cfg["torch_dtype"]), jnp.float32
    base = {}
    for i, (name, (shape, kind)) in enumerate(sorted(weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "proj":
            lim = 1.0 / math.sqrt(shape[-2])
            base[name] = jax.random.uniform(k, shape, f32, -lim, lim).astype(dt)
        elif kind in ("embed", "bias"):
            base[name] = (0.02 * jax.random.normal(k, shape)).astype(dt)
        elif kind == "conv":
            base[name] = (0.1 * jax.random.normal(k, shape)).astype(dt)
        elif kind == "ones":
            base[name] = jnp.ones(shape, f32)
        elif kind == "A_log":
            base[name] = jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0))
        else:  # dt_bias
            step = jnp.exp(jax.random.uniform(k, shape, f32, math.log(1e-3), math.log(0.1)))
            base[name] = step + jnp.log(-jnp.expm1(-step))
    lora = {}
    lkey = jax.random.fold_in(key, 10**6)
    for i, (name, shape) in enumerate(sorted(lora_shapes(cfg).items())):
        if name.endswith(".A"):
            lora[name] = jax.random.normal(jax.random.fold_in(lkey, i), shape) / math.sqrt(shape[1])
        else:
            lora[name] = jnp.zeros(shape, f32)
    return {"base": base, "lora": lora}


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _attention(cfg, n, p, lo, h, adapted, dot):
    s = h.shape[0]
    q = adapted(h, p["wq"], lo, "q").reshape(s, n["h"], n["hd"])
    k = dot("sd,de->se", h, p["wk"]).reshape(s, n["kv"], n["hd"])
    v = adapted(h, p["wv"], lo, "v").reshape(s, n["kv"], n["hd"])
    group = n["h"] // n["kv"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    sc = dot("qhd,khd->hqk", q, k) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = dot("hqk,khd->qhd", pr, v).reshape(s, n["h"] * n["hd"])
    return dot("se,ed->sd", o, p["wo"])


def _mamba(cfg, n, p, lo, h, adapted, dot):
    s, di, mh, mp, ns = h.shape[0], n["di"], n["mh"], n["mp"], n["n"]
    proj = adapted(h, p["in_proj"], lo, "in")
    z, xbc, dt = proj[:, :di], proj[:, di: di + n["conv_dim"]], proj[:, di + n["conv_dim"]:]
    k = n["conv"]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j: j + s] * p["conv_w"][j] for j in range(k)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    x, b, c = xbc[:, :di].reshape(s, mh, mp), xbc[:, di: di + ns], xbc[:, di + ns:]
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (s, mh)
    da = dt * -jnp.exp(p["A_log"])  # (s, mh) log-decay per step
    cum = jnp.cumsum(da, axis=0)
    causal = jnp.tril(jnp.ones((s, s), bool))
    decay = jnp.exp(jnp.where(causal[None], cum.T[:, :, None] - cum.T[:, None, :], -jnp.inf))
    cb = dot("tn,sn->ts", c, b)
    y = dot("hts,shp->thp", decay * cb[None], x * dt[:, :, None])
    y = (y + x * p["D"][None, :, None]).reshape(s, di)
    y = _rmsnorm(y * jax.nn.silu(z), p["gnorm"], cfg["rms_norm_eps"])
    return adapted(y, p["out_proj"], lo, "out")


def _moe(cfg, n, p, lo, h, scale, dot):
    logits = dot("sd,de->se", h, p["router"])
    top_v, top_e = jax.lax.top_k(logits, n["top"])
    gates = jax.nn.softmax(top_v, axis=-1)
    held = n["first"] + jnp.arange(n["held"])
    combine = jnp.sum(jnp.where(top_e[:, :, None] == held, gates[:, :, None], 0.0), axis=1)
    up = dot("sd,edf->esf", h, jnp.concatenate([p["e_gate"], p["e_up"]], axis=-1))
    up = up + scale * dot("esr,erf->esf", dot("sd,edr->esr", h, lo["e_in.A"]), lo["e_in.B"])
    act = jax.nn.silu(up[..., : n["ff"]]) * up[..., n["ff"]:]
    out = dot("esf,efd->esd", act, p["e_down"])
    out = out + scale * dot("esr,erd->esd", dot("esf,efr->esr", act, lo["e_out.A"]),
                            lo["e_out.B"])
    moe = jnp.sum(combine.T[:, :, None] * out, axis=0)
    shared = jax.nn.silu(dot("sd,df->sf", h, p["s_gate"])) * dot("sd,df->sf", h, p["s_up"])
    return moe + dot("sf,fd->sd", shared, p["s_down"])


def logits_of(cfg: dict, base: dict, lora: dict, tokens, dot=highest):
    """Logits (seq, vocab) of one sequence, float32."""
    n = dims(cfg)
    eps, mult = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(base["embed"])[tokens] * cfg["embedding_multiplier"]

    def adapted(h, w, lo, t):
        y = dot("sd,de->se", h, w)
        return y + scale * dot("sr,re->se", dot("sd,dr->sr", h, lo[f"{t}.A"][0]), lo[f"{t}.B"][0])

    def layer(kind, x, p, lo):
        p = {k: f32(v) for k, v in p.items()}
        mixer = _mamba if kind == "mamba" else _attention
        x = x + mult * mixer(cfg, n, p, lo, _rmsnorm(x, p["norm1"], eps), adapted, dot)
        return x + mult * _moe(cfg, n, p, lo, _rmsnorm(x, p["norm2"], eps), scale, dot)

    for i, kind in enumerate(n["kinds"]):
        pre = f"L{i}."
        p = {k[len(pre):]: v for k, v in base.items() if k.startswith(pre)}
        lo = {k[len(pre):]: v for k, v in lora.items() if k.startswith(pre)}
        x = jax.checkpoint(functools.partial(layer, kind))(x, p, lo)
    x = _rmsnorm(x, base["normf"], eps)
    return dot("sd,vd->sv", x, f32(base["embed"])) / cfg["logits_scaling"]


def loss(cfg: dict, base: dict, lora: dict, tokens, labels, dot=highest):
    """Mean next-token cross-entropy of one batch (batch, seq), float32."""
    per_seq = jax.vmap(lambda t: logits_of(cfg, base, lora, t, dot))
    logits = per_seq(tokens)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


@functools.partial(jax.jit, static_argnames=("cfg_items", "steps", "lr", "dot"))
def _local(cfg_items, base, lora, tokens, *, steps, lr, dot):
    cfg = _thaw(cfg_items)

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
    """The configuration as a hashable static argument."""
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else
                         tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, str, list, bool, dict))))


def _thaw(items: tuple) -> dict:
    return {k: _thaw(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple) else v
            for k, v in items}


def local_phase(cfg: dict, traffic: dict, base: dict, lora: dict, tokens, dot=highest):
    """Every client's local Adam steps from ``lora``: (deltas {name:
    (clients, ...)}, mean over clients of the last step's loss)."""
    return _local(_freeze(cfg), base, lora, jnp.asarray(tokens),
                  steps=traffic["local_steps"], lr=traffic["local_lr"], dot=dot)
