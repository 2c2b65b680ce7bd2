"""Operation and byte counts from shapes: the yardstick of the mfu and
roofline metrics.  Nothing here reads the program; every count follows from
the configuration file and the traffic file.
"""
from __future__ import annotations


def decoder_matmul_params(cfg: dict) -> dict:
    """Per-token matmul weights of a dense decoder, by part (no biases)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return {
        "layers": cfg["num_hidden_layers"] * per_layer,
        "head": d * cfg["vocab_size"],
    }


def lora_dims(cfg: dict) -> list:
    """(d_in, d_out) of every adapted projection in one layer."""
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    out = {"q": cfg["num_attention_heads"] * hd, "k": cfg["num_key_value_heads"] * hd,
           "v": cfg["num_key_value_heads"] * hd, "o": d}
    din = {"q": d, "k": d, "v": d, "o": cfg["num_attention_heads"] * hd}
    return [(din[t], out[t]) for t in cfg["lora_targets"]]


def lora_step_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one LoRA training step, per token.

    Forward: every matmul (2 per multiply-add), attention scores and
    values over the full ``seq`` context (no causal halving, as in the PaLM
    count), the LM head and the adapters.  Backward, what LoRA needs: input
    gradients through every frozen matmul and the head, twice the forward
    attention, and the adapters' input and weight gradients.  Gradients of
    the frozen weights are not computed, so they are not counted; nothing
    is recomputed.
    """
    p = decoder_matmul_params(cfg)
    layers = cfg["num_hidden_layers"]
    q_dim = cfg["num_attention_heads"] * (
        cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])
    r = cfg["lora_rank"]
    dense = 2.0 * (p["layers"] + p["head"])
    attn = layers * 2.0 * 2.0 * seq * q_dim
    lora_fwd = layers * sum(2.0 * r * (a + b) for a, b in lora_dims(cfg))
    forward = dense + attn + lora_fwd
    backward = dense + 2.0 * attn + 2.0 * lora_fwd
    return forward + backward


def round_flops(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one federated round's local phase."""
    tokens = traffic["seqs_per_client"] * traffic["seq_len"]
    per_client = traffic["local_steps"] * tokens * lora_step_flops_per_token(
        cfg, traffic["seq_len"])
    return traffic["clients"] * per_client


def adapter_module_dims(cfg: dict) -> list:
    """Vec dim of every adapter module (one A or B matrix of one layer),
    the rows of the matrices FedRPCA decomposes."""
    r = cfg["lora_rank"]
    dims = []
    for a, b in lora_dims(cfg):
        dims += [a * r, r * b]
    return dims * cfg["num_hidden_layers"]


def agg_flops(cfg: dict, clients: int, iters: int) -> float:
    """Gram and reconstruction FLOPs of one FedRPCA aggregation:
    4 * d * C^2 per module per ADMM iteration (X^T X, then X @ P), the same
    whichever SVT mode or tail computes them."""
    return float(sum(4.0 * d * clients * clients * iters for d in adapter_module_dims(cfg)))


def agg_least_bytes(cfg: dict, clients: int, iters: int) -> float:
    """HBM bytes no implementation can avoid per aggregation: per ADMM
    iteration five state-sized f32 passes (read M, S, Y; write S, Y), with L
    kept factored and the next Gram fused into the update."""
    state = sum(adapter_module_dims(cfg)) * clients * 4
    return 5.0 * state * iters


def roofline_time(flops: float, nbytes: float, peak: dict, chips: int) -> tuple:
    """(least seconds, which bound) for work spread evenly over ``chips``."""
    t_flops = flops / (chips * peak["bf16_flops"])
    t_bytes = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
