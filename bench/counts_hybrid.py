"""Operation counts of the hybrid Mamba-2 / attention / expert-share model
(granite-4.0-h-small) from shapes: the yardstick of ``mfu.hybrid``.  Nothing
here reads the program; every count follows from the configuration file and
the traffic file.

The count is of the chip's share as the configuration states it: the held
experts take, on average, ``top_k * held / router_experts`` of each token's
assignments (the router's published width and top-k), and the vocabulary is
the slice.  The program computes every held expert over every token (its
dropless form), which the count does not credit.
"""
from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    heads = cfg["num_attention_heads"]
    share = cfg["expert_share"]
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return dict(d=d, di=di, mh=cfg["mamba_n_heads"], mp=cfg["mamba_d_head"],
                n=cfg["mamba_d_state"], q=cfg["mamba_chunk_size"],
                in_proj=2 * di + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
                + cfg["mamba_n_heads"],
                q_dim=d, kv_dim=cfg["num_key_value_heads"] * (d // heads),
                ff=cfg["intermediate_size"], sff=cfg["shared_intermediate_size"],
                per_token=cfg["num_experts_per_tok"] * cfg["num_local_experts"]
                / share["router_experts"],
                router=share["router_experts"], vocab=cfg["vocab_size"], r=cfg["lora_rank"],
                mamba=kinds.count("mamba"), attention=kinds.count("attention"),
                layers=len(kinds))


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part: ``matmul`` (every frozen projection,
    the router and the head), ``mix`` (the quadratic parts: attention scores
    and values over the full ``seq``, no causal halving; the SSD's chunk
    scores, intra-chunk outputs, chunk states and inter-chunk outputs) and
    ``lora`` (the adapters, the held experts' at their routed share)."""
    n = _dims(cfg)
    d, r = n["d"], n["r"]
    mamba = 2 * d * n["in_proj"] + 2 * n["di"] * d
    ssd = 2 * n["q"] * n["n"] + 2 * n["mh"] * n["q"] * n["mp"] + 4 * n["mh"] * n["mp"] * n["n"]
    attn = 2 * d * (2 * n["q_dim"] + 2 * n["kv_dim"])
    expert = n["per_token"] * 3 * 2 * d * n["ff"]
    moe = expert + 3 * 2 * d * n["sff"] + 2 * d * n["router"]
    lora_mamba = 2 * r * (d + n["in_proj"]) + 2 * r * (n["di"] + d)
    lora_attn = 2 * r * (d + n["q_dim"]) + 2 * r * (d + n["kv_dim"])
    lora_expert = n["per_token"] * (2 * r * (d + 2 * n["ff"]) + 2 * r * (n["ff"] + d))
    return {
        "matmul": n["mamba"] * mamba + n["attention"] * attn + n["layers"] * moe
        + 2 * d * n["vocab"],
        "mix": n["mamba"] * ssd + n["attention"] * 2 * 2 * seq * n["q_dim"],
        "lora": n["mamba"] * lora_mamba + n["attention"] * lora_attn
        + n["layers"] * lora_expert,
    }


def lora_step_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one LoRA training step, per token: the forward, then
    what LoRA's backward needs (input gradients through every frozen matmul,
    twice the quadratic parts, the adapters' input and weight gradients).
    Nothing recomputed is counted."""
    f = forward_flops_per_token(cfg, seq)
    forward = f["matmul"] + f["mix"] + f["lora"]
    return forward + f["matmul"] + 2.0 * f["mix"] + 2.0 * f["lora"]


def round_flops(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one federated round's local phase."""
    tokens = traffic["seqs_per_client"] * traffic["seq_len"]
    return (traffic["clients"] * traffic["local_steps"] * tokens
            * lora_step_flops_per_token(cfg, traffic["seq_len"]))
