"""Plain reference of the deepseek-67b adapter tree: the shapes of one
client's LoRA delta, and FedRPCA over a cohort of them
(``bench/ref_fedrpca.py``).  Imports nothing of the program."""
from bench.ref_fedrpca import aggregate  # noqa: F401


def lora_shapes(cfg: dict) -> dict:
    d, r = cfg["hidden_size"], cfg["lora_rank"]
    out = {"q": cfg["num_attention_heads"] * cfg["head_dim"],
           "v": cfg["num_key_value_heads"] * cfg["head_dim"]}
    layers = cfg["num_hidden_layers"]
    shapes = {}
    for t in cfg["lora_targets"]:
        shapes[f"{t}.A"] = (layers, d, r)
        shapes[f"{t}.B"] = (layers, r, out[t])
    return shapes
