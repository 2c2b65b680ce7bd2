"""Tiny versions of the hybrid and mesh cells, for runs on the CPU, beside
``tiny.py``'s: ``register()`` adds them to its tables."""
from __future__ import annotations

from bench.tests import tiny

# One mamba and one attention layer at every mechanism of the configuration:
# a router over 8 experts (top-3) of which experts 2 and 3 are held here.
HYBRID = dict(num_hidden_layers=2, layer_types=["mamba", "attention"], hidden_size=64,
              num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
              mamba_d_state=16, mamba_chunk_size=16, intermediate_size=32,
              shared_intermediate_size=64, num_local_experts=2,
              expert_share={"router_experts": 8, "first_held": 2}, num_experts_per_tok=3,
              vocab_size=256)
TRAFFIC = {
    "hybrid_round": dict(clients=4, seqs_per_client=1, seq_len=32, token_rows=64,
                         trace_rounds=3),
    "agg_mesh": dict(clients=8, trace_rounds=3),
}


def register() -> None:
    tiny.TINY.setdefault("granite-4.0-h-small", HYBRID)
    for kind, sizes in TRAFFIC.items():
        tiny.TINY_TRAFFIC.setdefault(kind, sizes)
