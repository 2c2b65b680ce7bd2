"""Granite-4.0-H-Small — hybrid Mamba-2 / attention decoder with a 72-expert
top-10 MoE and a shared expert in every layer (32B total, 9B active).

[hf:ibm-granite/granite-4.0-h-small]  40 layers in periods of 10 (mamba x5,
attention, mamba x4), d_model 4096; attention 32 Q / 8 KV heads of 128, no
positional encoding, softmax scale 0.0078125; Mamba-2 128 heads x 64,
d_state 128, one group, conv 4, chunk 256; 72 experts of width 768, top-10,
shared expert of width 1536; embedding x12, residual branches x0.22, logits
/16; RMSNorm eps 1e-5; tied embeddings, vocab 100352.  LoRA r=8 on attention
Q/V, the mamba in/out projections and every expert's two linears.
"""
from repro.config import LoRAConfig, ModelConfig

PERIOD = ("ssd",) * 5 + ("attn",) + ("ssd",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    arch_type="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100_352,
    layer_pattern=PERIOD,
    use_rope=False,
    attention_multiplier=0.0078125,
    n_experts=72,
    top_k=10,
    experts_held=72,
    shared_d_ff=1536,
    router_aux_weight=0.0,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_chunk=256,
    conv_width=4,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    lora=LoRAConfig(rank=8, alpha=16.0, targets=("q", "v")),
    source="hf:ibm-granite/granite-4.0-h-small",
)
